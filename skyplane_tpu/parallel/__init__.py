"""Multi-chip scaling of the data path over a jax.sharding.Mesh.

The reference scales with processes and parallel TCP sockets
(SURVEY §2.9); the TPU-native analog for on-gateway compute is batch
sharding over a device mesh: the rows of a device window are spread over the
chips, whole chunks each (ops/fused_cdc.py ``make_sharded_kernels``).
"""

from skyplane_tpu.parallel.datapath_spmd import default_mesh

__all__ = ["default_mesh"]
