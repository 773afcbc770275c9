"""skyplane_tpu: a TPU-native cloud bulk-data-transfer framework.

Capability parity with skyplane-project/skyplane (reference survey in
SURVEY.md), re-architected so the gateway data path's content-defined
chunking and dedup fingerprinting run as JAX programs over HBM-resident
chunk batches, beside host kernels for compression and integrity checksums.

Public surface (reference: skyplane/__init__.py:1-28): ``SkyplaneClient``,
``Pipeline``, ``Dataplane``, ``TransferHook``, plus config dataclasses.
Heavy subpackages are imported lazily so that ``import skyplane_tpu`` stays
cheap on gateway VMs.
"""

from __future__ import annotations

__version__ = "0.1.0"

from skyplane_tpu.chunk import Chunk, ChunkRequest, ChunkState, WireProtocolHeader, Codec


_LAZY_EXPORTS = {
    "SkyplaneClient": ("skyplane_tpu.api.client", "SkyplaneClient"),
    "Pipeline": ("skyplane_tpu.api.pipeline", "Pipeline"),
    "Dataplane": ("skyplane_tpu.api.dataplane", "Dataplane"),
    "TransferHook": ("skyplane_tpu.api.tracker", "TransferHook"),
    "TransferConfig": ("skyplane_tpu.api.config", "TransferConfig"),
}


def __getattr__(name):
    if name in _LAZY_EXPORTS:
        import importlib

        module_name, attr = _LAZY_EXPORTS[name]
        try:
            module = importlib.import_module(module_name)
        except ModuleNotFoundError as e:
            # only mask "that submodule isn't built yet"; real import bugs propagate
            if e.name and e.name.startswith("skyplane_tpu"):
                raise AttributeError(f"module {__name__!r} has no attribute {name!r} ({module_name} unavailable)") from e
            raise
        return getattr(module, attr)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# star-import surface: concrete symbols plus whichever lazy exports are built
__all__ = ["Chunk", "ChunkRequest", "ChunkState", "WireProtocolHeader", "Codec", "__version__"] + [
    name for name, (mod, _) in _LAZY_EXPORTS.items() if __import__("importlib.util", fromlist=["util"]).find_spec(mod) is not None
]


def __dir__():
    return sorted(set(globals()) | set(__all__))
