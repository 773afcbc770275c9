"""SPMD data-path step: shard_map over a (data, seq) device mesh.

Parallel axes (TPU-native mapping of the reference's process/socket scaling,
SURVEY §2.9):

  data — chunk parallelism: different chunks on different devices (the
         reference's "independent chunks through concurrent operator
         workers").
  seq  — intra-chunk byte-range parallelism for very large chunks (the
         reference's multipart striping, but *within* the accelerator): the
         byte dimension splits across devices; the Gear rolling hash needs a
         (window-1)-byte halo from the left neighbor, exchanged with
         ``ppermute`` over ICI.

Fingerprint segments and blockpack blocks are aligned to the shard size, so
tags/fingerprints/literal compaction are fully local after the halo exchange
— the only cross-device traffic is the 31-byte halo per chunk per step.
"""

from __future__ import annotations

import os
import re
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from skyplane_tpu.ops import blockpack
from skyplane_tpu.ops.fingerprint import segment_fingerprint_device
from skyplane_tpu.ops.gear import GEAR_TABLE, GEAR_WINDOW, boundary_candidate_mask


def spmd_mode() -> str:
    """Parse SKYPLANE_TPU_SPMD into one of "off" / "auto" / "on".

    "off" disables mesh sharding entirely; "on" forces the mesh-backed runner
    even off-accelerator (forced-host CPU devices — bench/CI); anything else
    (including unset) is "auto": shard when maybe_default_mesh() finds a
    viable mesh, single-device otherwise.
    """
    v = os.environ.get("SKYPLANE_TPU_SPMD", "auto").strip().lower()
    if v in ("0", "off", "false", "no"):
        return "off"
    if v in ("1", "on", "true", "yes", "force"):
        return "on"
    return "auto"


def maybe_default_mesh() -> Optional[Mesh]:
    """A (data, seq) mesh over the attached devices when sharding is viable
    (more than one device, power-of-two count), else None. A backend that
    fails to initialize raises: that is a broken chip, not a reason to run
    single-device. Honors SKYPLANE_TPU_SPMD=off."""
    if spmd_mode() == "off":
        return None
    n = len(jax.devices())
    if n > 1 and (n & (n - 1)) == 0:
        return default_mesh()
    return None


_FORCE_HOST_RE = re.compile(r"--xla_force_host_platform_device_count=\d+")


def force_host_devices_env(n: int, base_env: Optional[dict] = None) -> dict:
    """Environment for a child process that should see ``n`` forced-host CPU
    devices. Spawn-safe: the returned dict must reach the child before any
    JAX import (pass it to subprocess/spawn env=), because XLA reads
    XLA_FLAGS exactly once at backend init. Existing force-host flags in the
    inherited XLA_FLAGS are replaced, other flags preserved; JAX_PLATFORMS is
    pinned to cpu because the chip belongs to the parent process."""
    env = dict(os.environ if base_env is None else base_env)
    flag = f"--xla_force_host_platform_device_count={n}"
    flags = env.get("XLA_FLAGS", "")
    if _FORCE_HOST_RE.search(flags):
        flags = _FORCE_HOST_RE.sub(flag, flags)
    else:
        flags = (flags + " " + flag).strip()
    env["XLA_FLAGS"] = flags
    env["JAX_PLATFORMS"] = "cpu"
    return env


def default_mesh(devices=None, data_parallel: Optional[int] = None) -> Mesh:
    """Build a (data, seq) mesh over the available devices."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if data_parallel is None:
        # favor seq-parallel for big-chunk throughput; keep data >= 1
        data_parallel = 2 if n >= 4 and n % 2 == 0 else 1
    seq = n // data_parallel
    arr = np.asarray(devices[: data_parallel * seq]).reshape(data_parallel, seq)
    return Mesh(arr, axis_names=("data", "seq"))


def _gear_hash_halo(chunk: jax.Array, axis_name: str) -> jax.Array:
    """Per-shard gear hash with left-neighbor halo over ``axis_name``.

    chunk: [n_local] uint8 (this device's contiguous byte range).
    Matches the unsharded ops.gear.gear_hash exactly: device 0's halo is
    zeros (ppermute leaves unmatched targets zero), which reproduces the
    zero-prefix semantics of the sequential recurrence.
    """
    table = jnp.asarray(GEAR_TABLE)
    g = table[chunk.astype(jnp.int32)]  # [n_local] uint32
    halo = jax.lax.ppermute(
        g[-(GEAR_WINDOW - 1) :],
        axis_name,
        perm=[(i, i + 1) for i in range(jax.lax.axis_size(axis_name) - 1)],
    )  # [W-1] from left neighbor; zeros on device 0
    g_ext = jnp.concatenate([halo, g])  # [n_local + W - 1]
    # same doubling kernel as the unsharded path (single source of truth for
    # the cross-host determinism contract); the first W-1 outputs are halo
    # positions and are discarded — local positions see the full window
    from skyplane_tpu.ops.gear import _windowed_sum_doubling

    return _windowed_sum_doubling(g_ext)[GEAR_WINDOW - 1 :]


def make_spmd_datapath(
    mesh: Mesh,
    chunk_bytes: int,
    batch_chunks: int,
    block_bytes: int = 512,
    fp_seg_bytes: int = 1 << 16,
    mask_bits: int = 16,
):
    """Compile the full batched data-path step sharded over ``mesh``.

    Returns a jitted fn: [batch_chunks, chunk_bytes] uint8 ->
      dict(candidates [B,N] bool, tags [B,N/block] uint8,
           literals [B,N] uint8, n_lit [B,seq] int32 (per seq-shard),
           fp_lanes [B, N/fp_seg, 8] uint32)
    """
    seq = mesh.shape["seq"]
    n_local = chunk_bytes // seq
    if chunk_bytes % seq or n_local % fp_seg_bytes or n_local % block_bytes:
        raise ValueError(
            f"chunk_bytes={chunk_bytes} must split over seq={seq} into shards divisible by "
            f"fp_seg_bytes={fp_seg_bytes} and block_bytes={block_bytes}"
        )
    if batch_chunks % mesh.shape["data"]:
        raise ValueError(f"batch_chunks={batch_chunks} must divide over data={mesh.shape['data']}")

    # resolve the Pallas flag OUTSIDE the traced function (it becomes part of
    # the returned closure; re-call make_spmd_datapath after flipping the env)
    from skyplane_tpu.ops.backend import on_accelerator
    from skyplane_tpu.ops.fingerprint import fixed_stride_lanes
    from skyplane_tpu.ops.pallas_kernels import use_pallas

    pallas = bool(use_pallas("fp") and on_accelerator())

    def per_shard(batch_local: jax.Array):
        # batch_local: [B/data, n_local] uint8
        def one(chunk_local):
            h = _gear_hash_halo(chunk_local, "seq")
            candidates = boundary_candidate_mask(h, mask_bits)
            tags, literals, n_lit = blockpack.encode_device(chunk_local, block_bytes=block_bytes)
            fp = fixed_stride_lanes(chunk_local, fp_seg_bytes, pallas=pallas)
            return candidates, tags, literals, n_lit[None], fp

        return jax.vmap(one)(batch_local)

    shard_fn = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=P("data", "seq"),
        out_specs=(
            P("data", "seq"),  # candidates [B, N]
            P("data", "seq"),  # tags       [B, N/block]
            P("data", "seq"),  # literals   [B, N] (dense per shard)
            P("data", "seq"),  # n_lit      [B, seq]
            P("data", "seq", None),  # fp_lanes [B, N/fp_seg, 8]
        ),
    )

    @jax.jit
    def step(batch: jax.Array):
        candidates, tags, literals, n_lit, fp = shard_fn(batch)
        return dict(candidates=candidates, tags=tags, literals=literals, n_lit=n_lit, fp_lanes=fp)

    in_sharding = NamedSharding(mesh, P("data", "seq"))
    return step, in_sharding
