"""The device mesh of a multi-chip gateway, and the switches that choose it.

The data path shards a window's ROWS over the mesh: boundary selection is
sequential within a chunk, so whole chunks are the parallel unit and every
mesh axis is flattened into the batch axis (ops/fused_cdc.py
``make_sharded_kernels``, driven by ``DeviceBatchRunner``). No collective
crosses chips. The mesh keeps the two names (data, seq) its callers and
counters use.
"""

from __future__ import annotations

import os
import re
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh


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
        # two axes so that a window smaller than the device count can still
        # shard over ``data`` alone (DeviceBatchRunner picks the axes)
        data_parallel = 2 if n >= 4 and n % 2 == 0 else 1
    seq = n // data_parallel
    arr = np.asarray(devices[: data_parallel * seq]).reshape(data_parallel, seq)
    return Mesh(arr, axis_names=("data", "seq"))
