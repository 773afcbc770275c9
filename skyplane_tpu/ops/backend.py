"""Backend selection: device kernels on accelerators, numpy on CPU backends."""

from __future__ import annotations

from typing import Optional

_is_accelerator: Optional[bool] = None


def on_accelerator() -> bool:
    global _is_accelerator
    if _is_accelerator is None:
        import os

        if os.environ.get("SKYPLANE_TPU_FORCE_ACCEL_PATH") == "1":
            # test/debug override: exercise the device-kernel code paths
            # (batch runner, device CDC/fingerprints) on a CPU backend
            _is_accelerator = True
            return True
        # no try/except: with no accelerator jax answers with its CPU backend
        # without raising, so an error here is a chip that is held by another
        # process or a broken runtime — the daemon must not carry on as a
        # host-path gateway as if nothing happened
        import jax

        _is_accelerator = jax.devices()[0].platform != "cpu"
    return _is_accelerator
