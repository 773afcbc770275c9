import os

# Tests run on the CPU backend with 8 virtual devices, set BEFORE jax is
# imported anywhere, so sharding tests exercise a multi-chip mesh without TPU
# hardware. The chip is exercised by chip_smoke.py, one process, not by this
# suite; SKYPLANE_TPU_TEST_REAL_DEVICE=1 leaves the platform to the caller.
if not os.environ.get("SKYPLANE_TPU_TEST_REAL_DEVICE"):
    os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Keep test runs hermetic: never read the developer's real config file.
os.environ.setdefault("SKYPLANE_TPU_CONFIG_ROOT", "/tmp/skyplane_tpu_test_config")

# Persistent XLA compile cache: kernel shapes repeat across test runs, so this
# turns 30-60s CPU compiles into cache hits after the first full run. jax
# reads the variables at import, so this comes before anything imports it.
from skyplane_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()
