"""What the chip bring-up (PR 21) changed, held on CPU: the compile-cache
helper, the native build stamp, chip_smoke.py off the chip, and a bench.py
with no probe, supervisor or CPU fallback."""

from __future__ import annotations

import importlib.util
import inspect
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]


def _run(code_or_args, env_extra=None, cwd=REPO, timeout=300):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra or {})
    args = [sys.executable, "-c", code_or_args] if isinstance(code_or_args, str) else [sys.executable, *code_or_args]
    return subprocess.run(args, env=env, cwd=cwd, capture_output=True, text=True, timeout=timeout)


# ---- compile cache: one helper, placed from outside ----


def test_compile_cache_honours_env_else_fixed_in_checkout_dir(tmp_path):
    code = "from skyplane_tpu.utils.compile_cache import configure_compile_cache as c; import jax; print(c()); print(jax.config.jax_compilation_cache_dir)"
    placed = _run(code, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")})
    assert placed.returncode == 0, placed.stderr
    assert placed.stdout.split() == [str(tmp_path / "cc")] * 2
    default = _run(code)
    assert default.returncode == 0, default.stderr
    # fixed and inside the checkout: never /tmp, a mkdtemp name, a pid or a time
    assert default.stdout.split() == [str(REPO / ".jax_cache")] * 2


def test_conftest_sets_the_cache_before_jax_is_imported():
    """The ordering bug: conftest.py used to set the variable after importing
    jax, which reads it at import — the test cache had never been on."""
    code = (
        "import sys; import tests.conftest; assert 'jax' in sys.modules; import jax; "
        "print(jax.config.jax_compilation_cache_dir); print(jax.config.jax_persistent_cache_min_compile_time_secs)"
    )
    proc = _run(code)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(REPO / ".jax_cache"), "1.0"]


def test_no_other_place_sets_a_compile_cache_dir():
    hits = subprocess.run(
        ["git", "grep", "-lE", r"setdefault\(.JAX_COMPILATION_CACHE_DIR|/tmp/jax_compile_cache", "--", "*.py", "*.sh"],
        cwd=REPO, capture_output=True, text=True,
    ).stdout.split()
    assert [h for h in hits if h != "tests/unit/test_bring_up.py"] == []


# ---- native library: stamped with what it depends on ----


def _fresh_native(monkeypatch, build_dir):
    from skyplane_tpu import native

    monkeypatch.setenv("SKYPLANE_TPU_NATIVE_BUILD_DIR", str(build_dir))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_info", {})
    return native


def test_native_loader_ignores_a_planted_library_and_rebuilds_on_stamp_mismatch(tmp_path, monkeypatch):
    pytest.importorskip("ctypes")
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no g++")
    native = _fresh_native(monkeypatch, tmp_path)
    # a foreign .so (here: not even a library) with no stamp must never be loaded
    (tmp_path / "libskydp.so").write_bytes(b"not a shared object built here")
    native.load_library()
    info = native.build_info()
    assert info["built"] and info["stamp"] == native.build_stamp()
    assert (tmp_path / "libskydp.stamp").read_text() == native.build_stamp()
    assert (tmp_path / "libskydp.so").read_bytes()[:4] == b"\x7fELF"
    # a matching stamp is trusted: no rebuild
    native = _fresh_native(monkeypatch, tmp_path)
    native.load_library()
    assert not native.build_info()["built"]
    # a stamp from other sources / flags / another CPU is not
    (tmp_path / "libskydp.stamp").write_text("0" * 64)
    native = _fresh_native(monkeypatch, tmp_path)
    native.load_library()
    assert native.build_info()["built"]


def test_native_stamp_covers_sources_flags_and_cpu(monkeypatch):
    from skyplane_tpu import native

    base = native.build_stamp()
    monkeypatch.setattr(native, "_cpu_features", lambda: "another cpu")
    assert native.build_stamp() != base
    monkeypatch.undo()
    monkeypatch.setattr(native, "_NATIVE_FLAGS", ("-O2",))
    assert native.build_stamp() != base


# ---- chip_smoke.py off the chip ----


def test_chip_smoke_rehearsal_on_cpu_runs_the_flow_and_never_passes(tmp_path):
    proc = _run(
        ["chip_smoke.py", "--chunk-mb", "1", "--snapshots", "2", "--chunks-per-snapshot", "2", "--workdir", str(tmp_path)],
        {"JAX_PLATFORMS": "cpu", "SKYPLANE_TPU_FORCE_ACCEL_PATH": "1", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")},
        timeout=600,
    )
    assert proc.returncode != 0
    record_line, verdict_line = proc.stdout.strip().splitlines()[-2:]
    result, verdict = json.loads(record_line), json.loads(verdict_line)
    assert result["ok"] is False and result["platform"] == "cpu"
    # the last line is the verdict and nothing else: ok, and the device as jax reports it
    assert verdict == {"ok": False, "device": {"platform": "cpu", "kind": result["device_kind"], "count": result["n_devices"]}}
    assert isinstance(verdict["device"]["kind"], str) and isinstance(verdict["device"]["count"], int)
    assert "platform is 'cpu', not 'tpu'" in result["failed"]
    # the flow itself ran and was right: only size and platform failed it
    assert len(result["failed"]) == 2, result["failed"]
    assert result["byte_identical"] and result["counters"]["batch_rows"] == result["chunks"] == 4
    assert result["counters"]["stage_failures"] == 0 and result["counters"]["ref_segments"] > 0
    assert all(r["identical"] for r in result["reference"].values()) and len(result["reference"]) == 2
    assert result["compile_cache"]["dir"] == str(tmp_path / "cc") and os.listdir(tmp_path / "cc")
    assert not list(tmp_path.glob("chip_smoke_*")), "the smoke must remove its data"


def test_chip_smoke_at_full_size_off_the_chip_prints_no_result():
    proc = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "platform 'cpu'" in proc.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    (tmp_path / "chip_smoke.py").write_bytes((REPO / "chip_smoke.py").read_bytes())
    proc = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}, cwd=tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


# ---- bench.py: one process, the backend jax gives it ----


def test_bench_has_no_probe_supervisor_or_cpu_fallback():
    spec = importlib.util.spec_from_file_location("bench_bring_up", REPO / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    # (spelled in two pieces: the tree is grepped for the old names)
    for gone in ("probe" + "_device", "_run_accel_bench_supervised", "PROBE_FALLBACK", "_PROBE_SNIPPET"):
        assert not hasattr(bench, gone), gone
    source = (REPO / "bench.py").read_text()
    for knob in ("PLATFORM", "PROBE_BUDGET", "PROBE_TIMEOUT", "BUSY_BUDGET", "INIT_BUDGET", "CHILD"):
        assert f"SKYPLANE_BENCH_{knob}" not in source, knob
    main = inspect.getsource(bench.main)
    assert "jax_platforms" not in main and 'environ["JAX_PLATFORMS"]' not in main  # no platform pin in code
    for key in ('"platform"', '"device_kind"', '"n_devices"'):
        assert key in main, f"bench.py result line lacks {key}"
    sys.path.insert(0, str(REPO / "scripts"))
    try:
        import check_bench_json
    finally:
        sys.path.pop(0)
    assert {"platform", "device_kind", "n_devices"} <= set(check_bench_json.REQUIRED_TOP)
    assert "device" not in check_bench_json.REQUIRED_TOP


def test_pump_module_does_not_import_jax():
    """Pump workers pin JAX_PLATFORMS inside the spawned child; that holds
    only while importing the module itself leaves jax alone."""
    proc = _run("import sys, skyplane_tpu.gateway.pump; print('jax' in sys.modules)")
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "False"


# ---- one device path: no kernel-selection flag, no second formulation ----


def test_no_kernel_switch_and_no_device_blockpack_in_the_program():
    """(spelled in pieces: the tree is grepped for the old names)"""
    kernel_lib = "pal" + "las"
    gone = (
        "jax.experimental." + kernel_lib,
        "jax.experimental import " + kernel_lib,
        "SKYPLANE_TPU_USE_" + kernel_lib.upper(),
        "SKYPLANE_TPU_" + "DONATE",
    )
    sources = [*sorted((REPO / "skyplane_tpu").rglob("*.py")), REPO / "bench.py", REPO / "chip_smoke.py"]
    assert len(sources) > 100
    for path in sources:
        text = path.read_text()
        for name in gone:
            assert name not in text, f"{path.relative_to(REPO)} still has {name}"
    assert not (REPO / "skyplane_tpu" / "ops" / f"{kernel_lib}_kernels.py").exists()
    blockpack = (REPO / "skyplane_tpu" / "ops" / "blockpack.py").read_text()
    assert "import jax" not in blockpack and "from jax" not in blockpack
