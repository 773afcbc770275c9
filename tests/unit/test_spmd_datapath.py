"""The data path sharded over a device mesh, held to the unsharded programs
and to the host pipeline (8 virtual CPU devices)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from skyplane_tpu.parallel.datapath_spmd import default_mesh

rng = np.random.default_rng(11)

CHUNK = 64 * 1024
BATCH = 4


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    return default_mesh()


def _batch():
    # mixed content: random, zeros, repeated pattern
    rows = []
    for i in range(BATCH):
        if i % 4 == 1:
            rows.append(np.zeros(CHUNK, np.uint8))
        elif i % 4 == 2:
            pat = rng.integers(0, 256, 1024, dtype=np.uint8)
            rows.append(np.tile(pat, CHUNK // 1024))
        else:
            rows.append(rng.integers(0, 256, CHUNK, dtype=np.uint8))
    return np.stack(rows)


def test_mesh_shape(mesh):
    assert mesh.shape["data"] * mesh.shape["seq"] == 8


@pytest.mark.parametrize("shard_axes", [("data",), None], ids=["data-axis", "all-axes"])
def test_sharded_kernels_equal_the_unsharded_programs(shard_axes):
    """``make_sharded_kernels`` over a (data 2, seq 2) mesh, rows spread over
    one axis or over all four devices: call A's packed candidates and call
    B's lanes equal the single-device programs', array for array."""
    from skyplane_tpu.ops.cdc import CDCParams, cdc_segment_ends
    from skyplane_tpu.ops.fused_cdc import _candidates_impl, _fp_impl, candidate_cap, make_sharded_kernels, slots_cap

    params = CDCParams(min_bytes=1024, avg_bytes=4096, max_bytes=16384)
    cap, n_slots = candidate_cap(CHUNK, params), slots_cap(CHUNK, params)
    batch = _batch()
    lens = np.asarray([CHUNK, CHUNK, CHUNK, CHUNK * 5 // 8 + 321], np.int32)
    batch[3, lens[3] :] = 0  # a tail row, zero-padded into the bucket
    ends_slots = np.full((BATCH, n_slots), CHUNK, np.int32)
    for i, n in enumerate(lens):
        ends = cdc_segment_ends(batch[i, :n], params)
        ends_slots[i, : len(ends)] = ends

    mesh = default_mesh(jax.devices()[:4], data_parallel=2)
    assert dict(mesh.shape) == {"data": 2, "seq": 2}
    cand_fn, fp_fn = make_sharded_kernels(mesh, params, CHUNK, shard_axes=shard_axes)
    packed = cand_fn(jnp.asarray(batch), jnp.asarray(lens))
    lanes = fp_fn(jnp.asarray(batch), jnp.asarray(ends_slots))
    n_shards = 2 if shard_axes else 4
    assert len({s.index for s in packed.addressable_shards}) == n_shards  # the rows really are spread

    want_packed = _candidates_impl(jnp.asarray(batch), jnp.asarray(lens), mask_bits=params.mask_bits, cap=cap)
    want_lanes = _fp_impl(jnp.asarray(batch), jnp.asarray(ends_slots), n_slots=n_slots)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(want_packed))
    np.testing.assert_array_equal(np.asarray(lanes), np.asarray(want_lanes))
    assert np.asarray(want_lanes)[0].any() and (np.asarray(want_packed)[:, cap] > 0).any()  # not a comparison of zeros


def test_meshed_batch_runner_matches_host_path(mesh):
    """The PRODUCTION batch runner (what gateway sender workers call) sharded
    over the mesh must produce bit-identical CDC boundaries and fingerprints
    to the single-device host pipeline (VERDICT r1 weak #4)."""
    from skyplane_tpu.ops.batch_runner import DeviceBatchRunner
    from skyplane_tpu.ops.cdc import CDCParams, cdc_segment_ends
    from skyplane_tpu.ops.fingerprint import segment_fingerprints_host_batch

    cdc = CDCParams()
    runner = DeviceBatchRunner(cdc_params=cdc, max_batch=8, mesh=mesh)
    local = np.random.default_rng(5)
    for trial in range(3):
        n = 1 << 16
        chunk = local.integers(0, 256, size=n, dtype=np.uint8)
        if trial == 1:
            chunk[: n // 3] = 0  # zero extents
        ends, fps = runner.cdc_and_fps(chunk, chunk)
        want_ends = cdc_segment_ends(chunk, cdc)
        want_fps = segment_fingerprints_host_batch(chunk, want_ends)
        np.testing.assert_array_equal(ends, want_ends)
        assert fps == want_fps


@pytest.mark.parametrize("n_devices,data_parallel", [(2, 1), (4, 2), (8, 2)], ids=["1x2", "2x2", "2x4"])
def test_meshed_runner_bit_identity_across_meshes(n_devices, data_parallel, monkeypatch):
    """ISSUE 18: the mesh-backed runner must be bit-identical to the host
    kernels on every viable mesh shape — 1x2, 2x2 and 2x4 — including a
    window that needs batch-dim padding (3 submissions into a 4-row window)
    and a near-duplicate corpus (the dedup REF workload). The structural
    assertion itself (SKYPLANE_TPU_SPMD_CHECK) is armed, so a diverging
    shard fails inside the runner, not in this test's comparisons."""
    from concurrent.futures import ThreadPoolExecutor

    from skyplane_tpu.ops.batch_runner import DeviceBatchRunner
    from skyplane_tpu.ops.cdc import CDCParams, cdc_segment_ends
    from skyplane_tpu.ops.fingerprint import segment_fingerprints_host_batch
    from skyplane_tpu.parallel.datapath_spmd import default_mesh

    monkeypatch.setenv("SKYPLANE_TPU_SPMD_CHECK", "1")
    cdc = CDCParams(min_bytes=1024, avg_bytes=4096, max_bytes=16384)
    mesh = default_mesh(jax.devices()[:n_devices], data_parallel=data_parallel)
    assert dict(mesh.shape) == {"data": data_parallel, "seq": n_devices // data_parallel}
    runner = DeviceBatchRunner(cdc_params=cdc, max_batch=4, max_wait_ms=50.0, mesh=mesh)
    local = np.random.default_rng(21)
    base = local.integers(0, 256, size=48_000, dtype=np.uint8)  # non-power-of-two -> padded bucket
    near_dup = base.copy()
    near_dup[1000:1100] = local.integers(0, 256, 100, dtype=np.uint8)
    zeros_head = base.copy()
    zeros_head[: len(base) // 3] = 0
    corpus = [base, near_dup, zeros_head]  # 3 rows -> one zero pad row in the 4-row window
    with ThreadPoolExecutor(max_workers=len(corpus)) as pool:
        results = list(pool.map(lambda c: runner.cdc_and_fps(c), corpus))
    for chunk, (ends, fps) in zip(corpus, results):
        want_ends = cdc_segment_ends(chunk, cdc)
        np.testing.assert_array_equal(ends, want_ends)
        assert fps == segment_fingerprints_host_batch(chunk, want_ends)
    # the near-dup shares almost every segment digest with its base — the
    # property the dedup index turns into REF spans downstream
    base_fps, dup_fps = set(results[0][1]), set(results[1][1])
    assert len(base_fps & dup_fps) > len(base_fps) // 2
    c = runner.counters()
    assert c["spmd_devices"] == n_devices
    assert c["spmd_batches"] >= 1
    assert c["spmd_check_batches"] >= 1, "the structural bit-identity assertion must have run"
    assert c["batch_padded_rows"] >= 1, "3 rows into a 4-row mesh window must pad"


def test_spmd_mode_parsing(monkeypatch):
    from skyplane_tpu.parallel.datapath_spmd import spmd_mode

    monkeypatch.delenv("SKYPLANE_TPU_SPMD", raising=False)
    assert spmd_mode() == "auto"
    for raw, want in (("0", "off"), ("off", "off"), ("no", "off"), ("1", "on"),
                      ("ON", "on"), ("force", "on"), ("auto", "auto"), ("bogus", "auto")):
        monkeypatch.setenv("SKYPLANE_TPU_SPMD", raw)
        assert spmd_mode() == want, raw


def _broken_backend():
    raise RuntimeError("Unable to initialize backend 'tpu': the chip is held by another process")


def test_maybe_default_mesh_off_and_backend_error_propagates(monkeypatch):
    """SKYPLANE_TPU_SPMD=off always yields None; a backend that fails to
    initialize RAISES — with no accelerator jax answers with its CPU backend
    without raising, so an error is a broken chip, not a reason to carry on
    single-device."""
    from skyplane_tpu.parallel import datapath_spmd

    monkeypatch.setenv("SKYPLANE_TPU_SPMD", "off")
    assert datapath_spmd.maybe_default_mesh() is None
    monkeypatch.delenv("SKYPLANE_TPU_SPMD", raising=False)

    monkeypatch.setattr(datapath_spmd.jax, "devices", _broken_backend)
    with pytest.raises(RuntimeError, match="held by another process"):
        datapath_spmd.maybe_default_mesh()
    # an odd device count is not an error: no mesh, single-device
    monkeypatch.setattr(datapath_spmd.jax, "devices", lambda: [object()] * 3)
    assert datapath_spmd.maybe_default_mesh() is None


def test_on_accelerator_backend_error_propagates(monkeypatch):
    """ops/backend.on_accelerator must not turn a failed backend into a quiet
    host-path gateway (and must not cache an answer it never got)."""
    from skyplane_tpu.ops import backend

    monkeypatch.delenv("SKYPLANE_TPU_FORCE_ACCEL_PATH", raising=False)
    monkeypatch.setattr(backend, "_is_accelerator", None)
    monkeypatch.setattr(jax, "devices", _broken_backend)
    with pytest.raises(RuntimeError, match="held by another process"):
        backend.on_accelerator()
    assert backend._is_accelerator is None


def test_force_host_devices_env(monkeypatch):
    """The spawn-safe harness helper: XLA_FLAGS gains (or replaces) the
    forced-host device count, other flags survive, JAX_PLATFORMS pins cpu,
    and the caller's env dict is never mutated."""
    from skyplane_tpu.parallel.datapath_spmd import force_host_devices_env

    base = {"XLA_FLAGS": "--xla_cpu_foo=1 --xla_force_host_platform_device_count=8", "PATH": "/bin"}
    env = force_host_devices_env(4, base_env=base)
    assert env["XLA_FLAGS"] == "--xla_cpu_foo=1 --xla_force_host_platform_device_count=4"
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["PATH"] == "/bin"
    assert base["XLA_FLAGS"].endswith("count=8"), "base env must not be mutated"
    env2 = force_host_devices_env(2, base_env={"PATH": "/bin"})
    assert env2["XLA_FLAGS"] == "--xla_force_host_platform_device_count=2"
    # default base: the process environment (conftest pins 8 virtual devices)
    env3 = force_host_devices_env(4)
    assert "--xla_force_host_platform_device_count=4" in env3["XLA_FLAGS"]
    assert env3["XLA_FLAGS"].count("xla_force_host_platform_device_count") == 1


def test_meshed_batch_runner_concurrent_submissions(mesh):
    """Multiple worker threads share the meshed runner: the micro-batching
    window must batch them through the sharded kernels correctly."""
    from concurrent.futures import ThreadPoolExecutor

    from skyplane_tpu.ops.batch_runner import DeviceBatchRunner
    from skyplane_tpu.ops.cdc import CDCParams, cdc_segment_ends
    from skyplane_tpu.ops.fingerprint import segment_fingerprints_host_batch

    cdc = CDCParams()
    runner = DeviceBatchRunner(cdc_params=cdc, max_batch=8, mesh=mesh)
    local = np.random.default_rng(6)
    chunks = [local.integers(0, 256, size=1 << 16, dtype=np.uint8) for _ in range(8)]
    with ThreadPoolExecutor(max_workers=8) as pool:
        results = list(pool.map(lambda c: runner.cdc_and_fps(c, c), chunks))
    for chunk, (ends, fps) in zip(chunks, results):
        want_ends = cdc_segment_ends(chunk, cdc)
        np.testing.assert_array_equal(ends, want_ends)
        assert fps == segment_fingerprints_host_batch(chunk, want_ends)
