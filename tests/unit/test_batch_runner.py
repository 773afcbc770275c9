"""DeviceBatchRunner: batched results must equal the sequential path, under
real concurrency (the device kernels run on the CPU backend in tests)."""

import threading

import numpy as np
import pytest

from skyplane_tpu.ops.batch_runner import DeviceBatchRunner
from skyplane_tpu.ops.cdc import CDCParams, cdc_segment_ends
from skyplane_tpu.ops.fingerprint import segment_fingerprints_host_batch

rng = np.random.default_rng(9)

PARAMS = CDCParams(min_bytes=1024, avg_bytes=4096, max_bytes=16384)


def _pad(arr):
    bucket = 1 << 16
    while bucket < len(arr):
        bucket <<= 1
    return np.concatenate([arr, np.zeros(bucket - len(arr), np.uint8)]) if len(arr) != bucket else arr


def _chunk(i, n=100_000):
    if i % 3 == 0:
        return rng.integers(0, 256, n, dtype=np.uint8)
    if i % 3 == 1:
        pat = rng.integers(0, 256, 4096, dtype=np.uint8)
        return np.tile(pat, n // 4096 + 1)[:n].copy()
    return np.concatenate([np.zeros(n // 2, np.uint8), rng.integers(0, 256, n - n // 2, dtype=np.uint8)])


def _expected(arr):
    ends = cdc_segment_ends(arr, PARAMS)
    return ends, segment_fingerprints_host_batch(arr, ends)


def test_concurrent_batch_matches_sequential():
    runner = DeviceBatchRunner(cdc_params=PARAMS, max_batch=4, max_wait_ms=20.0)
    chunks = [_chunk(i) for i in range(8)]
    results = [None] * 8
    errors = []

    def worker(i):
        try:
            results[i] = runner.cdc_and_fps(chunks[i], _pad(chunks[i]))
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    for i, chunk in enumerate(chunks):
        ends, fps = results[i]
        want_ends, want_fps = _expected(chunk)
        np.testing.assert_array_equal(ends, want_ends)
        assert fps == want_fps, f"chunk {i} fingerprints diverge between batched and sequential paths"


def test_single_submission_not_held_hostage():
    """A lone chunk must complete after ~max_wait, not wait for a full batch."""
    import time

    runner = DeviceBatchRunner(cdc_params=PARAMS, max_batch=8, max_wait_ms=10.0)
    chunk = _chunk(0, n=70_000)
    # warm the kernels so the timing assertion measures the window, not compile
    runner.cdc_and_fps(chunk, _pad(chunk))
    t0 = time.perf_counter()
    ends, fps = runner.cdc_and_fps(chunk, _pad(chunk))
    assert time.perf_counter() - t0 < 30  # bounded (compile-free) latency
    want_ends, want_fps = _expected(chunk)
    np.testing.assert_array_equal(ends, want_ends)
    assert fps == want_fps


def test_cross_bucket_traffic_does_not_starve_lone_flush():
    """The adaptive window holds a flush only while ITS OWN bucket's previous
    batch runs — sustained in-flight work in another bucket must not defer a
    lone chunk past its max_wait deadline (regression: a global busy gate
    starved small-bucket tail chunks under load)."""
    import threading
    import time

    runner = DeviceBatchRunner(cdc_params=PARAMS, max_batch=8, max_wait_ms=10.0)
    big = _chunk(1, n=120_000)
    small = _chunk(2, n=60_000)
    runner.cdc_and_fps(small, _pad(small))  # warm the small bucket's kernels
    # hold the BIG bucket 'in flight' by pinning a slow batch through the
    # fused layer (monkeypatched): the small bucket's flush must not wait
    real_fused = runner._fused

    class SlowFused:
        mesh = None
        rows_per_dispatch = real_fused.rows_per_dispatch

        def stage(self, arr):
            return real_fused.stage(arr)

        def dispatch(self, rows, lens, dev_rows=None):
            if (rows[0].shape[-1] if hasattr(rows[0], "shape") else len(rows[0])) == len(_pad(big)):
                time.sleep(1.5)
            return real_fused.dispatch(rows, lens, dev_rows=dev_rows)

    runner._fused = SlowFused()
    t_big = threading.Thread(target=runner.cdc_and_fps, args=(big, _pad(big)), daemon=True)
    t_big.start()
    time.sleep(0.2)  # big bucket is now mid-flight
    t0 = time.perf_counter()
    ends, fps = runner.cdc_and_fps(small, _pad(small))
    elapsed = time.perf_counter() - t0
    t_big.join(timeout=30)
    assert elapsed < 1.0, f"lone small-bucket flush starved {elapsed:.2f}s by cross-bucket traffic"
    want_ends, want_fps = _expected(small)
    np.testing.assert_array_equal(ends, want_ends)
    assert fps == want_fps


def test_error_wakes_all_waiters():
    runner = DeviceBatchRunner(cdc_params=PARAMS, max_batch=4, max_wait_ms=10.0)
    bad = np.zeros(10, np.uint8)  # padded shorter than arr -> stack/shape error in batch

    with pytest.raises(BaseException):
        runner.cdc_and_fps(bad, np.zeros(4, np.uint8))

def test_mesh_axis_selection_bounds_window_inflation():
    """A mesh larger than the batch window must not inflate the window past
    2x: the runner falls back to data-axis-only sharding, or unsharded."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices()[:8])
    mesh = Mesh(devs.reshape(2, 4), axis_names=("data", "seq"))
    # window smaller than the 8-device flat count but >= data axis (2)
    runner = DeviceBatchRunner(cdc_params=PARAMS, max_batch=3, max_wait_ms=5.0, mesh=mesh)
    assert runner.shard_axes == ("data",)
    assert runner.max_batch == 4  # rounded to the data axis, not to 8
    chunk = _chunk(0, n=70_000)
    ends, fps = runner.cdc_and_fps(chunk, _pad(chunk))
    want_ends, want_fps = _expected(chunk)
    np.testing.assert_array_equal(ends, want_ends)
    assert fps == want_fps
    # window smaller than every axis: mesh is dropped entirely
    runner2 = DeviceBatchRunner(cdc_params=PARAMS, max_batch=1, max_wait_ms=5.0, mesh=mesh)
    assert runner2.mesh is None and runner2.max_batch == 1


def test_wedged_in_flight_batch_does_not_defer_leader_forever():
    """ADVICE r5: the leader's window-deferral loop must have a hard ceiling.
    With a same-bucket batch permanently 'in flight' (wedged fused call), the
    leader used to busy-poll forever, never reaching the 600s entry.done
    backstop; now it flushes at defer_ceiling_s and completes."""
    import time

    runner = DeviceBatchRunner(cdc_params=PARAMS, max_batch=8, max_wait_ms=10.0)
    chunk = _chunk(0, n=70_000)
    runner.cdc_and_fps(chunk, _pad(chunk))  # warm kernels (compile off the clock)
    # simulate a wedged in-flight batch for this bucket: the counter never
    # returns to 0 (a hung fused call holds it in _run_batch's try body)
    bucket = len(_pad(chunk))
    with runner._lock:
        runner._in_flight[bucket] = 1
    runner.defer_ceiling_s = 0.3
    t0 = time.perf_counter()
    ends, fps = runner.cdc_and_fps(chunk, _pad(chunk))
    elapsed = time.perf_counter() - t0
    assert elapsed < 30, f"leader still deferring {elapsed:.1f}s past the hard ceiling"
    want_ends, want_fps = _expected(chunk)
    np.testing.assert_array_equal(ends, want_ends)
    assert fps == want_fps


@pytest.mark.parametrize("raw", ["inf", "nan", "-5", "1e12", "bogus"])
def test_batch_wait_env_rejects_nonfinite_and_clamps(monkeypatch, raw):
    """ADVICE r2: a typo'd SKYPLANE_TPU_BATCH_WAIT_MS (inf/nan/huge) must not
    make a partially filled window's leader wait forever."""
    monkeypatch.setenv("SKYPLANE_TPU_BATCH_WAIT_MS", raw)
    runner = DeviceBatchRunner(cdc_params=PARAMS, max_batch=4)
    assert 0 <= runner.max_wait_s <= 5.0
