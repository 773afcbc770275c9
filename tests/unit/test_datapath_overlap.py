"""Overlap-scheduled sender path: two-phase batch completion, HBM donation,
sharded stats, striped dedup index, and condition-driven window formation.
Device kernels run on the XLA-CPU backend; the scheduling logic is identical."""

import threading
import time

import numpy as np
import pytest

from skyplane_tpu.ops.batch_runner import DeviceBatchRunner
from skyplane_tpu.ops.cdc import CDCParams, cdc_segment_ends
from skyplane_tpu.ops.fingerprint import segment_fingerprints_host_batch

rng = np.random.default_rng(21)

PARAMS = CDCParams(min_bytes=1024, avg_bytes=4096, max_bytes=16384)


def _expected(arr):
    ends = cdc_segment_ends(arr, PARAMS)
    return ends, segment_fingerprints_host_batch(arr, ends)


# ---- two-phase completion ----


def test_submit_two_phase_results_exact():
    runner = DeviceBatchRunner(cdc_params=PARAMS, max_batch=4, max_wait_ms=5.0)
    chunk = rng.integers(0, 256, 70_000, dtype=np.uint8)
    handle = runner.submit(chunk)
    ends = handle.ends()
    # boundary-dependent work happens here, before fps are demanded
    spans = list(zip(np.concatenate([[0], ends[:-1]]), ends))
    fps = handle.fps()
    want_ends, want_fps = _expected(chunk)
    np.testing.assert_array_equal(ends, want_ends)
    assert fps == want_fps
    assert len(spans) == len(fps)
    assert handle.fps() is fps  # idempotent


def test_ends_ready_fires_before_fingerprint_readback():
    """A non-leader waiter must wake on phase 1 (ends) while the fingerprint
    lanes readback is still in flight. The fused driver is wrapped so the
    lanes materialization blocks until released; the leader is stuck inside
    it, and the JOINER must still observe its ends — if ends waited for
    phase 2, got_ends would never be set before the release."""
    runner = DeviceBatchRunner(cdc_params=PARAMS, max_batch=8, max_wait_ms=500.0)
    chunk = rng.integers(0, 256, 70_000, dtype=np.uint8)
    runner.cdc_and_fps(chunk)  # warm kernels

    real_fused = runner._fused
    release_lanes = threading.Event()

    class SlowLanesPending:
        def __init__(self, pending):
            self._p = pending
            self.ends_rows = pending.ends_rows
            self.fallback = pending.fallback

        def lanes(self):
            release_lanes.wait(timeout=30)
            return self._p.lanes()

    class SlowLanesFused:
        mesh = None
        rows_per_dispatch = real_fused.rows_per_dispatch

        def stage(self, arr):
            return real_fused.stage(arr)

        def dispatch(self, rows, lens, dev_rows=None):
            return SlowLanesPending(real_fused.dispatch(rows, lens, dev_rows=dev_rows))

    runner._fused = SlowLanesFused()
    got_ends = threading.Event()
    result = {}

    def leader():
        result["leader"] = runner.cdc_and_fps(chunk)  # blocks inside lanes()

    def joiner():
        handle = runner.submit(chunk)  # joins the leader's open window
        result["ends"] = handle.ends()
        got_ends.set()
        result["fps"] = handle.fps()

    t_lead = threading.Thread(target=leader, daemon=True)
    t_lead.start()
    time.sleep(0.1)  # well inside the 500 ms window
    t_join = threading.Thread(target=joiner, daemon=True)
    t_join.start()
    assert got_ends.wait(timeout=10), "ends-ready never fired while lanes readback was blocked"
    assert "fps" not in result
    release_lanes.set()
    t_join.join(timeout=30)
    t_lead.join(timeout=30)
    assert not t_join.is_alive() and not t_lead.is_alive()
    want_ends, want_fps = _expected(chunk)
    np.testing.assert_array_equal(result["ends"], want_ends)
    assert result["fps"] == want_fps
    np.testing.assert_array_equal(result["leader"][0], want_ends)
    assert result["leader"][1] == want_fps


def test_full_window_wakes_leader_immediately():
    """With a long max_wait, a window filling must flush NOW via the
    condition, not after the leader's deadline poll."""
    runner = DeviceBatchRunner(cdc_params=PARAMS, max_batch=2, max_wait_ms=2000.0)
    chunk = rng.integers(0, 256, 70_000, dtype=np.uint8)
    runner.cdc_and_fps(chunk)  # warm (lone flush; compiles the B=1 program)
    # warm the B=2 full-window program too (different batch shape)
    t_w = [threading.Thread(target=runner.cdc_and_fps, args=(chunk,)) for _ in range(2)]
    for t in t_w:
        t.start()
    for t in t_w:
        t.join(timeout=120)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=runner.cdc_and_fps, args=(chunk,)) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    elapsed = time.perf_counter() - t0
    assert elapsed < 1.5, f"full window waited {elapsed:.2f}s — leader slept through the flush event"


def test_batch_occupancy_counters():
    runner = DeviceBatchRunner(cdc_params=PARAMS, max_batch=2, max_wait_ms=5.0)
    chunk = rng.integers(0, 256, 70_000, dtype=np.uint8)
    for _ in range(3):
        runner.cdc_and_fps(chunk)  # lone flushes: occupancy 0.5 each at window size 2
    c = runner.counters()
    assert c["batch_windows"] == 3 and c["batch_rows"] == 3
    assert 0 < c["batch_occupancy"] <= 1.0


# ---- staging-failure diagnosability ----


def test_stage_failure_logged_once_per_bucket_and_counted():
    runner = DeviceBatchRunner(cdc_params=PARAMS, max_batch=2, max_wait_ms=2.0)
    chunk = rng.integers(0, 256, 70_000, dtype=np.uint8)
    runner.cdc_and_fps(chunk)  # warm
    warnings_seen = []
    runner._warn = warnings_seen.append  # instance attr shadows the staticmethod

    real_fused = runner._fused
    real_stage = real_fused.stage

    def flaky_stage(padded):
        raise RuntimeError("simulated H2D failure")

    real_fused.stage = flaky_stage
    try:
        for _ in range(3):
            ends, fps = runner.cdc_and_fps(chunk)  # host-upload fallback at flush
            want_ends, want_fps = _expected(chunk)
            np.testing.assert_array_equal(ends, want_ends)
            assert fps == want_fps
    finally:
        real_fused.stage = real_stage
    assert runner.counters()["stage_failures"] == 3
    stage_warnings = [m for m in warnings_seen if "staging failed" in m]
    assert len(stage_warnings) == 1, f"expected ONE throttled warning, got {len(stage_warnings)}"


# ---- HBM donation ----


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_donated_fp_call_bitexact_and_counted():
    from skyplane_tpu.ops.fused_cdc import FusedCDCFP

    chunk = rng.integers(0, 256, 70_000, dtype=np.uint8)
    padded = np.concatenate([chunk, np.zeros((1 << 17) - len(chunk), np.uint8)])
    plain = FusedCDCFP(PARAMS, donate=False)
    donating = FusedCDCFP(PARAMS, donate=True)
    want = plain(padded[None, :].copy(), [len(chunk)])  # 2D contiguous: never donated
    got = donating([padded, np.zeros_like(padded)], [len(chunk), 0])  # list form: donated
    np.testing.assert_array_equal(got[0][0], want[0][0])
    assert got[0][1] == want[0][1]
    assert donating.counters()["donated_batches"] == 1
    assert plain.counters()["donated_batches"] == 0


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_caller_provided_2d_batch_never_donated():
    """A contiguous caller batch must stay valid after the call — donation
    would let XLA invalidate (or scribble on an aliased) caller array."""
    from skyplane_tpu.ops.fused_cdc import FusedCDCFP

    chunk = rng.integers(0, 256, 1 << 16, dtype=np.uint8)
    batch = chunk[None, :].copy()
    before = batch.copy()
    fused = FusedCDCFP(PARAMS, donate=True)
    fused(batch, [len(chunk)])
    np.testing.assert_array_equal(batch, before)
    assert fused.counters()["donated_batches"] == 0


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize("accel", [True, False], ids=["accelerator", "cpu"])
def test_donation_follows_the_backend_and_reads_no_environment(accel, monkeypatch):
    """``donate=None`` is ``on_accelerator()``: the old switch, set to the
    opposite, moves nothing, and on an accelerator a batch the driver owns
    goes through the donated variant of call B."""
    import skyplane_tpu.ops.backend as backend
    from skyplane_tpu.ops.fused_cdc import FusedCDCFP

    monkeypatch.setenv("SKYPLANE_TPU_" + "DONATE", "0" if accel else "1")
    monkeypatch.setattr(backend, "_is_accelerator", accel)
    fused = FusedCDCFP(PARAMS)
    assert fused.donate is accel
    row = rng.integers(0, 256, 1 << 16, dtype=np.uint8)
    fused([row], [len(row)])  # list form: the driver stacks it, so it owns the batch
    assert fused.counters()["donated_batches"] == int(accel)


# ---- sharded DataPathStats ----


def test_stats_sharded_counters_exact_across_threads():
    from skyplane_tpu.ops.pipeline import DataPathStats, ProcessedPayload
    from skyplane_tpu.chunk import Codec

    stats = DataPathStats()
    N, T = 500, 8

    def worker():
        for _ in range(N):
            stats.observe(
                ProcessedPayload(
                    wire_bytes=b"x" * 10, codec=Codec.NONE, is_compressed=False, is_recipe=True,
                    raw_len=100, fingerprint="0" * 32, n_segments=3, n_ref_segments=1,
                )
            )
            stats.observe_device_wait(5)

    threads = [threading.Thread(target=worker) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    d = stats.as_dict()
    assert d["chunks"] == N * T
    assert d["raw_bytes"] == 100 * N * T and d["wire_bytes"] == 10 * N * T
    assert d["segments"] == 3 * N * T and d["ref_segments"] == N * T
    assert d["device_wait_ns"] == 5 * N * T
    assert d["compression_ratio"] == pytest.approx(10.0)


def test_stats_schema_stable_and_sources_merge():
    from skyplane_tpu.ops.pipeline import DataPathStats

    stats = DataPathStats()
    d = stats.as_dict()
    for key in DataPathStats.EXTERNAL_ZERO:
        assert key in d, f"counter key {key} missing from the stable schema"
    stats.add_source(lambda: {"pool_hits": 7, "pool_hit_rate": 0.9})
    d = stats.as_dict()
    assert d["pool_hits"] == 7 and d["pool_hit_rate"] == 0.9
    assert d["batch_windows"] == 0  # untouched keys keep their zero default


# ---- striped SenderDedupIndex ----


def _present_no_touch(idx, fp):
    """Membership WITHOUT refreshing recency (__contains__ touches)."""
    s = idx._stripe(fp)
    with s.lock:
        return fp in s.lru


def test_striped_index_global_lru_eviction_order():
    from skyplane_tpu.ops.dedup import SenderDedupIndex

    idx = SenderDedupIndex(max_bytes=1000, stripes=8)
    fps = [bytes([i]) * 16 for i in range(10)]
    for fp in fps:
        idx.add(fp, 100)
    assert fps[0] in idx  # touch: fp0 becomes globally most-recent
    idx.add(bytes([10]) * 16, 100)  # 1100 bytes > 1000: evicts globally-oldest (fp1)
    assert not _present_no_touch(idx, fps[1]), "eviction ignored the global recency order"
    assert _present_no_touch(idx, fps[0]), "the touched entry was evicted despite being most-recent"


def test_striped_index_concurrent_bound_holds():
    from skyplane_tpu.ops.dedup import SenderDedupIndex

    idx = SenderDedupIndex(max_bytes=50_000, stripes=16)
    errs = []

    def worker(seed):
        r = np.random.default_rng(seed)
        try:
            for _ in range(400):
                fp = bytes(r.integers(0, 256, 16, dtype=np.uint8))
                if fp in idx:
                    continue
                idx.add(fp, int(r.integers(50, 500)))
                if r.integers(0, 4) == 0:
                    idx.discard(fp)
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errs, errs
    # the global byte bound holds once traffic quiesces (the safety contract:
    # the sender index must stay strictly below receiver capacity)
    total = sum(s.bytes for s in idx._stripes)
    assert total <= idx.max_bytes
    assert idx._bytes == total, "global byte accounting drifted from stripe totals"


def test_striped_index_single_stripe_degenerates_to_plain_lru():
    from skyplane_tpu.ops.dedup import SenderDedupIndex

    idx = SenderDedupIndex(max_bytes=300, stripes=1)
    for i in range(5):
        idx.add(bytes([i]) * 16, 100)
    assert len(idx) == 3
    assert bytes([4]) * 16 in idx and bytes([0]) * 16 not in idx


# ---- pooled + phased processor path vs host path (end-to-end exactness) ----


def test_processor_pooled_phased_path_bitexact_vs_host(monkeypatch):
    """DataPathProcessor routed through the batch runner (pooled padding,
    two-phase completion, donation) must produce byte-identical wire frames
    to the pure host path — the acceptance bar for this whole subsystem."""
    from skyplane_tpu.ops.dedup import SenderDedupIndex
    from skyplane_tpu.ops.pipeline import DataPathProcessor

    data1 = rng.integers(0, 256, 200_000, dtype=np.uint8).tobytes()
    data2 = bytes(np.concatenate([np.frombuffer(data1, np.uint8)[:150_000],
                                  rng.integers(0, 256, 50_000, dtype=np.uint8)]))

    host = DataPathProcessor(codec_name="none", dedup=True, cdc_params=PARAMS)
    runner = DeviceBatchRunner(cdc_params=PARAMS, max_batch=2, max_wait_ms=2.0)
    dev = DataPathProcessor(codec_name="none", dedup=True, cdc_params=PARAMS, batch_runner=runner)

    inputs = (data1, data2, data1)
    idx_h = SenderDedupIndex()
    host_payloads = [host.process(data, idx_h) for data in inputs]  # before the patch: true host path
    monkeypatch.setattr(DataPathProcessor, "_on_accelerator", staticmethod(lambda: True))
    idx_d = SenderDedupIndex()
    for data, p_h in zip(inputs, host_payloads):
        p_d = dev.process(data, idx_d)
        assert p_h.wire_bytes == p_d.wire_bytes
        assert p_h.fingerprint == p_d.fingerprint
        assert p_h.n_segments == p_d.n_segments
    d = dev.stats.as_dict()
    assert d["pool_hits"] + d["pool_misses"] > 0, "pooled padding never engaged"
    assert runner.pool.counters()["pool_outstanding"] == 0
