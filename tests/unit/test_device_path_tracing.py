"""The device path's host steps: spans that tile the leader's time between
the two device programs, the bridge that writes them into a ``jax.profiler``
trace, and the always-on counters of ``/profile/compression`` that the
benchmark's per-layer metrics read (docs/observability.md "The device path").
"""

from __future__ import annotations

import glob
import os
import queue
import threading
import time

import numpy as np
import pytest

import skyplane_tpu.ops.fused_cdc as fused_mod
from skyplane_tpu.obs import NOOP_SPAN, configure_tracer, get_tracer
from skyplane_tpu.ops.batch_runner import DeviceBatchRunner
from skyplane_tpu.ops.cdc import CDCParams
from skyplane_tpu.ops.fused_cdc import FusedCDCFP
from skyplane_tpu.ops.pipeline import DataPathProcessor, DataPathStats

rng = np.random.default_rng(25)

PARAMS = CDCParams(min_bytes=1024, avg_bytes=4096, max_bytes=16384)
BUCKET = 1 << 16
#: every device-category site a lone submission passes, in the order one thread meets them
SITES = (
    "batch.stage",
    "batch.window_wait",
    "fused.stack",
    "fused.dispatch",
    "fused.select",
    "fused.enqueue_b",
    "fused.readback",
)
DISPATCH_SITES = SITES[2:]
NEW_KEYS = (
    "device_path_ns",
    "recipe_ns",
    "seal_ns",
    "fused_rows",
    "fused_gap_ns",
    "fused_gap_cpu_ns",
    "xla_compiles",
    "xla_compile_ns",
    "overflow_rows",  # PR 36
)


@pytest.fixture(autouse=True)
def _restore_tracer():
    yield
    configure_tracer()  # back to env defaults: a fresh, disabled tracer for the next test


def _chunk(n: int = 50_000) -> np.ndarray:
    return rng.integers(0, 256, n, dtype=np.uint8)


def _padded(chunk: np.ndarray, bucket: int = BUCKET) -> np.ndarray:
    return np.concatenate([chunk, np.zeros(bucket - len(chunk), np.uint8)])


def _spy_on_span(tracer) -> list:
    """Record (name, cat, what span() returned, thread) of every call, through
    the attribute the sites look up at call time."""
    calls = []
    real = tracer.span

    def span(name, trace_id=None, cat="", args=None, force=False):
        got = real(name, trace_id=trace_id, cat=cat, args=args, force=force)
        calls.append((name, cat, got, threading.get_ident()))
        return got

    tracer.span = span
    return calls


def _lone_submission(runner: DeviceBatchRunner):
    handle = runner.submit(_chunk())
    return handle.ends(), handle.fps()


# ---- (a) disabled: every site gets the shared no-op span


@pytest.mark.parametrize("site", SITES)
def test_disabled_tracer_gives_every_device_site_the_noop_span(site):
    runner = DeviceBatchRunner(cdc_params=PARAMS, max_batch=2, max_wait_ms=1.0)
    tracer = configure_tracer(sample=0.0)
    calls = _spy_on_span(tracer)
    _lone_submission(runner)
    got = [span for name, _cat, span, _tid in calls if name == site]
    assert got, f"{site} was not reached by a lone submission (sites seen: {[c[0] for c in calls]})"
    assert all(span is NOOP_SPAN for span in got)
    assert tracer.counters()["spans_recorded"] == 0


# ---- (b) enabled: sibling spans in order, disjoint, and no hole between A and B


def _device_spans(tracer) -> list:
    """(name, tid, start_us, end_us) of the recorded device spans, by start."""
    events = [e for e in tracer.export()["traceEvents"] if e.get("ph") == "X" and e.get("cat") == "device"]
    return [(e["name"], e["tid"], e["ts"], e["ts"] + e["dur"]) for e in sorted(events, key=lambda e: e["ts"])]


def test_one_dispatch_records_sibling_spans_that_tile_the_gap(monkeypatch):
    fused = FusedCDCFP(PARAMS)
    chunk = _chunk()
    batch = _padded(chunk)[None, :]
    fused(batch, [len(chunk)])  # compile outside what is timed
    tracer = configure_tracer(sample=1.0)
    before = fused.counters()
    real_select, real_fp = fused_mod.select_boundaries, fused_mod._fp_impl
    nap = 0.05

    def slow_select(*a, **kw):
        time.sleep(nap)
        return real_select(*a, **kw)

    def slow_fp(*a, **kw):
        time.sleep(nap)
        return real_fp(*a, **kw)

    monkeypatch.setattr(fused_mod, "select_boundaries", slow_select)
    monkeypatch.setattr(fused_mod, "_fp_impl", slow_fp)
    pending = fused.dispatch(batch, [len(chunk)])
    pending.lanes()

    spans = _device_spans(tracer)
    assert [s[0] for s in spans] == list(DISPATCH_SITES)
    assert len({s[1] for s in spans}) == 1, "one dispatch runs on one thread"
    # start is the wall clock's and the length the performance counter's: allow
    # the two clocks half a millisecond of disagreement, against naps of 50
    slack_us = 500.0
    for (name_a, _, _, end_a), (name_b, _, start_b, _) in zip(spans, spans[1:]):
        assert end_a <= start_b + slack_us, f"{name_a} overlaps {name_b}: siblings, not nested"
    by_name = {s[0]: s for s in spans}
    select, enqueue = by_name["fused.select"], by_name["fused.enqueue_b"]
    assert select[3] - select[2] >= nap * 1e6
    assert enqueue[3] - enqueue[2] >= nap * 1e6
    gap_us = enqueue[3] - by_name["fused.dispatch"][3]
    covered_us = (select[3] - select[2]) + (enqueue[3] - enqueue[2])
    assert covered_us >= 0.9 * gap_us, f"a hole between call A and call B: {covered_us:.0f} of {gap_us:.0f} us under a span"
    # the counter times the same interval
    assert fused.counters()["fused_gap_ns"] - before["fused_gap_ns"] >= 2 * nap * 1e9


# ---- (c) the bridge into the profile


def _profile_event_names(log_dir: str) -> set:
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    data = ProfileData.from_file(path)
    return {ev.name for plane in data.planes for line in plane.lines for ev in line.events}


@pytest.mark.parametrize("sample", [1.0, 0.0], ids=["enabled", "disabled"])
def test_device_spans_reach_a_jax_profile_only_when_the_tracer_is_on(sample, tmp_path):
    import jax

    fused = FusedCDCFP(PARAMS)
    chunk = _chunk()
    batch = _padded(chunk)[None, :]
    fused(batch, [len(chunk)])
    configure_tracer(sample=sample)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fused.dispatch(batch, [len(chunk)]).lanes()
    finally:
        jax.profiler.stop_trace()
    host = {n for n in _profile_event_names(str(tmp_path)) if n.startswith("host:")}
    if sample:
        assert {"host:fused.select", "host:fused.enqueue_b"} <= host, host
        assert host == {f"host:{site}" for site in DISPATCH_SITES}
    else:
        assert not host


def test_tracer_without_jax_keeps_the_ring_record(monkeypatch):
    import skyplane_tpu.obs.tracer as tracer_mod

    monkeypatch.setattr(tracer_mod, "_annotation_cls", False)  # what a failed import leaves
    tracer = configure_tracer(sample=1.0)
    with tracer.span("fused.select", cat="device"):
        pass
    assert tracer.counters()["spans_recorded"] == 1


# ---- (d) the benchmark's seam: a replacement installed on the singleton is what the sites call


@pytest.mark.parametrize("site", SITES)
def test_every_device_site_looks_span_up_at_call_time(site):
    runner = DeviceBatchRunner(cdc_params=PARAMS, max_batch=2, max_wait_ms=1.0)  # built BEFORE the swap, as the pair is
    seen = []

    def replacement(name, trace_id=None, cat="", args=None, force=False):
        seen.append((name, cat))
        return NOOP_SPAN

    get_tracer().span = replacement
    _lone_submission(runner)
    assert (site, "device") in seen, f"{site} did not go through get_tracer().span: {seen}"


# ---- (e) the gap and row counters


def test_gap_and_row_counters_count_real_rows_and_only_rise():
    fused = FusedCDCFP(PARAMS)
    chunk = _chunk()
    rows = [_padded(chunk), np.zeros(BUCKET, np.uint8)]  # one real row, one pad row
    snaps = [fused.counters()]
    for _ in range(2):
        fused(rows, [len(chunk), 0])
        snaps.append(fused.counters())
    assert [s["fused_rows"] for s in snaps] == [0, 1, 2]
    for before, after in zip(snaps, snaps[1:]):
        assert after["fused_gap_ns"] > before["fused_gap_ns"]
        assert after["fused_gap_cpu_ns"] >= before["fused_gap_cpu_ns"]
    assert 0 <= snaps[-1]["fused_gap_cpu_ns"] <= snaps[-1]["fused_gap_ns"]


# ---- (f) compiles


@pytest.fixture
def no_persistent_cache():
    """A program found in the persistent cache is a load, not a compile, and
    earlier runs of the suite may have left these shapes there."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def test_xla_compiles_rises_on_a_new_bucket_only(no_persistent_cache):
    params = CDCParams(min_bytes=512, avg_bytes=2048, max_bytes=8192)  # no other test's programs
    fused = FusedCDCFP(params)
    chunk = _chunk(20_000)
    small, large = _padded(chunk, 1 << 15)[None, :], _padded(chunk, 1 << 16)[None, :]
    c0 = fused.counters()
    fused(small, [len(chunk)])
    c1 = fused.counters()
    assert c1["xla_compiles"] >= c0["xla_compiles"] + 2, "call A and call B of a new bucket each compile"
    assert c1["xla_compile_ns"] > c0["xla_compile_ns"]
    fused(small, [len(chunk)])
    c2 = fused.counters()
    assert (c2["xla_compiles"], c2["xla_compile_ns"]) == (c1["xla_compiles"], c1["xla_compile_ns"])
    fused(large, [len(chunk)])
    assert fused.counters()["xla_compiles"] >= c2["xla_compiles"] + 2


def test_a_load_from_the_persistent_cache_is_not_a_compile(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    FusedCDCFP(PARAMS)  # the listener goes in with the first one built
    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    try:
        step = jax.jit(lambda x: (x * 3 + 1).sum())
        x = jnp.arange(2500, dtype=jnp.int32)
        c0 = fused_mod.compile_counters()["xla_compiles"]
        step(x).block_until_ready()
        c1 = fused_mod.compile_counters()["xla_compiles"]
        assert c1 == c0 + 1
        assert os.listdir(tmp_path), "the program was not written to the persistent cache"
        jax.clear_caches()  # forget it in memory: the next call asks the backend again
        step(x).block_until_ready()
        assert fused_mod.compile_counters()["xla_compiles"] == c1, "the cache hit was counted as a compile"
    finally:
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", old_min)
        compilation_cache.reset_cache()


# ---- (g) the per-chunk host steps


def test_device_path_and_recipe_time_counted_where_device_wait_reads_zero(monkeypatch):
    from skyplane_tpu.ops import backend
    from skyplane_tpu.ops.dedup import SenderDedupIndex

    runner = DeviceBatchRunner(cdc_params=PARAMS, max_batch=8, max_wait_ms=1.0)
    monkeypatch.setattr(backend, "_is_accelerator", True)  # the processor takes the device path on this CPU backend
    proc = DataPathProcessor(codec_name="none", dedup=True, cdc_params=PARAMS, batch_runner=runner)
    proc.process(_chunk().tobytes(), SenderDedupIndex())
    d = proc.stats.as_dict()
    assert d["chunks"] == 1 and d["batch_rows"] == 1
    assert d["device_wait_ns"] == 0, "a window of one row: its leader never waits on its own handle"
    assert d["device_path_ns"] > 0 and d["recipe_ns"] > 0
    assert d["seal_ns"] == 0
    assert d["fused_rows"] == 1 and d["fused_gap_ns"] > 0


@pytest.mark.parametrize("with_cipher", [True, False], ids=["cipher", "no-cipher"])
def test_seal_time_counted_only_with_a_cipher(with_cipher, tmp_path):
    from skyplane_tpu.chunk import Chunk, ChunkRequest
    from skyplane_tpu.gateway.chunk_store import ChunkStore
    from skyplane_tpu.gateway.crypto import generate_key
    from skyplane_tpu.gateway.gateway_queue import GatewayQueue
    from skyplane_tpu.gateway.operators.gateway_operator import GatewaySenderOperator

    store = ChunkStore(str(tmp_path / "chunks"))
    op = GatewaySenderOperator(
        handle="send",
        region="local:test",
        input_queue=GatewayQueue(),
        output_queue=None,
        error_event=threading.Event(),
        error_queue=queue.Queue(),
        chunk_store=store,
        target_gateway_id="gw_dst",
        target_host="127.0.0.1",
        target_control_port=1,
        codec_name="none",
        dedup=False,
        e2ee_key=generate_key() if with_cipher else None,
        use_tls=False,
    )
    data = _chunk(200_000).tobytes()
    chunk = Chunk(src_key="s", dest_key="d", chunk_id="ab" * 16, chunk_length_bytes=len(data))
    req = ChunkRequest(chunk=chunk, src_region="local:test", dst_region="local:test", src_type="local", dst_type="local")
    store.chunk_path(chunk.chunk_id).write_bytes(data)
    payload, wire, header = op._frame_chunk(req, None, n_left=0)
    assert payload.raw_len == len(data) and header.data_len == len(wire)
    seal_ns = op.datapath_counters()["seal_ns"]
    assert (seal_ns > 0) if with_cipher else (seal_ns == 0)


# ---- (g2) the REF path's two spans


def _send_twice_and_restore(trace_id: str):
    """One chunk sent twice (the second time all REFs) and restored; returns
    the second payload and what resolving its REFs counted."""
    from skyplane_tpu.chunk import ChunkFlags, WireProtocolHeader
    from skyplane_tpu.ops.dedup import SegmentStore, SenderDedupIndex

    data = _chunk(200_000).tobytes()
    proc = DataPathProcessor(codec_name="none", dedup=True, cdc_params=PARAMS)
    index, store = SenderDedupIndex(), SegmentStore()
    ref_stats: dict = {}
    for n in range(2):
        p = proc.process(data, index, trace_id=trace_id)
        for fp, size in p.new_fingerprints:
            index.add(fp, size)
        header = WireProtocolHeader(
            chunk_id=trace_id, data_len=len(p.wire_bytes), raw_data_len=p.raw_len, codec=int(p.codec),
            flags=int(ChunkFlags.RECIPE), fingerprint=p.fingerprint,
        )
        ref_stats = {}
        assert proc.restore(p.wire_bytes, header, store=store, ref_stats=ref_stats) == data
    return p, ref_stats


@pytest.mark.parametrize("span_name, cat", [("recipe.build", "sender"), ("decode.ref_resolve", "receiver")])
def test_ref_path_spans_record_when_enabled_and_carry_the_chunk_id(span_name, cat):
    tracer = configure_tracer(sample=1.0)
    p, ref_stats = _send_twice_and_restore("cd" * 16)
    assert p.n_ref_segments == p.n_segments and p.literal_bytes == 0
    assert ref_stats["ref_segments_resolved"] == p.n_segments and ref_stats["ref_bytes_resolved"] == p.raw_len
    events = [e for e in tracer.export()["traceEvents"] if e.get("name") == span_name]
    # recipe.build: once per process(); decode.ref_resolve: only the recipe that holds REFs
    assert len(events) == (2 if span_name == "recipe.build" else 1)
    assert all(e["cat"] == cat and e["args"]["chunk_id"] == "cd" * 16 for e in events)
    if span_name == "decode.ref_resolve":
        assert events[0]["dur"] * 1e3 <= ref_stats["ref_resolve_ns"]


@pytest.mark.parametrize("span_name", ["recipe.build", "decode.ref_resolve"])
def test_disabled_tracer_gives_the_ref_path_sites_the_noop_span(span_name):
    tracer = configure_tracer(sample=0.0)
    calls = _spy_on_span(tracer)
    _send_twice_and_restore("ef" * 16)
    got = [span for name, _cat, span, _tid in calls if name == span_name]
    assert len(got) == 2 and all(span is NOOP_SPAN for span in got)
    assert tracer.counters()["spans_recorded"] == 0


# ---- (g3) the codec's steps: counters and spans on both sides (PR 36)


def _compressible(n: int = 300_000) -> bytes:
    """Words from a small vocabulary, then a run of zeros: zstd shrinks the first, blockpack suppresses the second."""
    words = rng.integers(0x20, 0x40, (64, 8), dtype=np.uint8)
    text = words[rng.integers(0, 64, n // 8)].ravel()
    return np.concatenate([text, np.zeros(n // 4, np.uint8)]).tobytes()


#: codec name -> the steps its encode is made of that have a counter
CODEC_STEPS = {"none": (), "zstd": ("zstd",), "tpu": ("blockpack",), "tpu_zstd": ("blockpack", "zstd")}


@pytest.mark.parametrize("codec_name", sorted(CODEC_STEPS))
def test_encode_counters_and_spans_name_the_steps_of_the_codec_that_ran(codec_name):
    pytest.importorskip("zstandard")
    from skyplane_tpu.chunk import ChunkFlags, WireProtocolHeader
    from skyplane_tpu.ops.codecs import get_codec, timed_encoder
    from skyplane_tpu.ops.dedup import SegmentStore, SenderDedupIndex

    tracer = configure_tracer(sample=1.0)
    data, trace_id = _compressible(), "36" * 16
    proc = DataPathProcessor(codec_name=codec_name, dedup=True, cdc_params=PARAMS)
    p = proc.process(data, SenderDedupIndex(), trace_id=trace_id)
    d = proc.stats.as_dict()
    steps = CODEC_STEPS[codec_name]
    assert [k for k in ("blockpack", "zstd") if d[f"{k}_ns"] > 0] == list(steps)
    assert d["blockpack_ns"] + d["zstd_ns"] <= d["recipe_encode_ns"] <= d["recipe_ns"]
    # a recipe is 7 bytes of head, 25 bytes an entry, and the encoded literal blob
    assert d["literal_blob_bytes"] == p.literal_blob_bytes == len(p.wire_bytes) - 7 - 25 * p.n_segments > 0
    if steps:
        assert p.literal_blob_bytes < p.literal_bytes  # the content compresses under every step
    else:
        assert p.literal_blob_bytes == p.literal_bytes
    # taken step by step the codec gives the bytes it gives in one piece
    spec, timings = get_codec(codec_name), {}
    assert bytes(timed_encoder(spec, timings)(data)) == bytes(spec.encode(data))
    assert sorted(timings) == sorted(f"{k}_ns" for k in steps)

    header = WireProtocolHeader(
        chunk_id=trace_id, data_len=len(p.wire_bytes), raw_data_len=p.raw_len, codec=int(p.codec),
        flags=int(ChunkFlags.RECIPE), fingerprint=p.fingerprint,
    )
    ref_stats: dict = {}
    assert proc.restore(p.wire_bytes, header, store=SegmentStore(), ref_stats=ref_stats) == data
    assert 0 < ref_stats["blob_decode_ns"] <= ref_stats["literal_pass_ns"]

    events = {}
    for e in tracer.export()["traceEvents"]:
        if e.get("name") in ("recipe.build", "codec.blockpack", "codec.zstd", "decode.blob"):
            assert e["args"]["chunk_id"] == trace_id and e["name"] not in events
            events[e["name"]] = e
    assert sorted(events) == sorted(["recipe.build", "decode.blob"] + [f"codec.{k}" for k in steps])
    assert events["decode.blob"]["cat"] == "receiver" and events["decode.blob"]["dur"] * 1e3 <= ref_stats["blob_decode_ns"]
    build = events["recipe.build"]
    for k in steps:  # each step's span lies inside recipe.build and under its counter
        e = events[f"codec.{k}"]
        assert e["cat"] == "sender" and build["ts"] <= e["ts"] and e["ts"] + e["dur"] <= build["ts"] + build["dur"]
        assert e["dur"] * 1e3 <= d[f"{k}_ns"]


# ---- (h) the schema is stable, and served


def test_new_keys_zero_filled_with_no_runner_and_no_cipher():
    d = DataPathProcessor(codec_name="none", dedup=False).stats.as_dict()
    for key in NEW_KEYS:
        assert d[key] == 0 and not isinstance(d[key], bool), key
    assert set(NEW_KEYS) <= set(DataPathStats._KEYS) | set(DataPathStats.EXTERNAL_ZERO)


def test_loopback_pair_serves_the_new_keys_as_numbers(tmp_path):
    from tests.integration.harness import make_pair

    src, dst = make_pair(tmp_path, compress="none", dedup=False, encrypt=False, use_tls=False, num_connections=1)
    try:
        served = src.get("profile/compression", timeout=10).json()
    finally:
        src.stop()
        dst.stop()
    for key in NEW_KEYS:
        assert isinstance(served.get(key), (int, float)) and not isinstance(served[key], bool), (key, served.get(key))


#: the REF path's counters (PR 27): where each is served
REF_PATH_KEYS = (
    ("profile/compression", "literal_bytes"),
    ("profile/compression", "recipe_encode_ns"),
    ("profile/decode", "ref_resolve_ns"),
    ("profile/decode", "ref_segments_resolved"),
    ("profile/decode", "ref_bytes_resolved"),
    # the literal pass's (PR 30): served by the same route, zero where no recipe was parsed
    ("profile/decode", "literal_pass_ns"),
    ("profile/decode", "literal_segments_verified"),
    ("profile/decode", "literal_verify_calls"),
    # the codec's steps on both sides (PR 36)
    ("profile/compression", "blockpack_ns"),
    ("profile/compression", "zstd_ns"),
    ("profile/compression", "literal_blob_bytes"),
    ("profile/decode", "blob_decode_ns"),
)


@pytest.fixture(scope="module")
def served_with_dedup_off(tmp_path_factory):
    """Both endpoints of a loopback pair that moved one chunk with dedup off:
    no recipe was built and none was parsed."""
    pytest.importorskip("zstandard")
    from tests.integration.harness import dispatch_file, make_pair, wait_complete

    tmp = tmp_path_factory.mktemp("dedup_off")
    (tmp / "in.bin").write_bytes(_chunk(200_000).tobytes())
    # a codec, so that the chunk goes through the processor and not the raw passthrough
    src, dst = make_pair(tmp, compress="zstd", dedup=False, encrypt=False, use_tls=False, num_connections=1)
    try:
        wait_complete(dst, dispatch_file(src, tmp / "in.bin", tmp / "out.bin"))
        compression = src.get("profile/compression", timeout=10).json()
        decode = dst.get("profile/decode", timeout=10).json()["counters"]
    finally:
        src.stop()
        dst.stop()
    assert compression["chunks"] == 1 and decode["decode_chunks"] == 1
    return {"profile/compression": compression, "profile/decode": decode}


@pytest.mark.parametrize("route, key", REF_PATH_KEYS)
def test_ref_path_keys_are_served_zero_filled_with_dedup_off(served_with_dedup_off, route, key):
    from skyplane_tpu.gateway.operators.gateway_receiver import DECODE_COUNTER_ZERO

    value = served_with_dedup_off[route].get(key)
    assert value == 0 and isinstance(value, (int, float)) and not isinstance(value, bool), (route, key, value)
    schema = DataPathStats._KEYS if route == "profile/compression" else DECODE_COUNTER_ZERO
    assert key in schema


# ---- (i) pump workers' snapshots sum


def test_merge_numeric_counters_sums_the_new_keys():
    from skyplane_tpu.gateway.pump import merge_numeric_counters

    base = DataPathProcessor(codec_name="none", dedup=False).stats.as_dict()
    workers = [{key: 10 * (w + 1) + i for i, key in enumerate(NEW_KEYS)} for w in range(2)]
    merged = merge_numeric_counters(base, workers)
    for i, key in enumerate(NEW_KEYS):
        assert merged[key] == 30 + 2 * i, key


# ---- the stages' names in the two device programs


@pytest.mark.parametrize(
    "program, scopes",
    [
        ("call_a", ("cdc.gear_hash", "cdc.candidate_mask", "cdc.compaction")),
        (
            "call_b",
            ("fp.slot_bounds", "fp.power_tables", "fp.piece_bounds", "fp.lane_passes", "lane0", "lane7", "fp.piece_factors"),
        ),
    ],
)
def test_device_programs_carry_their_stage_scopes(program, scopes):
    """The names a device trace's reader maps instructions to (the results
    themselves are held bit-identical by test_fused_cdc.py)."""
    import jax
    import jax.numpy as jnp

    batch = jax.ShapeDtypeStruct((1, BUCKET), jnp.uint8)
    if program == "call_a":
        lowered = fused_mod._candidates_impl.lower(
            batch, jax.ShapeDtypeStruct((1,), jnp.int32), mask_bits=PARAMS.mask_bits, cap=128
        )
    else:
        n_slots = fused_mod.slots_cap(BUCKET, PARAMS)
        lowered = fused_mod._fp_impl.lower(batch, jax.ShapeDtypeStruct((1, n_slots), jnp.int32), n_slots=n_slots)
    text = lowered.as_text(debug_info=True)
    assert [s for s in scopes if s not in text] == []


def _indexed_ops(module):
    """(op name, number of index elements) of every gather and scatter in a StableHLO module, nested regions included."""
    found = []

    def walk(op):
        if op.name in ("stablehlo.gather", "stablehlo.scatter", "stablehlo.dynamic_gather"):
            found.append((op.name, int(np.prod(op.operands[1].type.shape))))
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    walk(inner.operation)

    walk(module.operation)
    return found


def test_call_a_gathers_nothing_per_byte():
    """Call A at a mid bucket indexes nothing over the `bucket` bytes (an indexed access costs a TPU v5e 8.6 ns
    an element, an element-wise one 0.03 ns): the gear values are selected, not looked up, and the compaction
    searches a blocked prefix count for `cap` queries, so the program holds no scatter at all and no gather
    whose index count reaches `bucket`."""
    import jax
    import jax.numpy as jnp

    bucket = 1 << 20
    cap = fused_mod.candidate_cap(bucket, fused_mod.CDCParams())
    lowered = fused_mod._candidates_impl.lower(
        jax.ShapeDtypeStruct((2, bucket), jnp.uint8), jax.ShapeDtypeStruct((2,), jnp.int32), mask_bits=14, cap=cap
    )
    indexed = _indexed_ops(lowered.compiler_ir("stablehlo"))
    assert [o for o in indexed if "scatter" in o[0]] == [], "call A scatters"
    assert [o for o in indexed if o[1] >= bucket] == [], f"indexed over the row's bytes (cap {cap}, bucket {bucket})"
    assert [o for o in indexed if "gather" in o[0]], "the compaction's searches gather per query: the reader sees call A's gathers"
    # the reader finds each per-byte form when it is there
    per_byte_gather = jax.jit(lambda t, d: t[d.astype(jnp.int32)]).lower(
        jax.ShapeDtypeStruct((256,), jnp.uint32), jax.ShapeDtypeStruct((bucket,), jnp.uint8)
    )
    assert [o for o in _indexed_ops(per_byte_gather.compiler_ir("stablehlo")) if "gather" in o[0] and o[1] >= bucket]
    per_byte_scatter = jax.jit(
        lambda to: jnp.full((cap,), bucket, jnp.int32).at[to].min(jax.lax.iota(jnp.int32, bucket), mode="drop")
    ).lower(jax.ShapeDtypeStruct((bucket,), jnp.int32))
    assert [o for o in _indexed_ops(per_byte_scatter.compiler_ir("stablehlo")) if "scatter" in o[0] and o[1] >= bucket]
