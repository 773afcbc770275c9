"""Observability layer: sampling tracer, ring buffers, Chrome export,
sender→receiver span correlation over a real loopback transfer, the unified
metrics registry's Prometheus exposition, and the profile-event drop
accounting (ISSUE 5 satellite: truncation must never be silent).
"""

from __future__ import annotations

import json
import queue
import socket
import sys
import threading
import time
import tracemalloc
import uuid
from pathlib import Path

import pytest

from skyplane_tpu.chunk import ChunkFlags, WireProtocolHeader
from skyplane_tpu.gateway.chunk_store import ChunkStore
from skyplane_tpu.gateway.operators.gateway_receiver import (
    DECODE_COUNTER_ZERO,
    GatewayReceiver,
    put_drop_oldest,
)
from skyplane_tpu.gateway.operators.sender_wire import (
    SENDER_WIRE_COUNTER_ZERO,
    EngineCallbacks,
    SenderWireEngine,
    WireFrame,
)
from skyplane_tpu.obs import NOOP_SPAN, MetricsRegistry, configure_tracer, get_tracer
from skyplane_tpu.obs.metrics import get_registry
from skyplane_tpu.obs.stage import Stage, StageCounters
from skyplane_tpu.obs.tracer import Tracer

REPO_ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(autouse=True)
def _restore_tracer():
    yield
    configure_tracer()  # back to env defaults so other tests see an off tracer


# ------------------------------------------------------------- sampling


def test_sampling_deterministic_across_instances():
    a, b = Tracer(sample=0.5), Tracer(sample=0.5)
    ids = [uuid.uuid4().hex for _ in range(2000)]
    va = [a.sampled(i) for i in ids]
    vb = [b.sampled(i) for i in ids]
    assert va == vb, "sampling must be a pure function of the id"
    assert va == [a.sampled(i) for i in ids], "re-asking must not flip decisions"
    frac = sum(va) / len(va)
    assert 0.4 < frac < 0.6, f"sample=0.5 hit {frac:.2f} of ids"
    assert Tracer(sample=1.0).sampled(ids[0]) and not Tracer(sample=0.0).sampled(ids[0])


def test_rate_zero_and_one_edge_cases():
    t = Tracer(sample=0.0)
    assert not t.enabled
    assert t.span("x") is NOOP_SPAN
    t1 = Tracer(sample=1.0)
    assert t1.enabled and all(t1.sampled(uuid.uuid4().hex) for _ in range(50))


# ------------------------------------------------- ring bound + drop accounting


def test_ring_buffer_bound_and_drop_counters():
    t = Tracer(sample=1.0, capacity=16)
    for i in range(50):
        with t.span(f"s{i}", trace_id="ab" * 16, cat="test"):
            pass
    c = t.counters()
    assert c["spans_recorded"] == 50
    assert c["spans_dropped"] == 50 - 16
    assert c["spans_buffered"] == 16
    spans = [e for e in t.export()["traceEvents"] if e.get("ph") == "X"]
    assert len(spans) == 16, "export must be bounded by the ring capacity"
    # overwrite-oldest: the survivors are the 16 NEWEST spans
    assert {e["name"] for e in spans} == {f"s{i}" for i in range(34, 50)}


def test_per_thread_rings_no_cross_talk():
    t = Tracer(sample=1.0, capacity=8)

    def worker(tag):
        for i in range(8):
            with t.span(f"{tag}{i}", cat="test"):
                pass

    threads = [threading.Thread(target=worker, args=(tag,)) for tag in ("a", "b", "c")]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    c = t.counters()
    assert c["trace_threads"] == 3 and c["spans_dropped"] == 0 and c["spans_recorded"] == 24


def test_dead_thread_rings_retire_bounded():
    """Per-connection thread churn must not grow tracer memory unboundedly:
    dead threads' rings beyond MAX_DEAD_RINGS retire, their totals survive."""
    t = Tracer(sample=1.0, capacity=16)
    t.MAX_DEAD_RINGS = 4

    def one_span(i):
        with t.span(f"churn{i}", cat="test"):
            pass

    for i in range(20):
        th = threading.Thread(target=one_span, args=(i,))
        th.start()
        th.join()
    # trigger retirement from a fresh registering thread
    th = threading.Thread(target=one_span, args=(99,))
    th.start()
    th.join()
    c = t.counters()
    assert c["trace_threads"] <= t.MAX_DEAD_RINGS + 2, "dead rings must retire"
    assert c["spans_recorded"] == 21, "retired rings' totals must survive"
    # exported tids are tracer-unique (thread idents recycle; tracks must not merge)
    tids = [e["tid"] for e in t.export()["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in t.export()["traceEvents"] if e.get("ph") == "X"}
    assert len(tids) == len(set(tids)) == len(names)


def test_chunk_traced_field_roundtrips():
    """The registration-borne trace decision survives the control-plane dict
    hop (sender pre-register -> destination operators)."""
    from skyplane_tpu.chunk import Chunk, ChunkRequest

    req = ChunkRequest(chunk=Chunk(src_key="s", dest_key="d", chunk_id=uuid.uuid4().hex, chunk_length_bytes=1))
    req.chunk.traced = True
    rt = ChunkRequest.from_dict(json.loads(json.dumps(req.as_dict())))
    assert rt.chunk.traced is True


def test_reset_drops_spans():
    t = Tracer(sample=1.0)
    with t.span("x"):
        pass
    t.reset()
    assert t.counters()["spans_recorded"] == 0
    assert not [e for e in t.export()["traceEvents"] if e.get("ph") == "X"]


# ------------------------------------------------------ no-op path is free


def test_noop_tracer_zero_allocation():
    t = Tracer(sample=0.0)
    # identity: every disabled span() returns THE shared singleton
    assert t.span("a") is t.span("b") is NOOP_SPAN
    # and the call path allocates nothing attributable to the tracer module
    tracer_file = sys.modules["skyplane_tpu.obs.tracer"].__file__
    for _ in range(100):  # warm any lazy state before measuring
        with t.span("warm", trace_id="00" * 16):
            pass
    tracemalloc.start()
    try:
        for _ in range(1000):
            with t.span("hot", trace_id="00" * 16, cat="bench"):
                pass
        snapshot = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    # a real per-call allocation (a span object, an args dict) would show up
    # ~1000 times; tolerate the odd interpreter-internal stray (count < 10)
    hits = [
        s
        for s in snapshot.statistics("filename")
        if s.traceback[0].filename == tracer_file and s.count >= 10
    ]
    assert not hits, f"disabled tracer allocates per call: {hits}"
    assert t.counters()["spans_recorded"] == 0

    # the stage timer of a chunk's round (obs/stage.py): with tracing off a
    # step is two clock reads into its counter, and a call makes no object
    configure_tracer(sample=0.0)
    counters = StageCounters(("hot_ns",))
    stage = Stage(counters.add, "hot_ns", "hot")
    assert stage("00" * 16, force=True) is stage
    stage_file = sys.modules["skyplane_tpu.obs.stage"].__file__
    for _ in range(100):
        with stage("00" * 16):
            pass
    before = counters.totals()["hot_ns"]
    tracemalloc.start()
    try:
        for _ in range(1000):
            with stage("00" * 16, force=False):
                pass
        snapshot = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    hits = [
        s
        for s in snapshot.statistics("filename")
        if s.traceback[0].filename in (tracer_file, stage_file) and s.count >= 10
    ]
    assert not hits, f"a stage allocates per call with tracing off: {hits}"
    assert counters.totals()["hot_ns"] > before and stage.last_ns >= 0
    assert get_tracer().counters()["spans_recorded"] == 0


def test_stage_counts_its_step_and_holds_its_profile_span():
    tracer = configure_tracer(sample=1.0)
    counters = StageCounters(("step_ns",))
    stage = Stage(counters.add, "step_ns", "step")
    with stage("ab" * 16, args={"gateway": "gw"}):
        time.sleep(0.01)
    (ev,) = [e for e in tracer.export()["traceEvents"] if e.get("name") == "step"]
    assert ev["cat"] == "device" and ev["args"] == {"gateway": "gw", "chunk_id": "ab" * 16}
    total = counters.totals()["step_ns"]
    assert total == stage.last_ns >= 10_000_000 and ev["dur"] * 1e3 <= total
    assert stage.ended_ns == stage.started_ns + stage.last_ns
    # a call that names a per-chunk record counts there, not in the sink
    record: dict = {}
    with stage("ab" * 16, into=record):
        pass
    assert record["step_ns"] == stage.last_ns and counters.totals()["step_ns"] == total


def test_stage_counters_sum_their_threads_shards():
    counters = StageCounters(("a_ns", "b_ns"))

    def work():
        for _ in range(1000):
            counters.add("a_ns", 2)
            counters.add("b_ns", 1)

    threads = [threading.Thread(target=work) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert counters.totals() == {"a_ns": 12_000, "b_ns": 6_000}


def test_unsampled_chunk_span_is_noop():
    t = Tracer(sample=0.5)
    miss = next(i for i in (uuid.uuid4().hex for _ in range(100)) if not t.sampled(i))
    assert t.span("x", trace_id=miss) is NOOP_SPAN
    assert t.span("x", trace_id=miss, force=True) is not NOOP_SPAN, "force (wire TRACED flag) bypasses sampling"


# ---------------------------------------------------- Chrome export schema


def _check_trace(trace: dict) -> int:
    """Run scripts/check_trace_json.py's validator on an export dict."""
    sys.path.insert(0, str(REPO_ROOT / "scripts"))
    try:
        import check_trace_json

        return check_trace_json.validate(trace)
    finally:
        sys.path.pop(0)


def test_chrome_export_schema_and_async_pairs():
    t = Tracer(sample=1.0)
    cid = uuid.uuid4().hex
    with t.span("parent", trace_id=cid, cat="sender"):
        with t.span("child", trace_id=cid, cat="sender"):
            time.sleep(0.001)
    t.record_span("lag", 5_000_000, time.time_ns(), trace_id=cid, cat="sender")
    out = t.export()
    events = out["traceEvents"]
    assert out["displayTimeUnit"] == "ms"
    xs = {e["name"]: e for e in events if e.get("ph") == "X"}
    assert set(xs) == {"parent", "child"}
    for e in xs.values():
        assert e["args"]["chunk_id"] == cid and e["dur"] >= 0 and {"pid", "tid", "ts"} <= set(e)
    # child nests inside parent on the same tid
    p, c = xs["parent"], xs["child"]
    assert p["tid"] == c["tid"]
    assert p["ts"] <= c["ts"] and c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 5.0
    bs = [e for e in events if e.get("ph") == "b"]
    es = [e for e in events if e.get("ph") == "e"]
    assert len(bs) == len(es) == 1 and bs[0]["id"] == es[0]["id"]
    assert bs[0]["args"]["dur_us"] == pytest.approx(5000.0)
    # json-serializable end to end
    json.loads(json.dumps(out))


# -------------------------- loopback sender→receiver span correlation


class _CountCb(EngineCallbacks):
    def __init__(self, n, done):
        self.n, self.done, self.delivered = n, done, 0

    def on_delivered(self, frame):
        self.delivered += 1
        if self.delivered >= self.n:
            self.done.set()


def test_loopback_transfer_spans_correlate_and_nest(tmp_path):
    """The PR's acceptance shape: one chunk's sender spans (frame → send →
    ack) and receiver spans (decode → store.write) share the chunk id and
    nest correctly, in one exported Chrome trace."""
    tracer = configure_tracer(sample=1.0)
    store = ChunkStore(str(tmp_path / "rx"))
    ev, eq = threading.Event(), queue.Queue()
    receiver = GatewayReceiver("local:local", store, ev, eq, use_tls=False, bind_host="127.0.0.1", decode_workers=2)
    port = receiver.start_server()
    payload = b"\xa5" * 65536
    headers = [
        WireProtocolHeader(chunk_id=uuid.uuid4().hex, data_len=len(payload), raw_data_len=len(payload))
        for _ in range(6)
    ]
    done = threading.Event()
    cb = _CountCb(len(headers), done)

    def connect():
        s = socket.create_connection(("127.0.0.1", port), timeout=10)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return s

    engine = SenderWireEngine(connect, cb, name="obs-test")
    try:
        for h in headers:
            h.flags |= ChunkFlags.TRACED

            def make(pending, h=h):
                with tracer.span("wire.frame", trace_id=h.chunk_id, cat="sender", force=True):
                    return WireFrame(None, h, payload, traced=True)

            engine.submit(make)
        assert done.wait(timeout=20), f"delivered {cb.delivered}/{len(headers)}"
    finally:
        engine.close()
        receiver.stop_all()
    out = tracer.export()
    by_chunk = {}
    for e in out["traceEvents"]:
        cid = (e.get("args") or {}).get("chunk_id")
        if cid:
            by_chunk.setdefault(cid, {}).setdefault(e["cat"], set()).add(e["name"])
    for h in headers:
        cats = by_chunk.get(h.chunk_id, {})
        # the envelopes keep their sides; the steps of the round are in the profile's category
        assert {"wire.frame", "wire.ack_lag"} <= cats.get("sender", set()), cats
        assert {"decode"} <= cats.get("receiver", set()), cats
        assert {"wire.send", "frame.recv", "store.write"} <= cats.get("device", set()), cats
    # store.write nests inside decode for every traced chunk (same worker tid)
    spans = [e for e in out["traceEvents"] if e.get("ph") == "X"]
    for h in headers:
        dec = next(e for e in spans if e["name"] == "decode" and e["args"]["chunk_id"] == h.chunk_id)
        st = next(e for e in spans if e["name"] == "store.write" and e["args"]["chunk_id"] == h.chunk_id)
        assert dec["tid"] == st["tid"]
        assert dec["ts"] <= st["ts"] and st["ts"] + st["dur"] <= dec["ts"] + dec["dur"] + 5.0
    # the full validator (schema + nesting + stitching) passes on the export
    assert _check_trace(out) == 0


def test_untraced_transfer_records_nothing(tmp_path):
    configure_tracer(sample=0.0)
    store = ChunkStore(str(tmp_path / "rx0"))
    ev, eq = threading.Event(), queue.Queue()
    receiver = GatewayReceiver("local:local", store, ev, eq, use_tls=False, bind_host="127.0.0.1", decode_workers=2)
    port = receiver.start_server()
    payload = b"\x11" * 4096
    h = WireProtocolHeader(chunk_id=uuid.uuid4().hex, data_len=len(payload), raw_data_len=len(payload))
    done = threading.Event()
    cb = _CountCb(1, done)
    engine = SenderWireEngine(
        lambda: socket.create_connection(("127.0.0.1", port), timeout=10), cb, name="obs-test-off"
    )
    try:
        engine.submit(lambda pending: WireFrame(None, h, payload))
        assert done.wait(timeout=10)
    finally:
        engine.close()
        receiver.stop_all()
    assert get_tracer().counters()["spans_recorded"] == 0


# --------------------------------------------------- prometheus exposition


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    c = reg.counter("chunks_total", help_="chunks processed")
    c.inc()
    c.inc(4)
    g = reg.gauge("queue_depth", help_="queued frames")
    g.set(7)
    reg.gauge("live_fn", fn=lambda: 2.5)
    h = reg.histogram("lat_seconds", help_="latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.render_prometheus()
    assert "# TYPE skyplane_chunks_total counter\nskyplane_chunks_total 5" in text
    assert "# TYPE skyplane_queue_depth gauge\nskyplane_queue_depth 7" in text
    assert "skyplane_live_fn 2.5" in text
    assert '# TYPE skyplane_lat_seconds histogram' in text
    assert 'skyplane_lat_seconds_bucket{le="0.1"} 1' in text
    assert 'skyplane_lat_seconds_bucket{le="1"} 2' in text  # cumulative
    assert 'skyplane_lat_seconds_bucket{le="+Inf"} 3' in text
    assert "skyplane_lat_seconds_count 3" in text
    # every sample line belongs to a HELP'd/TYPE'd family (format sanity)
    for line in text.strip().splitlines():
        assert line.startswith("#") or line.split("{")[0].split(" ")[0].startswith("skyplane_"), line


def test_registry_absorbs_counter_schemas_and_parent_chain():
    parent = MetricsRegistry()
    parent.counter("native_metric").inc(3)
    reg = MetricsRegistry(parent=parent)
    reg.register_provider("decode", lambda: dict(DECODE_COUNTER_ZERO))
    reg.register_provider("sender_wire", lambda: dict(SENDER_WIRE_COUNTER_ZERO))
    text = reg.render_prometheus()
    assert "skyplane_decode_decode_chunks 0" in text
    assert "skyplane_decode_decode_events_dropped 0" in text
    assert "skyplane_sender_wire_profile_events_dropped 0" in text
    assert "skyplane_sender_wire_frames_pipelined 0" in text
    assert "skyplane_native_metric 3" in text  # parent chain included
    broken = MetricsRegistry()
    broken.register_provider("boom", lambda: (_ for _ in ()).throw(RuntimeError("x")))
    broken.counter("still_there").inc()
    assert "skyplane_still_there 1" in broken.render_prometheus()  # scrape survives a bad provider


def test_histogram_create_or_get_is_shared():
    reg = get_registry()
    a = reg.histogram("obs_test_shared_seconds")
    b = reg.histogram("obs_test_shared_seconds")
    assert a is b


# ------------------------------------------- profile-event drop accounting


def test_put_drop_oldest_reports_drops():
    q: "queue.Queue[dict]" = queue.Queue(maxsize=2)
    assert put_drop_oldest(q, {"i": 0}) is False
    assert put_drop_oldest(q, {"i": 1}) is False
    assert put_drop_oldest(q, {"i": 2}) is True  # evicted the oldest
    assert [q.get_nowait()["i"] for _ in range(2)] == [1, 2], "drop-OLDEST keeps the freshest"


def test_decode_counter_schema_includes_drop_counters():
    assert "decode_events_dropped" in DECODE_COUNTER_ZERO
    assert "socket_events_dropped" in DECODE_COUNTER_ZERO
    assert "profile_events_dropped" in SENDER_WIRE_COUNTER_ZERO


def test_api_trace_and_metrics_routes(tmp_path):
    """GET /api/v1/trace serves the Chrome export; GET /api/v1/metrics serves
    Prometheus text — through the real HTTP server."""
    import urllib.request

    from skyplane_tpu.gateway.gateway_daemon_api import GatewayDaemonAPI
    from skyplane_tpu.gateway.gateway_queue import GatewayQueue

    tracer = configure_tracer(sample=1.0)
    with tracer.span("api.span", trace_id="cd" * 16, cat="sender"):
        pass
    reg = MetricsRegistry()
    reg.counter("api_route_probe").inc(9)
    store = ChunkStore(str(tmp_path / "chunks"))
    store.add_partition("default", GatewayQueue())

    class FakeReceiver:
        socket_profile_events = queue.Queue()

        def socket_events_dropped(self):
            return 0

    api = GatewayDaemonAPI(
        chunk_store=store,
        receiver=FakeReceiver(),
        error_event=threading.Event(),
        error_queue=queue.Queue(),
        terminal_operators={"default": []},
        handle_to_group={"default": {}},
        region="test:r",
        gateway_id="gw",
        host="127.0.0.1",
        port=0,
        metrics_fn=reg.render_prometheus,
    )
    api.start()
    try:
        base = f"http://127.0.0.1:{api.port}/api/v1"
        trace = json.loads(urllib.request.urlopen(f"{base}/trace", timeout=5).read())
        assert any(e.get("name") == "api.span" for e in trace["traceEvents"])
        resp = urllib.request.urlopen(f"{base}/metrics", timeout=5)
        assert resp.headers["Content-Type"].startswith("text/plain")
        body = resp.read().decode()
        assert "# TYPE skyplane_api_route_probe counter" in body
        assert "skyplane_api_route_probe 9" in body
    finally:
        api.stop()


def test_receiver_surfaces_event_drops(tmp_path):
    store = ChunkStore(str(tmp_path / "rxd"))
    ev, eq = threading.Event(), queue.Queue()
    receiver = GatewayReceiver("local:local", store, ev, eq, use_tls=False, bind_host="127.0.0.1", decode_workers=2)
    try:
        # simulate sustained truncation on the bounded decode-event queue
        receiver.decode_profile_events = queue.Queue(maxsize=1)
        for i in range(3):
            if put_drop_oldest(receiver.decode_profile_events, {"i": i}):
                with receiver._stats_lock:
                    receiver._decode_events_dropped += 1
        counters = receiver.decode_counters()
        assert counters["decode_events_dropped"] == 2
        assert counters["socket_events_dropped"] == 0
        assert receiver.socket_events_dropped() == 0
    finally:
        receiver.stop_all()
