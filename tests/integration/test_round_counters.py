"""Every step of a chunk's round has a counter and a profile span.

A loopback pair with TLS, E2EE and dedup on moves a few small chunks while a
span swap like the benchmark's (``benchmark/run.py`` ``annotate_device_spans``)
records every profile-category span. The new keys of ``/profile/compression``
and ``/profile/decode`` are served, rise with traffic, and tile their side's
residence; each step's span is entered once a chunk; and the benchmark's
per-layer metrics that read them find something to read.
"""

from __future__ import annotations

import importlib.util
import json
import threading
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from skyplane_tpu.obs import NOOP_SPAN, configure_tracer, get_tracer
from skyplane_tpu.obs.tracer import PROFILE_CAT

REPO = Path(__file__).resolve().parents[2]
N_CHUNKS = 4
CHUNK_BYTES = 1 << 20

#: the source's new keys (served by /profile/compression) and the leaves of its round
SOURCE_KEYS = ("residence_ns", "queue_wait_ns", "io_ns", "register_ns", "send_ns", "ack_lag_ns")
SOURCE_LEAVES = ("queue_wait_ns", "io_ns", "register_ns", "device_path_ns", "recipe_ns", "seal_ns", "send_ns", "ack_lag_ns")
#: the sink's (served by /profile/decode)
SINK_KEYS = ("residence_ns", "recv_ns", "queue_wait_ns", "open_ns", "land_ns", "handoff_ns", "write_local_ns")
SINK_LEAVES = ("recv_ns", "queue_wait_ns", "open_ns", "literal_pass_ns", "ref_resolve_ns", "land_ns", "handoff_ns", "write_local_ns")
#: the profile spans of one chunk's round, each entered once a chunk (no accelerator here: no device-path spans)
CHUNK_SPANS = (
    "chunk.read",
    "chunk.load",
    "recipe.build",
    "wire.seal",
    "wire.send",
    "frame.recv",
    "decode.open",
    "decode.literal_pass",
    "decode.ref_resolve",
    "store.write",
    "chunk.write_local",
)
NEW_METRICS = (
    "source_io_s_per_gib",
    "source_queue_s_per_chunk",
    "wire_send_s_per_gib",
    "sink_recv_s_per_gib",
    "sink_open_s_per_gib",
    "sink_io_s_per_gib",
    "sink_handoff_s_per_chunk",
    "source_counted_share",
    "sink_counted_share",
)


def _content(seed: int) -> bytes:
    """Chunks that each hold literals and REFs: fresh bytes, one block twice
    (the second copy's segments repeat the first's), fresh bytes again."""
    rng = np.random.default_rng(seed)
    parts = []
    for _ in range(N_CHUNKS):
        head, block, tail = (rng.integers(0, 256, CHUNK_BYTES // 4, dtype=np.uint8).tobytes() for _ in range(3))
        parts.append(head + block + block + tail)
    return b"".join(parts)


def _run_pair(tmp):
    """Served counters of both daemons after the chunks landed, and the
    profile-category spans entered: [(name, chunk id), ...]."""
    pytest.importorskip("zstandard")
    from tests.integration.harness import dispatch_file, make_pair, wait_complete

    src_path, dst_path = tmp / "in.bin", tmp / "out.bin"
    data = _content(38)
    src_path.write_bytes(data)
    entered, lock = [], threading.Lock()

    def span(name, trace_id=None, cat="", args=None, force=False):  # the benchmark's swap, recording
        if cat == PROFILE_CAT:
            with lock:
                entered.append((name, trace_id))
        return NOOP_SPAN

    configure_tracer(sample=0.0)
    get_tracer().span = span
    src, dst = make_pair(tmp, compress="zstd", dedup=True, encrypt=True, use_tls=True, num_connections=2)
    try:
        ids = dispatch_file(src, src_path, dst_path, chunk_bytes=CHUNK_BYTES)
        wait_complete(dst, ids)
        wait_complete(src, ids)  # the source counts a residence as the ack lands
        served = {
            "src": (src.get("profile/compression", timeout=10).json(), src.get("profile/decode", timeout=10).json()["counters"]),
            "dst": (dst.get("profile/compression", timeout=10).json(), dst.get("profile/decode", timeout=10).json()["counters"]),
        }
        wire = src.get("profile/socket/sender", timeout=10).json()["counters"]
    finally:
        src.stop()
        dst.stop()
        configure_tracer()
    assert dst_path.read_bytes() == data
    return {"ids": ids, "served": served, "wire": wire, "entered": list(entered)}


@pytest.fixture(scope="module")
def round_trip(tmp_path_factory):
    return _run_pair(tmp_path_factory.mktemp("round"))


@pytest.fixture(scope="module")
def python_ssl_trip(tmp_path_factory):
    """The same pair where the native TLS library cannot load."""
    from skyplane_tpu.native import tlsstream

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tlsstream, "load", lambda: None)
        return _run_pair(tmp_path_factory.mktemp("round_python_ssl"))


def test_every_tls_frame_went_over_the_native_stream(round_trip):
    wire, (_, decode) = round_trip["wire"], round_trip["served"]["dst"]
    assert wire["frames_sent"] >= N_CHUNKS and wire["tls_native_frames"] == wire["frames_sent"], wire
    assert decode["decode_chunks"] == N_CHUNKS and decode["recv_native_frames"] == decode["decode_chunks"], decode


def test_python_ssl_stream_where_the_library_cannot_load(python_ssl_trip):
    wire, (_, decode) = python_ssl_trip["wire"], python_ssl_trip["served"]["dst"]
    assert wire["frames_sent"] >= N_CHUNKS and wire["tls_native_frames"] == 0, wire
    assert decode["decode_chunks"] == N_CHUNKS and decode["recv_native_frames"] == 0, decode
    assert decode["recv_ns"] > 0 and python_ssl_trip["served"]["src"][0]["send_ns"] > 0


@pytest.mark.parametrize("key", SOURCE_KEYS)
def test_source_key_served_and_counted(round_trip, key):
    compression, _ = round_trip["served"]["src"]
    value = compression.get(key)
    assert isinstance(value, int) and not isinstance(value, bool) and value > 0, (key, value)
    # a gateway that runs no sender serves the key, at 0 for the steps only a sender runs
    other = round_trip["served"]["dst"][0].get(key)
    assert other == 0 and not isinstance(other, bool), (key, other)


@pytest.mark.parametrize("key", SINK_KEYS)
def test_sink_key_served_and_counted(round_trip, key):
    _, decode = round_trip["served"]["dst"]
    value = decode.get(key)
    assert isinstance(value, int) and not isinstance(value, bool) and value > 0, (key, value)
    assert round_trip["served"]["src"][1].get(key) == 0, "the source's receiver decoded nothing"


@pytest.mark.parametrize("side", ["src", "dst"])
def test_leaves_tile_the_residence(round_trip, side):
    compression, decode = round_trip["served"][side]
    counters, leaves = (compression, SOURCE_LEAVES) if side == "src" else (decode, SINK_LEAVES)
    covered = sum(counters[k] for k in leaves)
    assert 0 < covered <= counters["residence_ns"], {k: counters[k] for k in leaves + ("residence_ns",)}
    assert covered >= 0.5 * counters["residence_ns"], "most of the round should be under a counter"


@pytest.mark.parametrize("name", CHUNK_SPANS)
def test_each_step_span_entered_once_a_chunk(round_trip, name):
    per_chunk = Counter(cid for span, cid in round_trip["entered"] if span == name)
    assert per_chunk == Counter({cid: 1 for cid in round_trip["ids"]}), (name, per_chunk)


def test_registration_span_once_a_window(round_trip):
    windows = [cid for span, cid in round_trip["entered"] if span == "chunk.register"]
    assert 1 <= len(windows) <= N_CHUNKS and set(windows) == {None}


def test_envelopes_stay_out_of_the_profile(round_trip):
    names = {span for span, _ in round_trip["entered"]}
    assert not names & {"wire.frame", "decode", "decode.blob", "codec.zstd", "codec.blockpack"}, names


def _load_reader(name: str):
    path = REPO / "benchmark" / "metrics" / name
    spec = importlib.util.spec_from_file_location(f"metric_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _read_metric(spec: dict, facts: dict):
    """What benchmark/run.py's read_metric does with a metric file."""
    if "reader" in spec:
        return _load_reader(spec["reader"]).read(facts, {"metric": spec})
    ratio = spec["ratio"]
    num, den = facts.get(ratio["num"]), facts.get(ratio["den"])
    return None if num is None or not den else num / den * ratio["scale"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_metric_reads_the_served_counters(round_trip, metric):
    spec = json.loads((REPO / "benchmark" / "metrics" / f"{metric}.json").read_text())
    compression, _ = round_trip["served"]["src"]
    _, decode = round_trip["served"]["dst"]
    facts = {f"source_after_t0.{k}": v for k, v in compression.items() if isinstance(v, (int, float))}
    facts.update({f"sink_after_t0.{k}": v for k, v in decode.items() if isinstance(v, (int, float))})
    value = _read_metric(spec, facts)
    assert value is not None and value > 0, (metric, value)
    if spec["unit"] == "%":
        assert value <= 100.0
    entry = next(m for m in json.loads((REPO / "BENCHMARK.json").read_text())["per_layer"] if m["name"] == metric)
    assert {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")} == {
        k: spec[k] for k in ("unit", "better", "source", "layer", "moves")
    }


@pytest.mark.parametrize(
    "facts, expected",
    [
        ({"a": 2, "b": 3, "d": 10}, 50.0),  # (2 + 3) / 10 * 100
        ({"a": 2, "d": 10}, None),  # a program without counter b: nothing to read
        ({"a": 2, "b": 3, "d": 0}, None),  # nothing in the denominator
        ({"a": 2, "b": 3}, None),
    ],
    ids=["sum", "missing-part", "zero-den", "missing-den"],
)
def test_counter_sum_reader(facts, expected):
    spec = {"sum": ["a", "b"], "den": "d", "scale": 100.0}
    got = _load_reader("counter_sum.py").read(facts, {"metric": spec})
    assert got == (pytest.approx(expected) if expected is not None else None)
