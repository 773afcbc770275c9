"""Receiver decode path: parallel out-of-order decode with in-order acks,
per-fingerprint ref-arrival events, the striped SegmentStore's lock
discipline, and pooled recipe output assembly.

The determinism test is the PR's core contract: a multi-connection decode
run through the worker pool must produce chunk files and per-connection
ack/NACK sequences identical to the serial (1-worker) receiver.
"""

from __future__ import annotations

import queue
import socket
import struct
import threading
import time
import uuid

import numpy as np
import pytest

from skyplane_tpu.chunk import ChunkFlags, WireProtocolHeader
from skyplane_tpu.exceptions import DedupIntegrityException
from skyplane_tpu.gateway.chunk_store import ChunkStore
from skyplane_tpu.gateway.operators.gateway_receiver import (
    ACK_BYTE,
    DECODE_COUNTER_ZERO,
    NACK_UNRESOLVED,
    GatewayReceiver,
    put_drop_oldest,
)
from skyplane_tpu.ops import dedup as dedup_mod
from skyplane_tpu.ops.bufpool import BufferPool
from skyplane_tpu.ops.dedup import (
    PooledChunk,
    SegmentStore,
    SenderDedupIndex,
    build_recipe,
    parse_recipe,
)
from skyplane_tpu.ops.fingerprint import segment_fingerprint_host

rng = np.random.default_rng(11)
ident = lambda b: b  # noqa: E731


def _seg(n=1000):
    data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
    return segment_fingerprint_host(data), data


def _literal_frame(segments):
    """(header, wire, raw) for a recipe carrying the given segments as literals."""
    wire, *_ = build_recipe(segments, SenderDedupIndex(), ident)
    raw = b"".join(s for _, s in segments)
    header = WireProtocolHeader(
        chunk_id=uuid.uuid4().hex,
        data_len=len(wire),
        raw_data_len=len(raw),
        flags=int(ChunkFlags.RECIPE),
    )
    return header, wire, raw


def _ref_frame(fp, seg_len, raw):
    """(header, wire, raw) for a recipe that is ONE REF to fp."""
    wire = dedup_mod.MAGIC + struct.pack("<BI", dedup_mod.VERSION, 1) + dedup_mod._ENTRY.pack(dedup_mod.KIND_REF, fp, seg_len)
    header = WireProtocolHeader(
        chunk_id=uuid.uuid4().hex,
        data_len=len(wire),
        raw_data_len=seg_len,
        flags=int(ChunkFlags.RECIPE),
    )
    return header, wire, raw


def _mk_receiver(tmp_path, **kw):
    store = ChunkStore(str(tmp_path / f"rx_{uuid.uuid4().hex[:8]}"))
    ev, eq = threading.Event(), queue.Queue()
    r = GatewayReceiver(
        "local:local", store, ev, eq, use_tls=False, bind_host="127.0.0.1", dedup=True, **kw
    )
    port = r.start_server()
    return r, store, ev, port


def _send_frames(port, frames, read_responses=True, timeout=10.0):
    """Stream frames back-to-back on one connection (the sender's window
    pattern), then collect one response byte per frame in order."""
    sock = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    try:
        for header, wire, _ in frames:
            header.to_socket(sock)
            sock.sendall(wire)
        if not read_responses:
            return b""
        resp = b""
        while len(resp) < len(frames):
            b = sock.recv(1)
            if not b:
                break
            resp += b
        return resp
    finally:
        sock.close()


# ---------------------------------------------------------------- determinism


def _run_scenario(tmp_path, decode_workers):
    """Two connections, interleaved literals / refs / an unresolvable REF per
    connection, over a DETERMINISTIC corpus (seeded rng) so serial and pooled
    runs decode identical data. Returns (per-conn response bytes, chunk-id
    order per conn, {chunk_id: file bytes})."""
    scenario_rng = np.random.default_rng(2024)

    def seg(n):
        data = scenario_rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        return segment_fingerprint_host(data), data

    r, store, ev, port = _mk_receiver(tmp_path, ref_wait_timeout=0.3, decode_workers=decode_workers)
    try:
        conn_frames = []
        for _ in range(2):
            s1, s2, s3 = seg(1200), seg(800), seg(600)
            f1 = _literal_frame([s1, s2])
            f2 = _literal_frame([s3])
            f3 = _ref_frame(s1[0], len(s1[1]), s1[1])  # REF to f1's literal (same conn)
            f4 = _ref_frame(b"\xee" * 16, 64, None)  # unresolvable -> NACK
            f5 = _ref_frame(s3[0], len(s3[1]), s3[1])
            conn_frames.append([f1, f2, f3, f4, f5])
        results = [None, None]

        def drive(i):
            results[i] = _send_frames(port, conn_frames[i])

        threads = [threading.Thread(target=drive, args=(i,), daemon=True) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        files = {}
        for frames in conn_frames:
            for header, _, raw in frames:
                p = r.chunk_store.chunk_path(header.chunk_id)
                files[header.chunk_id] = p.read_bytes() if p.exists() else None
                if raw is not None:
                    assert files[header.chunk_id] == raw, "restored chunk bytes differ from the raw input"
        assert not ev.is_set(), "scenario must not kill the daemon"
        return results, [[f[0].chunk_id for f in frames] for frames in conn_frames], files
    finally:
        r.stop_all()


def test_out_of_order_decode_matches_serial_receiver(tmp_path):
    """Pool decode (8 workers) must be observationally identical to the
    serial receiver (1 worker): same per-connection ack/NACK sequences, same
    restored chunk files."""
    serial_resp, _, serial_files = _run_scenario(tmp_path / "serial", decode_workers=1)
    pool_resp, _, pool_files = _run_scenario(tmp_path / "pool", decode_workers=8)
    expected = ACK_BYTE * 3 + NACK_UNRESOLVED + ACK_BYTE
    for resp in (*serial_resp, *pool_resp):
        assert resp == expected, f"ack sequence {resp!r} != {expected!r}"
    # same outcomes per frame position; file CONTENT equality is asserted
    # against the raw inputs inside _run_scenario for both runs
    assert sorted(v for v in serial_files.values() if v is not None) == sorted(
        v for v in pool_files.values() if v is not None
    )


# ------------------------------------------------- cross-connection REF wait


def test_ref_before_literal_across_connections_wakes_via_event(tmp_path):
    """A REF landing on one socket before its LITERAL lands on ANOTHER socket
    parks one decode worker on the store's per-fp arrival event; the literal
    decode (a different worker) wakes it and the REF chunk acks."""
    r, store, ev, port = _mk_receiver(tmp_path, ref_wait_timeout=5.0, decode_workers=4)
    try:
        fp, data = _seg(2000)
        ref_frame = _ref_frame(fp, len(data), data)
        lit_frame = _literal_frame([(fp, data)])

        ref_resp = {}

        def send_ref():
            ref_resp["resp"] = _send_frames(port, [ref_frame], timeout=10.0)

        t = threading.Thread(target=send_ref, daemon=True)
        t.start()
        time.sleep(0.3)  # let the REF reach a worker and park
        t0 = time.monotonic()
        assert _send_frames(port, [lit_frame]) == ACK_BYTE
        t.join(timeout=10)
        waited = time.monotonic() - t0
        assert ref_resp["resp"] == ACK_BYTE, "REF chunk must ack once the literal lands"
        assert waited < 3.0, f"event wake took {waited:.2f}s — looks like a poll, not a wake"
        assert r.chunk_store.chunk_path(ref_frame[0].chunk_id).read_bytes() == data
        counters = r.decode_counters()
        assert counters["store_ref_wait_ns"] > 0, "the REF never actually waited"
        assert not ev.is_set()
    finally:
        r.stop_all()


def test_ref_timeout_nacks(tmp_path):
    r, store, ev, port = _mk_receiver(tmp_path, ref_wait_timeout=0.2, decode_workers=4)
    try:
        frame = _ref_frame(b"\xab" * 16, 32, None)
        assert _send_frames(port, [frame]) == NACK_UNRESOLVED
        assert r.nacks_total == 1
        assert r.decode_counters()["store_ref_timeouts"] >= 1
        assert not r.chunk_store.chunk_path(frame[0].chunk_id).exists()
        assert not ev.is_set(), "an unresolvable ref must degrade, not kill the daemon"
    finally:
        r.stop_all()


def test_decode_counters_schema_and_progress(tmp_path):
    r, store, ev, port = _mk_receiver(tmp_path, decode_workers=2)
    try:
        fp, data = _seg(500)
        assert _send_frames(port, [_literal_frame([(fp, data)])]) == ACK_BYTE
        counters = r.decode_counters()
        assert set(DECODE_COUNTER_ZERO) <= set(counters), "stable decode schema regressed"
        assert counters["decode_chunks"] >= 1
        assert counters["decode_raw_bytes"] >= len(data)
        assert counters["decode_workers"] == 2
        assert not r.decode_profile_events.empty(), "decode profile events not recorded"
    finally:
        r.stop_all()


# ------------------------------------------------------ striped SegmentStore


def test_store_zero_lock_held_disk_reads_under_contention(tmp_path):
    """SegmentStore.get under contention with a spilled working set: spill
    reads happen, but NEVER while the reading thread holds a store lock
    (counter-asserted; the counter is bumped by the read helper itself
    whenever the thread's held-lock depth is nonzero)."""
    store = SegmentStore(max_bytes=3_000, spill_dir=tmp_path / "spill", spill_max_bytes=1 << 30, stripes=4)
    segs = [_seg(500) for _ in range(40)]
    for fp, data in segs:
        store.put(fp, data)

    errors = []

    def hammer(seed):
        r = np.random.default_rng(seed)
        for i in r.permutation(len(segs)):
            fp, data = segs[i]
            try:
                if store.get(fp) != data:
                    errors.append(f"wrong bytes for {fp.hex()}")
            except DedupIntegrityException as e:  # pragma: no cover - would be a bug
                errors.append(str(e))

    threads = [threading.Thread(target=hammer, args=(i,), daemon=True) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors[:5]
    counters = store.counters()
    assert counters["store_spill_reads"] > 0, "working set never spilled — the scenario is vacuous"
    assert counters["store_lock_held_disk_reads"] == 0, "a disk read ran while holding a store lock"
    assert counters["store_promotions"] > 0


def test_store_arrival_event_wakes_without_poll():
    store = SegmentStore()
    fp, data = _seg(300)
    got = {}

    def waiter():
        t0 = time.monotonic()
        got["data"] = store.get(fp, wait_timeout=5.0)
        got["elapsed"] = time.monotonic() - t0

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    time.sleep(0.15)
    store.put(fp, data)
    t.join(timeout=5)
    assert got["data"] == data
    # event wake is scheduler-bound (ms); a 1s poll tick would blow this
    assert got["elapsed"] < 0.9, f"waiter took {got['elapsed']:.2f}s to wake"
    assert store.counters()["store_ref_wait_ns"] > 0
    # the waiter registry must not leak satisfied/abandoned entries
    assert all(not s.waiters for s in store._stripes)


def test_store_contains_takes_locks(tmp_path):
    store = SegmentStore(max_bytes=100, spill_dir=tmp_path / "spill")
    fp_a, data_a = _seg(80)
    fp_b, data_b = _seg(80)
    store.put(fp_a, data_a)
    store.put(fp_b, data_b)  # evicts A to spill
    assert fp_a in store  # spill membership via the spill index, not a path probe
    assert fp_b in store
    assert b"\x77" * 16 not in store


def test_store_global_eviction_order_across_stripes(tmp_path):
    """Eviction removes the globally least-recently-used segment, not a
    per-stripe approximation: fps landing in different stripes evict in
    touch order."""
    store = SegmentStore(max_bytes=250, spill_dir=None, stripes=4)
    fps = [bytes([i]) * 16 for i in range(4)]  # four distinct stripes
    for fp in fps[:3]:
        store.put(fp, b"z" * 80)
    assert store.get(fps[0]) == b"z" * 80  # touch 0: now 1 is globally oldest
    store.put(fps[3], b"z" * 80)  # over budget -> evict fp 1
    assert fps[1] not in store and fps[0] in store and fps[2] in store and fps[3] in store


# ------------------------------------------------------- pooled recipe output


def test_parse_recipe_pooled_output_identical_and_recycled():
    pool = BufferPool()
    s1, s2 = _seg(1500), _seg(700)
    wire, *_ = build_recipe([s1, s2, s1], SenderDedupIndex(), ident)
    expected = s1[1] + s2[1] + s1[1]

    plain = parse_recipe(wire, SegmentStore(), ident, verify_literals=True)
    assert plain == expected

    out = parse_recipe(wire, SegmentStore(), ident, verify_literals=True, out_pool=pool)
    assert isinstance(out, PooledChunk)
    assert len(out) == len(expected)
    assert bytes(out.view) == expected
    out.release()
    out.release()  # idempotent
    assert pool.counters()["pool_outstanding"] == 0
    assert pool.counters()["pool_recycled"] == 1
    # the next pooled parse reuses the recycled buffer
    out2 = parse_recipe(wire, SegmentStore(), ident, out_pool=pool)
    assert bytes(out2.view) == expected
    assert pool.counters()["pool_hits"] >= 1
    out2.release()


def test_parse_recipe_rejects_hostile_claimed_size_before_allocating():
    """A tiny frame whose entries claim a huge restored size must fail fast
    on the header cross-check — BEFORE sizing a pooled output buffer or
    touching the store (hostile allocation-size control)."""
    from skyplane_tpu.exceptions import CodecException

    pool = BufferPool()
    huge = (8 << 30) - 1  # just under the absolute cap, so only the header check rejects it
    wire = dedup_mod.MAGIC + struct.pack("<BI", dedup_mod.VERSION, 1) + dedup_mod._ENTRY.pack(dedup_mod.KIND_REF, b"\xaa" * 16, huge)
    with pytest.raises(CodecException, match="header declared"):
        parse_recipe(wire, SegmentStore(), ident, out_pool=pool, expected_raw_len=64)
    assert pool.counters()["pool_misses"] == 0, "the hostile claim drove an allocation"


def test_parse_recipe_pooled_releases_on_failure():
    pool = BufferPool()
    wire = dedup_mod.MAGIC + struct.pack("<BI", dedup_mod.VERSION, 1) + dedup_mod._ENTRY.pack(dedup_mod.KIND_REF, b"\xcd" * 16, 64)
    with pytest.raises(DedupIntegrityException):
        parse_recipe(wire, SegmentStore(), ident, out_pool=pool)
    assert pool.counters()["pool_outstanding"] == 0, "failed decode leaked the pooled buffer"


def test_paranoid_verify_counter_increments():
    from skyplane_tpu.ops.pipeline import DataPathProcessor

    data = rng.integers(0, 256, 150_000, dtype=np.uint8).tobytes()
    sender = DataPathProcessor(codec_name="none", dedup=True)
    idx = SenderDedupIndex()
    p = sender.process(data, idx)
    header = WireProtocolHeader(
        chunk_id="c" * 32,
        data_len=len(p.wire_bytes),
        raw_data_len=p.raw_len,
        codec=int(p.codec),
        flags=int(ChunkFlags.RECIPE),
        fingerprint=p.fingerprint,
    )
    recv = DataPathProcessor(codec_name="none", dedup=True, paranoid_verify=True)
    assert recv.restore(p.wire_bytes, header, store=SegmentStore()) == data
    counters = recv.verify_counters()
    assert counters["verify_total"] == 1
    assert counters["verify_batched"] == 0  # no batch runner on the CPU path


def test_put_drop_oldest_keeps_freshest():
    q = queue.Queue(maxsize=2)
    for i in range(4):
        put_drop_oldest(q, {"i": i})
    assert [q.get_nowait()["i"] for _ in range(2)] == [2, 3]


# ------------------------------------------- the literal pass in one sweep (PR 30)


def _reference_parse(wire, store, decode_blob, verify=True):
    """The per-segment literal pass as it stood: slice, fingerprint, put,
    place, one literal at a time; then the REFs. Returns (raw, ref stats)."""
    wire = bytes(wire)
    _ver, n_entries = struct.unpack_from("<BI", wire, 2)
    off = 2 + struct.calcsize("<BI")
    entries = []
    for _ in range(n_entries):
        entries.append(dedup_mod._ENTRY.unpack_from(wire, off))
        off += dedup_mod._ENTRY.size
    blob = bytes(decode_blob(wire[off:]))
    out = bytearray(sum(e[2] for e in entries))
    refs, at, lit_off = [], 0, 0
    for kind, fp, seg_len in entries:
        if kind == dedup_mod.KIND_LIT:
            seg = blob[lit_off : lit_off + seg_len]
            lit_off += seg_len
            if verify and segment_fingerprint_host(seg) != fp:
                raise DedupIntegrityException(f"literal segment fingerprint mismatch (claimed {fp.hex()})")
            store.put(fp, seg)
            out[at : at + seg_len] = seg
        else:
            refs.append((at, fp, seg_len))
        at += seg_len
    for at, fp, seg_len in refs:
        out[at : at + seg_len] = store.get(fp)
    stats = {"ref_segments_resolved": len(refs), "ref_bytes_resolved": sum(r[2] for r in refs)} if refs else {}
    return bytes(out), stats


def _recipe_case(name):
    """(segments in order, fingerprints the sender's index already holds)."""
    case_rng = np.random.default_rng(30)

    def seg(n):
        data = case_rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        return segment_fingerprint_host(data), data

    known = [seg(900), seg(1300), seg(70)]
    fresh = [seg(n) for n in (1100, 1, 4096, 513, 2048, 777, 5000, 64)]
    if name == "all_literal":
        return fresh, []
    if name == "all_ref":
        return known + known[:1], known
    if name == "interleaved":  # literal runs of 1, 2, 3 and 1 between REFs, a REF first and a literal last
        order = [known[0], fresh[0], known[1], fresh[1], fresh[2], known[2], fresh[3], fresh[4], fresh[5], known[0], fresh[6]]
        return order, known
    if name == "own_chunk_repeat":  # the second sight of a fresh segment is a REF into this very chunk
        return [fresh[0], fresh[1], fresh[0], known[0], fresh[2], fresh[2]], known
    if name == "empty":
        return [], []
    if name == "padded_last_block":  # 1100 + 1 + 4096 + 513 bytes of literals: not whole 512-byte blocks
        return fresh[:4] + [known[1]], known
    if name == "empty_literal":
        return [fresh[0], (segment_fingerprint_host(b""), b""), fresh[1]], []
    raise AssertionError(name)


def _codec_pair(codec_name):
    if codec_name == "ident":
        return ident, ident, None
    if codec_name == "tpu_zstd":
        pytest.importorskip("zstandard")
    from skyplane_tpu.ops.codecs import get_codec

    spec = get_codec(codec_name)
    return spec.encode, spec.decode, spec.decode_out_len


def _stores_equal(a: SegmentStore, b: SegmentStore, fps):
    assert a.mem_segment_count == b.mem_segment_count
    for fp in fps:
        assert (fp in a) == (fp in b)
        if fp in a:
            assert bytes(a.peek(fp)) == bytes(b.peek(fp))
            assert memoryview(b.peek(fp)).readonly, "a stored segment is bytes or a read-only view"


CASES = ["all_literal", "all_ref", "interleaved", "own_chunk_repeat", "empty", "padded_last_block", "empty_literal"]


@pytest.mark.parametrize("pooled", [False, True], ids=["plain", "pooled"])
@pytest.mark.parametrize("codec_name", ["ident", "tpu", "tpu_zstd"])
@pytest.mark.parametrize("case", CASES)
def test_literal_pass_matches_per_segment_reference(case, codec_name, pooled):
    """(a) the one-sweep literal pass against the per-segment form written
    out above: output bytes, store contents and ref_stats equal."""
    encode, decode, out_len = _codec_pair(codec_name)
    segments, known = _recipe_case(case)
    index = SenderDedupIndex()
    want_store, got_store = SegmentStore(), SegmentStore()
    for fp, data in known:
        index.add(fp, len(data))
        want_store.put(fp, data)
        got_store.put(fp, data)
    wire, n_ref, *_ = build_recipe(segments, index, encode)
    raw = b"".join(data for _, data in segments)
    want, want_stats = _reference_parse(wire, want_store, decode)
    assert want == raw
    pool = BufferPool()
    stats: dict = {}
    got = parse_recipe(
        wire, got_store, decode, verify_literals=True, out_pool=pool if pooled else None,
        expected_raw_len=len(raw), ref_stats=stats, blob_out_len=out_len,
    )
    if pooled and raw:
        assert isinstance(got, PooledChunk)
        assert bytes(got.view) == raw
        got.release()
    else:
        assert got == raw and type(got) is bytes
    assert pool.counters()["pool_outstanding"] == 0, "a pooled buffer of the literal pass was not released"
    _stores_equal(want_store, got_store, [fp for fp, _ in segments + known])
    assert {k: v for k, v in stats.items() if k in ("ref_segments_resolved", "ref_bytes_resolved")} == want_stats
    assert stats.get("ref_segments_resolved", 0) == n_ref
    n_lit = len(segments) - n_ref
    assert stats["literal_segments_verified"] == n_lit
    assert stats["literal_verify_calls"] == (1 if n_lit else 0)
    assert 0 < stats["literal_pass_ns"]
    assert ("ref_resolve_ns" in stats) == bool(n_ref)


@pytest.mark.parametrize("pooled", [False, True], ids=["plain", "pooled"])
@pytest.mark.parametrize("where", ["first", "middle", "last"])
def test_flipped_literal_byte_admits_no_literal_of_the_chunk(where, pooled):
    """(b) one flipped literal byte: DedupIntegrityException naming that
    literal's fingerprint, and NO literal of the chunk is in the store
    afterwards, those ahead of the bad one included; pooled buffers released."""
    segments, _ = _recipe_case("all_literal")
    wire, *_ = build_recipe(segments, SenderDedupIndex(), ident)
    k = {"first": 0, "middle": len(segments) // 2, "last": len(segments) - 1}[where]
    at = len(wire) - sum(len(d) for _, d in segments[k:]) + len(segments[k][1]) // 2
    bad = bytearray(wire)
    bad[at] ^= 0x01
    store, pool = SegmentStore(), BufferPool()
    with pytest.raises(DedupIntegrityException, match=segments[k][0].hex()):
        parse_recipe(bytes(bad), store, ident, verify_literals=True, out_pool=pool if pooled else None)
    assert store.mem_segment_count == 0 and all(fp not in store for fp, _ in segments)
    counters = pool.counters()
    assert counters["pool_outstanding"] == 0
    assert counters["pool_recycled"] == (1 if pooled else 0)  # ident takes no buffer: only the output was drawn


def test_flipped_literal_byte_releases_the_codecs_buffer_too():
    """(b) with a codec that decodes into memory its caller gives (blockpack):
    the output buffer is back in the pool after the refusal, and the codec's
    buffer (the one the store would have adopted) never came from it."""
    encode, decode, out_len = _codec_pair("tpu")
    segments, _ = _recipe_case("all_literal")
    wire, *_ = build_recipe(segments, SenderDedupIndex(), encode)
    bad = bytearray(wire)
    bad[-1000] ^= 0x80  # blockpack of random bytes: the container ends in its literal blocks (the last one padded)
    store, pool = SegmentStore(), BufferPool()
    with pytest.raises(DedupIntegrityException, match="fingerprint mismatch"):
        parse_recipe(bytes(bad), store, decode, verify_literals=True, out_pool=pool, blob_out_len=out_len)
    assert store.mem_segment_count == 0 and store.counters()["store_mem_bytes"] == 0
    counters = pool.counters()
    assert counters["pool_outstanding"] == 0 and counters["pool_recycled"] == 1 and counters["pool_misses"] == 1


@pytest.mark.parametrize("native", [True, False], ids=["native", "host_fallback"])
def test_literal_verify_is_one_call_a_chunk(native, monkeypatch):
    """(d) through restore(): one verify call for a chunk with literals, none
    for one without; every literal entry verified. With the native library and
    with the numpy form forced."""
    from skyplane_tpu.native import datapath as native_dp
    from skyplane_tpu.ops.pipeline import DataPathProcessor

    if native and not native_dp.available():
        pytest.skip("no native library on this host")
    if not native:
        monkeypatch.setattr(native_dp, "available", lambda: False)
    data = np.random.default_rng(5).integers(0, 256, 300_000, dtype=np.uint8).tobytes()
    proc = DataPathProcessor(codec_name="tpu", dedup=True)
    index, store = SenderDedupIndex(), SegmentStore()
    seen = []
    for _ in range(2):  # the second time every segment is a REF
        p = proc.process(data, index)
        for fp, size in p.new_fingerprints:
            index.add(fp, size)
        header = WireProtocolHeader(
            chunk_id="d" * 32, data_len=len(p.wire_bytes), raw_data_len=p.raw_len, codec=int(p.codec),
            flags=int(ChunkFlags.RECIPE), fingerprint=p.fingerprint,
        )
        stats: dict = {}
        out = proc.restore(p.wire_bytes, header, store=store, pooled=True, ref_stats=stats)
        assert bytes(out.view) == data
        out.release()
        seen.append((p, stats))
    (first, lit_stats), (second, ref_stats) = seen
    assert first.n_ref_segments == 0 and first.n_segments > 10
    assert lit_stats["literal_verify_calls"] == 1 and lit_stats["literal_segments_verified"] == first.n_segments
    assert second.n_ref_segments == second.n_segments
    assert ref_stats["literal_verify_calls"] == 0 and ref_stats["literal_segments_verified"] == 0
    assert ref_stats["ref_segments_resolved"] == second.n_segments
    assert proc.bufpool.counters()["pool_outstanding"] == 0


def test_literal_pass_counters_are_served(tmp_path):
    """(d) the three counters sit in the stable decode schema and move with a decoded chunk."""
    for key in ("literal_pass_ns", "literal_segments_verified", "literal_verify_calls"):
        assert DECODE_COUNTER_ZERO[key] == 0
    r, store, ev, port = _mk_receiver(tmp_path, decode_workers=2)
    try:
        segs = [_seg(700), _seg(300), _seg(1100)]
        assert _send_frames(port, [_literal_frame(segs)]) == ACK_BYTE
        counters = r.decode_counters()
        assert counters["literal_verify_calls"] == 1 and counters["literal_segments_verified"] == 3
        assert 0 < counters["literal_pass_ns"] <= counters["decode_ns"]
    finally:
        r.stop_all()


def test_decoding_3000_literals_calls_the_fingerprint_kernel_once(monkeypatch):
    """The guard: the number of native fingerprint calls a decode makes does
    not grow with the number of segments (per-segment calls each hand the
    interpreter lock over; at 3,000 a chunk that was most of the sink's decode)."""
    from skyplane_tpu.native import datapath as native_dp

    if not native_dp.available():
        pytest.skip("no native library on this host")
    blob = np.random.default_rng(9).integers(0, 256, 3000 * 640, dtype=np.uint8)
    ends = np.arange(1, 3001, dtype=np.int64) * 640
    from skyplane_tpu.ops.fingerprint import segment_fingerprints_host_batch

    fps = segment_fingerprints_host_batch(blob, ends)
    raw = blob.tobytes()
    segments = [(fp, raw[e - 640 : e]) for fp, e in zip(fps, ends.tolist())]
    wire, *_ = build_recipe(segments, SenderDedupIndex(), ident)
    calls = []
    real = native_dp.segment_fp_lanes

    def counted(data, seg_ends):
        calls.append(len(seg_ends))
        return real(data, seg_ends)

    monkeypatch.setattr(native_dp, "segment_fp_lanes", counted)
    stats: dict = {}
    assert parse_recipe(wire, SegmentStore(), ident, verify_literals=True, ref_stats=stats) == raw
    assert len(calls) <= 2 and sum(calls) == 3000, calls
    assert stats["literal_segments_verified"] == 3000 and stats["literal_verify_calls"] == 1


# sender side, pinned at the parent of PR 30 (7b483d0): blake2b-128 of what process() puts on the wire
SENDER_WIRE_DIGESTS = {
    "tpu": "15309385072878db9a483bf0771ec945",
    "none": "a246691e777b06fccdc951cb4915b21e",
}


@pytest.mark.parametrize("codec_name", sorted(SENDER_WIRE_DIGESTS))
def test_sender_side_bytes_are_the_parents(codec_name):
    """The literal pass is the receiver's alone: recipe layout, container
    layout and the frame's payload are byte for byte what the parent commit
    sent for the same input (zeros, a constant run and random bytes, so the
    container holds every tag), so old and new gateways decode each other."""
    import hashlib

    from skyplane_tpu.ops.pipeline import DataPathProcessor

    r = np.random.default_rng(3030)
    data = r.integers(0, 256, 200_000, dtype=np.uint8).tobytes() + bytes(40_000) + b"\x07" * 30_000
    data += r.integers(0, 256, 1234, dtype=np.uint8).tobytes()
    p = DataPathProcessor(codec_name=codec_name, dedup=True).process(data + data[:100_000], SenderDedupIndex())
    assert hashlib.blake2b(p.wire_bytes, digest_size=16).hexdigest() == SENDER_WIRE_DIGESTS[codec_name]


# ------------------------------- one buffer a chunk: the store's views (PR 37)


def _lit_buffer_len(segments, out_len):
    """Bytes of the buffer the literal pass hands the store for an all-literal recipe."""
    n = sum(len(d) for _, d in segments)
    return out_len(n) if out_len is not None else n


@pytest.mark.parametrize("codec_name", ["ident", "tpu", "tpu_zstd"])
def test_store_holds_no_view_of_pooled_memory(codec_name):
    """(a) two chunks decoded back to back through a pooled ``out_pool``; then
    every buffer the pool ever handed out is written over: each stored
    segment still equals its source bytes."""
    encode, decode, out_len = _codec_pair(codec_name)
    pool, store, handed = BufferPool(), SegmentStore(), []
    acquire = pool.acquire

    def acquire_and_note(bucket):
        handed.append(acquire(bucket))
        return handed[-1]

    pool.acquire = acquire_and_note
    chunks = [[_seg(n) for n in (1500, 700, 4096, 33)] for _ in range(2)]
    for segments in chunks:
        wire, *_ = build_recipe(segments, SenderDedupIndex(), encode)
        raw = b"".join(d for _, d in segments)
        out = parse_recipe(
            wire, store, decode, verify_literals=True, out_pool=pool, expected_raw_len=len(raw), blob_out_len=out_len
        )
        assert bytes(out.view) == raw
        out.release()
    assert handed and pool.counters()["pool_outstanding"] == 0
    for arr in handed:
        arr[:] = 0xA5
    for fp, data in (seg for segments in chunks for seg in segments):
        assert bytes(store.get(fp)) == data


@pytest.mark.parametrize("codec_name", ["ident", "tpu"])
def test_store_charges_a_buffer_once_and_credits_it_with_its_last_segment(codec_name, tmp_path):
    """(b) after one chunk the store is charged the buffer's length; the charge
    holds while all but one of the buffer's segments are evicted, and goes in
    full with the last."""
    encode, decode, out_len = _codec_pair(codec_name)
    store = SegmentStore(spill_dir=tmp_path / "spill")
    segments = [_seg(n) for n in (900, 1300, 70, 2048)]
    wire, *_ = build_recipe(segments, SenderDedupIndex(), encode)
    parse_recipe(wire, store, decode, verify_literals=True, blob_out_len=out_len)
    blob_len = _lit_buffer_len(segments, out_len)
    c = store.counters()
    assert c["store_mem_bytes"] == c["store_blob_bytes"] == blob_len
    lone_fp, lone = _seg(100)
    store.put(lone_fp, lone)
    assert store.counters()["store_mem_bytes"] == blob_len + len(lone)
    keep_fp = segments[2][0]
    store.get(keep_fp)  # the newest now: evicted last
    store.set_bounds(max_bytes=blob_len)  # the buffer's other three go (no credit), then the lone segment
    c = store.counters()
    assert store.mem_segment_count == 1 and c["store_mem_evictions"] == len(segments)
    assert c["store_mem_bytes"] == c["store_blob_bytes"] == blob_len
    store.set_bounds(max_bytes=blob_len - 1)  # the last segment of the buffer goes: the whole length is credited
    c = store.counters()
    assert store.mem_segment_count == 0 and c["store_mem_bytes"] == c["store_blob_bytes"] == 0
    for fp, data in segments + [(lone_fp, lone)]:
        assert bytes(store.get(fp)) == data  # from spill


def test_a_resident_fingerprint_takes_no_view_of_the_new_buffer():
    """(c) a literal whose fingerprint the store already holds keeps the held
    bytes; a chunk whose every literal is resident charges nothing."""
    store = SegmentStore()
    segments = [_seg(n) for n in (800, 600, 1200)]
    held = bytes(segments[1][1])
    store.put(segments[1][0], held)
    wire, *_ = build_recipe(segments, SenderDedupIndex(), ident)  # all three as literals
    parse_recipe(wire, store, ident, verify_literals=True)
    assert store.peek(segments[1][0]) is held
    c = store.counters()
    assert (c["store_blobs"], c["store_blob_segments"]) == (1, 2)
    assert c["store_mem_bytes"] == len(held) + 2600 and c["store_blob_bytes"] == 2600
    parse_recipe(wire, store, ident, verify_literals=True)  # every fingerprint resident now
    assert store.counters() == c
    assert store.mem_segment_count == 3


@pytest.mark.parametrize("codec_name", ["ident", "tpu"])
def test_a_segment_spilled_and_promoted_is_its_own_bytes_charged_alone(codec_name, tmp_path):
    """(d) every segment of a chunk evicted to spill, one promoted back: it
    resolves byte for byte, as ``bytes`` of its own, charged its length."""
    encode, decode, out_len = _codec_pair(codec_name)
    store = SegmentStore(spill_dir=tmp_path / "spill")
    segments = [_seg(n) for n in (700, 1900, 256)]
    wire, *_ = build_recipe(segments, SenderDedupIndex(), encode)
    parse_recipe(wire, store, decode, verify_literals=True, blob_out_len=out_len)
    store.flush_to_spill()
    assert store.mem_segment_count == 0 and store.counters()["store_mem_bytes"] == 0
    fp, data = segments[1]
    got = store.get(fp)
    assert type(got) is bytes and got == data
    c = store.counters()
    assert c["store_promotions"] == 1 and c["store_mem_bytes"] == len(data) and c["store_blob_bytes"] == 0


def test_store_blob_counters_count_one_buffer_a_chunk_with_literals(tmp_path):
    """(e) through a live receiver: a chunk with literals admits one buffer and
    as many views as it has literal entries; a chunk of REFs alone admits none.
    The three counters are in the stable decode schema."""
    for key in ("store_blobs", "store_blob_segments", "store_blob_bytes"):
        assert DECODE_COUNTER_ZERO[key] == 0
    r, store, ev, port = _mk_receiver(tmp_path, decode_workers=2)
    try:
        s1, s2, s3, s4 = _seg(700), _seg(300), _seg(1100), _seg(450)
        index = SenderDedupIndex()
        index.add(s2[0], len(s2[1]))
        mixed, *_ = build_recipe([s4, s2], index, ident)  # one literal, one REF
        mixed_frame = (
            WireProtocolHeader(chunk_id=uuid.uuid4().hex, data_len=len(mixed), raw_data_len=len(s4[1] + s2[1]),
                               flags=int(ChunkFlags.RECIPE)),
            mixed, s4[1] + s2[1],
        )
        frames = [_literal_frame([s1, s2, s3]), _ref_frame(s1[0], len(s1[1]), s1[1]), mixed_frame]
        assert _send_frames(port, frames) == ACK_BYTE * 3
        c = r.decode_counters()
        assert (c["store_blobs"], c["store_blob_segments"]) == (2, 4)
        assert c["store_blob_bytes"] == c["store_mem_bytes"] == sum(len(s[1]) for s in (s1, s2, s3, s4))
    finally:
        r.stop_all()


def test_blob_accounting_holds_under_concurrent_puts_and_evictions(tmp_path):
    """Many threads admit buffers (some segments shared between them) into a
    store small enough that eviction to spill runs all the time, with a short
    switch interval: the charge always equals what the memory tier holds,
    every segment reads back, and emptying the tier credits everything."""
    import os
    import sys

    store = SegmentStore(max_bytes=1_500, spill_dir=tmp_path / "spill", stripes=4)  # under one buffer: new views get evicted too
    common = [rng.integers(0, 256, 300, dtype=np.uint8).tobytes() for _ in range(6)]
    errors = []

    def worker(w):
        try:
            r = np.random.default_rng(1000 + w)
            for k in range(20):
                parts = [common[(w + k) % len(common)]] + [r.integers(0, 256, int(n), dtype=np.uint8).tobytes() for n in r.integers(1, 700, 6)]
                buf = b"".join(parts)
                ends = np.cumsum([len(p) for p in parts]).tolist()
                starts = [e - len(p) for e, p in zip(ends, parts)]
                fps = [segment_fingerprint_host(p) for p in parts]
                store.put_blob(buf, fps, starts, ends)
                for fp, p in zip(fps, parts):
                    assert bytes(store.get(fp)) == p
        except BaseException as e:  # noqa: BLE001 — handed to the main thread
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(w,), daemon=True) for w in range((os.cpu_count() or 4) + 4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    blobs, alone, live = {}, 0, {}
    for s in store._stripes:
        for data, _, blob in s.mem.values():
            if blob is None:
                alone += len(data)
            else:
                blobs[id(blob)] = blob
                live[id(blob)] = live.get(id(blob), 0) + 1
    assert all(blob.live == live[key] for key, blob in blobs.items())
    c = store.counters()
    assert c["store_blob_bytes"] == sum(b.nbytes for b in blobs.values())
    assert c["store_mem_bytes"] == alone + c["store_blob_bytes"]
    store.set_bounds(max_bytes=1)
    c = store.counters()
    assert store.mem_segment_count == 0 and c["store_mem_bytes"] == c["store_blob_bytes"] == 0
