import numpy as np
import pytest

from skyplane_tpu.exceptions import DedupIntegrityException, NoSuchObjectException
from skyplane_tpu.ops.dedup import (
    SegmentStore,
    SenderDedupIndex,
    build_recipe,
    parse_recipe,
)
from skyplane_tpu.ops.fingerprint import segment_fingerprint_host

rng = np.random.default_rng(3)
ident = lambda b: b


def _seg(n=1000):
    data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
    return segment_fingerprint_host(data), data


def test_recipe_roundtrip_and_dedup():
    index = SenderDedupIndex()
    store = SegmentStore()
    s1, s2 = _seg(), _seg()
    segments = [s1, s2, s1]  # in-chunk repeat -> 1 REF
    wire, n_ref, lit_bytes, new_fps, ref_fps = build_recipe(segments, index, ident)
    assert n_ref == 1 and len(new_fps) == 2
    assert len(index) == 0, "build_recipe must not mutate the index before delivery"
    out = parse_recipe(wire, store, ident, verify_literals=True)
    assert out == s1[1] + s2[1] + s1[1]
    # commit, then second chunk refs everything
    for fp, size in new_fps:
        index.add(fp, size)
    wire2, n_ref2, lit2, new2, refs2 = build_recipe([s1, s2], index, ident)
    assert n_ref2 == 2 and lit2 == 0 and not new2
    assert parse_recipe(wire2, store, ident) == s1[1] + s2[1]
    assert len(wire2) < 100  # refs only: ~25B/entry


def test_recipe_rejects_corrupted_literal():
    index = SenderDedupIndex()
    store = SegmentStore()
    fp, data = _seg()
    wire, *_ = build_recipe([(fp, data)], index, ident)
    corrupted = bytearray(wire)
    corrupted[-1] ^= 0xFF  # flip a literal byte
    with pytest.raises(DedupIntegrityException):
        parse_recipe(bytes(corrupted), store, ident, verify_literals=True)
    # and nothing was admitted to the store under the healthy fingerprint
    assert fp not in store


def test_unresolvable_ref_raises():
    store = SegmentStore()
    fp, data = _seg()
    index = SenderDedupIndex()
    index.add(fp)  # sender thinks receiver has it
    wire, n_ref, *_ = build_recipe([(fp, data)], index, ident)
    assert n_ref == 1
    with pytest.raises(DedupIntegrityException):
        parse_recipe(wire, store, ident, ref_wait_timeout=0.1)


def test_segment_store_spill(tmp_path):
    store = SegmentStore(max_bytes=2000, spill_dir=tmp_path / "spill")
    segs = [_seg(900) for _ in range(5)]
    for fp, data in segs:
        store.put(fp, data)
    for fp, data in segs:
        assert store.get(fp) == data  # spilled entries still resolve


def test_device_and_host_fingerprints_agree():
    import jax.numpy as jnp

    from skyplane_tpu.ops.fingerprint import finalize_fingerprint, segment_fingerprint_cumsum

    data = rng.integers(0, 256, 3000, dtype=np.uint8)
    seg_starts, seg_ends = np.array([0, 1200], np.int32), np.array([1200, 3000], np.int32)
    lanes = np.asarray(segment_fingerprint_cumsum(jnp.asarray(data), jnp.asarray(seg_starts), jnp.asarray(seg_ends), n_segments=2))
    host0 = segment_fingerprint_host(data[:1200].tobytes())
    host1 = segment_fingerprint_host(data[1200:].tobytes())
    assert bytes.fromhex(finalize_fingerprint(lanes[0], 1200)) == host0
    assert bytes.fromhex(finalize_fingerprint(lanes[1], 1800)) == host1


def test_posix_bucket_escape(tmp_path):
    from skyplane_tpu.obj_store.posix_file_interface import POSIXInterface

    (tmp_path / "bucket").mkdir()
    (tmp_path / "bucket2").mkdir()
    (tmp_path / "bucket2" / "secret").write_bytes(b"x")
    iface = POSIXInterface(str(tmp_path / "bucket"))
    with pytest.raises(NoSuchObjectException):
        iface.exists("../bucket2/secret")


def test_and_queue_requeue_single_branch():
    from skyplane_tpu.chunk import Chunk, ChunkRequest
    from skyplane_tpu.gateway.gateway_queue import GatewayANDQueue

    q = GatewayANDQueue()
    q.register_handle("a")
    q.register_handle("b")
    cr = ChunkRequest(chunk=Chunk(src_key="s", dest_key="d", chunk_id="0" * 32, chunk_length_bytes=1))
    q.put(cr)
    assert q.pop("a", timeout=0.1) is cr and q.pop("b", timeout=0.1) is cr
    q.put_for_handle("a", cr)  # requeue only to branch a
    assert q.pop("a", timeout=0.1) is cr
    import queue as _q

    with pytest.raises(_q.Empty):
        q.get_nowait("b")


def test_paranoid_verify_catches_poisoned_store():
    """A segment store poisoned with wrong bytes under a valid fingerprint
    slips past per-literal checks (REFs trust the fp) — paranoid receivers
    re-chunk the restored data and catch it end-to-end."""
    from skyplane_tpu.chunk import ChunkFlags, Codec, WireProtocolHeader
    from skyplane_tpu.exceptions import ChecksumMismatchException
    from skyplane_tpu.ops.pipeline import DataPathProcessor

    pytest.importorskip("zstandard")  # optional dep: minimal containers ship without it
    rng2 = np.random.default_rng(77)
    data = rng2.integers(0, 256, 200_000, dtype=np.uint8).tobytes()
    sender = DataPathProcessor(codec_name="zstd", dedup=True)
    idx = SenderDedupIndex()
    p1 = sender.process(data, idx)
    for fp, size in p1.new_fingerprints:
        idx.add(fp, size)
    p2 = sender.process(data, idx)  # all REFs
    assert p2.n_ref_segments == p2.n_segments

    # honest receiver
    store = SegmentStore()
    recv = DataPathProcessor(codec_name="none", dedup=True, paranoid_verify=True)
    hdr1 = WireProtocolHeader(
        chunk_id="a" * 32, data_len=len(p1.wire_bytes), raw_data_len=p1.raw_len,
        codec=int(p1.codec), flags=int(ChunkFlags.COMPRESSED | ChunkFlags.RECIPE), fingerprint=p1.fingerprint,
    )
    assert recv.restore(p1.wire_bytes, hdr1, store=store) == data

    # poison the store: swap one segment's bytes under its fingerprint
    # (reach into the owning stripe — the striped store has no single map)
    victim_fp = next(fp for s in store._stripes for fp in s.mem)
    entry = store._stripe(victim_fp).mem[victim_fp]
    entry[0] = bytes(len(entry[0]))
    hdr2 = WireProtocolHeader(
        chunk_id="b" * 32, data_len=len(p2.wire_bytes), raw_data_len=p2.raw_len,
        codec=int(p2.codec), flags=int(ChunkFlags.COMPRESSED | ChunkFlags.RECIPE), fingerprint=p2.fingerprint,
    )
    with pytest.raises(ChecksumMismatchException, match="paranoid"):
        recv.restore(p2.wire_bytes, hdr2, store=store)

    # non-paranoid receiver would have accepted the corruption silently
    lax = DataPathProcessor(codec_name="none", dedup=True, paranoid_verify=False)
    corrupted = lax.restore(p2.wire_bytes, hdr2, store=store)
    assert corrupted != data


def test_sender_index_rebound_evicts():
    from skyplane_tpu.ops.dedup import SenderDedupIndex

    idx = SenderDedupIndex(max_bytes=1000)
    for i in range(10):
        idx.add(bytes([i]) * 16, 100)
    assert len(idx) == 10
    idx.set_max_bytes(350)  # shrink: oldest entries evicted immediately
    assert len(idx) == 3
    assert bytes([9]) * 16 in idx and bytes([0]) * 16 not in idx
    assert idx.max_bytes == 350


def test_segment_store_capacity_advertised(tmp_path):
    from skyplane_tpu.ops.dedup import SegmentStore

    assert SegmentStore(max_bytes=100).capacity_bytes == 100  # no spill dir
    store = SegmentStore(max_bytes=100, spill_dir=tmp_path, spill_max_bytes=900)
    assert store.capacity_bytes == 1000


def test_multi_source_budget_split():
    """Each sender's index shrinks to capacity/(2*n_sources) as the sink
    reports more distinct sources."""
    from skyplane_tpu.gateway.operators.gateway_operator import GatewaySenderOperator

    op = GatewaySenderOperator.__new__(GatewaySenderOperator)  # no daemon wiring
    from skyplane_tpu.ops.dedup import SenderDedupIndex

    op.dedup_index = SenderDedupIndex(max_bytes=16 << 30)
    op._apply_dedup_budget({"dedup_capacity_bytes": 36 << 30, "n_sources": 3})
    assert op.dedup_index.max_bytes == 6 << 30
    op._apply_dedup_budget({})  # no capacity info: budget unchanged
    assert op.dedup_index.max_bytes == 6 << 30
    op.dedup_index = None
    op._apply_dedup_budget({"dedup_capacity_bytes": 1})  # dedup off: no-op
