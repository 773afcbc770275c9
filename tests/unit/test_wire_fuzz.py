"""Fuzz the parsers that consume bytes off the wire.

A receiver parses data sent by remote peers; malformed or corrupted input
must surface as the codec error contract (CodecException /
DedupIntegrityException / ChecksumMismatchException / SkyplaneTpuException),
never as raw IndexError / struct.error / MemoryError crashes that would take
down the connection handler in uncontrolled ways.

The injector-driven cases at the bottom push the same hostile conditions
through a LIVE GatewayReceiver at the framing boundary (short reads,
mid-frame disconnects, corrupt payloads, injected decode faults) and assert
the recovery contracts end to end: NACK -> literal resend, dropped
connections -> sender resend, and NO partial chunk ever exposed (a ``.done``
marker only ever appears on a byte-correct chunk file).
"""

import queue
import socket
import struct
import threading
import time
import uuid

import numpy as np
import pytest

from skyplane_tpu.chunk import HEADER_LENGTH_BYTES, ChunkFlags, WireProtocolHeader
from skyplane_tpu.exceptions import SkyplaneTpuException
from skyplane_tpu.faults import FaultPlan, configure_injector
from skyplane_tpu.gateway.chunk_store import ChunkStore
from skyplane_tpu.gateway.operators.gateway_receiver import ACK_BYTE, NACK_UNRESOLVED, GatewayReceiver
from skyplane_tpu.ops import blockpack
from skyplane_tpu.ops import dedup as dedup_mod
from skyplane_tpu.ops.dedup import SegmentStore, SenderDedupIndex, build_recipe, parse_recipe
from skyplane_tpu.ops.fingerprint import segment_fingerprint_host

rng = np.random.default_rng(1337)

ALLOWED = SkyplaneTpuException  # whole hierarchy (Codec/Dedup/Checksum/...)


def _mutations(base: bytes, n: int = 60):
    """Truncations, bit flips, random garbage of matching length."""
    out = []
    for _ in range(n // 3):
        cut = int(rng.integers(0, max(len(base), 1)))
        out.append(base[:cut])
    for _ in range(n // 3):
        b = bytearray(base)
        if b:
            for _ in range(int(rng.integers(1, 8))):
                b[int(rng.integers(0, len(b)))] ^= int(rng.integers(1, 256))
        out.append(bytes(b))
    for _ in range(n // 3):
        out.append(rng.integers(0, 256, len(base) or 1, dtype=np.uint8).tobytes())
    return out


def test_wire_header_fuzz():
    import uuid

    base = WireProtocolHeader(
        chunk_id=uuid.uuid4().hex, data_len=1000, raw_data_len=2000, codec=1, flags=3, fingerprint="ab" * 16
    ).to_bytes()
    for m in _mutations(base):
        if len(m) != HEADER_LENGTH_BYTES:
            with pytest.raises(ALLOWED):
                WireProtocolHeader.from_bytes(m)
        else:
            try:
                WireProtocolHeader.from_bytes(m)
            except ALLOWED:
                pass  # rejected cleanly (CRC catches essentially everything)


def test_blockpack_container_fuzz():
    data = rng.integers(0, 256, 20000, dtype=np.uint8).tobytes() + bytes(12000)
    base = blockpack.encode_container(data)
    for m in _mutations(base):
        try:
            blockpack.decode_container(m)
        except ALLOWED:
            pass


def test_recipe_fuzz():
    from skyplane_tpu.ops.fingerprint import segment_fingerprint_host

    segs = []
    for _ in range(4):
        b = rng.integers(0, 256, 3000, dtype=np.uint8).tobytes()
        segs.append((segment_fingerprint_host(b), b))
    wire, *_ = build_recipe(segs, SenderDedupIndex(), lambda b: b)
    store = SegmentStore()
    for m in _mutations(wire):
        try:
            parse_recipe(m, store, lambda b: b, verify_literals=True)
        except ALLOWED:
            pass


def test_recipe_huge_claimed_counts():
    """Adversarial entry counts must not allocate unbounded memory or crash."""
    import struct

    from skyplane_tpu.ops.dedup import MAGIC, VERSION

    evil = MAGIC + struct.pack("<BI", VERSION, 0xFFFFFFFF)  # 4B entries, no data
    with pytest.raises(ALLOWED):
        parse_recipe(evil, SegmentStore(), lambda b: b)


def test_corrupt_zstd_frame_stays_in_codec_contract():
    pytest.importorskip("zstandard")  # optional dep: minimal containers ship without it
    from skyplane_tpu.ops.codecs import get_codec

    spec = get_codec("zstd")
    good = spec.encode(b"payload " * 1000)
    for m in _mutations(good, 30):
        try:
            spec.decode(m)
        except ALLOWED:
            pass  # must never escape as raw zstandard.ZstdError


def test_truncated_tag_region_rejected():
    data = bytes(8192)
    enc = blockpack.encode_container(data)
    # cut inside the tag region (header is 20 bytes; zeros -> tiny container)
    with pytest.raises(ALLOWED):
        blockpack.decode_container(enc[:21])


# ---- the literal pass in one sweep (PR 30): the same contract for every input type and codec


def _as_input(kind, m: bytes):
    return m if kind == "bytes" else memoryview(bytearray(m))


@pytest.mark.parametrize("kind", ["bytes", "memoryview"])
@pytest.mark.parametrize("codec_name", ["none", "tpu"])
@pytest.mark.parametrize("pooled", [False, True], ids=["plain", "pooled"])
def test_recipe_fuzz_stays_in_contract_for_every_input(kind, codec_name, pooled):
    """Mutated recipes raise CodecException / DedupIntegrityException and never
    anything else, whether the payload arrives as bytes or as a memoryview, with
    and without the pooled buffers; no pooled buffer is left out."""
    from skyplane_tpu.exceptions import CodecException, DedupIntegrityException
    from skyplane_tpu.ops.bufpool import BufferPool
    from skyplane_tpu.ops.codecs import get_codec
    from skyplane_tpu.ops.dedup import PooledChunk

    spec = get_codec(codec_name)
    segs = []
    for n in (3000, 1, 700, 2049):
        b = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        segs.append((segment_fingerprint_host(b), b))
    wire, *_ = build_recipe(segs + segs[:1], SenderDedupIndex(), spec.encode)
    pool = BufferPool()
    refused = 0
    for m in _mutations(wire, 90) + [wire]:
        try:
            got = parse_recipe(
                _as_input(kind, m), SegmentStore(), spec.decode, verify_literals=True,
                out_pool=pool if pooled else None, blob_out_len=spec.decode_out_len,
            )
            if isinstance(got, PooledChunk):
                got.release()
        except (CodecException, DedupIntegrityException):
            refused += 1
        assert pool.counters()["pool_outstanding"] == 0
    assert 0 < refused <= 90, "the unmutated recipe has to pass and some mutation has to be refused"


@pytest.mark.parametrize("kind", ["bytes", "memoryview"])
def test_blockpack_container_fuzz_every_input(kind):
    from skyplane_tpu.exceptions import CodecException

    data = rng.integers(0, 256, 20000, dtype=np.uint8).tobytes() + bytes(12000) + b"\x05" * 3000
    base = blockpack.encode_container(data)
    assert blockpack.decode_container(_as_input(kind, base)) == data
    out = np.empty(blockpack.padded_len(len(data)), np.uint8)
    for m in _mutations(base):
        for buf in (None, out):
            try:
                blockpack.decode_container(_as_input(kind, m), buf)
            except CodecException:
                pass


def _hand_container(tags, literals: bytes, n_raw: int, block_log2: int = 8) -> bytes:
    head = blockpack.MAGIC + struct.pack("<BBQQ", blockpack.VERSION, block_log2, n_raw, len(literals))
    return head + blockpack._pack_tags(np.asarray(tags, np.uint8)) + literals


@pytest.mark.parametrize("native", [True, False], ids=["native", "host_fallback"])
def test_decode_container_into_a_callers_buffer(native, monkeypatch):
    """(e) decoding into memory the caller owns gives what decoding into a
    fresh array gives, for zero / constant / literal / invalid-tag blocks and
    a last block that is padded; the view is over the caller's array; a buffer
    shorter than the padded length is refused."""
    from skyplane_tpu.exceptions import CodecException
    from skyplane_tpu.native import datapath as native_dp

    if native and not native_dp.available():
        pytest.skip("no native library on this host")
    if not native:
        monkeypatch.setattr(native_dp, "available", lambda: False)
    lit = rng.integers(1, 255, 256, dtype=np.uint8).tobytes()
    want = bytes(256) + b"\x09" * 256 + lit + bytes(256) + lit[:100]  # zero, const, literal, invalid tag (a zero block), padded literal
    container = _hand_container([0, 1, 2, 3, 2], b"\x09" + lit + lit[:100] + bytes(156), n_raw=len(want))
    fresh = blockpack.decode_container(container)
    assert fresh == want and isinstance(fresh, memoryview)
    out = np.full(4096, 0xEE, np.uint8)  # longer than needed, and dirty
    got = blockpack.decode_container(container, out)
    assert got == want
    assert got.obj is out and bytes(out[: len(want)]) == want, "the blocks were not written into the caller's array"
    assert bytes(out[blockpack.padded_len(len(want), 256) :]) == b"\xee" * (4096 - 1280), "wrote past the padded length"
    exact = np.empty(blockpack.padded_len(len(want), 256), np.uint8)
    assert blockpack.decode_container(container, exact) == want
    with pytest.raises(CodecException, match="output buffer"):
        blockpack.decode_container(container, np.empty(len(want), np.uint8))  # n_raw bytes are not enough: the kernel writes whole blocks
    real = blockpack.encode_container(want + want)
    assert blockpack.decode_container(real, np.empty(blockpack.padded_len(2 * len(want)), np.uint8)) == want + want
    assert blockpack.decode_container(blockpack.encode_container(b""), out) == b""


# ----------------------------------------------------------------------------
# Injector-driven recovery at the receiver framing boundary
# (docs/fault-injection.md). A live GatewayReceiver, real sockets, no TLS.
# ----------------------------------------------------------------------------


@pytest.fixture(autouse=True)
def _disarm_injector():
    yield
    configure_injector(None)


def _mk_receiver(tmp_path):
    store = ChunkStore(str(tmp_path / f"rx_{uuid.uuid4().hex[:8]}"))
    ev, eq = threading.Event(), queue.Queue()
    r = GatewayReceiver(
        "local:local", store, ev, eq, use_tls=False, bind_host="127.0.0.1", dedup=True, decode_workers=2
    )
    port = r.start_server()
    return r, store, ev, port


def _recipe_frame(datas, chunk_id=None):
    """(header, wire, raw) — a recipe frame carrying ``datas`` as literals."""
    segs = [(segment_fingerprint_host(d), d) for d in datas]
    wire, *_ = build_recipe(segs, SenderDedupIndex(), lambda b: b)
    raw = b"".join(datas)
    header = WireProtocolHeader(
        chunk_id=chunk_id or uuid.uuid4().hex,
        data_len=len(wire),
        raw_data_len=len(raw),
        flags=int(ChunkFlags.RECIPE),
    )
    return header, wire, raw


def _connect(port):
    sock = socket.create_connection(("127.0.0.1", port), timeout=10)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return sock


def _send_frame(sock, header, wire):
    header.to_socket(sock)
    sock.sendall(wire)


def _assert_dropped(sock) -> None:
    """The peer dropped us without acking: clean EOF or an RST (the receiver
    closing with unread bytes still in its buffer) — both mean the same thing
    to a sender, which re-queues the chunk either way."""
    sock.settimeout(5.0)
    try:
        got = sock.recv(1)
    except ConnectionError:
        return
    assert got == b"", f"expected a dropped connection, got response byte {got!r}"


def _assert_not_exposed(store: ChunkStore, chunk_id: str):
    """The no-partial-exposure contract: no .done marker means downstream
    operators never see this chunk, whatever may be staged on disk."""
    assert not store.chunk_path(chunk_id).with_suffix(".done").exists(), (
        f"chunk {chunk_id} exposed to downstream operators without a successful decode+ack"
    )


def _wait_done(store: ChunkStore, chunk_id: str, timeout=5.0) -> bool:
    deadline = time.monotonic() + timeout
    marker = store.chunk_path(chunk_id).with_suffix(".done")
    while time.monotonic() < deadline:
        if marker.exists():
            return True
        time.sleep(0.02)
    return False


def test_injected_mid_frame_disconnect_then_resend_recovers(tmp_path):
    """receiver.recv fires mid-payload: the connection drops with no ack and
    NO partial chunk exposed; the sender-side resend on a fresh connection
    lands the identical bytes."""
    r, store, ev, port = _mk_receiver(tmp_path)
    header, wire, raw = _recipe_frame([rng.integers(0, 256, 3000, dtype=np.uint8).tobytes() for _ in range(3)])
    configure_injector(FaultPlan.from_dict({"seed": 1, "points": {"receiver.recv": {"p": 1.0, "max_fires": 1}}}))
    sock = _connect(port)
    try:
        _send_frame(sock, header, wire)
        _assert_dropped(sock)
    finally:
        sock.close()
    _assert_not_exposed(store, header.chunk_id)
    # the sender's socket-death contract: re-queue + resend on a new socket
    sock = _connect(port)
    try:
        _send_frame(sock, header, wire)
        sock.settimeout(10.0)
        assert sock.recv(1) == ACK_BYTE
    finally:
        sock.close()
    assert _wait_done(store, header.chunk_id)
    assert store.chunk_path(header.chunk_id).read_bytes() == raw
    assert not ev.is_set()


def test_short_read_peer_close_drops_partial_chunk(tmp_path):
    """A peer dying mid-payload (true short read at the framing boundary):
    the partial chunk is dropped, nothing is exposed, the daemon survives."""
    r, store, ev, port = _mk_receiver(tmp_path)
    header, wire, raw = _recipe_frame([rng.integers(0, 256, 8000, dtype=np.uint8).tobytes()])
    sock = _connect(port)
    header.to_socket(sock)
    sock.sendall(wire[: len(wire) // 2])  # half the payload, then vanish
    sock.close()
    time.sleep(0.5)
    _assert_not_exposed(store, header.chunk_id)
    assert not ev.is_set(), "a peer disconnect mid-chunk must never be daemon-fatal"
    # the resend completes normally
    sock = _connect(port)
    try:
        _send_frame(sock, header, wire)
        sock.settimeout(10.0)
        assert sock.recv(1) == ACK_BYTE
    finally:
        sock.close()
    assert _wait_done(store, header.chunk_id)
    assert store.chunk_path(header.chunk_id).read_bytes() == raw


def test_corrupt_payload_at_framing_boundary_never_exposes_partial(tmp_path):
    """A corrupted recipe payload (bad magic — what sender.corrupt_payload
    produces on an unsealed recipe frame): payload error, connection dropped,
    no ack, no exposure; the clean resend recovers."""
    r, store, ev, port = _mk_receiver(tmp_path)
    header, wire, raw = _recipe_frame([rng.integers(0, 256, 5000, dtype=np.uint8).tobytes()])
    corrupt = bytes([wire[0] ^ 0xFF]) + wire[1:]  # flip a magic byte; data_len unchanged
    sock = _connect(port)
    try:
        _send_frame(sock, header, corrupt)
        _assert_dropped(sock)
    finally:
        sock.close()
    _assert_not_exposed(store, header.chunk_id)
    assert not ev.is_set()
    sock = _connect(port)
    try:
        _send_frame(sock, header, wire)
        sock.settimeout(10.0)
        assert sock.recv(1) == ACK_BYTE
    finally:
        sock.close()
    assert _wait_done(store, header.chunk_id)
    assert store.chunk_path(header.chunk_id).read_bytes() == raw


def test_injected_decode_nack_then_literal_resend(tmp_path):
    """receiver.decode_nack fires: the response is an IN-BAND NACK on a live
    connection (the cheapest recovery), nothing is exposed, and the literal
    resend on the SAME socket acks — the NACK -> literal-resend contract."""
    r, store, ev, port = _mk_receiver(tmp_path)
    datas = [rng.integers(0, 256, 3000, dtype=np.uint8).tobytes() for _ in range(2)]
    header, wire, raw = _recipe_frame(datas)
    configure_injector(
        FaultPlan.from_dict({"seed": 2, "points": {"receiver.decode_nack": {"p": 1.0, "max_fires": 1}}})
    )
    sock = _connect(port)
    try:
        _send_frame(sock, header, wire)
        sock.settimeout(10.0)
        assert sock.recv(1) == NACK_UNRESOLVED
        _assert_not_exposed(store, header.chunk_id)
        # sender contract after NACK: discard the affected fps and resend as
        # pure literals — same socket, no reconnect needed
        _send_frame(sock, header, wire)
        assert sock.recv(1) == ACK_BYTE
    finally:
        sock.close()
    assert _wait_done(store, header.chunk_id)
    assert store.chunk_path(header.chunk_id).read_bytes() == raw
    assert r.nacks_total == 1
    assert not ev.is_set()


def test_injected_ref_to_missing_segment_nacks_in_band(tmp_path):
    """A REF whose literal never arrived (what spill faults degrade to):
    in-band NACK, connection stays up, the literal frame then resolves it."""
    r, store, ev, port = _mk_receiver(tmp_path)
    data = rng.integers(0, 256, 4000, dtype=np.uint8).tobytes()
    fp = segment_fingerprint_host(data)
    ref_wire = dedup_mod.MAGIC + struct.pack("<BI", dedup_mod.VERSION, 1) + dedup_mod._ENTRY.pack(
        dedup_mod.KIND_REF, fp, len(data)
    )
    ref_header = WireProtocolHeader(
        chunk_id=uuid.uuid4().hex, data_len=len(ref_wire), raw_data_len=len(data), flags=int(ChunkFlags.RECIPE)
    )
    r.ref_wait_timeout = 0.2  # don't park the test for the full default wait
    sock = _connect(port)
    try:
        _send_frame(sock, ref_header, ref_wire)
        sock.settimeout(10.0)
        assert sock.recv(1) == NACK_UNRESOLVED
        _assert_not_exposed(store, ref_header.chunk_id)
        lit_header, lit_wire, _ = _recipe_frame([data], chunk_id=ref_header.chunk_id)
        _send_frame(sock, lit_header, lit_wire)
        assert sock.recv(1) == ACK_BYTE
    finally:
        sock.close()
    assert _wait_done(store, ref_header.chunk_id)
    assert store.chunk_path(ref_header.chunk_id).read_bytes() == data
