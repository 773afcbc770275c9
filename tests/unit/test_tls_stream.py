"""The data socket's native TLS stream (skyplane_tpu/native/tlsstream.py).

It speaks the TLS a Python ``SSLSocket`` speaks, in every pairing of native
and Python ends; it raises what the callers of an ``SSLSocket`` catch; the
receiver and the raw-forward engine treat it as TLS; and a frame moves in
one foreign call with the interpreter lock released, so a busy Python thread
beside it does not slow it down.
"""

from __future__ import annotations

import mmap
import os
import queue
import select
import socket
import ssl
import threading
import time
import uuid

import pytest

from skyplane_tpu.chunk import WireProtocolHeader
from skyplane_tpu.gateway.cert import generate_self_signed_certificate
from skyplane_tpu.gateway.chunk_store import ChunkStore
from skyplane_tpu.gateway.operators.gateway_receiver import ACK_BYTE, GatewayReceiver
from skyplane_tpu.gateway.operators.sender_wire import RawForwardEngine, RawFrameSource, send_vectored
from skyplane_tpu.native import tlsstream
from skyplane_tpu.native.tlsstream import NativeTLSStream, TLSStreamContext, is_tls_stream

PAIRINGS = ("native-native", "native-server-python-client", "python-server-native-client")


@pytest.fixture(scope="module")
def certs(tmp_path_factory):
    d = tmp_path_factory.mktemp("certs")
    return generate_self_signed_certificate("skyplane-tpu-gateway", d / "cert.pem", d / "key.pem")


@pytest.fixture(scope="module")
def contexts(certs):
    """(native server, native client, Python server, Python client) contexts."""
    assert tlsstream.load() is not None, "libskytls should build and load where g++ and libssl are"
    server, client = TLSStreamContext(True, *certs), TLSStreamContext(False)
    assert server.native and client.native
    return server, client, server.py_context, client.py_context


def _wrappers(contexts, pairing):
    """(wrap server socket, wrap client socket) for a pairing of PAIRINGS or "python-python"."""
    server, client, py_server, py_client = contexts
    native_server = pairing.startswith("native")
    native_client = pairing.endswith("native") or pairing.endswith("native-client")
    wrap_server = server.wrap if native_server else (lambda s: py_server.wrap_socket(s, server_side=True))
    wrap_client = client.wrap if native_client else py_client.wrap_socket
    return wrap_server, wrap_client


def _connected(wrap_server, wrap_client, timeout=10.0):
    """(server stream, client stream) over loopback, both handshaken."""
    listener = socket.socket()
    listener.bind(("127.0.0.1", 0))
    listener.listen(1)
    out = {}

    def accept():
        conn, _ = listener.accept()
        try:
            out["server"] = wrap_server(conn)
        except (ssl.SSLError, OSError) as e:
            out["error"] = e
            conn.close()

    t = threading.Thread(target=accept)
    t.start()
    try:
        client = wrap_client(socket.create_connection(listener.getsockname(), timeout=timeout))
    finally:
        t.join(timeout=10)
        listener.close()
    assert not t.is_alive()
    if "error" in out:
        raise out["error"]
    return out["server"], client


def _read_exact(stream, n: int) -> bytes:
    buf = bytearray(n)
    if isinstance(stream, NativeTLSStream):
        stream.recv_exact_into(buf)
    else:
        view, got = memoryview(buf), 0
        while got < n:
            r = stream.recv_into(view[got:])
            assert r, "peer closed"
            got += r
    return bytes(buf)


def _negotiated(stream):
    return stream.negotiated() if isinstance(stream, NativeTLSStream) else (stream.version(), stream.cipher()[0])


def _close_all(*streams):
    for s in streams:
        s.close()


@pytest.mark.parametrize("size", [1, 16_383, 16_384, 16_385, (4 << 20) + 1])
@pytest.mark.parametrize("pairing", PAIRINGS)
def test_pairings_move_frames_byte_for_byte_both_ways(contexts, pairing, size):
    server, client = _connected(*_wrappers(contexts, pairing))
    try:
        payload = os.urandom(size)
        for src, dst, data in ((client, server, payload), (server, client, payload[::-1])):
            sender = threading.Thread(target=src.sendall, args=(data,))
            sender.start()
            assert _read_exact(dst, size) == data
            sender.join(timeout=10)
            assert not sender.is_alive()
    finally:
        _close_all(server, client)


@pytest.mark.parametrize("pairing", PAIRINGS)
def test_negotiates_what_two_python_ends_do(contexts, pairing):
    python_server, python_client = _connected(*_wrappers(contexts, "python-python"))
    try:
        expected = _negotiated(python_client)
        assert _negotiated(python_server) == expected
    finally:
        _close_all(python_server, python_client)
    server, client = _connected(*_wrappers(contexts, pairing))
    try:
        assert _negotiated(server) == _negotiated(client) == expected
    finally:
        _close_all(server, client)


def test_pending_sees_a_buffered_record_the_fd_does_not(contexts):
    server, client = _connected(*_wrappers(contexts, "native-native"))
    try:
        client.sendall(b"x" * 100)  # one record
        assert server.recv(10) == b"x" * 10
        assert server.pending() == 90
        assert select.select([server], [], [], 0.2)[0] == [], "the record is read off the fd already"
        assert server.recv(1000) == b"x" * 90
        assert server.pending() == 0
    finally:
        _close_all(server, client)


@pytest.mark.parametrize("how", ["python-fin", "python-close-notify", "python-close", "native-close"])
def test_peer_close_mid_frame_raises_connection_error(contexts, how):
    """A FIN with no close_notify (OpenSSL 3's unexpected EOF), a close_notify
    (its clean end of stream) and a close() that resets the connection (the
    peer never read the server's session tickets) all end the frame the same way."""
    pairing = "native-native" if how == "native-close" else "native-server-python-client"
    server, client = _connected(*_wrappers(contexts, pairing))
    closer = None
    try:
        client.sendall(b"y" * 100)
        if how == "python-fin":
            client.shutdown(socket.SHUT_WR)
        elif how == "python-close-notify":
            # unwrap() then waits for the server's close_notify, which never
            # comes, until the server closes
            closer = threading.Thread(target=_unwrap_quietly, args=(client,))
            closer.start()
        else:
            client.close()
        with pytest.raises(ConnectionError) as raised:
            server.recv_exact_into(bytearray(200))
        if how in ("python-fin", "python-close-notify"):
            assert "mid-payload (100/200 bytes)" in str(raised.value)
            assert server.recv(10) == b"", "after the close a read ends the stream"
    finally:
        server.close()
        if closer is not None:
            closer.join(timeout=15)
            assert not closer.is_alive()
        client.close()


def _unwrap_quietly(stream) -> None:
    try:
        stream.unwrap()
    except (ssl.SSLError, OSError):
        pass


@pytest.mark.parametrize("direction", ["read", "write"])
def test_stalled_peer_raises_timeout_within_the_timeout(contexts, direction):
    server, client = _connected(*_wrappers(contexts, "native-native"), timeout=0.5)
    try:
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            if direction == "read":
                client.recv_exact_into(bytearray(1000))  # the server sends nothing
            else:
                client.sendall(bytes(64 << 20))  # the server reads nothing: the socket buffers fill
        assert time.monotonic() - t0 < 5.0
    finally:
        _close_all(server, client)


def test_non_contiguous_and_read_only_targets_are_refused_before_any_call(contexts):
    server, client = _connected(*_wrappers(contexts, "native-native"))
    try:
        with pytest.raises(BufferError):
            client.sendall(memoryview(bytearray(100))[::2])
        with pytest.raises(BufferError):
            server.recv_exact_into(b"read-only bytes")
    finally:
        _close_all(server, client)


def test_sendall_takes_a_read_only_mmap_view(contexts, tmp_path):
    data = os.urandom(300_000)
    path = tmp_path / "staged.bin"
    path.write_bytes(data)
    server, client = _connected(*_wrappers(contexts, "native-native"))
    try:
        fd = os.open(path, os.O_RDONLY)
        try:
            with mmap.mmap(fd, len(data), prot=mmap.PROT_READ) as m, memoryview(m) as view:
                sender = threading.Thread(target=client.sendall, args=(view,))
                sender.start()
                got = _read_exact(server, len(data))
                sender.join(timeout=10)
        finally:
            os.close(fd)
        assert got == data
    finally:
        _close_all(server, client)


def test_every_data_socket_kind_is_classified(contexts):
    server, client = _connected(*_wrappers(contexts, "native-server-python-client"))
    a, b = socket.socketpair()
    try:
        assert is_tls_stream(server) and is_tls_stream(client)
        assert not is_tls_stream(a)
    finally:
        _close_all(server, client, a, b)


def test_send_vectored_writes_through_the_stream(contexts):
    server, client = _connected(*_wrappers(contexts, "native-native"))
    try:
        send_vectored(client, b"HDR", b"PAYLOAD")
        assert _read_exact(server, 10) == b"HDRPAYLOAD"
    finally:
        _close_all(server, client)


def test_raw_forward_takes_the_mmap_path_to_a_python_peer(contexts, tmp_path, monkeypatch):
    data = os.urandom(300_001)
    header = bytes(range(86))
    path = tmp_path / "frame.bin"
    path.write_bytes(data)

    def no_plaintext_splice(*args, **kwargs):
        raise AssertionError("sendfile would put plaintext on a TLS stream")

    monkeypatch.setattr(RawForwardEngine, "_send_sendfile", no_plaintext_splice)
    server, client = _connected(*_wrappers(contexts, "python-server-native-client"))
    source = RawFrameSource(os.open(path, os.O_RDONLY), len(data))
    try:
        sender = threading.Thread(target=RawForwardEngine().send, args=(client, header, source))
        sender.start()
        got = _read_exact(server, 86 + len(data))
        sender.join(timeout=10)
        assert not sender.is_alive()
        assert got == header + data
    finally:
        source.release()
        _close_all(server, client)


def test_a_busy_python_thread_does_not_slow_a_native_transfer(contexts):
    """8 MiB is 512 records: with one busy thread a Python loop waits about
    5 ms for the lock at each (seconds); the native loop waits at most twice."""
    server, client = _connected(*_wrappers(contexts, "native-native"))
    stop = threading.Event()

    def spin():
        x = 0
        while not stop.is_set():
            x += 1

    busy = threading.Thread(target=spin)
    busy.start()
    try:
        payload = os.urandom(8 << 20)
        t0 = time.monotonic()
        sender = threading.Thread(target=client.sendall, args=(payload,))
        sender.start()
        got = _read_exact(server, len(payload))
        sender.join(timeout=10)
        elapsed = time.monotonic() - t0
    finally:
        stop.set()
        busy.join(timeout=10)
        _close_all(server, client)
    assert got == payload
    assert elapsed < 1.5, f"{elapsed:.2f} s for 8 MiB beside one busy thread"


# ---- the receiver's accept path ----


def _tls_receiver(tmp_path):
    store = ChunkStore(str(tmp_path / f"rx_{uuid.uuid4().hex[:8]}"))
    ev, eq = threading.Event(), queue.Queue()
    r = GatewayReceiver("local:local", store, ev, eq, use_tls=True, bind_host="127.0.0.1")
    return r, store, ev, r.start_server()


def test_receiver_lands_a_frame_read_by_its_native_stream(tmp_path, contexts):
    r, store, ev, port = _tls_receiver(tmp_path)
    try:
        raw = os.urandom(100_000)
        header = WireProtocolHeader(chunk_id=uuid.uuid4().hex, data_len=len(raw), raw_data_len=len(raw))
        client = contexts[1].wrap(socket.create_connection(("127.0.0.1", port), timeout=10))
        try:
            send_vectored(client, header.to_bytes(), raw)
            assert client.recv(1) == ACK_BYTE
        finally:
            client.close()
        assert store.chunk_path(header.chunk_id).read_bytes() == raw
        counters = r.decode_counters()
        assert counters["recv_native_frames"] == counters["decode_chunks"] == 1
        assert not ev.is_set()
    finally:
        r.stop_all()


def test_receiver_drops_a_plaintext_client_at_the_handshake(tmp_path):
    r, _store, ev, port = _tls_receiver(tmp_path)
    try:
        with socket.create_connection(("127.0.0.1", port), timeout=10) as plain:
            header = WireProtocolHeader(chunk_id=uuid.uuid4().hex, data_len=4, raw_data_len=4)
            plain.sendall(header.to_bytes() + b"data")
            got = b""
            try:
                while True:
                    chunk = plain.recv(4096)
                    if not chunk:
                        break
                    got += chunk
            except ConnectionResetError:
                pass
        assert ACK_BYTE not in got, "a plaintext frame is never acked"
        assert r.decode_counters()["decode_chunks"] == 0
        assert not ev.is_set(), "a bad handshake drops the connection, not the daemon"
    finally:
        r.stop_all()


def test_close_from_another_thread_ends_a_blocked_read(contexts):
    """The owner is inside a native read when another thread closes the
    stream: the read returns with an error and the stream is freed after it,
    never under it."""
    server, client = _connected(*_wrappers(contexts, "native-native"))
    raised = []

    def read():
        try:
            server.recv_exact_into(bytearray(1000))
        except OSError as e:  # ConnectionError, or EBADF once closed
            raised.append(e)

    reader = threading.Thread(target=read)
    reader.start()
    try:
        time.sleep(0.2)  # the reader is blocked in the native call
        server.close()
        reader.join(timeout=10)
        assert not reader.is_alive() and len(raised) == 1, raised
        with pytest.raises(OSError):
            server.recv(1)
        assert server.pending() == 0
    finally:
        client.close()
