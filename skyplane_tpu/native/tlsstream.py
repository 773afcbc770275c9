"""The data socket's TLS stream, its record loop in native code.

A Python ``SSLSocket`` hands one TLS record (16 KB of plaintext) to the
interpreter per call, so a 58 MiB frame costs about 3,700 releases and
re-takes of the interpreter lock, and each re-take waits for whichever Python
thread holds it. :class:`NativeTLSStream` moves a whole frame in one foreign
call (``tlsstream.cpp``), with the lock released for all of it.

The settings are a Python ``ssl.SSLContext``'s: :class:`TLSStreamContext`
builds the one the gateways always built (``PROTOCOL_TLS_SERVER`` with the
receiver's certificate, or ``PROTOCOL_TLS_CLIENT`` that verifies nothing) and
gives the native context its options, protocol bounds, security level and
TLS 1.2 cipher list. The native library runs on the libssl that Python's
``_ssl`` mapped, so a native end and a Python end negotiate what two Python
ends do, and either end of a data socket may be either kind. Where the library
cannot build or load, the context hands out the Python context's own
``SSLSocket`` (logged once).
"""

from __future__ import annotations

import contextlib
import ctypes
import errno
import os
import socket
import ssl
import struct
import threading
import weakref
from typing import Iterator, Optional, Tuple

from skyplane_tpu.exceptions import MissingDependencyException
from skyplane_tpu.utils.logger import logger

# tlsstream.cpp's return codes
_PROTOCOL, _CLOSED, _TIMEOUT = -1, -2, -3  # and -4: a socket error, with its errno

_LOAD_LOCK = threading.Lock()
_loaded: dict = {}  # "lib": the bound library, or None once it failed to load


class _PyBuffer(ctypes.Structure):
    """CPython's ``Py_buffer``: the address of any bytes-like object, read-only
    ones (``bytes``, a ``PROT_READ`` mmap's view) included, without a copy."""

    _fields_ = [
        ("buf", ctypes.c_void_p),
        ("obj", ctypes.c_void_p),
        ("len", ctypes.c_ssize_t),
        ("itemsize", ctypes.c_ssize_t),
        ("readonly", ctypes.c_int),
        ("ndim", ctypes.c_int),
        ("format", ctypes.c_char_p),
        ("shape", ctypes.c_void_p),
        ("strides", ctypes.c_void_p),
        ("suboffsets", ctypes.c_void_p),
        ("internal", ctypes.c_void_p),
    ]


_PyBUF_SIMPLE, _PyBUF_WRITABLE = 0, 1
# private function objects: argtypes set on ctypes.pythonapi's own would be shared process-wide
_get_buffer = ctypes.PYFUNCTYPE(ctypes.c_int, ctypes.py_object, ctypes.POINTER(_PyBuffer), ctypes.c_int)(
    ("PyObject_GetBuffer", ctypes.pythonapi)
)
_release_buffer = ctypes.PYFUNCTYPE(None, ctypes.POINTER(_PyBuffer))(("PyBuffer_Release", ctypes.pythonapi))


@contextlib.contextmanager
def _buffer(obj, writable: bool = False) -> Iterator[Tuple[int, int]]:
    """(address, length) of a C-contiguous buffer, held for the block. A
    non-contiguous view raises ``BufferError``, a read-only one asked to be
    written ``BufferError`` too, both before any native call."""
    view = _PyBuffer()
    _get_buffer(obj, ctypes.byref(view), _PyBUF_WRITABLE if writable else _PyBUF_SIMPLE)
    try:
        yield view.buf or 0, view.len
    finally:
        _release_buffer(ctypes.byref(view))


def _mapped_libssl() -> Optional[str]:
    """The libssl that Python's ``_ssl`` mapped into this process."""
    import _ssl  # noqa: F401 — maps libssl

    with open("/proc/self/maps") as f:
        for line in f:
            parts = line.split(maxsplit=5)
            if len(parts) == 6 and os.path.basename(parts[5].strip()).startswith("libssl.so"):
                return parts[5].strip()
    return None


def _bind() -> ctypes.CDLL:
    from skyplane_tpu import native

    path = _mapped_libssl()
    if path is None:
        raise RuntimeError("Python's ssl module mapped no libssl")
    with native._BUILD_LOCK:
        lib, _info = native.build_and_load("skytls", ("tlsstream.cpp",))
    vp, u8p, c64, i64 = ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int64
    for name, restype, argtypes in (
        ("skytls_init", ctypes.c_int, [ctypes.c_char_p]),
        (
            "skytls_ctx_new",
            vp,
            [ctypes.c_int, c64, ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
             ctypes.c_char_p, ctypes.c_size_t],
        ),
        ("skytls_ctx_free", None, [vp]),
        ("skytls_new", vp, [vp, ctypes.c_int]),
        ("skytls_handshake", i64, [vp, ctypes.c_int]),
        ("skytls_read", i64, [vp, u8p, c64]),
        ("skytls_read_exact", i64, [vp, u8p, c64]),
        ("skytls_write_all", i64, [vp, u8p, c64]),
        ("skytls_free", None, [vp]),
    ):
        fn = getattr(lib, name)
        fn.restype, fn.argtypes = restype, argtypes
    # calls that never block keep the interpreter lock: a PyDLL view of the same library
    quick = ctypes.PyDLL(lib._name)
    for name, restype in (
        ("skytls_pending", ctypes.c_int),
        ("skytls_version", ctypes.c_char_p),
        ("skytls_cipher", ctypes.c_char_p),
        ("skytls_error", ctypes.c_char_p),
        ("skytls_errno", ctypes.c_int),
        ("skytls_done", c64),
    ):
        fn = getattr(quick, name)
        fn.restype, fn.argtypes = restype, [vp]
    rc = lib.skytls_init(path.encode())
    if rc != 0:
        raise RuntimeError(f"cannot bind to {path} ({'not mapped' if rc == -1 else 'a function is missing'})")
    lib.quick = quick
    return lib


def load() -> Optional[ctypes.CDLL]:
    """libskytls bound to this process's libssl, or None where it cannot be
    built or loaded: data sockets then use Python's ssl (logged once)."""
    with _LOAD_LOCK:
        if "lib" not in _loaded:
            try:
                _loaded["lib"] = _bind()
            except (MissingDependencyException, OSError, RuntimeError, AttributeError) as e:
                logger.fs.warning(f"native TLS stream unavailable, data sockets use Python's ssl: {e}")
                _loaded["lib"] = None
        return _loaded["lib"]


def is_tls_stream(sock) -> bool:
    """Whether bytes written to ``sock`` are encrypted on the wire: a Python
    SSLSocket or a native stream. A path that bypasses the socket's own write
    (``sendfile``, ``sendmsg``) is for plaintext sockets only."""
    return isinstance(sock, (ssl.SSLSocket, NativeTLSStream))


class TLSStreamContext:
    """Makes the TLS stream of a data socket, as the server (``certfile``,
    ``keyfile``) or as a client that verifies nothing (receivers' certificates
    are self-signed)."""

    def __init__(self, server_side: bool, certfile=None, keyfile=None):
        self.server_side = server_side
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER if server_side else ssl.PROTOCOL_TLS_CLIENT)
        if server_side:
            ctx.load_cert_chain(certfile=str(certfile), keyfile=str(keyfile))
        else:
            ctx.check_hostname = False
            ctx.verify_mode = ssl.CERT_NONE
        self.py_context = ctx
        self._native = None
        lib = load()
        if lib is not None:
            self._native = _NativeContext(lib, ctx, server_side, certfile, keyfile)

    @property
    def native(self) -> bool:
        return self._native is not None

    def wrap(self, sock: socket.socket):
        """Shake hands on a connected socket and return its stream. On failure
        raises ``ssl.SSLError`` or ``OSError``; the caller closes ``sock``."""
        if self._native is None:
            return self.py_context.wrap_socket(sock, server_side=self.server_side)
        return NativeTLSStream(self._native, sock, self.server_side)


class _NativeContext:
    """An OpenSSL context with the Python context's settings."""

    def __init__(self, lib: ctypes.CDLL, ctx: ssl.SSLContext, server_side: bool, certfile, keyfile):
        def version(v: ssl.TLSVersion) -> int:
            return max(0, int(v))  # MINIMUM_SUPPORTED / MAXIMUM_SUPPORTED: the library's own bound

        tls12 = [c["name"] for c in ctx.get_ciphers() if c["protocol"] != "TLSv1.3"]
        ciphers = f"@SECLEVEL={ctx.security_level}:" + ":".join(tls12)
        err = ctypes.create_string_buffer(256)
        handle = lib.skytls_ctx_new(
            int(server_side),
            int(ctx.options),
            version(ctx.minimum_version),
            version(ctx.maximum_version),
            ciphers.encode(),
            str(certfile).encode() if server_side else None,
            str(keyfile).encode() if server_side else None,
            err,
            len(err),
        )
        if not handle:
            raise ssl.SSLError(f"native TLS context: {err.value.decode(errors='replace')}")
        self.lib = lib
        self.handle = handle
        # streams hold their own reference to it; nothing is freed at exit,
        # where a daemon thread may still be inside a call
        weakref.finalize(self, lib.skytls_ctx_free, handle).atexit = False


class NativeTLSStream:
    """A TLS stream whose reads and writes run in native code, one call a
    frame. It owns the Python socket that owns the fd: the fd is blocking,
    and the socket's timeout, if it had one, becomes ``SO_RCVTIMEO`` /
    ``SO_SNDTIMEO``. Errors are what callers of an ``SSLSocket`` catch:
    ``ssl.SSLError`` for a protocol fault, ``ConnectionError`` when the peer
    closes inside a frame, ``TimeoutError`` when a kernel timeout expires,
    ``OSError`` for the rest. One thread owns a stream, as one owns a socket."""

    def __init__(self, ctx: _NativeContext, sock: socket.socket, server_side: bool):
        lib = ctx.lib
        self._lib = lib
        self._quick = lib.quick
        self._sock = sock
        timeout = sock.gettimeout()
        sock.settimeout(None)
        if timeout is not None:
            sec = int(timeout)
            tv = struct.pack("ll", sec, int((timeout - sec) * 1e6))
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVTIMEO, tv)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDTIMEO, tv)
        handle = lib.skytls_new(ctx.handle, sock.fileno())
        if not handle:
            raise ssl.SSLError("native TLS stream: SSL_new failed")
        self._h = handle
        self._free = weakref.finalize(self, lib.skytls_free, handle)
        self._free.atexit = False  # a daemon thread may still be inside a call at exit
        self._io = threading.Lock()  # held by a native read or write; close() waits for it
        rc = lib.skytls_handshake(handle, int(server_side))
        if rc < 0:
            exc = self._error(rc, "handshake")
            self._free()
            self._h = None
            raise exc

    # ---- what the data socket's owner calls ----

    def fileno(self) -> int:
        return self._sock.fileno()

    def pending(self) -> int:
        """Decrypted bytes a read returns without touching the fd."""
        with self._io:
            return self._quick.skytls_pending(self._h) if self._h else 0

    def recv(self, n: int) -> bytes:
        """Up to ``n`` bytes, at least one; ``b""`` once the peer closed."""
        buf = ctypes.create_string_buffer(n)
        got = self._call("recv", self._lib.skytls_read, buf, n)
        return buf.raw[:got]

    def recv_into(self, buf, nbytes: int = 0) -> int:
        """Up to ``nbytes`` (all of ``buf`` if 0) bytes into ``buf``; 0 once the peer closed."""
        with _buffer(buf, writable=True) as (addr, size):
            return self._call("recv_into", self._lib.skytls_read, addr, min(nbytes, size) if nbytes else size)

    def recv_exact_into(self, buf) -> None:
        """Fill ``buf`` in one native call; ``ConnectionError`` if the peer closes first."""
        with _buffer(buf, writable=True) as (addr, size):
            self._call("recv", self._lib.skytls_read_exact, addr, size)

    def sendall(self, data) -> None:
        """Every byte of ``data`` (any C-contiguous bytes-like object) in one native call."""
        with _buffer(data) as (addr, size):
            self._call("sendall", self._lib.skytls_write_all, addr, size)

    def negotiated(self) -> Tuple[str, str]:
        """(protocol version, cipher) of the handshake."""
        with self._io:
            h = self._live()
            return self._quick.skytls_version(h).decode(), self._quick.skytls_cipher(h).decode()

    def close(self) -> None:
        """Free the TLS state and close the socket. No close_notify is sent,
        as a Python SSLSocket's close() sends none. From a thread other than
        the owner's while a read or write is under way, the connection is
        shut down first so that the call returns."""
        if not self._io.acquire(blocking=False):
            try:
                self._sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            self._io.acquire()
        try:
            self._free()
            self._h = None
            self._sock.close()
        finally:
            self._io.release()

    # ---- internals ----

    def _live(self) -> int:
        if not self._h:
            raise OSError(errno.EBADF, "TLS stream is closed")
        return self._h

    def _call(self, what: str, fn, addr, size: int) -> int:
        """One native read or write; its error is read from the stream before
        the lock is let go, since a close() may free the stream after."""
        with self._io:
            rc = fn(self._live(), addr, size)
            if rc >= 0:
                return rc
            exc = self._error(rc, what, size)
        raise exc

    def _error(self, rc: int, what: str, size: int = 0) -> Exception:
        if rc == _PROTOCOL:
            return ssl.SSLError(f"{what}: {self._quick.skytls_error(self._h).decode(errors='replace')}")
        if rc == _CLOSED:
            if not size:
                return ConnectionError(f"{what}: the peer closed the connection")
            return ConnectionError(f"{what}: socket closed mid-payload ({self._quick.skytls_done(self._h)}/{size} bytes)")
        if rc == _TIMEOUT:
            return TimeoutError(f"{what}: timed out")
        code = self._quick.skytls_errno(self._h)
        return OSError(code, f"{what}: {os.strerror(code)}")
