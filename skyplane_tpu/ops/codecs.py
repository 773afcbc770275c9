"""Host-facing codec registry for the chunk data path.

Reference parity: the reference offers a single LZ4-frame CPU codec toggled by
``compress`` (skyplane/gateway/operators/gateway_operator.py:358-361,
gateway_receiver.py:191-201). Here codecs are first-class, carried per-chunk
in the wire header (chunk.py Codec), and include the TPU block-suppress path:

  none       — identity
  zstd       — CPU zstandard frame (the CPU reference path; lz4-class speed at
               better ratios)
  tpu        — blockpack container (ops/blockpack.py), zero/const suppression
               entirely on device
  tpu_zstd   — blockpack, then zstd over the compacted container (device does
               suppression; CPU entropy-codes only surviving literals)
  native_lz  — C++ LZ codec from skyplane_tpu/native (registered lazily)

The sender hands a codec its literal stream as spans of the chunk
(:func:`timed_encoder`): the blockpack codecs lay the spans down themselves,
in one native pass into a pooled container that zstd reads in place; the
others get the spans joined.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Dict, NamedTuple, Optional, Sequence, Tuple

from skyplane_tpu.chunk import Codec
from skyplane_tpu.exceptions import CodecException
from skyplane_tpu.obs import NOOP_SPAN
from skyplane_tpu.ops.bufpool import BufferPool, bucket_size


class CodecSpec(NamedTuple):
    """``decode`` takes any C-contiguous buffer and may return any (``bytes``, or
    a view of an array); a caller that needs ``bytes`` converts. A codec that
    can write into memory its caller owns says so with ``decode_out_len``:
    the length of the ``out`` array ``decode(buf, out)`` needs for ``n``
    decoded bytes. What comes back is then a view of ``out``.

    ``encode_steps`` names the steps ``encode`` is made of, in order, for the
    codecs whose steps have a counter (``blockpack``, ``zstd``): running them
    one after the other gives ``encode``'s bytes (see :func:`timed_encoder`).
    Where ``gather_bound`` is set the first step takes the stream as ``(buf,
    spans, out)`` in place of its bytes and lays it down itself into ``out``,
    an array of at least ``gather_bound(n)`` bytes for a stream of ``n``,
    returning a view of what it wrote and whether the native pass ran (see
    :func:`blockpack.encode_spans`)."""

    name: str
    codec_id: Codec
    encode: Callable[[bytes], bytes]
    decode: Callable[..., object]
    decode_out_len: Optional[Callable[[int], int]] = None
    encode_steps: Tuple[Tuple[str, Callable], ...] = ()
    gather_bound: Optional[Callable[[int], int]] = None


def _zstd():
    import zstandard

    return zstandard


_codec_local = threading.local()


def zstd_level() -> int:
    """Encoder level for the zstd-backed codecs (SKYPLANE_TPU_ZSTD_LEVEL).

    Default -2 (a standard zstd "fast" level — frames stay decoder-
    compatible): the data-path blobs this codec sees are dedup-collapsed
    literals (first-occurrence segments), where deeper match search buys
    little: level 3 measured +55% CPU for ~3% smaller wire vs level 1
    (round 2), and level 1 measured -6% throughput for +1.8% smaller wire
    vs -2 on the round-5 full-bench sweep (5.04 vs 4.75 Gbps; reduction
    6.02x vs 6.13x). At gateway line rates the CPU is the scarcer resource;
    set the env var to a positive level when egress dollars dominate.
    """
    return int(os.environ.get("SKYPLANE_TPU_ZSTD_LEVEL", "-2"))


def _encode_zstd(data: bytes) -> bytes:
    # multi-core gateways compress big chunks with one zstd worker per core;
    # on a single-core host the ZSTDMT context is pure overhead (measured 4x
    # slower than the plain path), so threads stay off there. The frame stays
    # standard and keeps the embedded content size the decoder cap requires.
    # The compressor is cached per worker thread — building a multithreaded
    # ZSTDMT context per chunk would churn a thread pool on every call.
    level = zstd_level()
    comp = getattr(_codec_local, "zstd_compressor", None)
    if comp is None or getattr(_codec_local, "zstd_level", None) != level:
        try:
            usable = len(os.sched_getaffinity(0))  # respects pinning/cgroups
        except AttributeError:  # non-Linux
            usable = os.cpu_count() or 1
        comp = _zstd().ZstdCompressor(level=level, threads=-1 if usable > 1 else 0)
        _codec_local.zstd_compressor = comp
        _codec_local.zstd_level = level
    return comp.compress(data)


def _decode_zstd(buf: bytes) -> bytes:
    # decode consumes bytes from the wire: corruption must surface inside the
    # codec error contract, not as a raw ZstdError the receiver treats as fatal.
    # The frame's embedded content size is attacker-controlled and is allocated
    # up front by decompress() — bound it before touching the allocator.
    from skyplane_tpu.chunk import MAX_CHUNK_BYTES

    zstd = _zstd()
    try:
        params = zstd.get_frame_parameters(buf)
        if params.content_size in (zstd.CONTENTSIZE_UNKNOWN, zstd.CONTENTSIZE_ERROR):
            # our encoder always embeds the content size; a sizeless frame is
            # either corrupt or hostile, and decompressing one would force an
            # allocation of max_output_size regardless of the actual payload
            raise CodecException("zstd frame does not declare content size (rejected)")
        if params.content_size > MAX_CHUNK_BYTES:
            raise CodecException(f"zstd frame claims {params.content_size} bytes (> {MAX_CHUNK_BYTES} cap)")
        # decompressor cached per worker thread (same discipline as the
        # encoder above): constructing a ZstdDecompressor per chunk puts an
        # allocation + context setup on the receiver hot path for nothing —
        # decompression state is reset per frame anyway
        decomp = getattr(_codec_local, "zstd_decompressor", None)
        if decomp is None:
            decomp = zstd.ZstdDecompressor()
            _codec_local.zstd_decompressor = decomp
        return decomp.decompress(buf)
    except zstd.ZstdError as e:
        raise CodecException(f"zstd decode failed (corrupt frame): {e}") from e


def _encode_tpu(data: bytes) -> bytes:
    from skyplane_tpu.ops import blockpack

    return blockpack.encode_container(data)


def _decode_tpu(buf, out=None):
    from skyplane_tpu.ops import blockpack

    return blockpack.decode_container(buf, out)


def _tpu_out_len(n: int) -> int:
    from skyplane_tpu.ops import blockpack

    return blockpack.padded_len(n)


def _encode_tpu_spans(buf, spans: Sequence[Tuple[int, int]], out):
    from skyplane_tpu.ops import blockpack

    return blockpack.encode_spans(buf, spans, out)


def _tpu_container_bound(n: int) -> int:
    from skyplane_tpu.ops import blockpack

    return blockpack.container_bound(n)


def _encode_tpu_zstd(data: bytes) -> bytes:
    return _encode_zstd(_encode_tpu(data))


def _decode_tpu_zstd(buf, out=None):
    return _decode_tpu(_decode_zstd(buf), out)


def _encode_native(data: bytes) -> bytes:
    from skyplane_tpu.native import lz as native_lz

    return native_lz.compress(data)


def _decode_native(buf: bytes) -> bytes:
    from skyplane_tpu.native import lz as native_lz

    return native_lz.decompress(bytes(buf))


def _encode_lz4(data: bytes) -> bytes:
    from skyplane_tpu.utils import lz4ref

    return lz4ref.compress(data)


def _decode_lz4(buf: bytes) -> bytes:
    # LZ4F frame content size is optional, so the decoder caps allocation at
    # the wire chunk bound rather than trusting the frame
    from skyplane_tpu.chunk import MAX_CHUNK_BYTES
    from skyplane_tpu.utils import lz4ref

    try:
        return lz4ref.decompress(bytes(buf), MAX_CHUNK_BYTES)
    except ValueError as e:
        raise CodecException(f"lz4 decode failed: {e}") from e


_REGISTRY: Dict[str, CodecSpec] = {
    "none": CodecSpec("none", Codec.NONE, lambda b: b, lambda b: b),
    "zstd": CodecSpec("zstd", Codec.ZSTD, _encode_zstd, _decode_zstd, None, (("zstd", _encode_zstd),)),
    "tpu": CodecSpec(
        "tpu", Codec.TPU_BLOCK, _encode_tpu, _decode_tpu, _tpu_out_len, (("blockpack", _encode_tpu_spans),), _tpu_container_bound
    ),
    "tpu_zstd": CodecSpec(
        "tpu_zstd", Codec.TPU_BLOCK_ZSTD, _encode_tpu_zstd, _decode_tpu_zstd, _tpu_out_len,
        (("blockpack", _encode_tpu_spans), ("zstd", _encode_zstd)), _tpu_container_bound,
    ),
    "native_lz": CodecSpec("native_lz", Codec.NATIVE_LZ, _encode_native, _decode_native),
    # the reference's wire codec (gateway_operator.py:358-361), bound to the
    # system liblz4; registered unconditionally — encode/decode raise on
    # hosts without the library, same lazy-failure contract as native_lz
    "lz4": CodecSpec("lz4", Codec.LZ4, _encode_lz4, _decode_lz4),
}

_BY_ID: Dict[int, CodecSpec] = {int(spec.codec_id): spec for spec in _REGISTRY.values()}


def _joined(buf, spans: Sequence[Tuple[int, int]]):
    """The bytes of ``buf`` in ``spans``, in order: ``buf`` itself where one span covers it."""
    if len(spans) == 1 and tuple(spans[0]) == (0, len(buf)):
        return buf
    view = memoryview(buf)
    return b"".join([view[start:end] for start, end in spans])


def timed_encoder(
    spec: CodecSpec, timings: dict, span=lambda name: NOOP_SPAN, pool: Optional[BufferPool] = None
) -> Callable[[object, Sequence[Tuple[int, int]]], bytes]:
    """``spec.encode`` over a stream given as spans: the callable returned
    takes ``(buf, spans)``, the bytes of ``buf`` in its ``(start, end)`` spans
    in order, and gives ``spec.encode`` of those bytes joined. It leaves
    ``<step>_ns`` in ``timings`` for each of ``spec.encode_steps``, each run
    under ``span("codec.<step>")``. A codec with a ``gather_bound`` lays the
    stream down in its first step, one pass from ``buf`` into a buffer drawn
    from ``pool`` (the bucket of the stream's worst case; a pool of its own
    where none is given); the next step reads it in place, and it goes back to
    the pool once the codec has returned. Any other codec gets the spans
    joined first, outside its steps. A stream of at least one byte also leaves
    ``literal_gathers`` 1 where the native pass laid it down, else
    ``literal_joins`` 1 (joined here, or laid down by the numpy fallback)."""
    pool = pool if pool is not None else BufferPool()

    def timed(step: str, fn, *args):
        t = time.perf_counter_ns()
        with span(f"codec.{step}"):
            out = fn(*args)
        timings[f"{step}_ns"] = time.perf_counter_ns() - t
        return out

    def encode(buf, spans: Sequence[Tuple[int, int]]) -> bytes:
        n_raw = sum(end - start for start, end in spans)
        if spec.gather_bound is None:
            data = _joined(buf, spans)
            if n_raw:
                timings["literal_joins"] = 1
            for step, fn in spec.encode_steps:
                data = timed(step, fn, data)
            return data if spec.encode_steps else spec.encode(data)
        (first, gather), rest = spec.encode_steps[0], spec.encode_steps[1:]
        out = pool.acquire(spec.gather_bound(bucket_size(n_raw)))
        try:
            data, native = timed(first, gather, buf, spans, out)
            if n_raw:
                timings["literal_gathers" if native else "literal_joins"] = 1
            for step, fn in rest:
                data = timed(step, fn, data)
            return data if rest else bytes(data)
        finally:
            pool.release(out)

    return encode


def get_codec(name: str) -> CodecSpec:
    if name not in _REGISTRY:
        raise CodecException(f"unknown codec {name!r}; available: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def get_codec_by_id(codec_id: int) -> CodecSpec:
    if codec_id not in _BY_ID:
        raise CodecException(f"unknown codec id {codec_id}")
    return _BY_ID[codec_id]
