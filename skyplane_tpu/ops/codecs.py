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
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Dict, NamedTuple, Optional, Tuple

from skyplane_tpu.chunk import Codec
from skyplane_tpu.exceptions import CodecException
from skyplane_tpu.obs import NOOP_SPAN


class CodecSpec(NamedTuple):
    """``decode`` takes any C-contiguous buffer and may return any (``bytes``, or
    a view of an array); a caller that needs ``bytes`` converts. A codec that
    can write into memory its caller owns says so with ``decode_out_len``:
    the length of the ``out`` array ``decode(buf, out)`` needs for ``n``
    decoded bytes. What comes back is then a view of ``out``.

    ``encode_steps`` names the steps ``encode`` is made of, in order, for the
    codecs whose steps have a counter (``blockpack``, ``zstd``): running them
    one after the other gives ``encode``'s bytes (see :func:`timed_encoder`)."""

    name: str
    codec_id: Codec
    encode: Callable[[bytes], bytes]
    decode: Callable[..., object]
    decode_out_len: Optional[Callable[[int], int]] = None
    encode_steps: Tuple[Tuple[str, Callable[[bytes], bytes]], ...] = ()


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
    "tpu": CodecSpec("tpu", Codec.TPU_BLOCK, _encode_tpu, _decode_tpu, _tpu_out_len, (("blockpack", _encode_tpu),)),
    "tpu_zstd": CodecSpec(
        "tpu_zstd", Codec.TPU_BLOCK_ZSTD, _encode_tpu_zstd, _decode_tpu_zstd, _tpu_out_len,
        (("blockpack", _encode_tpu), ("zstd", _encode_zstd)),
    ),
    "native_lz": CodecSpec("native_lz", Codec.NATIVE_LZ, _encode_native, _decode_native),
    # the reference's wire codec (gateway_operator.py:358-361), bound to the
    # system liblz4; registered unconditionally — encode/decode raise on
    # hosts without the library, same lazy-failure contract as native_lz
    "lz4": CodecSpec("lz4", Codec.LZ4, _encode_lz4, _decode_lz4),
}

_BY_ID: Dict[int, CodecSpec] = {int(spec.codec_id): spec for spec in _REGISTRY.values()}


def timed_encoder(spec: CodecSpec, timings: dict, span=lambda name: NOOP_SPAN) -> Callable[[bytes], bytes]:
    """``spec.encode`` taken step by step: the callable returned gives the
    same bytes and leaves ``<step>_ns`` in ``timings`` for each of
    ``spec.encode_steps``, each run under ``span("codec.<step>")``. A codec
    that names no steps is returned as it is."""
    if not spec.encode_steps:
        return spec.encode

    def encode(data: bytes) -> bytes:
        for step, fn in spec.encode_steps:
            t = time.perf_counter_ns()
            with span(f"codec.{step}"):
                data = fn(data)
            timings[f"{step}_ns"] = time.perf_counter_ns() - t
        return data

    return encode


def get_codec(name: str) -> CodecSpec:
    if name not in _REGISTRY:
        raise CodecException(f"unknown codec {name!r}; available: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def get_codec_by_id(codec_id: int) -> CodecSpec:
    if codec_id not in _BY_ID:
        raise CodecException(f"unknown codec id {codec_id}")
    return _BY_ID[codec_id]
