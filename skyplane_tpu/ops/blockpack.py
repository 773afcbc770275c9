"""Block-suppress codec: zero and constant blocks leave the literal stream.

Splits a chunk into fixed-size blocks and classifies each block:

  tag 0 — all-zero block       -> emits nothing
  tag 1 — constant block       -> emits 1 literal byte
  tag 2 — literal block        -> emits the full block

Literals are compacted into one dense buffer beside a per-block tag vector.
Zero/constant suppression is the dominant win on VM-snapshot corpora (sparse
filesystems); for general data the ``tpu_zstd`` codec further packs the
compacted literals with zstd.

Container layout (host-assembled, little-endian):
  magic 0xB1 0x0C | ver(1) | block_log2(1) | n_raw_bytes(8) | n_lit_bytes(8)
  | packed 2-bit tags (ceil(n_blocks/4) bytes) | literal bytes

``encode_container`` / ``decode_container`` frame the bytes around a host
kernel: the native single pass (native/datapath.cpp) when the library is
built, its numpy form (ops/host_fallback.py) otherwise. ``encode_spans``
gives ``encode_container``'s bytes for a stream given as spans of one buffer
(the sender's literal segments in their chunk), written into memory its
caller owns, without joining or padding the stream first.
"""

from __future__ import annotations

import struct
from typing import Optional, Sequence, Tuple

import numpy as np

from skyplane_tpu.exceptions import CodecException

MAGIC = b"\xb1\x0c"
VERSION = 1
DEFAULT_BLOCK_BYTES = 512

TAG_ZERO = 0
TAG_CONST = 1
TAG_LITERAL = 2

_HEAD = struct.Struct("<BBQQ")  # ver, block_log2, n_raw, n_lit (after MAGIC)
HEAD_BYTES = len(MAGIC) + _HEAD.size


def _pack_tags(tags: np.ndarray) -> bytes:
    """2-bit pack tags, 4 per byte."""
    pad = (-len(tags)) % 4
    t = np.concatenate([tags, np.zeros(pad, np.uint8)]).reshape(-1, 4)
    packed = t[:, 0] | (t[:, 1] << 2) | (t[:, 2] << 4) | (t[:, 3] << 6)
    return packed.astype(np.uint8).tobytes()


def _unpack_tags(buf, n_blocks: int) -> np.ndarray:
    packed = np.frombuffer(buf, dtype=np.uint8)
    t = np.stack([packed & 3, (packed >> 2) & 3, (packed >> 4) & 3, (packed >> 6) & 3], axis=1).reshape(-1)
    return t[:n_blocks]


def padded_len(n_raw: int, block_bytes: int = DEFAULT_BLOCK_BYTES) -> int:
    """Bytes ``decode_container`` writes for ``n_raw`` decoded bytes: whole blocks."""
    return -(-n_raw // block_bytes) * block_bytes


def _block_log2(block_bytes: int) -> int:
    block_log2 = int(block_bytes).bit_length() - 1
    if (1 << block_log2) != block_bytes:
        raise CodecException(f"block_bytes must be a power of two, got {block_bytes}")
    return block_log2


def container_bound(n_raw: int, block_bytes: int = DEFAULT_BLOCK_BYTES) -> int:
    """The most bytes a container of ``n_raw`` raw bytes takes: every block literal."""
    n_blocks = -(-n_raw // block_bytes)
    return HEAD_BYTES + (n_blocks + 3) // 4 + n_blocks * block_bytes


def encode_container(data: bytes, block_bytes: int = DEFAULT_BLOCK_BYTES) -> bytes:
    """Host entry: raw bytes -> blockpack container."""
    n_raw = len(data)
    block_log2 = _block_log2(block_bytes)
    if n_raw == 0:
        return MAGIC + _HEAD.pack(VERSION, block_log2, 0, 0)
    pad = (-n_raw) % block_bytes
    arr = np.frombuffer(data, np.uint8)
    if pad:
        arr = np.concatenate([arr, np.zeros(pad, np.uint8)])
    from skyplane_tpu.native import datapath as native_dp

    if native_dp.available():
        tags_np, lit_np, n_lit = native_dp.blockpack_encode(arr, block_bytes)
    else:
        from skyplane_tpu.ops.host_fallback import blockpack_encode_host

        tags_np, lit_np, n_lit = blockpack_encode_host(arr, block_bytes)
    header = MAGIC + _HEAD.pack(VERSION, block_log2, n_raw, n_lit)
    return header + _pack_tags(tags_np) + lit_np.tobytes()


def encode_spans(
    buf, spans: Sequence[Tuple[int, int]], out: np.ndarray, block_bytes: int = DEFAULT_BLOCK_BYTES
) -> Tuple[memoryview, bool]:
    """``encode_container`` of the bytes of ``buf`` in ``spans`` ((start, end)
    pairs, in order) joined, written into ``out``: a C-contiguous uint8 array
    of at least ``container_bound`` bytes of the stream. The tag region's
    length follows from the stream's, so the literals go straight to their
    final offset and the header, which holds their count, is written last.
    With the native library this is one pass over the spans in place, the
    interpreter lock released; without it the numpy kernel runs on the spans
    joined and padded. Returns a view of the container in ``out`` and whether
    the native pass laid it down."""
    block_log2 = _block_log2(block_bytes)
    n_raw = sum(end - start for start, end in spans)
    n_blocks = -(-n_raw // block_bytes)
    tag_end = HEAD_BYTES + (n_blocks + 3) // 4
    bound = container_bound(n_raw, block_bytes)
    if len(out) < bound:
        raise CodecException(f"blockpack output buffer holds {len(out)} bytes, the container can take {bound}")
    src = np.frombuffer(buf, np.uint8)
    from skyplane_tpu.native import datapath as native_dp

    native = native_dp.available()
    if native:
        n_lit = native_dp.blockpack_encode_gather(src, np.asarray(spans, np.int64), block_bytes, out[HEAD_BYTES:tag_end], out[tag_end:])
    elif n_raw:
        from skyplane_tpu.ops.host_fallback import blockpack_encode_host

        padded = np.zeros(n_blocks * block_bytes, np.uint8)
        at = 0
        for start, end in spans:
            padded[at : at + end - start] = src[start:end]
            at += end - start
        tags, lits, n_lit = blockpack_encode_host(padded, block_bytes)
        out[HEAD_BYTES:tag_end] = np.frombuffer(_pack_tags(tags), np.uint8)
        out[tag_end : tag_end + n_lit] = lits
    else:
        n_lit = 0
    out[: len(MAGIC)] = np.frombuffer(MAGIC, np.uint8)
    _HEAD.pack_into(out, len(MAGIC), VERSION, block_log2, n_raw, n_lit)
    return memoryview(out)[: tag_end + n_lit], native


def decode_container(buf, out: Optional[np.ndarray] = None) -> memoryview:
    """Host entry: blockpack container -> a view of the raw bytes.

    ``buf`` is any C-contiguous buffer; tags and literals are read in place.
    The kernel writes whole blocks, so ``out`` (a uint8 array the caller owns,
    a pooled buffer on the receiver's decode path) has to hold
    ``padded_len(n_raw, block_bytes)`` bytes; a shorter one is refused. Without
    ``out`` the blocks go into a fresh array. The view returned is over the
    first ``n_raw`` bytes of whichever it was and lives as long as that does.
    """
    buf = memoryview(buf)
    if len(buf) < 2 or buf[:2] != MAGIC:
        raise CodecException("not a blockpack container (bad magic)")
    if len(buf) < HEAD_BYTES:
        raise CodecException("truncated blockpack header")
    ver, block_log2, n_raw, n_lit = _HEAD.unpack_from(buf, len(MAGIC))
    if block_log2 > 30 or n_raw > (1 << 40) or n_lit > len(buf):
        raise CodecException("implausible blockpack header fields (corrupted container)")
    if ver != VERSION:
        raise CodecException(f"unsupported blockpack version {ver}")
    block_bytes = 1 << block_log2
    if n_raw == 0:
        return memoryview(b"")
    n_padded = padded_len(n_raw, block_bytes)
    n_blocks = n_padded // block_bytes
    tag_bytes = (n_blocks + 3) // 4
    if len(buf) < HEAD_BYTES + tag_bytes:
        raise CodecException("truncated blockpack container (tag region)")
    if out is None:
        out = np.empty(n_padded, np.uint8)
    elif len(out) < n_padded:
        raise CodecException(f"blockpack output buffer holds {len(out)} bytes, the container needs {n_padded}")
    tags = _unpack_tags(buf[HEAD_BYTES : HEAD_BYTES + tag_bytes], n_blocks)
    literals = np.frombuffer(buf[HEAD_BYTES + tag_bytes : HEAD_BYTES + tag_bytes + n_lit], np.uint8)
    if len(literals) != n_lit:
        raise CodecException("truncated blockpack container")
    from skyplane_tpu.native import datapath as native_dp

    if native_dp.available():
        native_dp.blockpack_decode(tags, literals, block_bytes, out)
    else:
        from skyplane_tpu.ops.host_fallback import blockpack_decode_host

        blockpack_decode_host(tags, literals, block_bytes, out)
    return memoryview(out)[:n_raw]
