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
built, its numpy form (ops/host_fallback.py) otherwise.
"""

from __future__ import annotations

import struct
from typing import Optional

import numpy as np

from skyplane_tpu.exceptions import CodecException

MAGIC = b"\xb1\x0c"
VERSION = 1
DEFAULT_BLOCK_BYTES = 512

TAG_ZERO = 0
TAG_CONST = 1
TAG_LITERAL = 2


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


def encode_container(data: bytes, block_bytes: int = DEFAULT_BLOCK_BYTES) -> bytes:
    """Host entry: raw bytes -> blockpack container."""
    n_raw = len(data)
    block_log2 = int(block_bytes).bit_length() - 1
    if (1 << block_log2) != block_bytes:
        raise CodecException(f"block_bytes must be a power of two, got {block_bytes}")
    if n_raw == 0:
        return MAGIC + struct.pack("<BBQQ", VERSION, block_log2, 0, 0)
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
    header = MAGIC + struct.pack("<BBQQ", VERSION, block_log2, n_raw, n_lit)
    return header + _pack_tags(tags_np) + lit_np.tobytes()


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
    head_len = 2 + struct.calcsize("<BBQQ")
    if len(buf) < 2 or buf[:2] != MAGIC:
        raise CodecException("not a blockpack container (bad magic)")
    if len(buf) < head_len:
        raise CodecException("truncated blockpack header")
    ver, block_log2, n_raw, n_lit = struct.unpack_from("<BBQQ", buf, 2)
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
    if len(buf) < head_len + tag_bytes:
        raise CodecException("truncated blockpack container (tag region)")
    if out is None:
        out = np.empty(n_padded, np.uint8)
    elif len(out) < n_padded:
        raise CodecException(f"blockpack output buffer holds {len(out)} bytes, the container needs {n_padded}")
    tags = _unpack_tags(buf[head_len : head_len + tag_bytes], n_blocks)
    literals = np.frombuffer(buf[head_len + tag_bytes : head_len + tag_bytes + n_lit], np.uint8)
    if len(literals) != n_lit:
        raise CodecException("truncated blockpack container")
    from skyplane_tpu.native import datapath as native_dp

    if native_dp.available():
        native_dp.blockpack_decode(tags, literals, block_bytes, out)
    else:
        from skyplane_tpu.ops.host_fallback import blockpack_decode_host

        blockpack_decode_host(tags, literals, block_bytes, out)
    return memoryview(out)[:n_raw]
