"""Block-suppress codec: a fully-parallel TPU compression stage.

Splits a chunk into fixed-size blocks and classifies each block:

  tag 0 — all-zero block       -> emits nothing
  tag 1 — constant block       -> emits 1 literal byte
  tag 2 — literal block        -> emits the full block

Literals are compacted with a prefix-sum scatter so the device emits one
dense literal buffer plus a per-block tag vector — both static-shaped, so the
whole encode/decode jits cleanly. Zero/constant suppression is the dominant
win on VM-snapshot corpora (sparse filesystems); for general data the
``tpu_zstd`` codec further packs the compacted literals with zstd on host.

Container layout (host-assembled, little-endian):
  magic 0xB1 0x0C | ver(1) | block_log2(1) | n_raw_bytes(8) | n_lit_bytes(8)
  | packed 2-bit tags (ceil(n_blocks/4) bytes) | literal bytes

The device functions below are pure and shape-static; ``encode_container`` /
``decode_container`` do the byte-level framing on host.
"""

from __future__ import annotations

import struct
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from skyplane_tpu.exceptions import CodecException

MAGIC = b"\xb1\x0c"
VERSION = 1
DEFAULT_BLOCK_BYTES = 512

TAG_ZERO = 0
TAG_CONST = 1
TAG_LITERAL = 2


@partial(jax.jit, static_argnames=("block_bytes",))
def encode_device(data: jax.Array, block_bytes: int = DEFAULT_BLOCK_BYTES):
    """[N] uint8 (N divisible by block_bytes) -> (tags[NB] uint8, literals[N] uint8, n_lit scalar).

    ``literals`` is a dense prefix of valid bytes (first n_lit entries); the
    tail is zero. Output shapes are static so callers slice on host.
    """
    n = data.shape[0]
    nb = n // block_bytes
    blocks = data.reshape(nb, block_bytes)
    first = blocks[:, :1]
    is_const = jnp.all(blocks == first, axis=1)
    is_zero = is_const & (first[:, 0] == 0)
    tags = jnp.where(is_zero, TAG_ZERO, jnp.where(is_const, TAG_CONST, TAG_LITERAL)).astype(jnp.uint8)

    # per-byte keep mask: literal blocks keep all bytes, const keeps byte 0
    col = jax.lax.broadcasted_iota(jnp.int32, (nb, block_bytes), 1)
    keep = jnp.where(
        (tags == TAG_LITERAL)[:, None],
        jnp.ones((nb, block_bytes), jnp.bool_),
        (tags == TAG_CONST)[:, None] & (col == 0),
    ).reshape(n)

    # stable compaction: dest position = exclusive prefix sum of keep
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    n_lit = jnp.where(keep.any(), pos[-1] + 1, 0)
    dest = jnp.where(keep, pos, n)  # dropped bytes scatter out of range
    literals = jnp.zeros((n,), jnp.uint8).at[dest].set(data, mode="drop")
    return tags, literals, n_lit.astype(jnp.int32)


@partial(jax.jit, static_argnames=("block_bytes",))
def decode_device(tags: jax.Array, literals: jax.Array, block_bytes: int = DEFAULT_BLOCK_BYTES):
    """Inverse of encode_device: (tags[NB], literals[*]) -> [NB*block_bytes] uint8."""
    nb = tags.shape[0]
    lit_len_per_block = jnp.where(tags == TAG_LITERAL, block_bytes, jnp.where(tags == TAG_CONST, 1, 0))
    # exclusive prefix sum = literal start offset of each block
    offsets = jnp.cumsum(lit_len_per_block) - lit_len_per_block
    col = jax.lax.broadcasted_iota(jnp.int32, (nb, block_bytes), 1)
    lit_index = jnp.where(
        (tags == TAG_LITERAL)[:, None],
        offsets[:, None] + col,
        offsets[:, None],  # const: every byte reads the single literal
    )
    gathered = literals[lit_index.reshape(-1)].reshape(nb, block_bytes)
    out = jnp.where((tags == TAG_ZERO)[:, None], jnp.uint8(0), gathered)
    return out.reshape(nb * block_bytes)


def _pack_tags(tags: np.ndarray) -> bytes:
    """2-bit pack tags, 4 per byte."""
    pad = (-len(tags)) % 4
    t = np.concatenate([tags, np.zeros(pad, np.uint8)]).reshape(-1, 4)
    packed = t[:, 0] | (t[:, 1] << 2) | (t[:, 2] << 4) | (t[:, 3] << 6)
    return packed.astype(np.uint8).tobytes()


def _unpack_tags(buf: bytes, n_blocks: int) -> np.ndarray:
    packed = np.frombuffer(buf, dtype=np.uint8)
    t = np.stack([packed & 3, (packed >> 2) & 3, (packed >> 4) & 3, (packed >> 6) & 3], axis=1).reshape(-1)
    return t[:n_blocks]


def encode_container(data: bytes, block_bytes: int = DEFAULT_BLOCK_BYTES) -> bytes:
    """Host entry: raw bytes -> blockpack container. Runs the device kernel on
    accelerators, the bit-identical numpy path on CPU backends."""
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
        # the native single-pass kernel runs at memcpy speed; the device
        # kernel would have to pull the (data-sized) literal stream back over
        # the host link, which costs more than the whole host pass. The
        # device kernel stays the path for device-resident consumers
        # (datapath_step).
        tags_np, lit_np, n_lit = native_dp.blockpack_encode(arr, block_bytes)
    else:
        from skyplane_tpu.ops.backend import on_accelerator

        if on_accelerator():
            tags, literals, n_lit = encode_device(jnp.asarray(arr), block_bytes=block_bytes)
            tags_np = np.asarray(tags)
            n_lit = int(n_lit)
            lit_np = np.asarray(literals[:n_lit]) if n_lit else np.empty(0, np.uint8)
        else:
            from skyplane_tpu.ops.host_fallback import blockpack_encode_host

            tags_np, lit_np, n_lit = blockpack_encode_host(arr, block_bytes)
    header = MAGIC + struct.pack("<BBQQ", VERSION, block_log2, n_raw, n_lit)
    return header + _pack_tags(tags_np) + lit_np.tobytes()


def decode_container(buf: bytes) -> bytes:
    """Host entry: blockpack container -> raw bytes."""
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
        return b""
    off = 2 + struct.calcsize("<BBQQ")
    n_padded = ((n_raw + block_bytes - 1) // block_bytes) * block_bytes
    n_blocks = n_padded // block_bytes
    tag_bytes = (n_blocks + 3) // 4
    if len(buf) < off + tag_bytes:
        raise CodecException("truncated blockpack container (tag region)")
    tags = _unpack_tags(buf[off : off + tag_bytes], n_blocks)
    literals = np.frombuffer(buf[off + tag_bytes : off + tag_bytes + n_lit], np.uint8)
    if len(literals) != n_lit:
        raise CodecException("truncated blockpack container")
    from skyplane_tpu.native import datapath as native_dp

    if native_dp.available():
        # memcpy-speed host kernel; the device path would pull the whole
        # decoded chunk back over the host link (see encode_container)
        out = native_dp.blockpack_decode(tags, literals, block_bytes)
    else:
        from skyplane_tpu.ops.backend import on_accelerator

        if on_accelerator():
            # device gather expects a static-size literal buffer >= any index it reads
            lit_padded = np.zeros(max(n_padded, 1), np.uint8)
            lit_padded[:n_lit] = literals
            out = np.asarray(decode_device(jnp.asarray(tags), jnp.asarray(lit_padded), block_bytes=block_bytes))
        else:
            from skyplane_tpu.ops.host_fallback import blockpack_decode_host

            out = blockpack_decode_host(tags, literals, block_bytes)
    return out[:n_raw].tobytes()
