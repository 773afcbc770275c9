"""Vectorized numpy forms of the host data-path kernels.

What a gateway runs where the native library (native/datapath.cpp) is not
built, and the reference the native and device kernels are held to: gear
hash and boundary candidates bit-identical to ops/gear.py, blockpack
bit-identical to the native single pass (tested).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from skyplane_tpu.ops.gear import GEAR_TABLE, GEAR_WINDOW


def gear_hash_host(data: np.ndarray) -> np.ndarray:
    """[N] uint8 -> [N] uint32, same log-doubling windowed sum as the device."""
    g = GEAR_TABLE[data]
    h = g.copy()
    off = 1
    while off < GEAR_WINDOW:
        shifted = np.zeros_like(h)
        shifted[off:] = h[:-off]
        h = (h + (shifted << np.uint32(off))).astype(np.uint32)
        off <<= 1
    return h


def boundary_candidates_host(h: np.ndarray, mask_bits: int) -> np.ndarray:
    return (h >> np.uint32(32 - mask_bits)) == 0


def blockpack_encode_host(data: np.ndarray, block_bytes: int) -> Tuple[np.ndarray, np.ndarray, int]:
    """[N] uint8 (N divisible by block_bytes) -> (tags [NB] uint8, literals
    [n_lit] uint8 dense in stream order, n_lit): ops/blockpack.py's block
    classification and literal compaction."""
    from skyplane_tpu.ops.blockpack import TAG_CONST, TAG_LITERAL, TAG_ZERO

    n = len(data)
    nb = n // block_bytes
    blocks = data.reshape(nb, block_bytes)
    first = blocks[:, :1]
    is_const = (blocks == first).all(axis=1)
    is_zero = is_const & (first[:, 0] == 0)
    tags = np.where(is_zero, TAG_ZERO, np.where(is_const, TAG_CONST, TAG_LITERAL)).astype(np.uint8)
    # stream order is preserved: per-block literal lengths -> offsets -> scatter
    lit_mask = tags == TAG_LITERAL
    const_mask = tags == TAG_CONST
    if lit_mask.any() or const_mask.any():
        # lengths per block: block_bytes / 1 / 0; offsets via cumsum
        lens = np.where(lit_mask, block_bytes, np.where(const_mask, 1, 0))
        total = int(lens.sum())
        out = np.empty(total, np.uint8)
        offsets = np.cumsum(lens) - lens
        # literal blocks: vectorized scatter of whole rows
        lit_idx = np.flatnonzero(lit_mask)
        if len(lit_idx):
            dst = (offsets[lit_idx][:, None] + np.arange(block_bytes)[None, :]).reshape(-1)
            out[dst] = blocks[lit_idx].reshape(-1)
        const_idx = np.flatnonzero(const_mask)
        if len(const_idx):
            out[offsets[const_idx]] = blocks[const_idx, 0]
        return tags, out, total
    return tags, np.empty(0, np.uint8), 0


def blockpack_decode_host(tags: np.ndarray, literals: np.ndarray, block_bytes: int, out=None) -> np.ndarray:
    """Same contract as native ``blockpack_decode``: the blocks go into the first
    ``len(tags) * block_bytes`` bytes of ``out`` where the caller gives one."""
    from skyplane_tpu.exceptions import CodecException
    from skyplane_tpu.ops.blockpack import TAG_CONST, TAG_LITERAL

    nb = len(tags)
    lens = np.where(tags == TAG_LITERAL, block_bytes, np.where(tags == TAG_CONST, 1, 0))
    if int(lens.sum()) > len(literals):
        # corrupted container: tags demand more literal bytes than shipped
        # (keep the error inside the codec contract)
        raise CodecException("blockpack container corrupt: tag/literal length mismatch")
    offsets = np.cumsum(lens) - lens
    if out is None:
        out = np.empty(nb * block_bytes, np.uint8)
    out = out[: nb * block_bytes]
    out[:] = 0
    blocks = out.reshape(nb, block_bytes)
    lit_idx = np.flatnonzero(tags == TAG_LITERAL)
    if len(lit_idx):
        src = (offsets[lit_idx][:, None] + np.arange(block_bytes)[None, :]).reshape(-1)
        blocks[lit_idx] = literals[src].reshape(len(lit_idx), block_bytes)
    const_idx = np.flatnonzero(tags == TAG_CONST)
    if len(const_idx):
        blocks[const_idx] = literals[offsets[const_idx]][:, None]
    return out
