"""Gear rolling hash for content-defined chunking, as a parallel windowed sum.

The classic Gear CDC loop is sequential:  ``h = (h << 1) + G[b_t]``
(one byte per iteration). Because the shift discards bits past 31, the hash
after byte t depends only on the last 32 bytes:

    h_t = sum_{i=0}^{31} G[b_{t-i}] << i        (mod 2^32)

which is a 32-tap weighted correlation — embarrassingly parallel, and the
formulation this module evaluates on the VPU. Nothing in it is indexed per
byte: ``G[b]`` is selected from the table's 256 constants by the byte's
bits (``gear_values``; a per-element gather costs a TPU v5e 0.66 s over a
64 MiB row, the select tree 7 ms), and the window is five shifted adds.
Boundary candidates are positions where the top ``mask_bits`` of ``h_t``
are zero (FastCDC-style high-bit mask; avg segment ≈ 2^mask_bits bytes).
Min/max segment-length enforcement is inherently sequential over the
(sparse) candidate list and is done on host in ops/cdc.py.

Reference behavior being replaced: the reference has no dedup at all; this is
the TPU-native data-path addition (BASELINE.json north star).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

GEAR_WINDOW = 32
_GEAR_SEED = 0x5EED_CDC1


def splitmix64_stream(seed: int, n: int) -> np.ndarray:
    """Deterministic uint64 stream (splitmix64). Implemented in-repo so the
    values are stable across numpy versions — gear tables and fingerprint
    bases MUST agree between every gateway in a deployment (cross-host dedup
    determinism contract)."""
    mask = (1 << 64) - 1
    out = np.empty(n, dtype=np.uint64)
    x = seed & mask
    for i in range(n):
        x = (x + 0x9E3779B97F4A7C15) & mask
        z = x
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & mask
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & mask
        out[i] = z ^ (z >> 31)
    return out


GEAR_TABLE = (splitmix64_stream(_GEAR_SEED, 256) & np.uint64(0xFFFFFFFF)).astype(np.uint32)


# Lanes of the 2-D view the look-up runs over: eight vector registers wide.
_VALUES_VIEW = 1024


def gear_values(data_u8: jax.Array) -> jax.Array:
    """[N] uint8 -> [N] uint32 ``GEAR_TABLE[b]`` of every byte, with no gather:
    an 8-level select tree over the table's 256 constants keyed on the
    byte's bits (255 selects and 8 bit tests a byte, one element-wise pass,
    exact for any table).

    The pass runs over the bytes viewed ``[N/w, w]``. Under ``vmap`` a ``[1, N]``
    row is laid one sublane deep on a TPU and the tree would run at an eighth
    of the vector unit (33.8 ms against 7.0 ms over 64 MiB on a v5e, and four
    times the compile time). The barriers pin the view, which XLA otherwise
    folds away, and keep the tree from being fused into each consumer again.
    """
    n = data_u8.shape[0]
    w = math.gcd(n, _VALUES_VIEW)
    b = jax.lax.optimization_barrier(data_u8.reshape(n // w, w)).astype(jnp.uint32)
    level = [np.uint32(v) for v in GEAR_TABLE]
    for k in range(8):
        bit = (b & np.uint32(1 << k)) != 0
        level = [jnp.where(bit, level[2 * i + 1], level[2 * i]) for i in range(len(level) // 2)]
    return jax.lax.optimization_barrier(level[0]).reshape(n)


def gear_hash(data_u8: jax.Array) -> jax.Array:
    """[N] uint8 -> [N] uint32 rolling gear hash, parallel windowed-sum form.

    Matches the sequential recurrence h_t = (h_{t-1} << 1) + G[b_t] for all t
    (the zero-filled prefix reproduces the h_0 = 0 start). Evaluated by
    log-doubling: with S_k(t) = sum_{i<2^k} g_{t-i} << i,
    S_{k+1}(t) = S_k(t) + (S_k(t - 2^k) << 2^k) — 5 shifted adds instead of 31.
    """
    return _windowed_sum_doubling(gear_values(data_u8))


def _windowed_sum_doubling(g: jax.Array) -> jax.Array:
    h = g
    off = 1
    while off < GEAR_WINDOW:
        shifted = jnp.concatenate([jnp.zeros((off,), jnp.uint32), h[:-off]])
        h = h + (shifted << np.uint32(off))
        off <<= 1
    return h


def boundary_candidate_mask(h: jax.Array, mask_bits: int) -> jax.Array:
    """[N] uint32 -> [N] bool: True where the top mask_bits of the hash are zero."""
    return (h >> np.uint32(32 - mask_bits)) == 0


def gear_hash_np(data: np.ndarray) -> np.ndarray:
    """Sequential numpy reference implementation (the classic Gear loop)."""
    h = np.uint32(0)
    out = np.empty(len(data), dtype=np.uint32)
    table = GEAR_TABLE
    for t in range(len(data)):
        h = np.uint32(((int(h) << 1) + int(table[data[t]])) & 0xFFFFFFFF)
        out[t] = h
    return out
