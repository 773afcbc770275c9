"""The plain reference for the device programs: segment ends and segment
fingerprints of one chunk in straightforward numpy.

Imports nothing of the program and takes nothing it has made. The gear table
and the fingerprint bases are rebuilt here from the constants that define the
deployment's cut (``skyplane_tpu/ops/gear.py``, ``ops/fingerprint.py``): every
gateway must cut the same bytes the same way, so the constants are part of
the configuration, not of an implementation. Follows the path
``chip_smoke.numpy_reference`` uses (gear hash by log-doubling, greedy min/max
boundary selection, per-segment polynomial sums mod 2^31 - 1, blake2b mix),
with the gear hash taken in blocks so that a 64 MiB row stays in cache.
"""

from __future__ import annotations

import hashlib
from typing import List, Sequence, Tuple

import numpy as np

GEAR_WINDOW = 32
GEAR_SEED = 0x5EED_CDC1
FP_BASE_SEED = 0x5EED_F1D0
N_LANES = 8
M31 = (1 << 31) - 1
HASH_BLOCK = 1 << 20


def splitmix64(seed: int, n: int) -> np.ndarray:
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


GEAR_TABLE = (splitmix64(GEAR_SEED, 256) & np.uint64(0xFFFFFFFF)).astype(np.uint32)
LANE_BASES = [int(b) for b in splitmix64(FP_BASE_SEED, N_LANES) % np.uint64(M31 - 3) + np.uint64(2)]


def gear_hash(data: np.ndarray) -> np.ndarray:
    """h_t = sum_{i<32} G[b_{t-i}] << i (mod 2^32), the windowed form of
    h = (h << 1) + G[b]."""
    g = GEAR_TABLE[data]
    h = g.copy()
    off = 1
    while off < GEAR_WINDOW:
        shifted = np.zeros_like(h)
        shifted[off:] = h[:-off]
        h = h + (shifted << np.uint32(off))
        off <<= 1
    return h


def candidates(data: np.ndarray, mask_bits: int) -> np.ndarray:
    """Positions whose hash has its top ``mask_bits`` bits zero."""
    found = []
    halo = GEAR_WINDOW - 1
    for start in range(0, len(data), HASH_BLOCK):
        lo = max(0, start - halo)
        h = gear_hash(data[lo : start + HASH_BLOCK])[start - lo :]
        found.append(np.flatnonzero((h >> np.uint32(32 - mask_bits)) == 0) + start)
    return np.concatenate(found) if found else np.empty(0, np.int64)


def select_boundaries(cands: np.ndarray, n: int, min_bytes: int, max_bytes: int) -> np.ndarray:
    """Greedy min/max enforcement over ascending candidate positions; a
    segment ends AFTER a candidate byte. Always terminated by ``n``."""
    ends: List[int] = []
    start = 0
    for p in cands.tolist():
        cut = p + 1
        if cut - start < min_bytes:
            continue
        while cut - start > max_bytes:
            start += max_bytes
            ends.append(start)
        if cut - start >= min_bytes:
            ends.append(cut)
            start = cut
    while n - start > max_bytes:
        start += max_bytes
        ends.append(start)
    if start < n or not ends:
        ends.append(n)
    return np.asarray(ends, dtype=np.int64)


_power_tables = {}


def power_table(base: int, n: int) -> np.ndarray:
    key = (base, n)
    if key not in _power_tables:
        out = np.empty(n, np.uint64)
        x = 1
        for i in range(n):
            out[i] = x
            x = x * base % M31
        _power_tables[key] = out
    return _power_tables[key]


def segment_digests(data: np.ndarray, ends: np.ndarray, bases: Sequence[int] = LANE_BASES) -> List[bytes]:
    """Per segment s = b_0..b_{L-1}: one lane F_r(s) = sum b_i r^(L-1-i)
    mod M31 per base r (the configuration states all eight), mixed with L
    into 16 bytes by blake2b."""
    ends_l = np.asarray(ends, np.int64).tolist()
    longest = max(e - s for s, e in zip([0] + ends_l[:-1], ends_l))
    tables = [power_table(b, longest) for b in bases]
    out = []
    start = 0
    for end in ends_l:
        length = end - start
        d = data[start:end].astype(np.uint64)
        lanes = np.empty(len(tables), "<u4")
        for li, table in enumerate(tables):
            t = d * table[:length][::-1]  # < 2^39
            t = (t >> np.uint64(31)) + (t & np.uint64(M31))  # 2^31 = 1 (mod M31)
            lanes[li] = int(t.sum()) % M31
        out.append(hashlib.blake2b(lanes.tobytes() + length.to_bytes(8, "little"), digest_size=16).digest())
        start = end
    return out


def cdc_and_fingerprints(
    data: np.ndarray, min_bytes: int, avg_bytes: int, max_bytes: int, bases: Sequence[int] = LANE_BASES
) -> Tuple[np.ndarray, List[bytes]]:
    mask_bits = max(1, int(np.log2(avg_bytes)))
    ends = select_boundaries(candidates(data, mask_bits), len(data), min_bytes, max_bytes)
    return ends, segment_digests(data, ends, bases)
