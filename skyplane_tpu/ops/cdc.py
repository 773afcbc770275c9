"""Content-defined chunking: device-parallel hash, host boundary selection.

The expensive stage — rolling-hash every byte and testing the boundary
predicate — runs on TPU (ops/gear.py). What remains is enforcing
min/max segment lengths over the sparse candidate list, which is a greedy
sequential pass but touches only ~N/avg_size positions, so it runs on host
over the candidate indices (a few thousand ints per 64 MB chunk).

Determinism contract: boundaries are a pure function of the chunk bytes and
the (min, avg, max) parameters, so sender and receiver / dedup index always
agree.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import numpy as np


@dataclass(frozen=True)
class CDCParams:
    # 16 KiB average segments: on snapshot-delta corpora they catch ~10% more
    # duplicate bytes than 64 KiB (a clustered write invalidates only the
    # segments it touches) at no throughput cost with the native/device
    # fingerprint kernels; per-segment recipe overhead stays ~0.15%.
    min_bytes: int = 4 * 1024
    avg_bytes: int = 16 * 1024
    max_bytes: int = 64 * 1024

    def __post_init__(self):
        from skyplane_tpu.ops.fingerprint import MAX_SEGMENT_BYTES

        if not (0 < self.min_bytes <= self.avg_bytes <= self.max_bytes):
            raise ValueError(f"CDC params must satisfy 0 < min <= avg <= max, got {self}")
        if self.max_bytes > MAX_SEGMENT_BYTES:
            # the fingerprint power tables only cover MAX_SEGMENT_BYTES; beyond
            # that, positions would alias and distinct segments could collide
            raise ValueError(f"cdc max_bytes {self.max_bytes} exceeds fingerprint MAX_SEGMENT_BYTES {MAX_SEGMENT_BYTES}")

    @property
    def mask_bits(self) -> int:
        return max(1, int(np.log2(self.avg_bytes)))


def select_boundaries(candidates: np.ndarray, n: int, params: CDCParams) -> np.ndarray:
    """Greedy min/max enforcement over sorted candidate positions.

    candidates: positions p where a boundary MAY end a segment (segment ends
    AFTER byte p, i.e. cut at p+1). Returns segment end offsets, always
    terminated by n.
    """
    ends: List[int] = []
    start = 0
    for p in candidates:
        cut = int(p) + 1
        if cut - start < params.min_bytes:
            continue
        # honor max: if the candidate overshoots, insert forced cuts first
        while cut - start > params.max_bytes:
            start += params.max_bytes
            ends.append(start)
        if cut - start >= params.min_bytes:
            ends.append(cut)
            start = cut
    while n - start > params.max_bytes:
        start += params.max_bytes
        ends.append(start)
    if start < n or not ends:
        ends.append(n)
    return np.asarray(ends, dtype=np.int64)


def cdc_segment_ends(data: bytes | np.ndarray, params: CDCParams = CDCParams()) -> np.ndarray:
    """Full CDC for one chunk on HOST kernels: returns segment end offsets
    (last == len(data)).

    Native single-pass C kernel when built (~60x the numpy fallback), numpy
    otherwise; bit-identical to the device path (ops/fused_cdc.py), which
    production accelerator callers use instead — it avoids this function's
    full-chunk candidate-mask materialization.
    """
    arr = np.frombuffer(data, np.uint8) if isinstance(data, (bytes, bytearray, memoryview)) else np.asarray(data, np.uint8)
    n = len(arr)
    if n == 0:
        return np.asarray([0], dtype=np.int64)
    from skyplane_tpu.native import datapath as native_dp

    if native_dp.available():
        mask = native_dp.gear_candidates(arr, params.mask_bits)
    else:
        from skyplane_tpu.ops.host_fallback import boundary_candidates_host, gear_hash_host

        mask = boundary_candidates_host(gear_hash_host(arr), params.mask_bits)
    candidates = np.flatnonzero(mask)
    return select_boundaries(candidates, n, params)


def cdc_and_fps_host(arr: np.ndarray, params: CDCParams = CDCParams()) -> Tuple[np.ndarray, list]:
    """Fused host CDC + segment digests: (ends, [fp16 bytes, ...]).

    One native call (skydp_cdc_fp: sparse gear candidates -> C boundary
    selection -> 8-lane fingerprints) when the library is built — ~2.5x the
    two-stage host path, which remains the fallback and the parity oracle
    (tests/unit/test_native_datapath.py pins them bit-identical).
    """
    arr = np.frombuffer(arr, np.uint8) if isinstance(arr, (bytes, bytearray, memoryview)) else np.asarray(arr, np.uint8)
    from skyplane_tpu.native import datapath as native_dp

    # the fused kernel tracks candidate positions as u32 — chunks >= 4 GiB
    # (MAX_CHUNK_BYTES allows 8 GiB) take the two-stage int64 path instead
    if len(arr) and len(arr) < (1 << 32) and native_dp.available():
        from skyplane_tpu.ops.fingerprint import digests_from_lanes

        ends, lanes = native_dp.cdc_fp(arr, params.mask_bits, params.min_bytes, params.max_bytes)
        return ends, digests_from_lanes(lanes, ends)
    ends = cdc_segment_ends(arr, params)
    from skyplane_tpu.ops.fingerprint import segment_fingerprints_host_batch

    return ends, segment_fingerprints_host_batch(arr, ends)
