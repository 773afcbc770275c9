"""Multi-lane polynomial segment fingerprints over GF(2^31 - 1).

For each CDC segment s = [b_0 .. b_{L-1}] and lane base r:

    F_r(s) = sum_i b_i * r^(L-1-i)   mod M31      (Horner-form poly hash)

Eight lanes with independent random bases give a per-pair collision
probability <= (L / M31)^8 ~= 2^-104 for L <= 256 KiB (Schwartz–Zippel), far
below corruption rates of the underlying networks. The 8x-uint32 lane vector
is mixed to the 128-bit wire fingerprint with blake2b on host (32 bytes per
segment — negligible).

Everything device-side is parallel: per-byte terms are ``mulmod31`` products
with a precomputed power and per-segment sums are limb-split (4 x 8-bit
limbs so uint32 accumulators cannot overflow). One device formulation:
``segment_fingerprint_cumsum`` (call B of ops/fused_cdc.py) takes the power
from the byte's position in the row alone and the sums from prefix-sum
differences, so nothing is indexed per byte. The host forms below (native
Horner kernel, numpy, python ints) are what it is held bit-identical to.
"""

from __future__ import annotations

import hashlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from skyplane_tpu.ops.u32 import M31, addmod31, fold31, mulmod31, powmod31_table, powmod31_table_device

N_LANES = 8
MAX_SEGMENT_BYTES = 1 << 18  # power table length; must cover cdc_max_bytes
_BASE_SEED = 0x5EED_F1D0

# deterministic per-deployment lane bases in [2, M31-2]; generated with the
# in-repo splitmix64 (NOT numpy Generator) so all hosts agree regardless of
# numpy version
from skyplane_tpu.ops.gear import splitmix64_stream  # noqa: E402

LANE_BASES = (splitmix64_stream(_BASE_SEED, N_LANES) % np.uint64(M31 - 3) + np.uint64(2)).astype(np.uint32)

_power_tables_cache = None


def _power_tables() -> np.ndarray:
    global _power_tables_cache
    if _power_tables_cache is None:
        _power_tables_cache = np.stack([powmod31_table(int(b), MAX_SEGMENT_BYTES) for b in LANE_BASES])
    return _power_tables_cache  # [LANES, MAX] uint32


@partial(jax.jit, static_argnames=("n_segments",))
def segment_fingerprint_cumsum(data: jax.Array, seg_starts: jax.Array, seg_ends: jax.Array, n_segments: int):
    """Per-segment 8-lane polynomial hash for CONTIGUOUS segments, with no
    per-byte index: no scatter, and no gather over the row.

    The row is read as blocks of ``T = min(MAX_SEGMENT_BYTES, N)`` bytes. With
    q = r^-1 in the field, a byte i of block k contributes

        b_i * r^(e-1-i)  =  r^(e-1-kT) * b_i * q^(i - kT)

    to the segment ending at e, so the per-byte factor q^(i mod T) depends on
    the POSITION alone: one [T] table broadcast over the row viewed [N/T, T].
    Per-segment sums are differences of the limb prefix sums of a block, taken
    once per block the segment touches — a segment no longer than T touches at
    most two, cut at the block edge — and each piece is scaled by r^(e-1-kT),
    exponent in [0, 2T), looked up per slot: a few n_segments-sized look-ups
    per lane and limb instead of one per byte. Exact in GF(2^31 - 1), so
    bit-identical to the host kernels (tested).

    Args:
      data:       [N] uint8 chunk bytes, N a multiple of T (every bucket is a
                  power of two, and so is MAX_SEGMENT_BYTES).
      seg_starts: [n_segments] int32 start offset per slot.
      seg_ends:   [n_segments] int32 end offset per slot (== start for empty
                  pad slots; both clamped to [0, N]). A slot longer than T
                  must hold zero bytes only (the garbage slot over the row's
                  zero padding): every sum over it is 0 whatever its factors.
      n_segments: static slot count.

    Exactness: limbs are 8-bit and a block's prefix sums restart at its first
    byte, so every prefix sum and every piece's limb sum is < 2^18 * 255 <
    2^26: uint32 holds them with no wrap.

    Returns [n_segments, N_LANES] uint32 lane values in canonical [0, M31).
    """
    n = data.shape[0]
    period = min(MAX_SEGMENT_BYTES, n)
    if n % period:
        raise ValueError(f"row length {n} is not a multiple of the fingerprint period {period}")
    bases = [int(b) for b in LANE_BASES]
    with jax.named_scope("fp.power_tables"):
        inv = powmod31_table_device([pow(b, -1, M31) for b in bases], period)  # q^j, the per-byte factor
        fwd = powmod31_table_device(bases, 2 * period)  # r^x, the per-piece factor

    with jax.named_scope("fp.piece_bounds"):
        # piece 1 = [start, mid) in the block of the first byte, piece 2 =
        # [block_hi, end) in the block of the last, when that is another one
        block_lo = (seg_starts // period) * period
        mid = jnp.minimum(seg_ends, block_lo + period)
        block_hi = ((seg_ends - 1) // period) * period
        exp1 = jnp.clip(seg_ends - 1 - block_lo, 0, 2 * period - 1)  # clip: empty and garbage slots
        exp2 = jnp.clip(seg_ends - 1 - block_hi, 0, 2 * period - 1)
        # the sum of a block's bytes before row offset idx is the block's
        # inclusive prefix sum at idx - 1, and 0 at the block's start
        bounds = jnp.stack([seg_starts, mid, seg_ends])  # [3, n_segments]
        last = jnp.clip(bounds - 1, 0, n - 1)
        block_row, block_col = last // period, last % period
        live = jnp.stack([seg_starts > block_lo, mid > block_lo, block_hi > block_lo])

    b = data.astype(jnp.uint32).reshape(n // period, period)
    reads = []  # per lane and limb, the prefix sums at the three bounds: [3, n_segments]
    with jax.named_scope("fp.lane_passes"):
        for li in range(N_LANES):
            with jax.named_scope(f"lane{li}"):  # each pass over the chunk under its own name in a device trace
                terms = mulmod31(b, inv[li][None, :])  # [N/T, T] < 2^31
                for k in range(4):
                    limb = (terms >> np.uint32(8 * k)) & np.uint32(0xFF)
                    cs = jnp.cumsum(limb, axis=1)  # per block, inclusive: < 2^26, no wrap
                    reads.append(cs[block_row, block_col])
    with jax.named_scope("fp.piece_factors"):
        # everything per slot, all lanes and limbs at once: [LANES, 4, n_segments]
        before = jnp.where(live, jnp.stack(reads).reshape(N_LANES, 4, 3, n_segments), np.uint32(0))
        shift = jnp.asarray([(1 << (8 * k)) % M31 for k in range(4)], jnp.uint32)[None, :, None]

        def recombine(limb_sums):  # exact limb sums (< 2^26) -> sum_k limb_k * 2^(8k) mod M31
            w = mulmod31(fold31(limb_sums), shift)
            return addmod31(addmod31(w[:, 0], w[:, 1]), addmod31(w[:, 2], w[:, 3]))

        piece1 = recombine(before[:, :, 1] - before[:, :, 0])
        piece2 = recombine(before[:, :, 2])
        lanes = addmod31(mulmod31(piece1, fwd[:, exp1]), mulmod31(piece2, fwd[:, exp2]))
    return lanes.T  # [n_segments, LANES]


def finalize_fingerprint(lanes: np.ndarray, length: int) -> str:
    """Mix one segment's 8 uint32 lanes + length into the 128-bit hex wire fingerprint."""
    h = hashlib.blake2b(np.asarray(lanes, dtype="<u4").tobytes() + int(length).to_bytes(8, "little"), digest_size=16)
    return h.hexdigest()


def digests_from_lanes(lanes: np.ndarray, ends: np.ndarray) -> list:
    """Finalize [n_segments, 8] uint32 lane rows into 16-byte wire digests.

    Identical bytes to ``bytes.fromhex(finalize_fingerprint(lanes[i], L_i))``
    — one bulk little-endian conversion instead of a numpy round trip per row.
    """
    la = np.ascontiguousarray(lanes, dtype="<u4").tobytes()
    ends_l = np.asarray(ends, np.int64).tolist()
    out = []
    start = 0
    for i, end in enumerate(ends_l):
        h = hashlib.blake2b(la[i * 32 : i * 32 + 32] + (end - start).to_bytes(8, "little"), digest_size=16)
        out.append(h.digest())
        start = end
    return out


def fingerprint_bytes_host(data: bytes) -> str:
    """Host fallback fingerprint (CPU codec path): blake2b-128 of the raw bytes."""
    return hashlib.blake2b(data, digest_size=16).hexdigest()


def segment_fingerprint_host(seg: bytes) -> bytes:
    """Host recompute of one segment's wire fingerprint (native kernel when
    available, numpy otherwise).

    Used by receivers to verify dedup literals before admitting them to the
    SegmentStore — a corrupted literal stored under a healthy fingerprint
    would otherwise spread to every chunk that later REFs it.
    """
    L = len(seg)
    if L > MAX_SEGMENT_BYTES:
        raise ValueError(f"segment length {L} exceeds MAX_SEGMENT_BYTES {MAX_SEGMENT_BYTES}")
    from skyplane_tpu.native import datapath as native_dp

    if L and native_dp.available():
        lanes = native_dp.segment_fp_lanes(np.frombuffer(seg, np.uint8), np.asarray([L], np.int64))[0]
        return bytes.fromhex(finalize_fingerprint(lanes, L))
    arr = np.frombuffer(seg, np.uint8).astype(np.uint64)
    tables = _power_tables()
    lanes = np.empty(N_LANES, np.uint32)
    for li in range(N_LANES):
        powers = tables[li][:L][::-1].astype(np.uint64)  # r^(L-1-i)
        # terms < 2^39, sum over <= 2^18 terms < 2^57: no u64 overflow
        lanes[li] = np.uint32((arr * powers % np.uint64(M31)).sum() % np.uint64(M31))
    return bytes.fromhex(finalize_fingerprint(lanes, L))


def segment_fp_lanes_numpy(arr: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """[n_segments, 8] uint32 lane values in vectorized numpy: the path hosts
    without the native library run, and the plain reference chip_smoke.py
    holds the device to at real chunk sizes."""
    ends = np.asarray(ends, np.int64)
    starts = np.concatenate([[0], ends[:-1]])
    tables64 = _power_tables().astype(np.uint64)  # [LANES, MAX]
    lanes = np.empty((len(ends), N_LANES), np.uint32)
    m31 = np.uint64(M31)
    # per-segment processing keeps the working set (<= 256 KiB slices) in
    # cache — full-array passes are DRAM-bound and measure ~6x slower
    for si, (s, e) in enumerate(zip(starts, ends)):
        d = arr[s:e].astype(np.uint64)
        length = int(e - s)
        for li in range(N_LANES):
            powers = tables64[li, :length][::-1]
            t = d * powers  # < 2^39
            t = (t >> np.uint64(31)) + (t & m31)  # < 2^31 + 2^8
            total = int(t.sum())  # <= 2^18 * 2^32 < 2^50, python int exact
            lanes[si, li] = total % M31
    return lanes


def segment_fingerprints_host_batch(arr: np.ndarray, ends: np.ndarray) -> list:
    """All segment fingerprints of one chunk. Uses the native single-pass
    Horner kernel when available (~10x the numpy path), else vectorized
    numpy. Returns 16-byte digests in segment order; identical to the device
    kernel + finalize (tested)."""
    n = len(arr)
    ends = np.asarray(ends, np.int64)
    if n == 0 or len(ends) == 0:
        return []
    from skyplane_tpu.native import datapath as native_dp

    if native_dp.available():
        lanes = native_dp.segment_fp_lanes(arr, ends)
    else:
        lanes = segment_fp_lanes_numpy(arr, ends)
    return digests_from_lanes(lanes, ends)


def segment_fingerprint_np(data: np.ndarray, boundaries: np.ndarray) -> np.ndarray:
    """Numpy reference: per-segment lanes via python ints. boundaries = segment end offsets."""
    out = np.zeros((len(boundaries), N_LANES), np.uint32)
    start = 0
    for si, end in enumerate(boundaries):
        seg = data[start:end]
        for li, base in enumerate(LANE_BASES):
            acc = 0
            for byte in seg:
                acc = (acc * int(base) + int(byte)) % M31
            out[si, li] = acc
        start = end
    return out
