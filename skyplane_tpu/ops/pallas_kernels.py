"""Pallas TPU kernels for the data-path hot loops.

The XLA path materializes each doubling pass of the gear windowed sum to HBM
(5 full-array round trips); this kernel tiles the array through VMEM and runs
all passes on-chip, reading HBM once and writing once. Cross-tile state is a
31-element halo carried via overlapping block reads (the input is padded by
one tile so tile i can read its predecessor without negative indexing).

Enabled with SKYPLANE_TPU_USE_PALLAS=1 (off by default). chip_smoke.py
compiles both kernels on the chip and prints whether each compiled and
matched the XLA path; PERF.md records the verdicts.
"""

from __future__ import annotations

import functools
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from skyplane_tpu.ops.gear import GEAR_TABLE, GEAR_WINDOW

TILE = 64 * 1024  # uint32 elements per grid step: 256 KiB VMEM per ref


def _windowed_sum_kernel(prev_ref, cur_ref, out_ref):
    """One tile of h_t = sum_{i<32} g_{t-i} << i via log-doubling.

    prev_ref/cur_ref: [TILE] uint32 (previous and current tiles of g).
    The doubling recurrence needs GEAR_WINDOW-1 elements of left context;
    taking them from the already-computed *input* of the previous tile (not
    its output) is correct because the recurrence reads raw g values only.
    """
    ext = jnp.concatenate([prev_ref[TILE - (GEAR_WINDOW - 1) :], cur_ref[:]])  # [TILE+31]
    h = ext
    off = 1
    while off < GEAR_WINDOW:
        # shift right by `off` with zero fill, staying in VMEM
        shifted = jnp.concatenate([jnp.zeros((off,), jnp.uint32), h[:-off]])
        h = h + (shifted << np.uint32(off))
        off <<= 1
    out_ref[:] = h[GEAR_WINDOW - 1 :]


@functools.cache
def _windowed_sum_fn(interpret: bool):
    """The tiled kernel over one [N] row, with its own rule for ``vmap``:
    Mosaic refuses the batched block Pallas would derive (a squeezed leading
    dim over rank-1 blocks: "last two dimensions of your block shape are
    divisible by 8 and 128"), so a batch of rows — how fused_cdc's call A
    runs it — is walked row by row with the flat kernel instead."""

    @jax.custom_batching.custom_vmap
    def rows(g):
        n = g.shape[0]
        padded = jnp.concatenate([jnp.zeros((TILE,), jnp.uint32), g])  # zero tile in front
        return pl.pallas_call(
            _windowed_sum_kernel,
            out_shape=jax.ShapeDtypeStruct((n,), jnp.uint32),
            grid=(n // TILE,),
            in_specs=[
                pl.BlockSpec((TILE,), lambda i: (i,)),  # previous tile (padded offset)
                pl.BlockSpec((TILE,), lambda i: (i + 1,)),  # current tile
            ],
            out_specs=pl.BlockSpec((TILE,), lambda i: (i,)),
            interpret=interpret,
        )(padded, padded)

    @rows.def_vmap
    def _row_by_row(axis_size, in_batched, g):
        return jax.lax.map(rows, g), True

    return rows


@partial(jax.jit, static_argnames=("interpret",))
def gear_windowed_sum_pallas(g: jax.Array, interpret: bool = False) -> jax.Array:
    """[N] uint32 gear values -> [N] uint32 rolling hashes (N % TILE == 0)."""
    if g.shape[0] % TILE:
        raise ValueError(f"N={g.shape[0]} must be a multiple of TILE={TILE}")
    return _windowed_sum_fn(interpret)(g)


def use_pallas(kernel: str = "") -> bool:
    """Master flag SKYPLANE_TPU_USE_PALLAS, overridable per kernel with
    SKYPLANE_TPU_USE_PALLAS_{GEAR,FP}: the kernels lower independently on
    real Mosaic toolchains, so one failing validation must not disable the
    other (bench.py sets each from :func:`validate_on_device`)."""
    if kernel:
        v = os.environ.get(f"SKYPLANE_TPU_USE_PALLAS_{kernel.upper()}", "").strip().lower()
        if v:
            return v in ("1", "true", "on")
    return os.environ.get("SKYPLANE_TPU_USE_PALLAS", "0").strip().lower() in ("1", "true", "on")


# ---- fixed-stride segment fingerprints ----

FP_MAX_TILE = 1 << 16  # powers-slice VMEM budget: [8, S] u32 = 2 MiB at 2^16 (limb sums are bounded per sub-tile now)
SEGS_PER_BLOCK = 8  # Mosaic needs the output sublane dim divisible by 8
FP_SUB_TILE = 1 << 13  # uint8 columns per grid step: bounds live VMEM temporaries


def _segment_fp_kernel(data_ref, powers_ref, out_ref):
    """One grid step = SEGS_PER_BLOCK segments x FP_SUB_TILE byte columns of
    the 8-lane polynomial hash, accumulated across the column grid axis.

    data_ref: [SEGS_PER_BLOCK, SUB] uint8 (row = segment, cols = sub-range j
    of the segment); powers_ref: [LANES, SUB] uint32 (r^(S-1-i) slice for
    sub-range j — shared by every segment row); out_ref:
    [SEGS_PER_BLOCK, LANES], revisited for every j (TPU grids iterate the
    minor axis innermost, so accumulation is race-free).

    Mosaic constraints shape the whole kernel: no dynamic sublane slicing
    (lane rows are selected with an iota mask + cross-sublane sum), no
    unsigned reductions (limb sums stay < 2^21 so int32 is exact), and all
    block dims static multiples of (8, 128). Lanes run under a fori_loop so
    only one [SEGS, SUB] term array is live at a time; the column grid axis
    keeps that array at most ~256 KiB regardless of segment size. The u32
    field arithmetic is the same limb math as the XLA kernel (ops/u32.py) —
    TPUs have no 64-bit integer lanes. Per-column partial lane sums are
    congruent mod M31 by distributivity, and fold31/addmod31 keep values
    canonical, so results are bit-identical to segment_fingerprint_device.
    """
    from skyplane_tpu.ops.fingerprint import N_LANES
    from skyplane_tpu.ops.u32 import M31, addmod31, fold31, mulmod31

    j = pl.program_id(1)
    data = data_ref[:, :].astype(jnp.uint32)  # [SEGS, SUB]
    # powers fit int31 so int32 masking/summing is exact (bit patterns equal)
    powers = powers_ref[:, :].astype(jnp.int32)  # [LANES, SUB]
    lane_row_iota = jax.lax.broadcasted_iota(jnp.int32, powers.shape, 0)
    out_col_iota = jax.lax.broadcasted_iota(jnp.int32, (SEGS_PER_BLOCK, N_LANES), 1)

    def lane_body(li, acc):
        # select powers row li without sublane slicing: mask + sublane sum
        row = jnp.sum(jnp.where(lane_row_iota == li, powers, 0), axis=0, keepdims=True)
        terms = mulmod31(data, row.astype(jnp.uint32))  # [SEGS, SUB] < 2^31
        lane_acc = jnp.zeros((SEGS_PER_BLOCK,), jnp.uint32)
        for k in range(4):
            limb = (terms >> np.uint32(8 * k)) & np.uint32(0xFF)
            s = jnp.sum(limb.astype(jnp.int32), axis=1)  # < SUB * 255 < 2^21
            lane_acc = addmod31(lane_acc, mulmod31(fold31(s.astype(jnp.uint32)), jnp.uint32((1 << (8 * k)) % M31)))
        return jnp.where(out_col_iota == li, lane_acc[:, None], acc)

    acc = jax.lax.fori_loop(0, N_LANES, lane_body, jnp.zeros((SEGS_PER_BLOCK, N_LANES), jnp.uint32))

    @pl.when(j == 0)
    def _init():
        out_ref[:, :] = jnp.zeros((SEGS_PER_BLOCK, N_LANES), jnp.uint32)

    out_ref[:, :] = addmod31(out_ref[:, :], acc)


@partial(jax.jit, static_argnames=("fp_seg_bytes", "interpret"))
def segment_fp_fixed_pallas(chunk: jax.Array, fp_seg_bytes: int, interpret: bool = False) -> jax.Array:
    """[N] uint8 -> [N/fp_seg_bytes, 8] uint32 lane values, one VMEM pass per
    segment (the XLA path materializes the [N]-sized term array to HBM per
    lane). Bit-identical to segment_fingerprint_device on fixed strides.

    The segment count is padded to a multiple of SEGS_PER_BLOCK with all-zero
    segments (sliced off the result) so the output tiling stays legal for any
    power-of-two chunk bucket down to one segment.
    """
    from skyplane_tpu.ops.fingerprint import N_LANES, _power_tables

    n = chunk.shape[0]
    if n % fp_seg_bytes:
        raise ValueError(f"N={n} must be a multiple of fp_seg_bytes={fp_seg_bytes}")
    if fp_seg_bytes > FP_MAX_TILE:
        raise ValueError(f"fp_seg_bytes={fp_seg_bytes} exceeds the limb-sum-safe tile {FP_MAX_TILE}")
    sub = min(fp_seg_bytes, FP_SUB_TILE)
    if fp_seg_bytes % sub:  # column grid would floor-truncate: tail bytes would silently never be hashed
        raise ValueError(f"fp_seg_bytes={fp_seg_bytes} must be a multiple of FP_SUB_TILE={FP_SUB_TILE} (or <= it)")
    n_segments = n // fp_seg_bytes
    pad_segs = -n_segments % SEGS_PER_BLOCK
    if pad_segs:
        chunk = jnp.concatenate([chunk, jnp.zeros((pad_segs * fp_seg_bytes,), jnp.uint8)])
    rows = chunk.reshape(n_segments + pad_segs, fp_seg_bytes)  # one row per segment
    # r^(S-1-i) for i in [0, S): the same slice serves every segment
    powers = jnp.asarray(np.ascontiguousarray(_power_tables()[:, :fp_seg_bytes][:, ::-1]))
    out = pl.pallas_call(
        _segment_fp_kernel,
        out_shape=jax.ShapeDtypeStruct((n_segments + pad_segs, N_LANES), jnp.uint32),
        grid=((n_segments + pad_segs) // SEGS_PER_BLOCK, fp_seg_bytes // sub),
        in_specs=[
            pl.BlockSpec((SEGS_PER_BLOCK, sub), lambda i, j: (i, j)),
            pl.BlockSpec((N_LANES, sub), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((SEGS_PER_BLOCK, N_LANES), lambda i, j: (i, 0)),
        interpret=interpret,
    )(rows, powers)
    return out[:n_segments] if pad_segs else out


def gear_hash_pallas(data_u8: jax.Array, interpret: bool = False) -> jax.Array:
    """Full gear hash with the table gather in XLA and the windowed sum in
    Pallas. Requires len % TILE == 0 (the data path pads chunks to power-of-
    two buckets >= 64 KiB, so this always holds there)."""
    table = jnp.asarray(GEAR_TABLE)
    g = table[data_u8.astype(jnp.int32)]
    return gear_windowed_sum_pallas(g, interpret=interpret)


# ---- on-device verdicts ----


def _verdict(check) -> dict:
    """{"compiled", "identical", "error"} for one kernel. A Mosaic refusal is
    the fact being reported here, so the compiler's message is kept."""
    try:
        return {"compiled": True, "identical": bool(check()), "error": ""}
    except Exception as e:  # noqa: BLE001 — the verdict carries the message
        return {"compiled": False, "identical": False, "error": f"{type(e).__name__}: {e}"[:2000]}


def validate_on_device(seed: int = 7) -> dict:
    """Compile each kernel WITHOUT ``interpret`` on the default backend, at
    the production tile and (gear) under ``vmap`` as fused_cdc's call A runs
    it, and compare with the XLA path bit for bit. Returns
    ``{"gear": {...}, "fp": {...}}`` (see :func:`_verdict`)."""
    from skyplane_tpu.ops.fingerprint import segment_fingerprint_device
    from skyplane_tpu.ops.gear import _windowed_sum_doubling

    rng = np.random.default_rng(seed)

    def gear() -> bool:
        g = jnp.asarray(rng.integers(0, 2**32, size=(2, 4 * TILE), dtype=np.uint32))
        want = np.asarray(jax.vmap(_windowed_sum_doubling)(g))
        flat = np.asarray(gear_windowed_sum_pallas(g[0]))
        batched = np.asarray(jax.vmap(gear_windowed_sum_pallas)(g))
        return np.array_equal(want[0], flat) and np.array_equal(want, batched)

    def fp() -> bool:
        # FP_MAX_TILE is datapath_step's default fp_seg_bytes: a smaller tile
        # would validate a different Mosaic lowering than the one that runs
        seg = FP_MAX_TILE
        data = jnp.asarray(rng.integers(0, 256, size=4 * seg, dtype=np.uint8))
        pos = np.arange(4 * seg, dtype=np.int32)
        want = segment_fingerprint_device(data, jnp.asarray(pos // seg), jnp.asarray(seg - 1 - pos % seg), n_segments=4)
        return np.array_equal(np.asarray(want), np.asarray(segment_fp_fixed_pallas(data, seg)))

    return {"gear": _verdict(gear), "fp": _verdict(fp)}
