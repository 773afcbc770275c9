"""Fused single-dispatch CDC+fingerprint kernel: bit-exact vs the host path
for all inputs (including the bounded-candidate overflow fallback)."""

from functools import partial

import numpy as np
import pytest

from skyplane_tpu.ops.cdc import CDCParams, cdc_segment_ends
from skyplane_tpu.ops.fingerprint import (
    MAX_SEGMENT_BYTES,
    segment_fingerprint_cumsum,
    segment_fingerprint_np,
    segment_fingerprints_host_batch,
)
from skyplane_tpu.ops.fused_cdc import FusedCDCFP, candidate_cap

rng = np.random.default_rng(31)

PARAMS = CDCParams(min_bytes=1024, avg_bytes=4096, max_bytes=16384)


def _pad(arr, bucket=None):
    b = bucket or (1 << 16)
    while b < len(arr):
        b <<= 1
    return np.concatenate([arr, np.zeros(b - len(arr), np.uint8)]) if len(arr) != b else arr


def _expected(arr, params=PARAMS):
    ends = cdc_segment_ends(arr, params)
    return ends, segment_fingerprints_host_batch(arr, ends)


def _check(chunks, params=PARAMS):
    fused = FusedCDCFP(params)
    padded = [_pad(c) for c in chunks]
    bucket = max(len(p) for p in padded)
    batch = np.stack([_pad(p, bucket) for p in padded])
    results = fused(batch, [len(c) for c in chunks])
    for c, (ends, fps) in zip(chunks, results):
        want_ends, want_fps = _expected(c, params)
        np.testing.assert_array_equal(ends, want_ends)
        assert fps == want_fps


class TestFusedMatchesHost:
    def test_random_chunks_various_lengths(self):
        _check([rng.integers(0, 256, n, dtype=np.uint8) for n in (1, 100, 4096, 65536, 100_000, 1 << 17)])

    def test_structured_chunks(self):
        pat = rng.integers(0, 256, 4096, dtype=np.uint8)
        tiled = np.tile(pat, 40)[: 150_000].copy()
        half_zero = np.concatenate([np.zeros(60_000, np.uint8), rng.integers(0, 256, 70_000, dtype=np.uint8)])
        all_zero = np.zeros(1 << 16, np.uint8)
        _check([tiled, half_zero, all_zero])

    def test_batch_with_zero_pad_rows(self):
        """Rows with n=0 (batch padding) must not crash or corrupt neighbors."""
        fused = FusedCDCFP(PARAMS)
        c = rng.integers(0, 256, 50_000, dtype=np.uint8)
        batch = np.stack([_pad(c), np.zeros(1 << 16, np.uint8)])
        results = fused(batch, [len(c), 0])
        want_ends, want_fps = _expected(c)
        np.testing.assert_array_equal(results[0][0], want_ends)
        assert results[0][1] == want_fps

    def test_overflow_falls_back_exactly(self, monkeypatch):
        """Candidate counts above the compaction capacity must route the row
        through the exact host fallback. The natural cap carries 8x headroom,
        so force overflow by shrinking it and verify (a) the device list
        really truncates (count > cap) and (b) results stay bit-exact."""
        import skyplane_tpu.ops.fused_cdc as fused_mod

        params = CDCParams(min_bytes=64, avg_bytes=256, max_bytes=1024)
        n = 1 << 16
        chunk = rng.integers(0, 256, n, dtype=np.uint8)
        # ~n/256 = 256 expected candidates; cap of 16 guarantees overflow
        monkeypatch.setattr(fused_mod, "candidate_cap", lambda bucket, params=None: 16)
        fused = fused_mod.FusedCDCFP(params)
        called = {}
        real_fallback = fused_mod._host_exact
        monkeypatch.setattr(fused_mod, "_host_exact", lambda arr, p: called.setdefault("x", real_fallback(arr, p)))
        (ends, fps), = fused(chunk[None, :], [n])
        assert "x" in called, "overflow did not trigger the host fallback"
        want_ends, want_fps = _expected(chunk, params)
        np.testing.assert_array_equal(ends, want_ends)
        assert fps == want_fps

    def test_an_overflowing_row_is_counted_once_and_still_exact(self, monkeypatch):
        """``overflow_rows`` (PR 36): a row whose candidates pass the cap is made
        again on the host and says so in the runner's counters, where nothing
        else would; the row beside it, under the cap, is not counted."""
        import skyplane_tpu.ops.fused_cdc as fused_mod
        from skyplane_tpu.ops.batch_runner import DeviceBatchRunner

        params = CDCParams(min_bytes=64, avg_bytes=256, max_bytes=1024)
        n = 1 << 16
        crowded = rng.integers(0, 256, n, dtype=np.uint8)  # ~n/256 = 256 candidates
        sparse = np.zeros(n, np.uint8)  # no candidate in a run of zeros: every cut forced
        monkeypatch.setattr(fused_mod, "candidate_cap", lambda bucket, params=None: 64)
        runner = DeviceBatchRunner(cdc_params=params, max_batch=2, max_wait_ms=2.0)
        assert runner.counters()["overflow_rows"] == 0
        ends, fps = runner.cdc_and_fps(sparse)
        assert runner.counters()["overflow_rows"] == 0
        want_ends, want_fps = _expected(sparse, params)
        np.testing.assert_array_equal(ends, want_ends)
        assert fps == want_fps
        ends, fps = runner.cdc_and_fps(crowded)
        assert runner.counters()["overflow_rows"] == 1
        want_ends, want_fps = _expected(crowded, params)
        np.testing.assert_array_equal(ends, want_ends)
        assert fps == want_fps


def test_fuzz_params_and_lengths():
    """Seeded sweep over CDC params x lengths x content shapes: the fused
    path must be bit-identical to the host path everywhere."""
    r = np.random.default_rng(1234)
    param_sets = [
        CDCParams(min_bytes=512, avg_bytes=2048, max_bytes=8192),
        CDCParams(min_bytes=4096, avg_bytes=16384, max_bytes=65536),
        CDCParams(min_bytes=1024, avg_bytes=1024, max_bytes=4096),  # min == avg
        CDCParams(min_bytes=2048, avg_bytes=8192, max_bytes=8192),  # avg == max
    ]
    for params in param_sets:
        fused = FusedCDCFP(params)
        lens = [int(x) for x in r.integers(1, 1 << 17, 4)] + [1 << 16, 5]
        chunks = []
        for i, n in enumerate(lens):
            if i % 3 == 0:
                c = r.integers(0, 256, n, dtype=np.uint8)
            elif i % 3 == 1:
                pat = r.integers(0, 256, max(1, n // 7), dtype=np.uint8)
                c = np.tile(pat, 8)[:n].copy()
            else:
                c = np.zeros(n, np.uint8)
                c[:: max(1, n // 50)] = r.integers(1, 256)
            chunks.append(c)
        bucket = 1 << 17
        batch = np.stack([_pad(c, bucket) for c in chunks])
        results = fused(batch, [len(c) for c in chunks])
        for c, (ends, fps) in zip(chunks, results):
            want_ends, want_fps = _expected(c, params)
            np.testing.assert_array_equal(ends, want_ends)
            assert fps == want_fps


def test_all_fallback_batch_releases_pooled_scratch(monkeypatch):
    """When EVERY row of a batch overflows the candidate cap, lanes() is
    never demanded by result_row — the all-fallback path must still release
    the pooled ends scratch (and consume the enqueued fingerprint readback)
    so BufferPool._outstanding returns to zero (ROADMAP open item from PR 3)."""
    import skyplane_tpu.ops.fused_cdc as fused_mod
    from skyplane_tpu.ops.bufpool import BufferPool

    params = CDCParams(min_bytes=64, avg_bytes=256, max_bytes=1024)
    n = 1 << 16
    # ~n/256 = 256 expected candidates per row; cap of 16 guarantees overflow
    monkeypatch.setattr(fused_mod, "candidate_cap", lambda bucket, params=None: 16)
    pool = BufferPool()
    fused = fused_mod.FusedCDCFP(params, pool=pool)
    batch = rng.integers(0, 256, (2, n), dtype=np.uint8)  # pathological density corpus
    pending = fused.dispatch(batch, [n, n])
    assert all(f is not None for f in pending.fallback), "scenario must be all-fallback"
    for i in range(2):
        ends, fps = pending.result_row(i)
        want_ends, want_fps = _expected(batch[i], params)
        np.testing.assert_array_equal(ends, want_ends)
        assert fps == want_fps
    counters = pool.counters()
    assert counters["pool_outstanding"] == 0, "all-fallback batch stranded the pooled ends scratch"
    assert counters["pool_recycled"] >= 1


def test_mixed_fallback_batch_releases_scratch_via_lanes(monkeypatch):
    """A batch mixing overflow and normal rows releases scratch through the
    normal lanes() path — the all-fallback release must not double-release."""
    import skyplane_tpu.ops.fused_cdc as fused_mod
    from skyplane_tpu.ops.bufpool import BufferPool

    params = CDCParams(min_bytes=64, avg_bytes=256, max_bytes=1024)
    n = 1 << 16
    # cap of 16: row 0 (random content, ~256 candidates) overflows; row 1
    # (all zeros -> few/no gear candidates) stays on the device path
    monkeypatch.setattr(fused_mod, "candidate_cap", lambda bucket, params_=None: 16)
    pool = BufferPool()
    fused = fused_mod.FusedCDCFP(params, pool=pool)
    batch = np.stack([rng.integers(0, 256, n, dtype=np.uint8), np.zeros(n, np.uint8)])
    pending = fused.dispatch(batch, [n, n])
    assert pending.fallback[0] is not None and pending.fallback[1] is None, "scenario must be mixed"
    for i in range(2):
        ends, fps = pending.result_row(i)
        want_ends, want_fps = _expected(batch[i], params)
        np.testing.assert_array_equal(ends, want_ends)
        assert fps == want_fps
    assert pool.counters()["pool_outstanding"] == 0


# ---- call A's compaction: the first `cap` set positions and the count ----
#
# `_first_candidates` against np.flatnonzero of a mask made on the host: the
# contract any formulation of the compaction has to keep.

COMPACT_BUCKET = 1 << 16
COMPACT_CAP = 64


def _want_candidates(mask, cap):
    pos = np.flatnonzero(mask)
    out = np.full(cap + 1, len(mask), np.int32)
    out[: min(cap, len(pos))] = pos[:cap]
    out[-1] = len(pos)
    return out


def _mask_with(positions, n=COMPACT_BUCKET):
    mask = np.zeros(n, bool)
    mask[np.asarray(positions, dtype=np.int64)] = True
    return mask


def _scattered(count, seed):
    return np.random.default_rng(seed).choice(COMPACT_BUCKET, count, replace=False)


COMPACTION_CASES = {  # name: (mask, cap)
    "no_candidate": (_mask_with([]), COMPACT_CAP),
    "exactly_cap": (_mask_with(_scattered(COMPACT_CAP, 1)), COMPACT_CAP),
    "cap_plus_one_overflows": (_mask_with(_scattered(COMPACT_CAP + 1, 2)), COMPACT_CAP),
    "first_and_last_position": (_mask_with([0, COMPACT_BUCKET - 1]), COMPACT_CAP),
    "one_candidate": (_mask_with([5000]), COMPACT_CAP),
    "a_run_longer_than_cap": (_mask_with(np.arange(2000, 2000 + 3 * 2048 + 7)), COMPACT_CAP),
    "a_run_that_fills_most_of_a_large_cap": (_mask_with(np.arange(2000, 2000 + 3 * 2048 + 7)), 8192),
    "every_position": (np.ones(COMPACT_BUCKET, bool), COMPACT_CAP),
    "two_rows_that_differ": (np.stack([_mask_with(_scattered(40, 3)), _mask_with(_scattered(300, 4))]), COMPACT_CAP),
    # the edges of a blocked prefix count (fused_cdc._COUNT_BLOCK = 2048 bytes a block)
    "last_column_of_a_block_then_first_of_the_next": (_mask_with([2047, 2048, 4095, 4096, 6143]), COMPACT_CAP),
    "a_full_block_then_empty_blocks": (_mask_with(np.arange(2048, 4096)), 4096),
    "a_full_block_overflows_a_small_cap": (_mask_with(np.arange(2048, 4096)), COMPACT_CAP),
    "cap_th_entry_at_the_last_position": (_mask_with([*range(0, 1000 * (COMPACT_CAP - 1), 1000), COMPACT_BUCKET - 1]), COMPACT_CAP),
    "bucket_a_block_does_not_divide": (_mask_with([0, 7, 8, 15, 16, 1500, 2047, 2048, 2992, 2999], n=3000), COMPACT_CAP),
    "bucket_a_block_does_not_divide_overflows": (np.ones(3000, bool), COMPACT_CAP),
    "odd_bucket": (_mask_with([0, 1, 500, 1000], n=1001), COMPACT_CAP),
    "bucket_smaller_than_a_block": (_mask_with([0, 511, 512, 1023], n=1024), COMPACT_CAP),
    "four_rows_of_0_1_cap_and_cap_plus_1_entries": (
        np.stack([_mask_with(_scattered(c, 10 + c)) for c in (0, 1, COMPACT_CAP, COMPACT_CAP + 1)]),
        COMPACT_CAP,
    ),
}


@pytest.mark.parametrize("case", sorted(COMPACTION_CASES))
def test_compaction_matches_flatnonzero(case):
    import jax

    from skyplane_tpu.ops.fused_cdc import _first_candidates

    mask, cap = COMPACTION_CASES[case]
    fn = partial(_first_candidates, cap=cap)
    got = np.asarray(jax.jit(fn if mask.ndim == 1 else jax.vmap(fn))(mask))
    want = _want_candidates(mask, cap) if mask.ndim == 1 else np.stack([_want_candidates(m, cap) for m in mask])
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("cut", ["before_the_first_candidate", "mid_row", "full_row"])
def test_candidates_beyond_the_row_length_are_dropped(cut):
    """Call A on real bytes: what the gear mask sets in the padded tail of a
    row shorter than its bucket is not a candidate, in either row of a batch."""
    import skyplane_tpu.ops.fused_cdc as fused_mod

    rows = np.stack([_random(COMPACT_BUCKET, 5), _random(COMPACT_BUCKET, 6)])  # the tail is NOT zeroed: it has candidates
    positions = [_candidate_positions(r, PARAMS) for r in rows]
    assert all(len(p) > 4 for p in positions)
    n1 = {"before_the_first_candidate": int(positions[1][0]), "mid_row": int(positions[1][len(positions[1]) // 2]) + 1, "full_row": COMPACT_BUCKET}[cut]
    lens = np.array([COMPACT_BUCKET, n1], np.int32)
    cap = candidate_cap(COMPACT_BUCKET, PARAMS)
    got = np.asarray(fused_mod._candidates_impl(rows, lens, mask_bits=PARAMS.mask_bits, cap=cap))
    for row, pos, n in zip(got, positions, lens):
        mask = _mask_with(pos[pos < n])
        np.testing.assert_array_equal(row, _want_candidates(mask, cap))
    if cut == "before_the_first_candidate":
        assert got[1, -1] == 0 and (got[1, :-1] == COMPACT_BUCKET).all()


# ---- rows of several fingerprint blocks (bucket >= 4 x the period T) ----
#
# The buckets above (64-128 KiB) are all smaller than MAX_SEGMENT_BYTES, so
# call B sees one block there. These rows are 1 MiB at the shipped CDC
# parameters: T = 256 KiB, four blocks, and segments that meet the block
# edges in every way a segment can.

SHIPPED = CDCParams()  # 4 / 16 / 64 KiB
BIG = 1 << 20
EDGE = MAX_SEGMENT_BYTES  # the first block edge of a BIG row


def _random(n, seed=77):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)


def _candidate_positions(arr, params):
    from skyplane_tpu.ops.host_fallback import boundary_candidates_host, gear_hash_host

    return np.flatnonzero(boundary_candidates_host(gear_hash_host(arr), params.mask_bits))


def _forced_cuts_only(n, params, seed, keep_first=False):
    """Random bytes with every natural cut candidate removed (one byte at the
    candidate bumped until the mask misses), so CDC cuts at max_bytes strides;
    ``keep_first`` leaves the first candidate that can end a segment, which
    shifts every later stride off the block edges."""
    arr = _random(n, seed)
    kept = None
    for _ in range(64):
        cands = _candidate_positions(arr, params)
        if keep_first and kept is None:
            kept = next(int(p) for p in cands if params.min_bytes <= p + 1 < params.max_bytes)
        cands = cands[cands != kept] if kept is not None else cands
        if len(cands) == 0:
            break
        arr[cands] += 1
    else:
        raise AssertionError("could not clear the cut candidates")
    return arr


MULTI_BLOCK_CASES = {
    "random_full_row": lambda: [_random(BIG)],
    "segment_ends_on_block_edge": lambda: [_forced_cuts_only(BIG, SHIPPED, seed=5)],
    "segment_starts_on_block_edge": lambda: [_forced_cuts_only(2 * EDGE + 5000, SHIPPED, seed=6)],
    "max_segment_straddles_block_edge": lambda: [_forced_cuts_only(BIG, SHIPPED, seed=7, keep_first=True)],
    "garbage_slot_spans_blocks": lambda: [_random(100_000)],
    "batch_with_zero_length_pad_row": lambda: [_random(EDGE + 70_000), np.zeros(0, np.uint8)],
}


@pytest.mark.parametrize("name", list(MULTI_BLOCK_CASES))
def test_rows_of_several_blocks_match_host(name):
    chunks = MULTI_BLOCK_CASES[name]()
    ends = [cdc_segment_ends(c, SHIPPED) for c in chunks if len(c)]
    starts = [np.concatenate([[0], e[:-1]]) for e in ends]
    # each case is what its name says, by the host's own cuts
    if name == "segment_ends_on_block_edge":
        assert {EDGE, 2 * EDGE, 3 * EDGE} <= set(ends[0].tolist())
    elif name == "segment_starts_on_block_edge":
        assert starts[0][-1] == 2 * EDGE and ends[0][-1] == 2 * EDGE + 5000
    elif name == "max_segment_straddles_block_edge":
        across = (starts[0] < EDGE) & (ends[0] > EDGE)
        assert across.any() and (ends[0] - starts[0])[across][0] == SHIPPED.max_bytes
    elif name == "random_full_row":
        assert ((starts[0] // EDGE) != ((ends[0] - 1) // EDGE)).any(), "no segment crosses a block edge"
    fused = FusedCDCFP(SHIPPED)
    batch = np.stack([_pad(c, BIG) for c in chunks])
    results = fused(batch, [len(c) for c in chunks])
    for c, (got_ends, got_fps) in zip(chunks, results):
        if not len(c):
            continue  # the pad row: nothing to compare, it must only not disturb its neighbour
        want_ends, want_fps = _expected(c, SHIPPED)
        np.testing.assert_array_equal(got_ends, want_ends)
        assert got_fps == want_fps


def test_segment_fingerprint_cumsum_hand_placed_ends():
    """The device formulation alone, slots placed by hand around two block
    edges of a three-block row, against the per-byte python reference."""
    import jax.numpy as jnp

    t = MAX_SEGMENT_BYTES
    n = 3 * t
    data = np.zeros(n, np.uint8)
    r = np.random.default_rng(9)
    ends = np.array(
        [
            t - 9000,  # zero bytes only, inside block 0
            t - 3000,  # inside block 0
            t,  # ends on the edge
            t + 1,  # starts on the edge, one byte
            2 * t - 700,  # zero tail but a live head: still inside block 1
            2 * t - 700,  # an empty slot
            2 * t + 800,  # crosses the second edge
            n,  # the rest: zeros over a whole block and more
            n,  # an empty slot at the row's end
        ],
        np.int64,
    )
    starts = np.concatenate([[0], ends[:-1]])
    live = [(t - 9000, t + 1), (2 * t - 700, 2 * t + 800)]  # windows of slot-aligned random bytes
    data[t + 1 : t + 2000] = r.integers(1, 256, 1999, dtype=np.uint8)  # the head of slot 4
    for a, b in live:
        data[a:b] = r.integers(0, 256, b - a, dtype=np.uint8)
    got = np.asarray(
        segment_fingerprint_cumsum(
            jnp.asarray(data), jnp.asarray(starts, jnp.int32), jnp.asarray(ends, jnp.int32), n_segments=len(ends)
        )
    )
    want = np.zeros((len(ends), 8), np.uint32)
    for a, b in live:
        inside = (starts >= a) & (ends <= b) & (ends > starts)
        want[inside] = segment_fingerprint_np(data[a:b], ends[inside] - a)
    want[4] = segment_fingerprint_np(data[t + 1 : 2 * t - 700], [t - 701])[0]
    np.testing.assert_array_equal(got, want)
    assert want[[1, 2, 3, 4, 6]].all() and not want[[0, 5, 7, 8]].any()


def test_graft_entry_is_the_two_live_programs_and_matches_the_host():
    """``__graft_entry__.entry()`` jits, and what it returns is call A's
    packed candidates and call B's lanes: boundaries selected from the one
    and digests finalized from the other equal the host path on its batch."""
    import importlib.util
    from pathlib import Path

    import jax

    from skyplane_tpu.ops.cdc import select_boundaries
    from skyplane_tpu.ops.fused_cdc import finalize_row

    spec = importlib.util.spec_from_file_location("graft_entry", Path(__file__).resolve().parents[2] / "__graft_entry__.py")
    graft = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(graft)
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    packed, lanes = np.asarray(out["candidates"]), np.asarray(out["fp_lanes"])
    batch, lens, ends_slots = (np.asarray(a) for a in args)
    params = CDCParams()
    cap = candidate_cap(batch.shape[1], params)
    assert packed.shape == (2, cap + 1) and lanes.shape == ends_slots.shape + (8,)
    for i, n in enumerate(lens.tolist()):
        want_ends, want_fps = _expected(batch[i, :n], params)
        assert 0 < packed[i, cap] <= cap
        ends = select_boundaries(packed[i, : packed[i, cap]].astype(np.int64), n, params)
        np.testing.assert_array_equal(ends, want_ends)
        np.testing.assert_array_equal(ends_slots[i, : len(ends)], want_ends)
        assert finalize_row(lanes[i], ends) == want_fps
