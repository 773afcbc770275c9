import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from skyplane_tpu.ops import u32
from skyplane_tpu.ops.gear import GEAR_TABLE, gear_hash, gear_hash_np, gear_values, boundary_candidate_mask
from skyplane_tpu.ops import blockpack
from skyplane_tpu.ops.cdc import CDCParams, cdc_segment_ends, select_boundaries
from skyplane_tpu.ops.fingerprint import (
    segment_fingerprint_cumsum,
    segment_fingerprint_np,
    finalize_fingerprint,
)

rng = np.random.default_rng(42)


def cumsum_lanes(data: np.ndarray, ends, n_segments: int) -> np.ndarray:
    """Call B's formulation over one row: slot j is [ends[j-1], ends[j]), the
    slots past the last end are empty (start == end == len(data))."""
    seg_ends = np.full(n_segments, len(data), np.int32)
    seg_ends[: len(ends)] = ends
    seg_starts = np.concatenate([[0], seg_ends[:-1]]).astype(np.int32)
    lanes = segment_fingerprint_cumsum(jnp.asarray(data), jnp.asarray(seg_starts), jnp.asarray(seg_ends), n_segments=n_segments)
    return np.asarray(lanes)


class TestU32:
    def test_mulmod_matches_python_ints(self):
        a = rng.integers(0, u32.M31, size=1000, dtype=np.uint32)
        b = rng.integers(0, u32.M31, size=1000, dtype=np.uint32)
        got = np.asarray(u32.mulmod31(jnp.asarray(a), jnp.asarray(b)))
        want = (a.astype(np.uint64) * b.astype(np.uint64)) % np.uint64(u32.M31)
        np.testing.assert_array_equal(got.astype(np.uint64), want)

    def test_mulmod_edge_cases(self):
        edge = np.array([0, 1, 2, u32.M31 - 1, u32.M31 - 2, 0x7FFF, 0x8000, 0xFFFF, 0x10000], dtype=np.uint32)
        aa, bb = np.meshgrid(edge, edge)
        got = np.asarray(u32.mulmod31(jnp.asarray(aa.ravel()), jnp.asarray(bb.ravel())))
        want = (aa.ravel().astype(np.uint64) * bb.ravel().astype(np.uint64)) % np.uint64(u32.M31)
        np.testing.assert_array_equal(got.astype(np.uint64), want)

    def test_addmod(self):
        a = rng.integers(0, u32.M31, size=100, dtype=np.uint32)
        b = rng.integers(0, u32.M31, size=100, dtype=np.uint32)
        got = np.asarray(u32.addmod31(jnp.asarray(a), jnp.asarray(b)))
        want = (a.astype(np.uint64) + b.astype(np.uint64)) % np.uint64(u32.M31)
        np.testing.assert_array_equal(got.astype(np.uint64), want)

    def test_pow_table(self):
        t = u32.powmod31_table(12345, 100)
        acc = 1
        for i in range(100):
            assert t[i] == acc
            acc = (acc * 12345) % u32.M31


class TestGear:
    def test_parallel_matches_sequential(self):
        data = rng.integers(0, 256, size=4096, dtype=np.uint8)
        got = np.asarray(gear_hash(jnp.asarray(data)))
        want = gear_hash_np(data)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize(
        "shape",
        [(256,), (2, 1 << 16), (1000,), (7,)],
        ids=["every_byte_value", "batch_2x64KiB", "not_a_multiple_of_the_view", "odd_length"],
    )
    def test_gear_values_equal_the_table(self, shape):
        """The select tree is the table, bit for bit, whatever the view it runs over."""
        data = np.arange(256, dtype=np.uint8) if shape == (256,) else rng.integers(0, 256, size=shape, dtype=np.uint8)
        fn = gear_values if data.ndim == 1 else jax.vmap(gear_values)
        got = np.asarray(jax.jit(fn)(jnp.asarray(data)))
        assert got.dtype == np.uint32
        np.testing.assert_array_equal(got, GEAR_TABLE[data])

    def test_candidate_density(self):
        # expected candidate rate with k mask bits is ~2^-k
        data = rng.integers(0, 256, size=1 << 20, dtype=np.uint8)
        mask = np.asarray(boundary_candidate_mask(gear_hash(jnp.asarray(data)), 10))
        rate = mask.mean()
        assert 0.5 * 2**-10 < rate < 2 * 2**-10


class TestBlockpack:
    @pytest.mark.parametrize("case", ["zeros", "const", "random", "mixed", "text"])
    def test_roundtrip(self, case):
        n = 8192
        if case == "zeros":
            data = bytes(n)
        elif case == "const":
            data = b"\xab" * n
        elif case == "random":
            data = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
        elif case == "mixed":
            parts = [bytes(512), b"\x07" * 512, rng.integers(0, 256, 512, dtype=np.uint8).tobytes()] * 5
            data = b"".join(parts)
        else:
            data = (b"the quick brown fox jumps over the lazy dog\n" * 200)[:n]
        enc = blockpack.encode_container(data)
        assert blockpack.decode_container(enc) == data

    def test_unaligned_length(self):
        data = rng.integers(0, 256, size=1000, dtype=np.uint8).tobytes() + bytes(3000) + b"xyz"
        enc = blockpack.encode_container(data, block_bytes=256)
        assert blockpack.decode_container(enc) == data

    def test_sparse_ratio(self):
        # 90% zero blocks -> container should be ~10x smaller
        blocks = []
        for i in range(100):
            blocks.append(rng.integers(0, 256, 512, dtype=np.uint8).tobytes() if i % 10 == 0 else bytes(512))
        data = b"".join(blocks)
        enc = blockpack.encode_container(data)
        assert len(enc) < len(data) * 0.15

    def test_incompressible_overhead(self):
        data = rng.integers(0, 256, size=1 << 16, dtype=np.uint8).tobytes()
        enc = blockpack.encode_container(data)
        assert len(enc) < len(data) * 1.01  # tags add ~0.05%

    def test_empty(self):
        assert blockpack.decode_container(blockpack.encode_container(b"")) == b""

    def test_bad_magic(self):
        from skyplane_tpu.exceptions import CodecException

        with pytest.raises(CodecException):
            blockpack.decode_container(b"\x00" * 64)


class TestCDC:
    def test_boundaries_deterministic_and_bounded(self):
        params = CDCParams(min_bytes=256, avg_bytes=1024, max_bytes=4096)
        data = rng.integers(0, 256, size=1 << 18, dtype=np.uint8).tobytes()
        ends = cdc_segment_ends(data, params)
        ends2 = cdc_segment_ends(data, params)
        np.testing.assert_array_equal(ends, ends2)
        assert ends[-1] == len(data)
        lens = np.diff(np.concatenate([[0], ends]))
        assert (lens <= params.max_bytes).all()
        # all but the final segment respect min
        assert (lens[:-1] >= params.min_bytes).all()
        # average in a sane band around target
        assert params.min_bytes < lens.mean() < 4 * params.avg_bytes

    def test_shift_resync(self):
        # inserting bytes at the front should re-sync boundaries (content-defined)
        params = CDCParams(min_bytes=256, avg_bytes=1024, max_bytes=8192)
        base = rng.integers(0, 256, size=1 << 17, dtype=np.uint8).tobytes()
        shifted = b"PREFIX!!" + base
        e1 = set(cdc_segment_ends(base, params).tolist())
        e2 = set((np.asarray(cdc_segment_ends(shifted, params)) - 8).tolist())
        # most cut points should coincide after the offset correction
        common = len(e1 & e2)
        assert common / max(len(e1), 1) > 0.75

    def test_select_boundaries_max_enforced_without_candidates(self):
        params = CDCParams(min_bytes=10, avg_bytes=20, max_bytes=100)
        ends = select_boundaries(np.array([], dtype=np.int64), 450, params)
        np.testing.assert_array_equal(ends, [100, 200, 300, 400, 450])

    def test_empty_input(self):
        assert cdc_segment_ends(b"").tolist() == [0]


class TestFingerprint:
    def test_device_matches_numpy_reference(self):
        data = rng.integers(0, 256, size=2048, dtype=np.uint8)
        ends = np.array([100, 512, 1000, 2048])
        got = cumsum_lanes(data, ends, 4)
        want = segment_fingerprint_np(data, ends)
        np.testing.assert_array_equal(got, want)

    def test_identical_segments_same_fp_different_segments_differ(self):
        seg = rng.integers(0, 256, size=500, dtype=np.uint8)
        seg_mut = ((seg.astype(np.int32) + 1) % 256).astype(np.uint8)
        data = np.concatenate([seg, seg, seg_mut])
        ends = np.array([500, 1000, 1500])
        fps = cumsum_lanes(data, ends, 3)
        assert (fps[0] == fps[1]).all()
        assert not (fps[0] == fps[2]).all()
        f0 = finalize_fingerprint(fps[0], 500)
        f1 = finalize_fingerprint(fps[1], 500)
        f2 = finalize_fingerprint(fps[2], 500)
        assert f0 == f1 and f0 != f2 and len(f0) == 32

    def test_padding_slots_do_not_affect_real_segments(self):
        data = rng.integers(0, 256, size=300, dtype=np.uint8)
        ends = np.array([300])
        a = cumsum_lanes(data, ends, 1)
        b = cumsum_lanes(data, ends, 8)
        np.testing.assert_array_equal(a[0], b[0])
        assert not b[1:].any(), "an empty slot has lanes 0"
