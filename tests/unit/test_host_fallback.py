"""The numpy forms of the host kernels: bit-identical to the device gear hash,
to the native library, and to what each input gives by construction."""

import numpy as np
import pytest

import jax.numpy as jnp

from skyplane_tpu.ops import blockpack
from skyplane_tpu.ops.gear import gear_hash
from skyplane_tpu.ops.host_fallback import (
    blockpack_decode_host,
    blockpack_encode_host,
    boundary_candidates_host,
    gear_hash_host,
)

rng = np.random.default_rng(77)


def test_gear_host_matches_device():
    data = rng.integers(0, 256, 100_000, dtype=np.uint8)
    np.testing.assert_array_equal(gear_hash_host(data), np.asarray(gear_hash(jnp.asarray(data))))


def test_boundary_candidates_host():
    data = rng.integers(0, 256, 1 << 18, dtype=np.uint8)
    h = gear_hash_host(data)
    mask = boundary_candidates_host(h, 10)
    rate = mask.mean()
    assert 0.5 * 2**-10 < rate < 2 * 2**-10


BLOCK = 512
CASES = ["zeros", "const", "random", "mixed"]


def _blockpack_case(case):
    """(data, tags, literals): the input and what its blocks are by construction."""
    from skyplane_tpu.ops.blockpack import TAG_CONST, TAG_LITERAL, TAG_ZERO

    nb = 16
    if case == "zeros":
        return np.zeros(nb * BLOCK, np.uint8), [TAG_ZERO] * nb, np.empty(0, np.uint8)
    if case == "const":
        return np.full(nb * BLOCK, 0xAB, np.uint8), [TAG_CONST] * nb, np.full(nb, 0xAB, np.uint8)
    if case == "random":
        data = rng.integers(0, 256, nb * BLOCK, dtype=np.uint8)
        return data, [TAG_LITERAL] * nb, data
    groups = [(np.zeros(BLOCK, np.uint8), np.full(BLOCK, 7, np.uint8), rng.integers(0, 256, BLOCK, dtype=np.uint8)) for _ in range(5)]
    data = np.concatenate([b for g in groups for b in g])
    literals = np.concatenate([np.concatenate([[np.uint8(7)], g[2]]) for g in groups])
    return data, [TAG_ZERO, TAG_CONST, TAG_LITERAL] * 5, literals


@pytest.mark.parametrize("case", CASES)
def test_blockpack_host_tags_and_literals_by_construction(case):
    data, want_tags, want_lit = _blockpack_case(case)
    tags, lit, n_lit = blockpack_encode_host(data, BLOCK)
    np.testing.assert_array_equal(tags, want_tags)
    assert n_lit == len(want_lit) == len(lit)
    np.testing.assert_array_equal(lit, want_lit)
    # host decode inverts host encode
    np.testing.assert_array_equal(blockpack_decode_host(tags, lit, BLOCK), data)


@pytest.mark.parametrize("case", CASES)
def test_container_without_native_library_is_the_numpy_form_on_any_backend(case, monkeypatch):
    """A host whose native library is missing runs the numpy form whatever
    jax runs on, an accelerator included, and writes the container the native
    path writes."""
    import skyplane_tpu.ops.backend as backend
    import skyplane_tpu.ops.host_fallback as host_fallback
    from skyplane_tpu.native import datapath as native_dp

    data = _blockpack_case(case)[0].tobytes() + b"tail"  # not a multiple of the block
    by_native = blockpack.encode_container(data) if native_dp.available() else None
    calls = []

    def recorded(name):
        fn = getattr(host_fallback, name)

        def call(*args):
            calls.append(name)
            return fn(*args)

        return call

    for name in ("blockpack_encode_host", "blockpack_decode_host"):
        monkeypatch.setattr(host_fallback, name, recorded(name))
    monkeypatch.setattr(native_dp, "available", lambda: False)
    monkeypatch.setattr(backend, "_is_accelerator", True)
    container = blockpack.encode_container(data)
    assert blockpack.decode_container(container) == data
    assert calls == ["blockpack_encode_host", "blockpack_decode_host"]
    if by_native is not None:  # no g++ here: the round trip held, there is no native container to compare
        assert container == by_native


def test_container_roundtrip_mixed_content():
    data = rng.integers(0, 256, 100_000, dtype=np.uint8).tobytes() + bytes(50_000)
    assert blockpack.decode_container(blockpack.encode_container(data)) == data


def test_batch_host_fingerprints_match_per_segment():
    from skyplane_tpu.ops.fingerprint import segment_fingerprint_host, segment_fingerprints_host_batch

    data = rng.integers(0, 256, 20_000, dtype=np.uint8)
    ends = np.array([5000, 5017, 12_000, 20_000])
    batch = segment_fingerprints_host_batch(data, ends)
    start = 0
    for i, e in enumerate(ends):
        assert batch[i] == segment_fingerprint_host(data[start:e].tobytes())
        start = int(e)


def test_accelerator_path_matches_host_path(monkeypatch):
    """Force the accelerator code path on the CPU device: CDC boundaries and
    recipe output must be identical to the host path."""
    import skyplane_tpu.ops.backend as backend
    from skyplane_tpu.ops.dedup import SenderDedupIndex
    from skyplane_tpu.ops.pipeline import DataPathProcessor

    data = (
        rng.integers(0, 256, 200_000, dtype=np.uint8).tobytes()
        + bytes(100_000)
        + rng.integers(0, 256, 100_000, dtype=np.uint8).tobytes()
    )

    def run(accel: bool):
        monkeypatch.setattr(backend, "_is_accelerator", accel)
        pytest.importorskip("zstandard")  # optional dep: minimal containers ship without it
        proc = DataPathProcessor(codec_name="zstd", dedup=True)
        p = proc.process(data, SenderDedupIndex())
        return p

    host = run(False)
    accel = run(True)
    assert host.fingerprint == accel.fingerprint  # same segment fps -> same chunk fp
    assert host.n_segments == accel.n_segments
    assert host.wire_bytes == accel.wire_bytes
