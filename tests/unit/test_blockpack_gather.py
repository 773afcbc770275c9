"""The sender's literal stream laid down from its spans (``blockpack.encode_spans``,
``codecs.timed_encoder``): one pass from the chunk into a pooled container
gives, for every input, the bytes ``encode_container`` gives for the spans
joined, so the zstd frame and the recipe on the wire are the parent's.
"""

from __future__ import annotations

import numpy as np
import pytest

from skyplane_tpu.native import datapath as native_dp
from skyplane_tpu.ops import blockpack
from skyplane_tpu.ops.bufpool import BufferPool
from skyplane_tpu.ops.codecs import get_codec, timed_encoder
from skyplane_tpu.ops.dedup import SenderDedupIndex, build_recipe

BLOCK = blockpack.DEFAULT_BLOCK_BYTES


def _content(kind: str, n: int, seed: int) -> bytes:
    rng = np.random.default_rng([seed, n])
    if kind == "random":
        return rng.integers(0, 256, n, dtype=np.uint8).tobytes()
    out = np.zeros(n, np.uint8)
    if kind == "zero_runs":  # zeros with random islands that start and end inside blocks
        for start in range(300, n, 2_900):
            out[start : start + 700] = rng.integers(0, 256, min(700, n - start), dtype=np.uint8)
    elif kind == "constant_runs":  # runs of one byte each, their edges off the block grid
        edges = np.sort(rng.integers(0, n, 12))
        for k, (a, b) in enumerate(zip(np.r_[0, edges], np.r_[edges, n])):
            out[a:b] = (k * 37) % 256
    elif kind == "volume_mix":  # the benchmark's block mix: zero, text, records and random extents
        extent = 4_096
        for k, start in enumerate(range(0, n, extent)):
            end = min(start + extent, n)
            kind_k = ("zero", "text", "records", "random")[k % 4]
            if kind_k == "text":
                words = (rng.integers(0, 256, (64, 8), dtype=np.uint8) & 0x3F) | 0x20
                out[start:end] = words[rng.integers(0, 64, extent // 8)].ravel()[: end - start]
            elif kind_k == "records":
                out[start:end] = np.tile(rng.integers(0, 256, 64, dtype=np.uint8), extent // 64)[: end - start]
            elif kind_k == "random":
                out[start:end] = rng.integers(0, 256, end - start, dtype=np.uint8)
    return out.tobytes()


def _cut(n: int, seed: int, keep_share: float):
    """Segments of 100-3,000 bytes over ``n`` bytes; each kept (a literal) with ``keep_share``."""
    rng = np.random.default_rng([seed, n, 7])
    spans, start = [], 0
    while start < n:
        end = min(n, start + int(rng.integers(100, 3_000)))
        if rng.random() < keep_share:
            spans.append((start, end))
        start = end
    return spans


#: id -> (content, chunk length, spans, native library present)
CASES = {
    "random_single_part": ("random", 100_003, [(0, 100_003)], True),
    "random_spans_across_block_edges": ("random", 20_000, [(3, 700), (700, 1_500), (2_049, 2_049), (2_049, 5_000), (5_001, 19_999)], True),
    "zero_runs": ("zero_runs", 64 << 10, _cut(64 << 10, 1, 0.8), True),
    "constant_runs": ("constant_runs", 48_111, _cut(48_111, 2, 0.7), True),
    "volume_mix": ("volume_mix", 96 << 10, _cut(96 << 10, 3, 0.75), True),
    "total_not_a_multiple_of_512": ("volume_mix", 7 * BLOCK + 5, [(0, 3 * BLOCK + 1), (4 * BLOCK, 7 * BLOCK + 5)], True),
    "one_byte": ("random", 1, [(0, 1)], True),
    "no_parts": ("random", 4_096, [], True),
    "fallback_volume_mix": ("volume_mix", 96 << 10, _cut(96 << 10, 4, 0.75), False),
    "fallback_single_part_unaligned": ("zero_runs", 10_001, [(0, 10_001)], False),
    "fallback_no_parts": ("random", 1_000, [], False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_gathered_container_is_the_joined_streams(case, monkeypatch):
    kind, n, spans, native = CASES[case]
    chunk = _content(kind, n, seed=43)
    joined = b"".join(chunk[a:b] for a, b in spans)
    want = blockpack.encode_container(joined)  # the parent's bytes, with the native pass
    if not native:
        monkeypatch.setattr(native_dp, "_available", False)

    pool = BufferPool()
    out = pool.acquire(blockpack.container_bound(n))
    view, gathered = blockpack.encode_spans(chunk, spans, out)
    assert gathered is native
    assert bytes(view) == want
    assert bytes(blockpack.decode_container(view)) == joined
    pool.release(out)

    for name in ("tpu", "tpu_zstd"):
        spec, timings = get_codec(name), {}
        wire = timed_encoder(spec, timings, pool=pool)(chunk, spans)
        assert wire == spec.encode(joined)
        assert bytes(spec.decode(wire)) == joined
        counted = {k: timings[k] for k in ("literal_gathers", "literal_joins") if k in timings}
        assert counted == ({} if not joined else {"literal_gathers" if native else "literal_joins": 1})
        assert timings["blockpack_ns"] > 0 and ("zstd_ns" in timings) == (name == "tpu_zstd")
    assert pool.counters()["pool_outstanding"] == 0


@pytest.mark.parametrize("codec_name", ["none", "zstd", "tpu", "tpu_zstd", "native_lz"])
def test_a_recipe_from_spans_of_the_chunk_is_the_recipe_from_its_joined_literals(codec_name):
    """``build_recipe`` with the chunk hands the codec the spans of its
    literal runs (adjacent literals in one span); the recipe is the one the
    joined literals give, REFs, in-chunk repeats and all."""
    chunk = _content("volume_mix", 64 << 10, seed=5)
    cuts = _cut(len(chunk), 6, 1.0)
    segments = [(bytes([k % 7]) * 16, memoryview(chunk)[a:b]) for k, (a, b) in enumerate(cuts)]  # 7 fingerprints: repeats
    index = SenderDedupIndex()
    index.add(segments[1][0], len(segments[1][1]))  # one fingerprint the sink already holds
    spec, pool, timings = get_codec(codec_name), BufferPool(), {}
    got = build_recipe(segments, index, timed_encoder(spec, timings, pool=pool), timings, chunk=chunk)
    want = build_recipe([(fp, bytes(seg)) for fp, seg in segments], index, spec.encode)
    assert got == want
    assert got[1] > 0 and got[2] > 0  # REFs beside literals
    assert timings["literal_blob_bytes"] == len(got[0]) - 7 - 25 * len(segments)
    assert timings.get("literal_gathers", 0) == int(spec.gather_bound is not None and native_dp.available())
    assert pool.counters()["pool_outstanding"] == 0


@pytest.mark.parametrize("spans", [[(0, 4_097)], [(-1, 10)], [(10, 5)]], ids=["past_the_end", "negative", "reversed"])
def test_spans_outside_the_buffer_are_refused_before_the_native_pass(spans):
    if not native_dp.available():
        pytest.skip("the native library does not build here")
    out = np.empty(blockpack.container_bound(8_192), np.uint8)
    with pytest.raises(ValueError, match="spans outside"):
        native_dp.blockpack_encode_gather(np.zeros(4_096, np.uint8), np.asarray(spans, np.int64), BLOCK, out[:64], out[64:])
