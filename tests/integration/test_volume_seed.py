"""Regions of a volume sent for the first time through a loopback gateway pair
at the shipped transfer settings, held to a plain reference of the same
semantics: segment ends, fingerprints, which segments exact dedup sends as
REFs (a run of zeros repeats inside its own chunk and in the set-up region),
the literal bytes, the restored bytes, and the counters that split the
codec's steps (the deployment of the benchmark's ``volume-seed``
configuration, at a small chunk size on the CPU).

The reference is ``test_snapshot_chain.py``'s: straightforward numpy that
imports nothing of the program. The traffic below restates the
configuration's block mix in numpy and imports nothing of the benchmark.
"""

from __future__ import annotations

from typing import List

import numpy as np
import pytest

pytest.importorskip("zstandard")  # the shipped codec and crypto are optional deps
pytest.importorskip("cryptography")

from tests.integration.harness import dispatch_file, make_pair, wait_complete  # noqa: E402
from tests.integration.test_snapshot_chain import CDC, plain_dedup  # noqa: E402

# ---- the deployment's traffic, small ----

REGION = 4 << 20  # one chunk
EXTENT = REGION // 32  # 128 KiB: two forced cuts long, so every zero extent holds a whole zero segment
EXTENTS_BY_TYPE = {"zero": 8, "text": 11, "records": 8, "random": 5}  # 25 / 34.4 / 25 / 15.6% of the bytes
REGIONS = 4  # the set-up region and three more
ZERO_SEGMENT = bytes(CDC[2])  # a run of zeros holds no candidate: every cut is forced at the longest segment


def make_region(seed: int, i: int, vocabulary: np.ndarray) -> np.ndarray:
    """Whole extents of one type each, their order a permutation from (seed, i)."""
    rng = np.random.default_rng([seed, i, 1])
    types = rng.permutation(np.repeat(list(EXTENTS_BY_TYPE), list(EXTENTS_BY_TYPE.values())))
    out = np.zeros((len(types), EXTENT), np.uint8)
    for row, kind in enumerate(types.tolist()):
        if kind == "text":  # a stream of 8-byte words from a vocabulary of 512
            out[row] = vocabulary[rng.integers(0, len(vocabulary), EXTENT // 8)].ravel()
        elif kind == "records":  # one 64-byte record tiled, then a byte in 32 edited
            out[row] = np.tile(rng.integers(0, 256, 64, dtype=np.uint8), EXTENT // 64)
            out[row, rng.integers(0, EXTENT, EXTENT // 32)] = rng.integers(0, 256, EXTENT // 32, dtype=np.uint8)
        elif kind == "random":
            out[row] = rng.integers(0, 256, EXTENT, dtype=np.uint8)
    return out.ravel()


def make_rows(seed: int) -> List[np.ndarray]:
    vocabulary = (np.random.default_rng([seed, 0, 2]).integers(0, 256, (512, 8), dtype=np.uint8) & 0x3F) | 0x20
    return [make_region(seed, i, vocabulary) for i in range(REGIONS)]


@pytest.fixture(scope="module")
def seeding(tmp_path_factory):
    """One transfer of the set-up region and three more; what the pair
    counted, what landed, and what the reference says of the same rows."""
    tmp = tmp_path_factory.mktemp("volume_seed")
    (tmp / "src").mkdir()
    (tmp / "out").mkdir()
    rows = make_rows(36)
    src, dst = make_pair(tmp, compress="tpu_zstd", dedup=True, encrypt=True, use_tls=True, num_connections=4)
    try:
        for i, row in enumerate(rows):
            (tmp / "src" / f"region{i}.bin").write_bytes(row.tobytes())
        # the set-up region lands and is committed to the sender's index before
        # any other is cut: a REF is only ever sent for an acked literal
        ids = dispatch_file(src, tmp / "src" / "region0.bin", tmp / "out" / "region0.bin", chunk_bytes=REGION)
        wait_complete(dst, ids)
        wait_complete(src, ids)
        ids = []
        for i in range(1, len(rows)):
            ids += dispatch_file(src, tmp / "src" / f"region{i}.bin", tmp / "out" / f"region{i}.bin", chunk_bytes=REGION)
        wait_complete(dst, ids)
        wait_complete(src, ids)
        source = src.get("profile/compression", timeout=10).json()
        sink = dst.get("profile/decode", timeout=10).json()["counters"]
        landed = [(tmp / "out" / f"region{i}.bin").read_bytes() for i in range(len(rows))]
    finally:
        src.stop()
        dst.stop()
    return {"rows": rows, "reference": plain_dedup(rows), "source": source, "sink": sink, "landed": landed}


def segments_of(row: np.ndarray, ref: dict):
    return [row[a:b].tobytes() for a, b in zip([0] + ref["ends"][:-1], ref["ends"])]


def test_the_traffic_is_the_stated_block_mix(seeding):
    for row in seeding["rows"]:
        extents = row.reshape(-1, EXTENT)
        assert int((~extents.any(axis=1)).sum()) == EXTENTS_BY_TYPE["zero"]
        # text extents: every byte of the 32 values 0x20-0x3F; no other type is made of them alone
        assert int(((extents >= 0x20) & (extents < 0x40)).all(axis=1).sum()) == EXTENTS_BY_TYPE["text"]


def test_every_region_holds_whole_zero_segments_and_a_cut_forced_at_the_longest(seeding):
    for row, ref in zip(seeding["rows"], seeding["reference"]):
        assert segments_of(row, ref).count(ZERO_SEGMENT) >= EXTENTS_BY_TYPE["zero"] - 1


def test_segment_count_equals_the_references(seeding):
    assert seeding["source"]["segments"] == sum(len(r["fps"]) for r in seeding["reference"])
    assert seeding["source"]["chunks"] == REGIONS


def test_the_refs_are_the_zero_segments_own_chunk_repeats_and_the_set_up_regions(seeding):
    """Exact dedup sends as a REF every whole zero segment but the first of
    the set-up region (``build_recipe``'s ``emitted_here`` branch there, the
    index in the regions after it), and nothing else repeats."""
    want = sum(sum(r["is_ref"]) for r in seeding["reference"])
    assert seeding["source"]["ref_segments"] == want
    for n, (row, ref) in enumerate(zip(seeding["rows"], seeding["reference"])):
        refs = [seg for seg, is_ref in zip(segments_of(row, ref), ref["is_ref"]) if is_ref]
        assert set(refs) == {ZERO_SEGMENT}
        assert len(refs) == segments_of(row, ref).count(ZERO_SEGMENT) - (n == 0)
    assert sum(seeding["reference"][0]["is_ref"]) >= EXTENTS_BY_TYPE["zero"] - 2  # repeats inside the first chunk sent


def test_literal_bytes_equal_the_references_to_the_byte(seeding):
    assert seeding["source"]["literal_bytes"] == sum(r["literal_bytes"] for r in seeding["reference"])
    # what dedup takes off is the zero extents' whole segments: under the 25% of the bytes that are zeros
    assert 0.75 * REGION * REGIONS < seeding["source"]["literal_bytes"] < 0.92 * REGION * REGIONS


@pytest.mark.parametrize("region", range(REGIONS))
def test_every_region_is_restored_byte_identical(seeding, region):
    assert seeding["landed"][region] == seeding["rows"][region].tobytes()
    assert seeding["reference"][region]["restored"] == seeding["rows"][region].tobytes()


def test_the_sink_resolved_every_ref_and_verified_every_literal_the_source_sent(seeding):
    source, sink = seeding["source"], seeding["sink"]
    assert sink["ref_segments_resolved"] == source["ref_segments"] > 0
    assert sink["ref_bytes_resolved"] == source["raw_bytes"] - source["literal_bytes"] == source["ref_segments"] * CDC[2]
    assert sink["literal_segments_verified"] == source["segments"] - source["ref_segments"] > 0
    assert sink["literal_verify_calls"] == sink["decode_chunks"] == REGIONS  # REFs and literals side by side in every recipe


def test_the_blobs_lengths_are_counted_and_the_codec_shrinks_them(seeding):
    """A recipe is 7 bytes of head, 25 bytes an entry and the encoded literal
    blob; ``wire_bytes`` counts the recipes before the seal."""
    source = seeding["source"]
    assert source["literal_blob_bytes"] == source["wire_bytes"] - 7 * source["chunks"] - 25 * source["segments"]
    assert 2.0 < source["literal_bytes"] / source["literal_blob_bytes"] < 6.0


def test_the_codecs_steps_lie_inside_the_time_they_split(seeding):
    source, sink = seeding["source"], seeding["sink"]
    # a host with no accelerator runs plain zstd for tpu_zstd (effective_codec_name): blockpack then reads 0
    assert 0 < source["zstd_ns"] <= source["blockpack_ns"] + source["zstd_ns"] <= source["recipe_encode_ns"] <= source["recipe_ns"]
    assert 0 < sink["blob_decode_ns"] <= sink["literal_pass_ns"] < sink["decode_ns"]


@pytest.mark.parametrize("counter", ["decode_nacks", "store_ref_wait_ns", "store_ref_timeouts"])
def test_no_ref_waited_or_was_refused(seeding, counter):
    assert seeding["sink"][counter] == 0


def test_no_row_overflowed_the_candidate_list(seeding):
    assert seeding["source"]["overflow_rows"] == 0
