"""Generations of one volume region through a loopback gateway pair at the
shipped transfer settings, held to a plain reference of the same semantics:
segment ends, fingerprints, which segments exact dedup sends as REFs, the
literal bytes, and the restored bytes (the deployment of the benchmark's
``snapshot-chain`` configuration, at a small chunk size on the CPU).

The reference below is straightforward numpy and imports nothing of the
program: it rebuilds the gear table and the fingerprint bases from the
constants that define how every gateway cuts (``ops/gear.py``,
``ops/fingerprint.py``).
"""

from __future__ import annotations

import hashlib
from typing import Dict, List, Tuple

import numpy as np
import pytest

pytest.importorskip("zstandard")  # the shipped codec and crypto are optional deps
pytest.importorskip("cryptography")

from tests.integration.harness import dispatch_file, make_pair, wait_complete  # noqa: E402

# ---- the plain reference ----

CDC = (4096, 16384, 65536)  # shipped TransferConfig(): min / avg / max
GEAR_WINDOW = 32
M31 = (1 << 31) - 1


def splitmix64(seed: int, n: int) -> List[int]:
    mask = (1 << 64) - 1
    out, x = [], seed & mask
    for _ in range(n):
        x = (x + 0x9E3779B97F4A7C15) & mask
        z = x
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & mask
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & mask
        out.append(z ^ (z >> 31))
    return out


GEAR_TABLE = np.array([v & 0xFFFFFFFF for v in splitmix64(0x5EED_CDC1, 256)], dtype=np.uint32)
LANE_BASES = [v % (M31 - 3) + 2 for v in splitmix64(0x5EED_F1D0, 8)]


def plain_ends(row: np.ndarray, lo: int, avg: int, hi: int) -> List[int]:
    """h_t = sum_{i<32} G[b_{t-i}] << i (mod 2^32); a byte whose hash has its
    top log2(avg) bits zero is a candidate; a segment ends after a candidate
    no nearer than ``lo`` to its start, and at ``hi`` at the latest."""
    h = GEAR_TABLE[row].copy()
    off = 1
    while off < GEAR_WINDOW:
        shifted = np.zeros_like(h)
        shifted[off:] = h[:-off]
        h = h + (shifted << np.uint32(off))
        off <<= 1
    bits = int(np.log2(avg))
    ends, start = [], 0
    for p in np.flatnonzero((h >> np.uint32(32 - bits)) == 0).tolist():
        cut = p + 1
        if cut - start < lo:
            continue
        while cut - start > hi:
            start += hi
            ends.append(start)
        if cut - start >= lo:
            ends.append(cut)
            start = cut
    while len(row) - start > hi:
        start += hi
        ends.append(start)
    if start < len(row) or not ends:
        ends.append(len(row))
    return ends


_powers: Dict[int, np.ndarray] = {}


def powers_of(base: int) -> np.ndarray:
    """base^0 .. base^(hi-1) mod 2^31-1: enough for the longest segment."""
    if base not in _powers:
        table, x = np.empty(CDC[2], np.uint64), 1
        for i in range(CDC[2]):
            table[i] = x
            x = x * base % M31
        _powers[base] = table
    return _powers[base]


def plain_fingerprint(seg: np.ndarray) -> bytes:
    """One lane F_r(s) = sum b_i r^(L-1-i) mod 2^31-1 per base r, mixed with
    the length into 16 bytes."""
    data = seg.astype(np.uint64)
    lanes = np.empty(len(LANE_BASES), "<u4")
    for li, base in enumerate(LANE_BASES):
        terms = data * powers_of(base)[: len(seg)][::-1]  # each < 2^39, at most 2^16 of them
        lanes[li] = int(terms.sum()) % M31
    return hashlib.blake2b(lanes.tobytes() + len(seg).to_bytes(8, "little"), digest_size=16).digest()


def plain_dedup(rows: List[np.ndarray], cdc: Tuple[int, int, int] = CDC) -> List[dict]:
    """Per row: segment ends, fingerprints, which segments exact dedup sends
    as REFs (the fingerprint was in an earlier row or earlier in this one),
    the raw bytes that go as literals, and the row as a receiver restores it
    from the literals it was sent and the segments it holds."""
    held: Dict[bytes, bytes] = {}
    out = []
    for row in rows:
        ends = plain_ends(row, *cdc)
        fps, is_ref, literal_bytes, restored = [], [], 0, []
        for a, b in zip([0] + ends[:-1], ends):
            fp = plain_fingerprint(row[a:b])
            fps.append(fp)
            is_ref.append(fp in held)
            if fp not in held:
                held[fp] = row[a:b].tobytes()
                literal_bytes += b - a
            restored.append(held[fp])
        out.append({"ends": ends, "fps": fps, "is_ref": is_ref, "literal_bytes": literal_bytes, "restored": b"".join(restored)})
    return out


# ---- the deployment's traffic, small ----

REGION = 2 << 20  # one chunk
EXTENT = REGION // 128  # four of them: 1/32 of the region
EXTENTS = 4
GENERATIONS = 6


def make_rows(seed: int) -> List[np.ndarray]:
    """The base region, then generations of it: each the base with four
    extents rewritten at uniform byte offsets that do not overlap."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 256, REGION, dtype=np.uint8)
    rows = [base]
    for _ in range(GENERATIONS):
        row = base.copy()
        gaps = np.sort(rng.integers(0, REGION - EXTENTS * EXTENT + 1, EXTENTS))
        for at in (gaps + np.arange(EXTENTS) * EXTENT).tolist():
            row[at : at + EXTENT] = rng.integers(0, 256, EXTENT, dtype=np.uint8)
        rows.append(row)
    return rows


@pytest.fixture(scope="module")
def chain(tmp_path_factory):
    """One transfer of the base and its generations; what the pair counted,
    what landed, and what the reference says of the same rows."""
    tmp = tmp_path_factory.mktemp("snapshot_chain")
    (tmp / "src").mkdir()
    (tmp / "out").mkdir()
    rows = make_rows(27)
    src, dst = make_pair(tmp, compress="tpu_zstd", dedup=True, encrypt=True, use_tls=True, num_connections=4)
    try:
        ids = []
        for i, row in enumerate(rows):
            (tmp / "src" / f"gen{i}.bin").write_bytes(row.tobytes())
        # the base lands and is committed to the sender's index before any
        # generation is cut: a REF is only ever sent for an acked literal
        base_ids = dispatch_file(src, tmp / "src" / "gen0.bin", tmp / "out" / "gen0.bin", chunk_bytes=REGION)
        wait_complete(dst, base_ids)
        wait_complete(src, base_ids)
        for i in range(1, len(rows)):
            ids += dispatch_file(src, tmp / "src" / f"gen{i}.bin", tmp / "out" / f"gen{i}.bin", chunk_bytes=REGION)
        wait_complete(dst, ids)
        wait_complete(src, ids)
        source = src.get("profile/compression", timeout=10).json()
        sink = dst.get("profile/decode", timeout=10).json()["counters"]
        landed = [(tmp / "out" / f"gen{i}.bin").read_bytes() for i in range(len(rows))]
    finally:
        src.stop()
        dst.stop()
    return {"rows": rows, "reference": plain_dedup(rows), "source": source, "sink": sink, "landed": landed}


def test_the_traffic_is_the_stated_change_model(chain):
    base = chain["rows"][0]
    for row in chain["rows"][1:]:
        changed = np.flatnonzero(row != base)
        # a rewritten byte keeps its value once in 256
        assert EXTENTS * EXTENT * 0.98 < len(changed) <= EXTENTS * EXTENT == REGION // 32


def test_segment_count_equals_the_references(chain):
    assert chain["source"]["segments"] == sum(len(r["fps"]) for r in chain["reference"])
    assert chain["source"]["chunks"] == len(chain["rows"])


def test_ref_count_equals_the_references_and_is_most_of_the_segments(chain):
    want = sum(sum(r["is_ref"]) for r in chain["reference"])
    assert chain["source"]["ref_segments"] == want
    window = chain["reference"][1:]
    assert sum(sum(r["is_ref"]) for r in window) > 0.75 * sum(len(r["fps"]) for r in window)


def test_literal_bytes_equal_the_references_to_the_byte(chain):
    assert chain["source"]["literal_bytes"] == sum(r["literal_bytes"] for r in chain["reference"])
    assert chain["reference"][0]["literal_bytes"] == REGION  # nothing to dedup in the base


@pytest.mark.parametrize("generation", range(GENERATIONS + 1))
def test_every_generation_is_restored_byte_identical(chain, generation):
    assert chain["landed"][generation] == chain["rows"][generation].tobytes()
    assert chain["reference"][generation]["restored"] == chain["rows"][generation].tobytes()


def test_the_sink_resolved_every_ref_the_source_sent(chain):
    assert chain["sink"]["ref_segments_resolved"] == chain["source"]["ref_segments"] > 0
    assert chain["sink"]["ref_bytes_resolved"] == chain["source"]["raw_bytes"] - chain["source"]["literal_bytes"]
    assert 0 < chain["sink"]["ref_resolve_ns"] < chain["sink"]["decode_ns"]


def test_the_sink_verified_every_literal_the_source_sent_one_call_a_chunk(chain):
    """The literal pass (PR 30): every literal entry is fingerprint-checked, by one batched call a chunk."""
    assert chain["sink"]["literal_segments_verified"] == chain["source"]["segments"] - chain["source"]["ref_segments"] > 0
    assert 0 < chain["sink"]["literal_verify_calls"] <= chain["sink"]["decode_chunks"]
    assert 0 < chain["sink"]["literal_pass_ns"] < chain["sink"]["decode_ns"]


def test_the_codec_part_of_the_recipe_time_is_counted(chain):
    assert 0 < chain["source"]["recipe_encode_ns"] <= chain["source"]["recipe_ns"]


@pytest.mark.parametrize("counter", ["decode_nacks", "store_ref_wait_ns", "store_ref_timeouts"])
def test_no_ref_waited_or_was_refused(chain, counter):
    assert chain["sink"][counter] == 0
