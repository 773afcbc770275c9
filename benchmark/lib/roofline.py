"""What the data path's device work needs at the least, from shapes alone, and
the table of peaks it is held against.

The device programs cut a chunk into content-defined segments and fingerprint
each: whatever implements that has to read every byte of the row once, take
the segment ends in, and give the candidate positions and the fingerprint
lanes out. The arithmetic per byte (one table look-up and shift-add for the
gear hash, eight multiply-adds mod 2^31 - 1 for the lanes) runs on the vector
unit, for which no peak is published, so the bound used is memory: bytes over
the chip's HBM bandwidth.
"""

from __future__ import annotations

import json
from pathlib import Path

from lib.reference import N_LANES


def bucket_bytes(row_bytes: int, smallest: int = 64 << 10) -> int:
    """The power-of-two bucket a row is padded into (``ops/bufpool.py``)."""
    bucket = smallest
    while bucket < row_bytes:
        bucket <<= 1
    return bucket


def least_bytes(row_bytes: int, cdc_min_bytes: int, cdc_avg_bytes: int) -> int:
    """HBM bytes one row's CDC + fingerprints cannot do without: the row read
    once, the candidate positions out (int32, 8x the expected one per average
    segment, and their count), the segment ends in (int32, one slot per
    smallest possible segment) and the lanes out (8 x uint32 per slot)."""
    bucket = bucket_bytes(row_bytes)
    candidates = max(64, 8 * (bucket // cdc_avg_bytes)) + 1
    slots = bucket // cdc_min_bytes + 2
    return row_bytes + 4 * candidates + 4 * slots + 4 * N_LANES * slots


def peaks_for(device_kind: str) -> dict:
    """The published peaks of this kind of chip; an unknown kind is an error,
    never a default."""
    table = json.loads((Path(__file__).resolve().parents[1] / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"benchmark/peaks.json has no entry for device kind {device_kind!r}")
    return table[device_kind]
