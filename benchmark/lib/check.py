"""The comparison that decides ``correct``, and its controls.

Every number compared is a count of disagreements with the plain reference or
with what the configuration guarantees, so every limit is 0: the device
programs' segment ends and fingerprints are integers and digests, a restored
file is byte-identical or it is not. ``Observed`` holds what the timed path
produced; ``compare`` sets it against the reference. A control replaces part
of what was observed with what a path that breaks one stated guarantee would
have produced, and has to come out as not correct.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from lib import reference


def file_digest(path: Path, flip_first_byte: bool = False) -> Optional[str]:
    """blake2b-128 of a file, or None where there is none."""
    if not path.exists():
        return None
    h = hashlib.blake2b(digest_size=16)
    with open(path, "rb") as f:
        first = True
        while block := f.read(8 << 20):
            if first and flip_first_byte:
                block = bytes([block[0] ^ 1]) + block[1:]
            first = False
            h.update(block)
    return h.hexdigest()


def bytes_digest(arr: np.ndarray) -> str:
    return hashlib.blake2b(arr, digest_size=16).hexdigest()


def row_key(arr: np.ndarray) -> str:
    """Names a row by about 16 Ki of its bytes, evenly spaced (every 4096th
    of a 64 MiB row, every byte of a row up to 16 KiB), and its length: cheap
    enough for the timed path's own thread, and different for any two chunks
    of a cell, small objects of one length among them."""
    step = max(1, len(arr) >> 14)
    return hashlib.blake2b(np.ascontiguousarray(arr[::step]).tobytes() + len(arr).to_bytes(8, "little"), digest_size=16).hexdigest()


@dataclass
class Sent:
    """One chunk the run sent: what the generator made, and what came of it."""

    index: int  # 0 is the set-up chunk
    chunk_id: str
    key: str
    digest: str
    n_bytes: int
    src_path: Path
    dst_path: Path
    posted_at: float
    completed_at: Optional[float] = None  # sink's status log stamp
    cpu_at_completion: Optional[float] = None


@dataclass
class Observed:
    """What the timed path produced, gathered once the window has closed."""

    sent: List[Sent]
    file_digests: Dict[int, Optional[str]]  # index -> digest of the sink's file
    device_rows: Dict[int, Tuple[np.ndarray, List[bytes]]]  # index -> (ends, digests) as the device path gave them
    row_bytes: Callable[[int], np.ndarray]  # index -> the row's bytes, made again from (seed, index)
    counters: Dict[str, float]  # source /profile/compression, whole run
    frames: List[dict]  # sink /profile/decode events, whole run
    gateway_errors: int
    as_built_departures: List[str]
    cdc: Tuple[int, int, int]
    wire_codec_id: int
    reference_rows: Dict[int, Tuple[np.ndarray, List[bytes]]] = field(default_factory=dict)
    # the set-up rows landed one at a time, each in the source's index before
    # the next was posted (not the set-up bursts' rows, which go together)
    setup_rows: Tuple[int, ...] = (0,)


def expected_refs(
    device_rows: Dict[int, Tuple[np.ndarray, List[bytes]]], order: List[int], setup_rows: Tuple[int, ...] = (0,)
) -> Tuple[int, int, int]:
    """(segments, fewest REFs, most REFs) the recipes of these chunks hold if
    dedup is exact. Fewest: a segment is a REF when its fingerprint is earlier
    in its own chunk, or in a set-up row that landed before its chunk was
    posted: for set-up row k the set-up rows before k, for every other row all
    of them. Most: also when it is in any chunk sent before it (the two differ
    only if chunks other than the set-up rows share content). ``order`` is by
    index, so the set-up rows come first."""
    setup_all = set().union(*(device_rows[i][1] for i in setup_rows if i in device_rows))
    setup_before: set = set()
    segments = fewest = most = 0
    seen_before: set = set()
    for idx in order:
        fps = device_rows[idx][1]
        segments += len(fps)
        known = setup_before if idx in setup_rows else setup_all
        own: set = set()
        for fp in fps:
            if fp in known or fp in own:
                fewest += 1
                most += 1
            elif fp in seen_before:
                most += 1
            own.add(fp)
        seen_before |= own
        if idx in setup_rows:
            setup_before |= own
    return segments, fewest, most


def compare(obs: Observed) -> Dict[str, dict]:
    """name -> {"value", "limit"}; the run is correct when no value passes
    its limit."""
    landed = [s for s in obs.sent if s.completed_at is not None]
    out: Dict[str, float] = {}
    out["chunks_never_landed"] = len(obs.sent) - len(landed)
    out["files_not_identical"] = sum(1 for s in landed if obs.file_digests.get(s.index) != s.digest)
    out["rows_off_device"] = sum(1 for s in obs.sent if s.index not in obs.device_rows) + max(
        0, len(obs.sent) - int(obs.counters.get("batch_rows", 0))
    ) + int(obs.counters.get("stage_failures", 0))
    ends_differ = fps_differ = 0
    for idx, (ref_ends, ref_fps) in obs.reference_rows.items():
        got = obs.device_rows.get(idx)
        if got is None or not np.array_equal(np.asarray(got[0]), ref_ends):
            ends_differ += 1
            fps_differ += 1
        elif list(got[1]) != list(ref_fps):
            fps_differ += 1
    out["rows_ends_differ"] = ends_differ
    out["rows_fingerprints_differ"] = fps_differ
    order = [s.index for s in sorted(obs.sent, key=lambda s: s.index) if s.index in obs.device_rows]
    segments, fewest, most = expected_refs(obs.device_rows, order, obs.setup_rows)
    out["segments_off"] = abs(int(obs.counters.get("segments", 0)) - segments)
    refs = int(obs.counters.get("ref_segments", 0))
    out["ref_segments_off"] = max(fewest - refs, refs - most, 0)
    by_chunk = {ev["chunk_id"]: ev for ev in obs.frames}
    out["frames_missing"] = sum(1 for s in landed if s.chunk_id not in by_chunk)
    out["frames_other_codec"] = sum(1 for ev in obs.frames if int(ev["codec"]) != obs.wire_codec_id)
    out["frames_wrong_length"] = sum(1 for s in landed if s.chunk_id in by_chunk and int(by_chunk[s.chunk_id]["raw_bytes"]) != s.n_bytes)
    out["gateway_errors"] = obs.gateway_errors
    out["as_built_departures"] = len(obs.as_built_departures)
    return {name: {"value": value, "limit": 0} for name, value in out.items()}


def passed(compared: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


def compute_reference(obs: Observed, pool=None) -> dict:
    """The plain reference over every row the run sent, the set-up rows
    included, at the configuration's cut: no row's ends or fingerprints reach
    the other comparisons unchecked. A run hands its ``lib.refpool`` pool,
    whose workers make each row again themselves; without one the rows come
    from ``obs.row_bytes`` and are cut here, one after the other. Returns
    what the pool says of its work."""
    if pool is not None:
        rows, info = pool.rows(s.index for s in obs.sent)
        obs.reference_rows.update(rows)
        return info
    for s in obs.sent:
        obs.reference_rows[s.index] = reference.cdc_and_fingerprints(obs.row_bytes(s.index), *obs.cdc)
    return {"rows": len(obs.sent), "workers": 0}


# ---- controls: one stated guarantee broken, in the program's place ----


def control_rows(obs: Observed) -> List[int]:
    """The rows a control computes in the program's place: the set-up row and
    the first window row are enough for it to fail."""
    return sorted(obs.reference_rows)[:2]


def control_fp_4_lanes(obs: Observed) -> None:
    """Fingerprints of 4 lanes where the configuration states 8: half of call
    B's work, the step that would tempt a later PR. The reference, computed
    so, stands in for the device path's first rows."""
    for idx in control_rows(obs):
        obs.device_rows[idx] = reference.cdc_and_fingerprints(obs.row_bytes(idx), *obs.cdc, bases=reference.LANE_BASES[:4])


def control_cdc_avg_halved(obs: Observed) -> None:
    """Segments cut at half the configured average: a gateway that cuts the
    same bytes another way. The reference, computed so, stands in."""
    lo, avg, hi = obs.cdc
    for idx in control_rows(obs):
        obs.device_rows[idx] = reference.cdc_and_fingerprints(obs.row_bytes(idx), lo, avg // 2, hi)


def control_restore_flips_byte(obs: Observed) -> None:
    """A sink that lands one wrong byte in one file: restore is no longer
    byte-identical."""
    victim = next(s for s in obs.sent if s.completed_at is not None and s.index in obs.file_digests)
    obs.file_digests[victim.index] = file_digest(victim.dst_path, flip_first_byte=True)


CONTROLS: Dict[str, Callable[[Observed], None]] = {
    "fp_4_lanes": control_fp_4_lanes,
    "cdc_avg_halved": control_cdc_avg_halved,
    "restore_flips_byte": control_restore_flips_byte,
}
