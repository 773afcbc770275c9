"""ctypes bindings for the native CPU data-path kernels (datapath.cpp).

Bit-identical to both the numpy fallbacks and the device kernels (tested);
used by the host paths in ops/cdc.py and ops/fingerprint.py when the native
library is available (opt out with SKYPLANE_TPU_NATIVE_DATAPATH=0).
"""

from __future__ import annotations

import ctypes
import os
from typing import Optional

import numpy as np

from skyplane_tpu.native import load_library

_available: Optional[bool] = None


def available() -> bool:
    """True when the native library builds/loads and the opt-out is not set."""
    global _available
    if _available is None:
        if os.environ.get("SKYPLANE_TPU_NATIVE_DATAPATH", "1").strip().lower() in ("0", "false", "off"):
            _available = False
        else:
            try:
                load_library()
                _available = True
            except Exception:  # noqa: BLE001 — no g++ etc.: numpy fallbacks serve
                _available = False
    return _available


def _u8p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _u32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))


def gear_candidates(data: np.ndarray, mask_bits: int) -> np.ndarray:
    """[N] uint8 -> [N] bool boundary-candidate mask (gear hash + top-bits
    test in ONE pass)."""
    if not 1 <= mask_bits <= 31:
        raise ValueError(f"mask_bits must be in [1, 31], got {mask_bits}")
    from skyplane_tpu.ops.gear import GEAR_TABLE

    data = np.ascontiguousarray(data, dtype=np.uint8)
    table = np.ascontiguousarray(GEAR_TABLE, dtype=np.uint32)
    out = np.empty(len(data), np.uint8)
    load_library().skydp_gear_candidates(_u8p(data), len(data), _u32p(table), mask_bits, _u8p(out))
    return out.view(bool)


def cdc_fp(data: np.ndarray, mask_bits: int, min_bytes: int, max_bytes: int):
    """Fused CDC + fingerprints for one chunk in a single native call.

    [N] uint8 -> (ends [n_segments] int64, lanes [n_segments, 8] uint32).
    Bit-identical to cdc_segment_ends + segment_fp_lanes (tested), but never
    materializes the per-byte candidate mask and runs boundary selection in C
    — the host sender's hot path.
    """
    if not 1 <= mask_bits <= 31:
        raise ValueError(f"mask_bits must be in [1, 31], got {mask_bits}")
    from skyplane_tpu.ops.gear import GEAR_TABLE
    from skyplane_tpu.ops.fingerprint import LANE_BASES

    data = np.ascontiguousarray(data, dtype=np.uint8)
    n = len(data)
    if n == 0:
        return np.asarray([0], np.int64), np.zeros((1, 8), np.uint32)
    table = np.ascontiguousarray(GEAR_TABLE, dtype=np.uint32)
    bases = np.ascontiguousarray(LANE_BASES, dtype=np.uint32)
    max_ends = n // min_bytes + 2
    ends = np.empty(max_ends, np.int64)
    lanes = np.empty((max_ends, 8), np.uint32)
    n_ends = load_library().skydp_cdc_fp(
        _u8p(data),
        n,
        _u32p(table),
        mask_bits,
        min_bytes,
        max_bytes,
        _u32p(bases),
        ends.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        _u32p(lanes.reshape(-1)),
        max_ends,
    )
    if n_ends == np.iinfo(np.uint64).max:
        raise MemoryError("skydp_cdc_fp: segment buffer overflow (impossible sizing?) or OOM")
    return ends[:n_ends].copy(), lanes[:n_ends].copy()


def blockpack_encode(data: np.ndarray, block_bytes: int):
    """[N] uint8 (N % block_bytes == 0) -> (tags [NB] uint8, literals, n_lit),
    same contract as host_fallback.blockpack_encode_host."""
    data = np.ascontiguousarray(data, dtype=np.uint8)
    nb = len(data) // block_bytes
    tags = np.empty(nb, np.uint8)
    lits = np.empty(len(data), np.uint8)  # worst case: everything literal
    n_lit = load_library().skydp_blockpack_encode(_u8p(data), len(data), block_bytes, _u8p(tags), _u8p(lits))
    return tags, lits[:n_lit], int(n_lit)


def blockpack_encode_gather(buf: np.ndarray, spans: np.ndarray, block_bytes: int, packed_tags: np.ndarray, lits: np.ndarray) -> int:
    """The stream of ``buf``'s bytes in ``spans`` ([n, 2] int64 (start, end),
    in order), padded with zeros to whole blocks -> its tags packed 4 to a
    byte into ``packed_tags`` and its literals into ``lits`` (views the caller
    sized: ceil(nb/4) and nb * block_bytes bytes at most), in one pass with
    the interpreter lock released. Returns n_lit."""
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    spans = np.ascontiguousarray(spans, dtype=np.int64).reshape(-1, 2)
    if len(spans) and (spans.min() < 0 or spans.max() > len(buf) or (spans[:, 1] < spans[:, 0]).any()):
        raise ValueError(f"blockpack_encode_gather: spans outside the {len(buf)}-byte buffer")
    n_blocks = -(-int((spans[:, 1] - spans[:, 0]).sum()) // block_bytes)
    if len(packed_tags) < (n_blocks + 3) // 4 or len(lits) < n_blocks * block_bytes:
        raise ValueError(f"blockpack_encode_gather: outputs too short for {n_blocks} blocks")
    n_lit = load_library().skydp_blockpack_encode_gather(
        _u8p(buf),
        spans.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(spans),
        block_bytes,
        _u8p(packed_tags),
        _u8p(lits),
    )
    if n_lit == np.iinfo(np.uint64).max:
        raise MemoryError("skydp_blockpack_encode_gather: no memory for the staging block")
    return int(n_lit)


def blockpack_decode(tags: np.ndarray, literals: np.ndarray, block_bytes: int, out=None) -> np.ndarray:
    """(tags [NB], literals, block_bytes) -> [NB*block_bytes] uint8, written into
    the head of ``out`` (a C-contiguous uint8 array at least that long) where the
    caller gives one; raises CodecException on a tag/literal length mismatch
    (corrupt container)."""
    from skyplane_tpu.exceptions import CodecException

    tags = np.ascontiguousarray(tags, dtype=np.uint8)
    literals = np.ascontiguousarray(literals, dtype=np.uint8)
    n_out = len(tags) * block_bytes
    if out is None:
        out = np.empty(n_out, np.uint8)
    elif len(out) < n_out or out.dtype != np.uint8 or not out.flags.c_contiguous:
        raise ValueError(f"blockpack_decode needs a C-contiguous uint8 output of {n_out} bytes")
    rc = load_library().skydp_blockpack_decode(
        _u8p(tags), len(tags), _u8p(literals), len(literals), block_bytes, _u8p(out)
    )
    if rc != 0:
        raise CodecException("blockpack container corrupt: tag/literal length mismatch")
    return out[:n_out]


def segment_fp_lanes(data: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """[N] uint8 + segment ends -> [n_segments, 8] uint32 fingerprint lanes."""
    from skyplane_tpu.ops.fingerprint import LANE_BASES

    data = np.ascontiguousarray(data, dtype=np.uint8)
    ends = np.ascontiguousarray(ends, dtype=np.int64)
    bases = np.ascontiguousarray(LANE_BASES, dtype=np.uint32)
    out = np.empty((len(ends), 8), np.uint32)
    load_library().skydp_segment_fp(
        _u8p(data),
        len(data),
        ends.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(ends),
        _u32p(bases),
        _u32p(out),
    )
    return out
