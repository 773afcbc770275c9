"""Native (C++) data-path components, built on demand with g++.

``libskydp`` (skylz.cpp, datapath.cpp) holds the host codecs and data-path
kernels; ``libskytls`` (tlsstream.cpp, loaded by tlsstream.py) the data
socket's TLS record loop. Each library is cached next to the sources with a
stamp of its own; set
``SKYPLANE_TPU_NATIVE_BUILD_DIR`` to relocate build artifacts (e.g. on
read-only installs).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional, Tuple

from skyplane_tpu.exceptions import MissingDependencyException

_SRC_DIR = Path(__file__).parent
_BUILD_LOCK = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


def _build_dir() -> Path:
    override = os.environ.get("SKYPLANE_TPU_NATIVE_BUILD_DIR")
    return Path(override) if override else _SRC_DIR


_SOURCES = ("skylz.cpp", "datapath.cpp")
_NATIVE_FLAGS = ("-O3", "-march=native", "-shared", "-fPIC")
_PORTABLE_FLAGS = ("-O3", "-shared", "-fPIC")
_build_info: dict = {}


def _cpu_features() -> str:
    """The CPU's instruction-set flags as the kernel reports them: what a
    ``-march=native`` build actually depends on (an AVX-512 build SIGILLs on
    a CPU without it, whatever the host is called)."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return " ".join(sorted(line.split(":", 1)[1].split()))
    except OSError:
        pass
    import platform

    return f"{platform.machine()} {platform.processor()}"


def build_stamp(sources: Tuple[str, ...] = _SOURCES) -> str:
    """Digest of everything a compiled library depends on: its sources, the
    compiler flags and this CPU's feature flags. A library whose sidecar
    stamp differs (stale sources, built on another CPU, planted) is rebuilt."""
    import hashlib

    h = hashlib.sha256()
    for name in sources:
        h.update((_SRC_DIR / name).read_bytes())
    h.update(" ".join(_NATIVE_FLAGS).encode())
    h.update(_cpu_features().encode())
    return h.hexdigest()


def build_info() -> dict:
    """{"path", "stamp", "built"} of the loaded library ("built": compiled by
    this process rather than found with a matching stamp); empty before load."""
    return dict(_build_info)


def _compile(out: Path, sources: Tuple[str, ...]) -> None:
    src_args = [str(_SRC_DIR / name) for name in sources]
    # build beside the target and rename: a concurrent loader (pump workers
    # start together) never maps a half-written library
    tmp = out.with_name(f"{out.name}.{os.getpid()}.tmp")
    try:
        # -march=native can fail in emulated environments; retry portable
        for flags in (_NATIVE_FLAGS, _PORTABLE_FLAGS):
            try:
                proc = subprocess.run(["g++", *flags, *src_args, "-o", str(tmp), "-ldl"], capture_output=True, text=True, timeout=120)
            except FileNotFoundError as e:
                raise MissingDependencyException("native libraries require g++ in PATH") from e
            if proc.returncode == 0:
                os.replace(tmp, out)
                return
        raise MissingDependencyException(f"native build of {out.name} failed: {proc.stderr[-2000:]}")
    finally:
        tmp.unlink(missing_ok=True)


def build_and_load(name: str, sources: Tuple[str, ...]) -> Tuple[ctypes.CDLL, dict]:
    """Build ``lib<name>.so`` from ``sources`` unless its stamp matches, and
    load it. Returns the library and its {"path", "stamp", "built"}. Callers
    hold ``_BUILD_LOCK``."""
    out = _build_dir() / f"lib{name}.so"
    stamp_file = _build_dir() / f"lib{name}.stamp"
    stamp = build_stamp(sources)
    built = not out.exists() or not stamp_file.exists() or stamp_file.read_text() != stamp
    if built:
        out.parent.mkdir(parents=True, exist_ok=True)
        _compile(out, sources)
        stamp_file.write_text(stamp)
    return ctypes.CDLL(str(out)), {"path": str(out), "stamp": stamp, "built": built}


def load_library() -> ctypes.CDLL:
    """Build (if needed) and load libskydp."""
    global _lib
    if _lib is not None:
        return _lib
    with _BUILD_LOCK:
        if _lib is not None:
            return _lib
        lib, info = build_and_load("skydp", _SOURCES)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        for name, restype, argtypes in (
            ("skylz_max_compressed_size", ctypes.c_uint64, [ctypes.c_uint64]),
            ("skylz_compress", ctypes.c_uint64, [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64]),
            ("skylz_decompressed_size", ctypes.c_uint64, [ctypes.c_char_p, ctypes.c_uint64]),
            ("skylz_decompress", ctypes.c_uint64, [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64]),
            ("skylz_checksum64", ctypes.c_uint64, [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64]),
            ("skydp_gear_candidates", None, [u8p, ctypes.c_uint64, u32p, ctypes.c_uint32, u8p]),
            ("skydp_segment_fp", None, [u8p, ctypes.c_uint64, i64p, ctypes.c_uint64, u32p, u32p]),
            (
                "skydp_cdc_fp",
                ctypes.c_uint64,
                [u8p, ctypes.c_uint64, u32p, ctypes.c_uint32, ctypes.c_uint64, ctypes.c_uint64, u32p, i64p, u32p, ctypes.c_uint64],
            ),
            ("skydp_blockpack_encode", ctypes.c_uint64, [u8p, ctypes.c_uint64, ctypes.c_uint64, u8p, u8p]),
            ("skydp_blockpack_encode_gather", ctypes.c_uint64, [u8p, i64p, ctypes.c_uint64, ctypes.c_uint64, u8p, u8p]),
            ("skydp_blockpack_decode", ctypes.c_int, [u8p, ctypes.c_uint64, u8p, ctypes.c_uint64, ctypes.c_uint64, u8p]),
        ):
            fn = getattr(lib, name)
            fn.restype = restype
            fn.argtypes = argtypes
        _build_info.update(info)
        _lib = lib
        return _lib
