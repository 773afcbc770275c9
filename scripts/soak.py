#!/usr/bin/env python
"""Long-haul soak: sustained transfer watching memory and fd ceilings.

Runs the full in-process data plane (framed TLS sockets, windowed acks,
dedup recipes, E2EE) over a multi-GB snapshot-shaped corpus streamed in
waves, and reports throughput plus RSS / open-fd growth between early and
late waves — flat curves mean no leak in the pump, session caches, or
segment store. ROADMAP 'long-haul soak' item.

Usage: python scripts/soak.py [--gb 2] [--wave-mb 256] [--chunk-mb 4]
"""

from __future__ import annotations

import argparse
import os
import resource
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


def open_fds() -> int:
    return len(os.listdir("/proc/self/fd"))


def rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gb", type=float, default=2.0)
    ap.add_argument("--wave-mb", type=int, default=256)
    ap.add_argument("--chunk-mb", type=int, default=4)
    args = ap.parse_args()

    import hashlib

    import numpy as np

    from tests.integration.harness import dispatch_file, make_pair, wait_complete

    # bound the receiver segment store well below the corpus so the soak can
    # observe the RSS plateau (the leak signal is growth PAST the cap)
    os.environ.setdefault("SKYPLANE_TPU_SEGSTORE_MB", "512")
    os.environ.setdefault("SKYPLANE_TPU_SEGSTORE_SPILL_MB", "1024")
    tmp = Path(tempfile.mkdtemp(prefix="soak_"))
    # core-time attribution over the whole soak (docs/observability.md
    # "Core-time profiling"): honor SKYPLANE_TPU_PROFILE_HZ like a gateway
    # would — off by default, a core-budget line in the summary when armed
    from skyplane_tpu.obs.profiler import get_profiler

    profiler = get_profiler()
    profiler.ensure_started()
    src, dst = make_pair(tmp, compress="zstd", dedup=True, encrypt=True, use_tls=True, num_connections=4)
    rng = np.random.default_rng(3)
    base_block = rng.integers(0, 256, args.wave_mb << 20, dtype=np.uint8)

    n_waves = max(1, int(args.gb * 1024) // args.wave_mb)
    total_bytes = 0
    t0 = time.perf_counter()
    stats = []
    try:
        for wave in range(n_waves):
            # each wave: previous wave's bytes with CLUSTERED write runs (the
            # snapshot-delta shape) — scattered single-byte mutations would
            # touch every CDC segment and make dedup degenerate
            n_sites = max(1, len(base_block) // (4 << 20))
            starts = rng.integers(0, len(base_block), n_sites)
            for s in starts:
                run = int(rng.geometric(1.0 / (64 << 10)))
                base_block[s : s + run] = rng.integers(0, 256, len(base_block[s : s + run]), dtype=np.uint8)
            src_file = tmp / f"wave{wave}.bin"
            base_block.tofile(src_file)  # no transient full-copy in the measured process
            dst_file = tmp / "out" / f"wave{wave}.bin"
            ids = dispatch_file(src, src_file, dst_file, chunk_bytes=args.chunk_mb << 20)
            wait_complete(src, ids, timeout=900)
            wait_complete(dst, ids, timeout=900)
            # full content check: dedup REF resolution + E2EE are in the loop,
            # and a wrong-segment substitution would be size-preserving
            want = hashlib.md5(memoryview(base_block)).hexdigest()
            got = hashlib.md5(dst_file.read_bytes()).hexdigest()
            assert got == want, f"wave {wave}: content mismatch"
            src_file.unlink()
            dst_file.unlink()
            total_bytes += len(base_block)
            stats.append({"wave": wave, "fds": open_fds(), "rss_mb": round(rss_mb(), 1)})
            print(f"wave {wave + 1}/{n_waves}: fds={stats[-1]['fds']} rss={stats[-1]['rss_mb']}MB", flush=True)
        dt = time.perf_counter() - t0
        gbps = total_bytes * 8 / 1e9 / dt
        first, last = stats[0], stats[-1]
        fd_growth = last["fds"] - first["fds"]
        # RSS must plateau once the bounded segment store fills: compare the
        # last two waves, not first-to-last (the fill phase is expected)
        late_growth_mb = stats[-1]["rss_mb"] - stats[-2]["rss_mb"] if len(stats) >= 2 else 0.0
        summary = (
            f"{total_bytes / (1 << 30):.2f} GiB in {dt:.0f}s = {gbps:.2f} Gbps logical; "
            f"fds {first['fds']} -> {last['fds']} (growth {fd_growth}), "
            f"peak RSS {last['rss_mb']} MB (late-wave growth {late_growth_mb:.0f} MB)"
        )
        if profiler.enabled:
            prof = profiler.cpu_breakdown()
            top = sorted(prof["stage_cpu_s"].items(), key=lambda kv: -kv[1])[:4]
            summary += (
                f"; core budget: {prof['cores_effective']} cores effective, "
                f"GIL wait {100.0 * prof['gil_wait_fraction']:.1f}%, "
                f"top CPU stages {', '.join(f'{s} {v:.1f}s' for s, v in top if v > 0)} "
                f"({prof['profile_samples']} samples, {prof['profile_samples_dropped']} dropped)"
            )
        failures = []
        if fd_growth > 32:
            failures.append(f"fd growth {fd_growth} > 32")
        if late_growth_mb > args.wave_mb:
            failures.append(f"late-wave RSS growth {late_growth_mb:.0f} MB > wave size {args.wave_mb} MB")
        if failures:
            print(f"\nSOAK FAIL: {summary}\n  " + "; ".join(failures), file=sys.stderr)
            sys.exit(1)
        print(f"\nSOAK OK: {summary}")
    finally:
        src.stop()
        dst.stop()


if __name__ == "__main__":
    main()
