#!/usr/bin/env python
"""Localhost byte-pump benchmark: sender/receiver socket throughput vs worker
count (VERDICT r1 weak #3: no measurement of the thread-model ceiling).

Runs the REAL data plane (two in-process daemons, framed sockets, windowed
acks) with codec/dedup/E2EE off so the measurement isolates the socket pump:
recv_into, framing, chunk-store IO, ack collection. Sweep ``--workers`` on a
multi-core gateway VM; if Gbps stops scaling with workers while cores idle,
the GIL is the ceiling and the pump should move to processes (reference uses
one process per sender connection / receiver socket).

That process-model pump now exists: ``SKYPLANE_TPU_PUMP_PROCS=N``
(gateway/pump.py, docs/datapath-performance.md "Multi-process pump") shards
the same stack across worker processes — export it before running this
sweep to measure the sharded plane, and see ``bench.py``'s
``wire_gbps_by_procs`` for the gated 1/2/4-proc scaling curve.

Usage:
    python scripts/bench_pump.py [--sizes-mb 256] [--chunk-mb 4] \
        [--workers 1,2,4,8] [--tls] [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tests"))


def run_once(total_mb: int, chunk_mb: int, workers: int, use_tls: bool) -> dict:
    from integration.harness import dispatch_file, make_pair, wait_complete

    tmp = Path(tempfile.mkdtemp(prefix="pump_"))
    src_file = tmp / "src.bin"
    src_file.write_bytes(os.urandom(total_mb << 20))
    dst_file = tmp / "out" / "dst.bin"
    src, dst = make_pair(tmp, compress="none", dedup=False, encrypt=False, use_tls=use_tls, num_connections=workers)
    try:
        t0 = time.perf_counter()
        ids = dispatch_file(src, src_file, dst_file, chunk_bytes=chunk_mb << 20)
        wait_complete(src, ids, timeout=600)
        wait_complete(dst, ids, timeout=600)
        dt = time.perf_counter() - t0
        assert dst_file.stat().st_size == src_file.stat().st_size
        return {
            "workers": workers,
            "total_mb": total_mb,
            "chunk_mb": chunk_mb,
            "tls": use_tls,
            "seconds": round(dt, 2),
            "gbps": round(total_mb * 8 / 1000 / dt, 3),
        }
    finally:
        src.stop()
        dst.stop()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", type=int, default=256)
    ap.add_argument("--chunk-mb", type=int, default=4)
    ap.add_argument("--workers", default="1,2,4,8")
    ap.add_argument("--tls", action="store_true")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()


    results = []
    for w in [int(x) for x in args.workers.split(",")]:
        r = run_once(args.sizes_mb, args.chunk_mb, w, args.tls)
        results.append(r)
        line = json.dumps(r) if args.json else (
            f"workers={r['workers']:>2}  {r['gbps']:.2f} Gbps  ({r['seconds']}s for {r['total_mb']} MB"
            + (", TLS)" if r["tls"] else ")")
        )
        print(line, flush=True)
    if len(results) > 1 and not args.json:
        base = results[0]["gbps"]
        peak = max(r["gbps"] for r in results)
        print(f"scaling: {peak / base:.2f}x from {results[0]['workers']} -> best worker count "
              f"({os.cpu_count()} cores on this host)")


if __name__ == "__main__":
    main()
