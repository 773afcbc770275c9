#!/usr/bin/env python
"""Fixed-vs-byte-scaled overhead report: sweep a loopback transfer across
corpus sizes, reconstruct each run's timeline from the fleet event log, and
fit ``wall = overhead_s + bytes / rate`` (obs/critical_path.py's least
squares). This is the standalone face of the ISSUE-20 attribution engine:

  PYTHONPATH=. JAX_PLATFORMS=cpu python scripts/report_overhead.py \
      --sizes-mb 1,4,16

prints the largest run's waterfall (critical path starred, largest fixed
phase named) plus the fit line; ``--json`` dumps the machine-readable report
scripts/bench_e2e.py banks and scripts/check_bench_json.py gates.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))



def one_timeline_run(tmp: Path, size_bytes: int, chunk_bytes: int) -> dict:
    """One loopback transfer through the real tracker, collector armed;
    returns the run's timeline report plus the (bytes, wall_s) fit sample."""
    import numpy as np

    from skyplane_tpu.api.config import TransferConfig
    from skyplane_tpu.api.tracker import TransferProgressTracker
    from skyplane_tpu.obs import configure_recorder
    from skyplane_tpu.obs.timeline import load_fleet_log, resolve_fleet_log, timeline_report
    from tests.integration.harness import HarnessCopyJob, StubDataplane, bind_gateway, make_pair

    fleet_dir = tmp / "fleet"
    os.environ["SKYPLANE_TPU_COLLECT"] = "1"
    os.environ["SKYPLANE_TPU_FLEET_DIR"] = str(fleet_dir)
    # fresh recorder per run: one fleet log per transfer, no cross-run tails
    configure_recorder()

    rng = np.random.default_rng(size_bytes & 0xFFFF)
    (tmp / "src").mkdir(exist_ok=True)
    (tmp / "out").mkdir(exist_ok=True)
    src_file = tmp / "src" / f"corpus_{size_bytes}.bin"
    dst_file = tmp / "out" / f"corpus_{size_bytes}.bin"
    src_file.write_bytes(rng.integers(0, 256, size_bytes, dtype=np.uint8).tobytes())

    src, dst = make_pair(tmp, compress="none", dedup=False, encrypt=False, use_tls=False)
    try:
        dp = StubDataplane([bind_gateway(src, "local:srcA")], [bind_gateway(dst, "local:dstB")])
        job = HarnessCopyJob(src_file, dst_file, chunk_bytes=chunk_bytes, batch_size=8)
        tracker = TransferProgressTracker(dp, [job], TransferConfig())
        t0 = time.monotonic()
        tracker.start()
        tracker.join(timeout=600)
        wall_s = time.monotonic() - t0
        if tracker.is_alive() or tracker.error is not None:
            raise RuntimeError(f"timeline sweep transfer failed: {tracker.error}")
        if dst_file.read_bytes() != src_file.read_bytes():
            raise RuntimeError("timeline sweep: destination bytes differ from source")
        log = resolve_fleet_log(tracker.transfer_id, fleet_dir)
        if log is None:
            raise RuntimeError(f"timeline sweep: no fleet event log in {fleet_dir}")
        report = timeline_report(load_fleet_log(log), job=tracker.transfer_id)
        report["bytes"] = size_bytes
        report["process_wall_s"] = wall_s
        return report
    finally:
        src.stop()
        dst.stop()


def run_sweep(sizes_bytes, chunk_bytes: int = 256 << 10) -> dict:
    """Sweep >=3 corpus sizes, fit the fixed/byte-scaled split, and bank the
    largest run's critical-path attribution. Returns the dict bench_e2e.py
    embeds in its summary (keys gated by check_bench_json.py)."""
    from skyplane_tpu.obs.critical_path import fit_fixed_overhead

    samples = []
    reports = []
    with tempfile.TemporaryDirectory(prefix="skyplane_timeline_") as tmp_s:
        for i, size in enumerate(sorted(sizes_bytes)):
            run_dir = Path(tmp_s) / f"run{i}"
            run_dir.mkdir()
            rep = one_timeline_run(run_dir, size, chunk_bytes)
            reports.append(rep)
            samples.append((float(size), rep["timeline"]["wall_s"]))
            print(
                f"size {size >> 20:4d} MiB: wall {rep['timeline']['wall_s']:.3f}s, "
                f"critical path {rep['critical_path']['critical_path_s']:.3f}s "
                f"({100.0 * rep['critical_path']['coverage']:.1f}%)",
                file=sys.stderr,
            )
    fit = fit_fixed_overhead(samples)
    largest = reports[-1]
    cp = largest["critical_path"]
    rate = fit["rate_bytes_per_s"] if fit else None
    return {
        "timeline_sizes_bytes": [int(b) for b, _ in samples],
        "timeline_samples": [{"bytes": int(b), "wall_s": round(w, 4)} for b, w in samples],
        "e2e_fixed_overhead_s": round(fit["overhead_s"], 4) if fit else None,
        "e2e_fit_rate_bytes_per_s": (round(rate, 1) if rate not in (None, float("inf")) else "inf"),
        "e2e_fit_r2": round(fit["r2"], 4) if fit else None,
        "timeline_critical_path_s": round(cp["critical_path_s"], 4),
        "timeline_wall_s": round(cp["wall_s"], 4),
        "timeline_coverage": round(cp["coverage"], 4),
        "timeline_fixed_s": round(cp["fixed_s"], 4),
        "timeline_scaled_s": round(cp["scaled_s"], 4),
        "timeline_largest_fixed_phase": cp["largest_fixed_phase"] or "",
        "timeline_phase_count": len(largest["timeline"]["phases"]),
        "timeline_text": largest["text"],
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", default="1,4,16", help=">=3 corpus sizes for the overhead fit")
    ap.add_argument("--chunk-kb", type=int, default=256)
    ap.add_argument("--json", action="store_true", help="print the machine-readable report")
    args = ap.parse_args()

    sizes = [int(float(s) * (1 << 20)) for s in args.sizes_mb.split(",")]
    if len(sizes) < 3 or len(set(sizes)) < 2:
        print("report_overhead: need >=3 sizes (>=2 distinct) for the fit", file=sys.stderr)
        return 2
    result = run_sweep(sizes, chunk_bytes=args.chunk_kb << 10)
    if args.json:
        out = dict(result)
        out.pop("timeline_text", None)
        out["metric"] = "timeline_overhead"
        out["unit"] = "seconds"
        print(json.dumps(out), flush=True)
        return 0
    print(result["timeline_text"])
    if result["e2e_fixed_overhead_s"] is not None:
        rate = result["e2e_fit_rate_bytes_per_s"]
        rate_str = "inf" if rate == "inf" else f"{float(rate) / 1e6:.1f} MB/s"
        print(
            f"\nfit over {len(result['timeline_sizes_bytes'])} sizes: "
            f"wall = {result['e2e_fixed_overhead_s']:.3f}s + bytes / {rate_str} "
            f"(r2={result['e2e_fit_r2']:.3f})"
        )
        print(f"largest fixed cost: {result['timeline_largest_fixed_phase']} — see waterfall above")
    else:
        print("\nfit unavailable (need >=3 samples across >=2 sizes)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
