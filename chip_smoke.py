#!/usr/bin/env python3
"""The quickest proof that the default gateway pair still runs on the chip.

One process holds the chip and runs a source and a sink ``GatewayDaemon`` as
threads (the loopback harness). The shipped ``TransferConfig()`` — tpu_zstd,
dedup, E2EE + TLS, 32 connections, 64 MiB chunks, batch window 8, CDC
4/16/64 KiB — moves a redundant snapshot chain (BASELINE.json configuration 3,
``bench.make_corpus`` from ``--seed``) from one POSIX directory to another:
requests go in through the source's control API, bytes come out at the sink.
Then it holds the result to the repo's own references and prints two JSON
lines on stdout: the run's record, and last the verdict
``{"ok": ..., "device": {"platform", "kind", "count"}}`` with the device as
jax reports it. Exit 0 only on a TPU, at full width, with every check
passed. Sets no JAX_PLATFORMS, starts no process that imports jax, takes no
lock; the compile cache goes where JAX_COMPILATION_CACHE_DIR points.

    python chip_smoke.py                      # on the chip: the real thing
    JAX_PLATFORMS=cpu SKYPLANE_TPU_FORCE_ACCEL_PATH=1 \\
      python chip_smoke.py --chunk-mb 1 --snapshots 2 --chunks-per-snapshot 2
                                              # rehearsal: same flow, never ok
"""

from __future__ import annotations

import argparse
import hashlib
import json
import logging
import os
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

MIN_CHUNKS = 24  # >= 3 full device windows of 8
MIN_SNAPSHOTS = 3
T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


def emit(record: dict, ok: bool, device: dict) -> None:
    """The record of the run, then the verdict: the last line of stdout is
    exactly ``ok`` and the device as jax reports it, nothing else."""
    print(json.dumps({"ok": ok, **record}))
    print(json.dumps({"ok": ok, "device": device}), flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk-mb", type=int, default=None, help="rehearsal only: the default is TransferConfig's")
    ap.add_argument("--snapshots", type=int, default=MIN_SNAPSHOTS)
    ap.add_argument("--chunks-per-snapshot", type=int, default=8, help="chunks in each snapshot's one file")
    ap.add_argument("--deadline-s", type=float, default=1100.0, help="overall limit; the process exits at it")
    ap.add_argument("--workdir", default=None, help="parent of the temporary data directory")
    return ap.parse_args(argv)


def arm_deadline(seconds: float, tmp: Path, device: dict) -> None:
    """The 600 s waits inside the batch runner cannot be the only backstop,
    and a hung device call cannot be interrupted: at the deadline, say so,
    remove the data and leave."""

    def fire():
        log(f"FAIL: overall deadline of {seconds:.0f}s reached")
        shutil.rmtree(tmp, ignore_errors=True)
        emit({"failed": [f"deadline of {seconds:.0f}s reached"]}, False, device)
        os._exit(3)

    timer = threading.Timer(seconds, fire)
    timer.daemon = True
    timer.start()


def file_digest(path: Path) -> str:
    h = hashlib.blake2b(digest_size=16)
    with open(path, "rb") as f:
        while block := f.read(8 << 20):
            h.update(block)
    return h.hexdigest()


def numpy_reference(arr, params):
    """Segment ends and digests by the plain numpy path (host_fallback gear
    hash, select_boundaries, per-segment lane sums): no native library, no
    device."""
    import numpy as np

    from skyplane_tpu.ops.cdc import select_boundaries
    from skyplane_tpu.ops.fingerprint import digests_from_lanes, segment_fp_lanes_numpy
    from skyplane_tpu.ops.host_fallback import boundary_candidates_host, gear_hash_host

    candidates = np.flatnonzero(boundary_candidates_host(gear_hash_host(arr), params.mask_bits))
    ends = select_boundaries(candidates, len(arr), params)
    return ends, digests_from_lanes(segment_fp_lanes_numpy(arr, ends), ends)


def first_compile_seconds(events) -> float | None:
    """The phase.first_compile interval the batch runner journals around its
    first window (ops/batch_runner.py), read as the timeline reads it."""
    from skyplane_tpu.obs.events import PH_FIRST_COMPILE
    from skyplane_tpu.obs.timeline import build_timeline

    for phase in build_timeline(events)["phases"]:
        if phase["kind"] == PH_FIRST_COMPILE:
            return round(phase["busy_s"], 3)
    return None


def main(argv=None) -> int:
    args = parse_args(argv)
    from skyplane_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    cache_empty_at_start = not (os.path.isdir(cache_dir) and os.listdir(cache_dir))

    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    device = {"platform": platform, "kind": kind, "count": len(devices)}

    from skyplane_tpu.api.config import TransferConfig

    cfg = TransferConfig()
    chunk_mb = args.chunk_mb if args.chunk_mb is not None else cfg.multipart_chunk_size_mb
    n_chunks = args.snapshots * args.chunks_per_snapshot
    full_size = chunk_mb == cfg.multipart_chunk_size_mb and n_chunks >= MIN_CHUNKS and args.snapshots >= MIN_SNAPSHOTS
    if platform != "tpu":
        log(f"jax found platform {platform!r} ({kind} x{len(devices)}), not a TPU: this run cannot pass")
        if full_size:
            return 2  # no result off the chip; a rehearsal names its (small) size

    failed: list = []

    def check(ok: bool, what: str) -> bool:
        if not ok:
            failed.append(what)
            log(f"CHECK FAILED: {what}")
        return bool(ok)

    # what jax compiles while we run, and what the persistent cache spared
    compiles: list = []
    cache_hits = [0]

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration" and duration >= 1.0:
            compiles.append({"fun": kw.get("fun_name", "?"), "s": round(duration, 2)})

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            cache_hits[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    # the daemons' one line on what they run on (gateway_daemon.py)
    daemon_lines: list = []

    class Capture(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            if "jax platform=" in msg:
                daemon_lines.append(msg)

    logging.getLogger("skyplane_tpu.fs").addHandler(Capture())

    import numpy as np

    import bench
    from skyplane_tpu import native
    from skyplane_tpu.chunk import Codec
    from skyplane_tpu.native import datapath as native_dp
    from skyplane_tpu.ops.cdc import cdc_and_fps_host
    from tests.integration.harness import dispatch_file, make_pair, wait_complete

    phases = {}
    src = dst = None
    tmp = Path(tempfile.mkdtemp(prefix="chip_smoke_", dir=args.workdir))
    arm_deadline(args.deadline_s, tmp, device)
    result: dict = {}
    try:
        # ---- corpus: the snapshot chain, one file per snapshot
        t = time.monotonic()
        chunks = bench.make_corpus(
            seed=args.seed, chunk_mb=chunk_mb, n_snapshots=args.snapshots, chunks_per_snapshot=args.chunks_per_snapshot
        )
        src_dir, dst_dir = tmp / "source", tmp / "sink"
        src_dir.mkdir()
        dst_dir.mkdir()
        files = []
        for s in range(args.snapshots):
            path = src_dir / f"snapshot_{s:02d}.img"
            digest = hashlib.blake2b(digest_size=16)
            with open(path, "wb") as f:
                for c in chunks[s * args.chunks_per_snapshot : (s + 1) * args.chunks_per_snapshot]:
                    f.write(c)
                    digest.update(c)
            files.append((path, dst_dir / path.name, digest.hexdigest()))
        corpus_bytes = sum(len(c) for c in chunks)
        # rows for the reference check: one full chunk, one tail-shaped row
        # (shorter than its power-of-two bucket, so it is zero-padded)
        ref_full = np.frombuffer(chunks[0], np.uint8)
        ref_tail = ref_full[: len(ref_full) * 5 // 8 + 4321]
        del chunks
        phases["corpus_s"] = round(time.monotonic() - t, 2)
        log(f"corpus: {args.snapshots} snapshots x {args.chunks_per_snapshot} x {chunk_mb} MiB = {corpus_bytes >> 20} MiB in {phases['corpus_s']}s")

        # ---- the pair, as TransferConfig() ships it
        t = time.monotonic()
        src, dst = make_pair(
            tmp,
            compress=cfg.compress,
            dedup=cfg.dedup,
            encrypt=cfg.encrypt_e2e,
            use_tls=cfg.encrypt_socket_tls,
            num_connections=cfg.num_connections,
        )
        runner = src.daemon.batch_runner
        phases["start_pair_s"] = round(time.monotonic() - t, 2)
        for line in daemon_lines:
            log(line)
        check(runner is not None, "source gateway has no device batch runner: it fell to the host data path")
        check(src.daemon.cdc_params == cfg.cdc_params(), "gateway CDC parameters differ from TransferConfig()")
        mesh = runner.mesh if runner is not None else None
        mesh_label = dict(mesh.shape) if mesh is not None else None

        # ---- the transfer: a snapshot is dispatched when the one before it
        # has landed and verified, as a backup chain arrives
        t_first_dispatch = time.monotonic()
        identical = []
        for path, out, want in files:
            ids = dispatch_file(src, path, out, chunk_bytes=chunk_mb << 20)
            wait_complete(src, ids, timeout=args.deadline_s)
            wait_complete(dst, ids, timeout=args.deadline_s)
            identical.append(out.exists() and file_digest(out) == want)
            check(identical[-1], f"sink file {out.name} is not byte-identical to its source")
            log(f"{path.name}: {len(ids)} chunks landed and verified at +{time.monotonic() - t_first_dispatch:.1f}s")
        transfer_s = round(time.monotonic() - t_first_dispatch, 3)

        # ---- what the gateways say happened
        for gw in (src, dst):
            errors = gw.get("errors", timeout=30).json()["errors"]
            check(not errors, f"{gw.daemon.gateway_id} reports errors: {str(errors[:1])[:500]}")
        counters = src.get("profile/compression", timeout=30).json()
        check(counters["batch_rows"] == n_chunks, f"batch_rows {counters['batch_rows']} != chunks {n_chunks}: not every chunk went through the device runner")
        check(counters["batch_windows"] >= args.snapshots, f"batch_windows {counters['batch_windows']} < {args.snapshots}")
        check(counters["stage_failures"] == 0, f"stage_failures {counters['stage_failures']} != 0: async device staging failed")
        check(counters["ref_segments"] > 0, "no REF segments: dedup found nothing in a snapshot chain")
        check(counters["wire_bytes"] < counters["raw_bytes"], "wire bytes not below raw bytes")
        decode = dst.get("profile/decode", timeout=30).json()
        wire_codecs = sorted({ev["codec"] for ev in decode["events"]})
        check(len(decode["events"]) == n_chunks, f"sink decoded {len(decode['events'])} frames, expected {n_chunks}")
        check(
            wire_codecs == [int(Codec.TPU_BLOCK_ZSTD)],
            f"codec ids on the wire {wire_codecs} != [{int(Codec.TPU_BLOCK_ZSTD)}] (tpu_zstd): the gateway substituted another codec",
        )
        if mesh is not None:
            check(counters["spmd_batches"] == counters["batch_windows"], f"mesh active but spmd_batches {counters['spmd_batches']} != batch_windows {counters['batch_windows']}")
        events = src.get("events", params={"since": 0}, timeout=30).json()["events"]
        first_compile_s = first_compile_seconds(events)

        # ---- the device against the plain references, at the real shape,
        # through the source daemon's own runner, outside the timed transfer
        t = time.monotonic()
        reference = {}
        if runner is not None:
            for name, row in (("full_chunk", ref_full), ("tail_row", ref_tail)):
                ends, fps = runner.cdc_and_fps(row)
                native_ends, native_fps = cdc_and_fps_host(row, runner.cdc_params)
                np_ends, np_fps = numpy_reference(row, runner.cdc_params)
                same = (
                    np.array_equal(ends, native_ends)
                    and np.array_equal(ends, np_ends)
                    and list(fps) == list(native_fps) == list(np_fps)
                )
                reference[name] = {"bytes": len(row), "segments": len(ends), "identical": bool(same)}
                check(same, f"device segment ends / fingerprints differ from the host references on {name} ({len(row)} bytes)")
                log(f"reference {name}: {len(row)} bytes, {len(ends)} segments, identical={same}")
            if mesh is not None:
                # four chips visible must mean four chips working: where do
                # the fingerprint output's shards of one window sit?
                rows = [ref_full] * runner.max_batch
                pending = runner._fused.dispatch(np.stack(rows), [len(ref_full)] * len(rows))
                on = pending.lane_devices()
                pending.lanes()
                check(on == set(mesh.devices.flat), f"fingerprint shards sit on {len(on)} of {mesh.devices.size} mesh devices")
        phases["reference_s"] = round(time.monotonic() - t, 2)

        # ---- the native host library the data path leans on
        native_ok = native_dp.available()
        info = native.build_info()
        check(native_ok, "native.datapath.available() is false: the numpy paths served")
        check(native_ok and (info["built"] or info["stamp"] == native.build_stamp()), "libskydp was neither built in this run nor matches this host's build stamp")

        mem = devices[0].memory_stats() or {}
        check(full_size, f"reduced below the contract's size ({chunk_mb} MiB chunks, {n_chunks} chunks): a rehearsal never passes")
        check(platform == "tpu", f"platform is {platform!r}, not 'tpu'")
        import jaxlib

        try:
            import libtpu

            libtpu_version = libtpu.__version__
        except ImportError:
            libtpu_version = None
        result = {
            "platform": platform,
            "device_kind": kind,
            "n_devices": len(devices),
            "mesh": mesh_label,
            "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu_version},
            "config": {
                "compress": cfg.compress,
                "dedup": cfg.dedup,
                "encrypt_e2e": cfg.encrypt_e2e,
                "tls": cfg.encrypt_socket_tls,
                "num_connections": cfg.num_connections,
                "chunk_mb": chunk_mb,
                "batch_window": runner.max_batch if runner is not None else None,
                "cdc_bytes": [cfg.cdc_min_bytes, cfg.cdc_avg_bytes, cfg.cdc_max_bytes],
            },
            "reduced": {
                "corpus": f"BASELINE.json configuration 3 is a 1 TB snapshot corpus; this is {corpus_bytes >> 20} MiB "
                f"(~{(10**12) // corpus_bytes}x smaller), {args.snapshots} snapshots of one {args.chunks_per_snapshot * chunk_mb} MiB file, "
                "cut to fit a smoke's run time",
                "layout": "source and sink gateway share one process and one chip; loopback, no WAN",
            },
            "seed": args.seed,
            "corpus_bytes": corpus_bytes,
            "chunks": n_chunks,
            "counters": {
                k: counters.get(k, 0)
                for k in (
                    "batch_rows", "batch_windows", "batch_padded_rows", "spmd_batches", "stage_failures",
                    "donated_batches", "segments", "ref_segments", "literal_bytes", "raw_bytes", "wire_bytes",
                )
            }
            | {"ref_segments_resolved": decode["counters"].get("ref_segments_resolved", 0)},  # the sink's
            "wire_codecs": wire_codecs,
            "byte_identical": all(identical),
            "reference": reference,
            "native": {"available": bool(native_ok), "built_this_run": bool(info.get("built"))},
            "first_compile_s": first_compile_s,
            "transfer_s": transfer_s,
            "device_wait_ns": counters["device_wait_ns"],
            "hbm": {"peak_bytes_in_use": mem.get("peak_bytes_in_use"), "bytes_limit": mem.get("bytes_limit")},
            "compile_cache": {"dir": cache_dir, "empty_at_start": cache_empty_at_start, "hits": cache_hits[0]},
            "compiles": compiles,
            "phases": phases,
            "failed": failed,
        }
    finally:
        for gw in (src, dst):
            if gw is not None:
                gw.stop()
        shutil.rmtree(tmp, ignore_errors=True)
    result["wall_s"] = round(time.monotonic() - T0, 1)
    emit(result, not failed, device)
    return 0 if not failed else 1


if __name__ == "__main__":
    sys.exit(main())
