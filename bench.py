#!/usr/bin/env python
"""Benchmark: sender-side data-path effective throughput (dedup + compress).

Measures the TPU data path (CDC + 8-lane fingerprints + dedup recipes +
blockpack/zstd, DataPathProcessor) against TWO CPU baselines on a synthetic
redundant snapshot corpus (the BASELINE.json workload shape):

- ``vs_baseline`` / ``baseline_gbps``: plain zstd-3 per chunk (a stronger
  modern codec than the reference ships — kept for round-over-round
  comparability);
- ``vs_baseline_lz4`` / ``baseline_lz4_gbps``: REAL LZ4 frames via the system
  liblz4 — the exact codec family the reference runs on gateway CPUs
  (skyplane/gateway/operators/gateway_operator.py:358-361 uses
  ``lz4.frame.compress``, which wraps the same library). LZ4 is much faster
  per core than zstd-3, so this is the harder, honest bar; when the raw-Gbps
  ratio loses, ``wan_crossover_vs_lz4_gbps`` reports the WAN bandwidth below
  which the dedup path's ~6x wire reduction still wins end-to-end
  (planner/estimator.wan_crossover_gbps).

Effective throughput = raw corpus bits / wall time of producing wire bytes —
the number that bounds what a gateway VM can push when the WAN is not the
bottleneck; with dedup it also collapses wire bytes, which BASELINE.md's
north-star metric (effective Gbps post-dedup) credits.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": "Gbps", "vs_baseline": N, ...}
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Optional

import numpy as np

CHUNK_MB = int(os.environ.get("SKYPLANE_BENCH_CHUNK_MB", "8"))
N_SNAPSHOTS = int(os.environ.get("SKYPLANE_BENCH_SNAPSHOTS", "4"))
CHUNKS_PER_SNAPSHOT = int(os.environ.get("SKYPLANE_BENCH_SNAP_CHUNKS", "6"))
ZERO_FRAC = 0.25  # sparse filesystem pages (free extents)
BLOCK = 4096


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


WRITE_SITE_FRAC = 0.004  # clustered write sites between snapshots
WRITE_RUN_BLOCKS = 8  # mean blocks touched per write site


def _clustered_mask(rng, n_blocks: int, site_frac: float, mean_run: int) -> np.ndarray:
    """Mask of blocks covered by randomly-placed runs (disk writes / free
    extents are contiguous, not scattered)."""
    mask = np.zeros(n_blocks, bool)
    n_sites = max(1, int(n_blocks * site_frac))
    starts = rng.integers(0, n_blocks, n_sites)
    lengths = rng.geometric(1.0 / mean_run, n_sites)
    for s, l in zip(starts, lengths):
        mask[s : s + l] = True
    return mask


def _filesystem_content(rng, n_bytes: int) -> np.ndarray:
    """Content with a realistic entropy mix for a VM/filesystem snapshot.

    Pure random bytes would be the LEAST representative choice: they hit
    zstd's incompressible fast path (flattering the CPU baseline's speed)
    and model no real corpus — disks hold text/logs/configs, structured
    binary records (databases, executables), and some already-compressed
    media. Composition below: ~35% text-like (6-bit symbol entropy),
    ~25% structured records (strong LZ matches), ~25% zero extents
    (clustered, applied by the caller), rest incompressible."""
    out = rng.integers(0, 256, n_bytes, dtype=np.uint8)  # base: incompressible
    n_blocks = n_bytes // BLOCK
    # text-like runs: token stream over a small vocabulary (logs/configs/
    # source repeat identifiers and phrases — that token reuse, not symbol
    # distribution, is what makes real text compress well)
    text = _clustered_mask(rng, n_blocks, 0.35 / 24, 24)
    tmask = np.repeat(text, BLOCK)
    n_text = int(tmask.sum())
    if n_text:
        vocab = ((rng.integers(0, 256, (512, 8), dtype=np.uint8) & 0x3F) | 0x20).reshape(512, 8)
        toks = rng.integers(0, 512, n_text // 8 + 1)
        out[tmask] = vocab[toks].reshape(-1)[:n_text]
    # structured records: repeat a per-run record with sparse field edits
    # (database pages, arrays of structs). Tiling gives zstd real matches.
    rec = _clustered_mask(rng, n_blocks, 0.25 / 24, 24) & ~text
    run_id = np.cumsum(rec & ~np.concatenate([[False], rec[:-1]]))  # per-run index
    out2d = out.reshape(n_blocks, BLOCK)
    for rid in np.unique(run_id[rec]):
        blocks = np.flatnonzero(rec & (run_id == rid))
        record = rng.integers(0, 256, 64, dtype=np.uint8)
        span = np.tile(record, (len(blocks) * BLOCK) // 64)
        # sparse field mutations so runs are not pure repeats
        edits = rng.integers(0, len(span), max(1, len(span) // 32))
        span[edits] = rng.integers(0, 256, len(edits), dtype=np.uint8)
        out2d[blocks] = span.reshape(len(blocks), BLOCK)
    return out


def make_corpus(
    seed: int = 0,
    chunk_mb: int = CHUNK_MB,
    n_snapshots: int = N_SNAPSHOTS,
    chunks_per_snapshot: int = CHUNKS_PER_SNAPSHOT,
):
    """Synthetic snapshot-chain corpus, BASELINE.json workload shape: each
    snapshot is the previous one with a small set of *clustered* writes
    applied (real snapshot deltas are localized); zero pages form contiguous
    free extents; content has a realistic entropy mix (_filesystem_content).
    A chain of ``n_snapshots`` models an incremental backup corpus —
    conservative vs production chains, which often run to dozens of
    snapshots. Returns the chunks snapshot by snapshot, in order."""
    rng = np.random.default_rng(seed)
    chunk_bytes = chunk_mb << 20
    n_blocks = chunk_bytes // BLOCK
    snap = []
    for _ in range(chunks_per_snapshot):
        blocks = _filesystem_content(rng, chunk_bytes).reshape(n_blocks, BLOCK)
        # zero extents: clustered runs totalling ~ZERO_FRAC of the chunk
        zero_mask = _clustered_mask(rng, n_blocks, ZERO_FRAC / 16, 16)
        blocks[zero_mask] = 0
        snap.append(blocks)
    chunks = [b.reshape(-1).tobytes() for b in snap]
    for _ in range(n_snapshots - 1):  # each snapshot: clustered writes on the last
        nxt = []
        for b in snap:
            b2 = b.copy()
            mut = _clustered_mask(rng, n_blocks, WRITE_SITE_FRAC, WRITE_RUN_BLOCKS)
            b2[mut] = _filesystem_content(rng, int(mut.sum()) * BLOCK).reshape(-1, BLOCK)
            nxt.append(b2)
        chunks.extend(b.reshape(-1).tobytes() for b in nxt)
        snap = nxt
    return chunks


def batch_chunks(workers: int) -> int:
    """Device batch-window size (accelerator path only). min(8, workers)
    keeps the default 24-chunk corpus in exactly-full windows (3x8) with
    2x window overlap at 16 workers — zero padded rows in the timed region.
    SKYPLANE_BENCH_BATCH overrides for dispatch-latency experiments (pair
    it with SKYPLANE_BENCH_SNAP_CHUNKS so windows stay full)."""
    if os.environ.get("SKYPLANE_BENCH_BATCH"):
        return int(os.environ["SKYPLANE_BENCH_BATCH"])
    return min(8, workers)


def n_workers() -> int:
    """Gateway sender pool size. On an accelerator the workers mostly wait on
    device round trips, so the pool is 2x the batch window to keep a second window forming while
    the first is in flight; on pure CPU extra threads just fight over cores."""
    if os.environ.get("SKYPLANE_BENCH_WORKERS"):
        return int(os.environ["SKYPLANE_BENCH_WORKERS"])
    from skyplane_tpu.ops.backend import on_accelerator

    return 16 if on_accelerator() else min(8, os.cpu_count() or 1)


def _effective_codec(name: str) -> str:
    from skyplane_tpu.ops.pipeline import effective_codec_name

    return effective_codec_name(name)


def pick_codecs():
    """(ours codec name, baseline label, baseline per-chunk encoder).

    Degrades gracefully when ``zstandard`` is not installed (minimal
    containers): the in-repo native_lz codec stands in on BOTH sides so the
    bench — and the devloop bench-smoke schema gate — still runs; the JSON
    labels the substitution (``codec_ours``/``codec_baseline``) so rounds on
    different hosts are never naively compared."""
    try:
        import zstandard

        return "tpu_zstd", "zstd-3", lambda c: len(zstandard.ZstdCompressor(level=3).compress(c))
    except ImportError:
        from skyplane_tpu.ops.codecs import get_codec

        enc = get_codec("native_lz").encode
        log("WARN: zstandard not installed; benchmarking with native_lz for ours AND the baseline")
        return "native_lz", "native_lz", lambda c: len(enc(c))


def bench_ours(chunks, workers: Optional[int] = None, codec_name: Optional[str] = None) -> dict:
    """Model the gateway sender pool: N worker threads share one processor and
    one destination dedup index; fingerprints commit after 'delivery'
    (numpy/zstd/XLA all release the GIL, matching the real operator pool)."""
    from concurrent.futures import ThreadPoolExecutor

    from skyplane_tpu.ops.cdc import CDCParams
    from skyplane_tpu.ops.dedup import SenderDedupIndex
    from skyplane_tpu.ops.pipeline import DataPathProcessor, effective_codec_name

    from skyplane_tpu.ops.backend import on_accelerator

    if workers is None:
        workers = n_workers()
    cdc = CDCParams()
    batch_runner = None
    if on_accelerator():
        # mirror the gateway: workers share a micro-batching device runner,
        # sharded over a mesh when multiple chips are attached (the
        # production configuration on TPU slices). workers > max_batch keeps
        # a second window forming while the first is in flight.
        from skyplane_tpu.ops.batch_runner import DeviceBatchRunner
        from skyplane_tpu.parallel.datapath_spmd import maybe_default_mesh

        mesh = maybe_default_mesh()
        if mesh is not None:
            log(f"batch runner sharded over mesh {dict(mesh.shape)}")
        batch = batch_chunks(workers)
        log(f"device batch window: {batch} chunks, {workers} workers")
        batch_runner = DeviceBatchRunner(cdc_params=cdc, max_batch=batch, mesh=mesh)
    # warm-up: compile all shape buckets (separate corpus so the index stays
    # cold). With a batch runner, submit concurrently so the BATCHED kernel
    # shapes compile now rather than inside the timed region.
    # same hardware-aware codec choice the gateway daemon makes at operator
    # construction (tpu_zstd -> zstd on hosts with no accelerator)
    if codec_name is None:
        codec_name = pick_codecs()[0]
    codec_name = effective_codec_name(codec_name)
    warm_proc = DataPathProcessor(codec_name=codec_name, dedup=True, cdc_params=cdc, batch_runner=batch_runner)
    warm_rng = np.random.default_rng(99)
    t_warm = time.perf_counter()
    if batch_runner is not None:
        warm_chunks = [warm_rng.integers(0, 256, CHUNK_MB << 20, dtype=np.uint8).tobytes() for _ in range(workers)]
        with ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(lambda c: warm_proc.process(c, SenderDedupIndex()), warm_chunks))
    else:
        warm = warm_rng.integers(0, 256, CHUNK_MB << 20, dtype=np.uint8).tobytes()
        warm_proc.process(warm, SenderDedupIndex())
    log(f"warm-up done in {time.perf_counter() - t_warm:.1f}s ({workers} workers)")

    # best-of-N (see bench_baseline): each rep gets a FRESH processor and
    # dedup index — a warm index would turn rep 2+ into an all-REF fast path
    best: Optional[dict] = None
    for _ in range(max(1, BENCH_REPS)):
        proc = DataPathProcessor(codec_name=codec_name, dedup=True, cdc_params=cdc, batch_runner=batch_runner)
        index = SenderDedupIndex()

        def one(c: bytes) -> int:
            p = proc.process(c, index)
            for fp, size in p.new_fingerprints:  # frame delivered -> commit (sender contract)
                index.add(fp, size)
            return len(p.wire_bytes)

        # the runner and its pool are SHARED across warmup + reps; snapshot
        # before the timed region so the reported counters describe THIS rep
        pre = proc.stats.as_dict()
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=workers) as pool:
            wire = sum(pool.map(one, chunks))
        dt = time.perf_counter() - t0
        if best is None or dt < best["seconds"]:
            raw = sum(len(c) for c in chunks)
            stats = _rep_counter_delta(pre, proc.stats.as_dict(), batch_runner.max_batch if batch_runner else 0)
            best = {"seconds": dt, "raw_bytes": raw, "wire_bytes": wire, "stats": stats}
    return best


def _rep_counter_delta(pre: dict, post: dict, max_batch: int) -> dict:
    """Per-rep view of the shared-subsystem counters: cumulative pool/batch/
    donation counts become this-rep deltas, and the derived ratios are
    recomputed from the deltas. Gauges (idle/outstanding) stay as-is."""
    out = dict(post)
    for k, v in post.items():
        if k.startswith(("pool_", "batch_", "donated_", "stage_")) and k not in (
            "pool_hit_rate", "pool_idle_bytes", "pool_outstanding", "batch_occupancy",
        ):
            out[k] = v - pre.get(k, 0)
    lookups = out.get("pool_hits", 0) + out.get("pool_misses", 0)
    out["pool_hit_rate"] = round(out.get("pool_hits", 0) / lookups, 4) if lookups else 0.0
    cap = out.get("batch_windows", 0) * max_batch
    out["batch_occupancy"] = round(out.get("batch_rows", 0) / cap, 4) if cap else 0.0
    return out


BENCH_REPS = int(os.environ.get("SKYPLANE_BENCH_REPS", "3"))

# decode-counter keys reported in the result's decode_counters section —
# the receiver-side mirror of datapath_counters; check_bench_json.py (and so
# the devloop bench-smoke) asserts they are always present
DECODE_COUNTER_KEYS = (
    "store_mem_hits",
    "store_spill_reads",
    "store_lock_held_disk_reads",
    "store_stripe_contention",
    "store_ref_wait_ns",
    "pool_hit_rate",
    "verify_total",
    "verify_batched",
    # parse_recipe's literal pass (PR 30): segments per call is how often the batch engages
    "literal_pass_ns",
    "literal_segments_verified",
    "literal_verify_calls",
)


def encode_frames_for_decode(chunks, codec_name: str):
    """Encode the corpus once through the sender path into framed recipe
    payloads (wire header + wire bytes), committing fingerprints after each
    chunk — so later chunks REF earlier ones, exactly the stream a receiver
    sees from one well-behaved sender."""
    from skyplane_tpu.chunk import ChunkFlags, Codec, WireProtocolHeader
    from skyplane_tpu.ops.cdc import CDCParams
    from skyplane_tpu.ops.dedup import SenderDedupIndex
    from skyplane_tpu.ops.pipeline import DataPathProcessor

    proc = DataPathProcessor(codec_name=codec_name, dedup=True, cdc_params=CDCParams())
    index = SenderDedupIndex()
    frames = []
    for i, c in enumerate(chunks):
        p = proc.process(c, index)
        for fp, size in p.new_fingerprints:
            index.add(fp, size)
        flags = ChunkFlags.RECIPE | (ChunkFlags.COMPRESSED if p.codec != Codec.NONE else 0)
        frames.append(
            (
                WireProtocolHeader(
                    chunk_id=f"{i:032x}",
                    data_len=len(p.wire_bytes),
                    raw_data_len=p.raw_len,
                    codec=int(p.codec),
                    flags=int(flags),
                    fingerprint=p.fingerprint,
                ),
                p.wire_bytes,
            )
        )
    return frames


def bench_decode(frames, workers=None) -> dict:
    """Receiver decode-path throughput: parallel restore of the framed corpus
    through a fresh SegmentStore per rep (the decode pool's hot loop —
    pooled output assembly, striped store, per-fp ref waits — without socket
    framing). Workers decode OUT OF ORDER like the gateway's decode pool;
    refs to earlier chunks' literals resolve via the store's arrival events."""
    from concurrent.futures import ThreadPoolExecutor

    from skyplane_tpu.ops.dedup import SegmentStore
    from skyplane_tpu.ops.pipeline import DataPathProcessor

    if workers is None:
        workers = int(os.environ.get("SKYPLANE_BENCH_DECODE_WORKERS", "0")) or min(8, os.cpu_count() or 1)
    best = None
    for _ in range(max(1, BENCH_REPS)):
        # fresh store + receiver per rep: a warm store would turn rep 2+ into
        # an all-mem-hit fast path that no first-contact receiver ever sees
        store = SegmentStore()
        recv = DataPathProcessor(codec_name="none", dedup=True)

        def one(frame) -> int:
            header, wire = frame
            out = recv.restore(wire, header, store=store, ref_wait_timeout=60.0, pooled=True)
            n = len(out)
            if not isinstance(out, (bytes, bytearray)):
                out.release()  # recycle the pooled output buffer
            return n

        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=workers) as pool:
            restored = sum(pool.map(one, frames))
        dt = time.perf_counter() - t0
        assert restored == sum(h.raw_data_len for h, _ in frames), "decode bench restored wrong byte count"
        if best is None or dt < best["seconds"]:
            counters = {**store.counters(), **recv.bufpool.counters(), **recv.verify_counters()}
            best = {"seconds": dt, "raw_bytes": restored, "counters": counters, "workers": workers}
    return best


# sender wire-counter keys reported in the result's wire_counters section —
# the wire mirror of datapath_counters/decode_counters; check_bench_json.py
# (and so the devloop bench-smoke) asserts they are always present
WIRE_COUNTER_KEYS = (
    "frames_pipelined",
    "wire_stall_ns",
    "ack_lag_ns",
    "wire_inflight_bytes",
    "streams_open",
    "windows",
    "wire_stall_ns_per_window",
    "serial_drain_ns_per_window",
)

WIRE_FRAMES = int(os.environ.get("SKYPLANE_BENCH_WIRE_FRAMES", "48"))
WIRE_FRAME_KB = int(os.environ.get("SKYPLANE_BENCH_WIRE_FRAME_KB", "256"))
WIRE_WINDOW = 8
WIRE_ACK_DELAY_S = 0.002  # emulated per-frame receiver service time (~WAN ack lag)


def _wire_ack_server():
    """Loopback receiver double for the wire bench: parses frames, services
    each for WIRE_ACK_DELAY_S (standing in for decode + RTT), acks in frame
    order. Returns (port, stop)."""
    import socket as socket_mod
    import threading

    from skyplane_tpu.chunk import WireProtocolHeader

    listener = socket_mod.socket(socket_mod.AF_INET, socket_mod.SOCK_STREAM)
    listener.setsockopt(socket_mod.SOL_SOCKET, socket_mod.SO_REUSEADDR, 1)
    listener.bind(("127.0.0.1", 0))
    listener.listen(16)
    port = listener.getsockname()[1]

    def conn_loop(conn):
        try:
            while True:
                header = WireProtocolHeader.from_socket(conn)
                remaining = header.data_len
                while remaining:
                    got = conn.recv(min(1 << 20, remaining))
                    if not got:
                        return
                    remaining -= len(got)
                time.sleep(WIRE_ACK_DELAY_S)
                conn.sendall(b"\x06")  # ACK_BYTE
        except (ConnectionError, OSError):
            pass
        finally:
            conn.close()

    def accept_loop():
        while True:
            try:
                conn, _ = listener.accept()
            except OSError:
                return
            conn.setsockopt(socket_mod.IPPROTO_TCP, socket_mod.TCP_NODELAY, 1)
            threading.Thread(target=conn_loop, args=(conn,), daemon=True).start()

    threading.Thread(target=accept_loop, daemon=True).start()
    return port, listener.close


def _wire_frames():
    from skyplane_tpu.chunk import WireProtocolHeader

    payload = b"\x5a" * (WIRE_FRAME_KB << 10)
    return [
        (WireProtocolHeader(chunk_id=f"{i:032x}", data_len=len(payload), raw_data_len=len(payload)), payload)
        for i in range(WIRE_FRAMES)
    ]


def bench_wire() -> dict:
    """Local-loopback sender wire bench: the serial wire loop (stream one
    window, then block collecting its acks — a full frame+ack drain per
    window boundary) vs the pipelined engine (operators/sender_wire.py) over
    IDENTICAL frames. Reports the engine's stable wire-counter schema plus
    the per-window stall comparison the acceptance gate checks:
    ``wire_stall_ns_per_window`` (pipelined socket transmit-idle with work
    queued) must sit strictly below ``serial_drain_ns_per_window``."""
    import socket as socket_mod
    import threading

    from skyplane_tpu.gateway.operators.sender_wire import EngineCallbacks, SenderWireEngine, WireFrame

    frames = _wire_frames()
    n_windows = (len(frames) + WIRE_WINDOW - 1) // WIRE_WINDOW
    port, stop_server = _wire_ack_server()
    try:
        # --- serial reference: stream a window, drain its acks, repeat ---
        sock = socket_mod.create_connection(("127.0.0.1", port), timeout=30)
        sock.setsockopt(socket_mod.IPPROTO_TCP, socket_mod.TCP_NODELAY, 1)
        serial_drain_ns = 0
        t_serial = time.perf_counter()
        for w in range(0, len(frames), WIRE_WINDOW):
            window = frames[w : w + WIRE_WINDOW]
            for header, payload in window:
                header.to_socket(sock)
                sock.sendall(payload)
            t0 = time.perf_counter_ns()  # last frame sent: the socket goes idle here
            for _ in window:
                ack = sock.recv(1)
                assert ack == b"\x06", f"wire bench serial leg got {ack!r}"
            serial_drain_ns += time.perf_counter_ns() - t0
        serial_seconds = time.perf_counter() - t_serial
        sock.close()

        # --- pipelined engine over the same frames ---
        done = threading.Event()
        delivered = [0]

        class _Count(EngineCallbacks):
            def on_delivered(self, frame):
                delivered[0] += 1
                if delivered[0] >= len(frames):
                    done.set()

            def on_fatal(self, msg):
                log(f"WARN: wire bench engine fatal: {msg}")
                done.set()

        def connect():
            s = socket_mod.create_connection(("127.0.0.1", port), timeout=30)
            s.setsockopt(socket_mod.IPPROTO_TCP, socket_mod.TCP_NODELAY, 1)
            return s

        engine = SenderWireEngine(connect, _Count(), inflight_limit_bytes=64 << 20, frame_ahead=4, name="bench-wire")
        t_pipe = time.perf_counter()
        for w in range(0, len(frames), WIRE_WINDOW):
            engine.note_window()
            for header, payload in frames[w : w + WIRE_WINDOW]:
                engine.submit(lambda pending, h=header, p=payload: WireFrame(None, h, p))
        done.wait(timeout=60)
        pipe_seconds = time.perf_counter() - t_pipe
        counters = engine.counters()  # snapshot BEFORE close zeroes the gauges
        engine.close()
        if delivered[0] < len(frames):
            log(f"WARN: wire bench pipelined leg delivered {delivered[0]}/{len(frames)} frames")
        wire = {k: counters.get(k, 0) for k in WIRE_COUNTER_KEYS if k in counters}
        wire["windows"] = counters.get("windows", n_windows)
        wire["wire_stall_ns_per_window"] = counters.get("wire_stall_ns", 0) // max(1, n_windows)
        wire["serial_drain_ns_per_window"] = serial_drain_ns // max(1, n_windows)
        wire["serial_seconds"] = round(serial_seconds, 6)
        wire["pipelined_seconds"] = round(pipe_seconds, 6)
        return wire
    finally:
        stop_server()


# trace-derived per-stage latency breakdown (docs/observability.md): where a
# chunk's wall time goes across the lifecycle. check_bench_json.py requires
# every key, so a future perf PR can prove WHERE it moved time. The stage ->
# span mapping and the arithmetic live in obs/collector.py (STAGE_SPANS /
# stage_breakdown) — the SAME code path `skyplane-tpu bottleneck` aggregates
# fleet traces with, so the two reconcile by construction.
TRACE_STAGES = ("frame", "send_stall", "ack_lag", "decode", "store")


def bench_trace(untraced_wall_s: float) -> dict:
    """Fully-sampled loopback sender→receiver transfer through the REAL
    instrumented paths (wire engine -> GatewayReceiver decode pool -> chunk
    store), exporting Chrome trace-event JSON and deriving the per-stage
    latency breakdown from it. Also measures the DISABLED tracer's span cost
    directly — ``trace_overhead_pct`` is the projected throughput tax of the
    instrumentation with tracing off (the <2% acceptance gate in
    scripts/check_bench_json.py), computed from measured no-op span cost
    rather than wall-clock noise between runs.

    Set SKYPLANE_BENCH_TRACE_OUT=<path> to write the exported trace (the
    devloop trace-smoke step validates it with scripts/check_trace_json.py).
    """
    import queue as queue_mod
    import shutil
    import socket as socket_mod
    import tempfile
    import threading

    from skyplane_tpu.chunk import ChunkFlags
    from skyplane_tpu.gateway.chunk_store import ChunkStore
    from skyplane_tpu.gateway.operators.gateway_receiver import GatewayReceiver
    from skyplane_tpu.gateway.operators.sender_wire import EngineCallbacks, SenderWireEngine, WireFrame
    from skyplane_tpu.obs.tracer import configure_tracer

    frames = _wire_frames()
    # ---- disabled-tracer span cost (the quantity the <2% gate is about) ----
    off = configure_tracer(sample=0.0)
    n_iter = 20000
    t0 = time.perf_counter_ns()
    for _ in range(n_iter):
        with off.span("overhead.probe", trace_id="00" * 16, cat="bench"):
            pass
    noop_span_ns = (time.perf_counter_ns() - t0) / n_iter

    # ---- sampled loopback transfer ----
    tracer = configure_tracer(sample=1.0)
    tmp = tempfile.mkdtemp(prefix="skyplane_trace_bench_")
    err_event, err_q = threading.Event(), queue_mod.Queue()
    receiver = GatewayReceiver(
        "local:local", ChunkStore(tmp), err_event, err_q, use_tls=False, bind_host="127.0.0.1", decode_workers=2
    )
    port = receiver.start_server()
    done = threading.Event()
    delivered = [0]

    class _Count(EngineCallbacks):
        def on_delivered(self, frame):
            delivered[0] += 1
            if delivered[0] >= len(frames):
                done.set()

        def on_fatal(self, msg):
            log(f"WARN: trace bench engine fatal: {msg}")
            done.set()

    def connect():
        s = socket_mod.create_connection(("127.0.0.1", port), timeout=30)
        s.setsockopt(socket_mod.IPPROTO_TCP, socket_mod.TCP_NODELAY, 1)
        return s

    # small in-flight window (vs the frames' total bytes) so send_stall
    # spans actually occur on the loopback
    engine = SenderWireEngine(connect, _Count(), inflight_limit_bytes=1 << 20, frame_ahead=2, name="trace-bench")
    try:
        for header, payload in frames:
            header.flags |= ChunkFlags.TRACED  # the sampled-chunk wire marker

            def make(pending, h=header, p=payload):
                with tracer.span("wire.frame", trace_id=h.chunk_id, cat="sender", force=True):
                    return WireFrame(None, h, p, traced=True)

            engine.submit(make)
        if not done.wait(timeout=60):
            log(f"WARN: trace bench delivered {delivered[0]}/{len(frames)} frames before timeout")
    finally:
        engine.close()
        receiver.stop_all()
        shutil.rmtree(tmp, ignore_errors=True)
    export = tracer.export()
    configure_tracer()  # back to the environment's sampling config

    trace_out = os.environ.get("SKYPLANE_BENCH_TRACE_OUT")
    if trace_out:
        with open(trace_out, "w") as f:
            json.dump(export, f)
        log(f"trace written to {trace_out} (loads in https://ui.perfetto.dev)")

    from skyplane_tpu.obs.collector import stage_breakdown

    n_spans = 0
    n_chunk_spans = 0
    for ev in export["traceEvents"]:
        if ev.get("ph") not in ("X", "b"):
            continue
        n_spans += 1
        if ev.get("args", {}).get("chunk_id"):
            n_chunk_spans += 1
    breakdown = stage_breakdown(export["traceEvents"])
    stage_latency_us = {stage: row["mean_us"] for stage, row in breakdown.items()}
    spans_per_chunk = max(1.0, n_chunk_spans / max(1, len(frames)))
    overhead_pct = 100.0 * (noop_span_ns * spans_per_chunk * len(frames)) / max(1.0, untraced_wall_s * 1e9)
    return {
        "stage_latency_us": stage_latency_us,
        "trace_overhead_pct": round(overhead_pct, 5),
        "trace_spans": n_spans,
        "noop_span_ns": round(noop_span_ns, 1),
    }


PROFILE_HZ = float(os.environ.get("SKYPLANE_BENCH_PROFILE_HZ", "97"))


def bench_cpu_profile() -> dict:
    """Core-time attribution of the loopback wire stack: run the sampling
    profiler (obs/profiler.py) over a full sender→receiver loopback transfer
    and report ``cpu_breakdown`` — per-stage CPU seconds, the GIL-probe
    ``gil_wait_fraction`` (with its CPU-identity cross-check), and
    ``cores_effective``. This is the single-core-ceiling measurement ROADMAP
    item 1's multi-core pump will be judged against (docs/benchmark.md).

    The sampler's own cost is measured directly (steady-state cost of one
    ``sample_once()`` times the configured rate) and reported as
    ``profile_overhead_pct`` — the share of ONE core the profiler consumes,
    gated < 2% in scripts/check_bench_json.py so always-on profiling stays
    affordable. Tracing is left OFF for this pass so the profile sees the
    production-shaped stack, not the tracer's.

    Set SKYPLANE_BENCH_PROFILE_OUT=<path> to write the speedscope JSON (the
    devloop profile-smoke step validates it with
    scripts/check_speedscope_json.py; open it at https://www.speedscope.app).
    """
    import queue as queue_mod
    import shutil
    import socket as socket_mod
    import tempfile
    import threading

    from skyplane_tpu.gateway.chunk_store import ChunkStore
    from skyplane_tpu.gateway.operators.gateway_receiver import GatewayReceiver
    from skyplane_tpu.gateway.operators.sender_wire import EngineCallbacks, SenderWireEngine, WireFrame
    from skyplane_tpu.obs.profiler import PROFILE_STAGES, configure_profiler

    frames = _wire_frames()
    prof = configure_profiler(hz=PROFILE_HZ)
    prof.ensure_started()  # no-op (and a zeroed breakdown below) when PROFILE_HZ <= 0
    tmp = tempfile.mkdtemp(prefix="skyplane_cpu_bench_")
    err_event, err_q = threading.Event(), queue_mod.Queue()
    receiver = GatewayReceiver(
        "local:local", ChunkStore(tmp), err_event, err_q, use_tls=False, bind_host="127.0.0.1", decode_workers=2
    )
    port = receiver.start_server()
    done = threading.Event()
    delivered = [0]
    target = [len(frames)]  # raised per round by the streaming loop below

    class _Count(EngineCallbacks):
        def on_delivered(self, frame):
            delivered[0] += 1
            if delivered[0] >= target[0]:
                done.set()

        def on_fatal(self, msg):
            log(f"WARN: cpu-profile bench engine fatal: {msg}")
            done.set()

    def connect():
        s = socket_mod.create_connection(("127.0.0.1", port), timeout=30)
        s.setsockopt(socket_mod.IPPROTO_TCP, socket_mod.TCP_NODELAY, 1)
        return s

    engine = SenderWireEngine(connect, _Count(), inflight_limit_bytes=4 << 20, frame_ahead=4, name="cpu-bench")
    # the corpus alone finishes in well under a second on loopback — too few
    # samples and CPU-clock refreshes for stable attribution, so stream it in
    # rounds until the profiled window reaches PROFILE_MIN_S of wall time
    min_s = float(os.environ.get("SKYPLANE_BENCH_PROFILE_MIN_S", "2.0"))
    t0 = time.perf_counter()
    rounds = 0
    try:
        while True:
            rounds += 1
            target[0] = rounds * len(frames)
            done.clear()
            for header, payload in frames:
                engine.submit(lambda pending, h=header, p=payload: WireFrame(None, h, p))
            if not done.wait(timeout=60):
                log(f"WARN: cpu-profile bench delivered {delivered[0]}/{target[0]} frames before timeout")
                break
            if time.perf_counter() - t0 >= min_s:
                break
    finally:
        engine.close()
        receiver.stop_all()
        shutil.rmtree(tmp, ignore_errors=True)
    wall_s = time.perf_counter() - t0
    breakdown = prof.cpu_breakdown()

    # export BEFORE the overhead loop below: its synthetic sample_once()
    # calls would otherwise pollute the flame graph with the bench's own
    # measurement stacks
    profile_out = os.environ.get("SKYPLANE_BENCH_PROFILE_OUT")
    if profile_out:
        with open(profile_out, "w") as f:
            json.dump(prof.speedscope(), f)
        log(f"cpu profile written to {profile_out} (open at https://www.speedscope.app)")

    # sampler self-cost, measured (not modeled): steady-state per-sample wall
    # cost x rate = the fraction of one core an always-on profiler burns
    prof.sample_once()  # warm the code-info / stage caches
    n_iter = 200
    t0 = time.perf_counter()
    for _ in range(n_iter):
        prof.sample_once()
    sample_cost_s = (time.perf_counter() - t0) / n_iter
    overhead_pct = 100.0 * sample_cost_s * PROFILE_HZ
    configure_profiler()  # back to the environment's profiling config

    stage_cpu = breakdown.get("stage_cpu_s") or {}
    return {
        "stage_cpu_s": {k: stage_cpu.get(k, 0.0) for k in PROFILE_STAGES},
        "gil_wait_fraction": breakdown["gil_wait_fraction"],
        "gil_wait_expected": breakdown["gil_wait_expected"],
        "cores_effective": breakdown["cores_effective"],
        "runnable_threads": breakdown["runnable_threads"],
        "cpu_clock": breakdown["cpu_clock"],
        "profile_hz": PROFILE_HZ,
        "profile_samples": breakdown["profile_samples"],
        "profile_samples_dropped": breakdown["profile_samples_dropped"],
        "profile_overhead_pct": round(overhead_pct, 4),
        "sample_cost_us": round(sample_cost_s * 1e6, 1),
        "transfer_wall_s": round(wall_s, 4),
    }


PUMP_PROC_COUNTS = (1, 2, 4)
PUMP_MB = int(os.environ.get("SKYPLANE_BENCH_PUMP_MB", "16"))


def bench_pump_scaling() -> dict:
    """Full-stack localhost Gbps vs pump process count (ROADMAP item 1's
    Gbps-vs-cores deliverable, docs/benchmark.md): the REAL two-daemon
    harness (control API, chunk store, operators, framed sockets, receiver
    decode + write_local) at ``SKYPLANE_TPU_PUMP_PROCS`` = 1/2/4, codec and
    crypto off so the measurement isolates the wire stack the pump shards.
    On runners with enough cores the numbers must scale monotonically and
    clear the 2 Gbps floor at 4 procs (scripts/check_bench_json.py); on
    small runners the gate downgrades on ``pump_cores_available``.

    Also reports ``pump_cores_effective``: the 4-proc run's merged
    parent+worker profiler summary — the number that must climb past the
    single-core ceiling banked in docs/benchmark.md.
    """
    import shutil
    import sys as sys_mod
    import tempfile
    from pathlib import Path

    sys_mod.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests"))
    from integration.harness import dispatch_file, make_pair, wait_complete

    from skyplane_tpu.gateway.pump import PUMP_PROCS_ENV
    from skyplane_tpu.obs.profiler import configure_profiler

    cores = os.cpu_count() or 1
    saved = {k: os.environ.get(k) for k in (PUMP_PROCS_ENV, "SKYPLANE_TPU_PROFILE_HZ")}
    # arm the sampling profiler for parent AND (env-inherited) pump workers:
    # the merged summary is where cores_effective must exceed 1.0
    os.environ.setdefault("SKYPLANE_TPU_PROFILE_HZ", "47")
    configure_profiler()
    payload = np.random.default_rng(11).integers(0, 256, PUMP_MB << 20, dtype=np.uint8).tobytes()
    by_procs = {}
    cores_effective = 0.0
    respawns = 0
    try:
        for n in PUMP_PROC_COUNTS:
            os.environ[PUMP_PROCS_ENV] = str(n)
            tmp = Path(tempfile.mkdtemp(prefix=f"skyplane_pump_bench_{n}_"))
            src_file = tmp / "src.bin"
            src_file.write_bytes(payload)
            dst_file = tmp / "out" / "dst.bin"
            src, dst = make_pair(
                tmp, compress="none", dedup=False, encrypt=False, use_tls=False, num_connections=max(2, n)
            )
            try:
                # spawn warm-up OUTSIDE the timed region: wait until every
                # worker finished its (jax-heavy) import and pushed its
                # first counter snapshot
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    c_src, c_dst = src.daemon._pump_counters(), dst.daemon._pump_counters()
                    if c_src["ctrl_messages"] >= c_src["procs"] and c_dst["ctrl_messages"] >= c_dst["procs"]:
                        break
                    time.sleep(0.05)
                t0 = time.perf_counter()
                ids = dispatch_file(src, src_file, dst_file, chunk_bytes=1 << 20)
                wait_complete(src, ids, timeout=600)
                wait_complete(dst, ids, timeout=600)
                dt = time.perf_counter() - t0
                by_procs[str(n)] = round(len(payload) * 8 / 1e9 / dt, 3)
                merged = src.daemon._merged_profile_summary()
                cores_effective = max(cores_effective, float(merged.get("cores_effective") or 0.0))
                respawns += src.daemon._pump_counters()["worker_respawns"]
                respawns += dst.daemon._pump_counters()["worker_respawns"]
                log(f"pump bench: {n} proc(s) -> {by_procs[str(n)]} Gbps ({dt:.2f}s for {PUMP_MB} MiB)")
            finally:
                src.stop()
                dst.stop()
                shutil.rmtree(tmp, ignore_errors=True)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        configure_profiler()
    return {
        "wire_gbps_by_procs": by_procs,
        "pump_cores_available": cores,
        "pump_cores_effective": round(cores_effective, 3),
        "pump_corpus_mb": PUMP_MB,
        "pump_respawns": respawns,
    }


SPMD_DEVICE_COUNTS = (1, 2, 4, 8)
SPMD_CHUNK_MB = int(os.environ.get("SKYPLANE_BENCH_SPMD_MB", "1"))

# child body for one spmd sweep point: forced-host devices are armed through
# the ENV (before any jax import — the whole reason this is a subprocess);
# argv = [n_devices, chunk_bytes, reps]. Prints one JSON line.
_SPMD_CHILD = """\
import json, sys, threading, time
import jax
import numpy as np
from skyplane_tpu.ops.batch_runner import DeviceBatchRunner
from skyplane_tpu.ops.cdc import CDCParams, cdc_and_fps_host
from skyplane_tpu.parallel.datapath_spmd import default_mesh

n = int(sys.argv[1])
chunk_bytes = int(sys.argv[2])
reps = int(sys.argv[3])
assert len(jax.devices()) >= n, f"forced-host arming failed: {len(jax.devices())} < {n}"
mesh = default_mesh(jax.devices()[:n]) if n > 1 else None
params = CDCParams()
runner = DeviceBatchRunner(cdc_params=params, max_batch=8, mesh=mesh)
rng = np.random.default_rng(3)
chunks = [rng.integers(0, 256, chunk_bytes, dtype=np.uint8) for _ in range(runner.max_batch)]

def one_round():
    results = [None] * len(chunks)
    def sub(i):
        h = runner.submit(chunks[i])
        results[i] = (h.ends(), h.fps())
    ts = [threading.Thread(target=sub, args=(i,)) for i in range(len(chunks))]
    [t.start() for t in ts]
    [t.join() for t in ts]
    return results

results = one_round()  # warm-up: compiles the (sharded) kernels
identical = all(
    np.array_equal(np.asarray(e), np.asarray(re)) and list(f) == list(rf)
    for (e, f), (re, rf) in zip(results, (cdc_and_fps_host(c, params) for c in chunks))
)
t0 = time.perf_counter()
for _ in range(reps):
    one_round()
dt = time.perf_counter() - t0
total = reps * sum(len(c) for c in chunks)
print(json.dumps({
    "n": n,
    "gbps": round(total * 8 / 1e9 / dt, 3),
    "mesh": "x".join(str(s) for s in mesh.shape.values()) if mesh is not None else "1x1",
    "identical": bool(identical),
}))
"""


def _main_mesh_label() -> str:
    """The (data x seq) mesh label for THIS process's jax client ("1x1" when
    sharding is not viable) — the required ``mesh`` artifact field."""
    from skyplane_tpu.parallel.datapath_spmd import maybe_default_mesh

    mesh = maybe_default_mesh()
    return "x".join(str(s) for s in mesh.shape.values()) if mesh is not None else "1x1"


def bench_spmd_scaling() -> dict:
    """Mesh-sharded batch runner Gbps vs device count (ROADMAP item 1's
    multi-chip scaling curve): the batched CDC+fingerprint path at 1/2/4/8
    forced-host devices (``--xla_force_host_platform_device_count``, one
    subprocess per point — the flag must land before any jax import), each
    window submitted from max_batch concurrent threads exactly like gateway
    sender workers. Each child verifies byte-identity against the host
    kernels (``spmd_identical``) before the timed reps.

    Device counts are capped at the runner's core count — forcing 8 "devices"
    onto 1 core measures scheduler noise, not scaling — and the
    check_bench_json gate arms only at ``spmd_devices_available >= 2``
    (graceful small-runner downgrade, same pattern as the pump core gates).
    Intra-op threads are pinned to 1 in EVERY child so the 1-device run
    cannot silently spread across all cores and erase the curve. The
    children are CPU-only by construction (``force_host_devices_env`` pins
    JAX_PLATFORMS=cpu), so they never contend with this process for a chip.
    """
    from skyplane_tpu.parallel.datapath_spmd import force_host_devices_env

    cores = os.cpu_count() or 1
    avail = max(1, min(8, cores))
    counts = [n for n in SPMD_DEVICE_COUNTS if n <= avail]
    chunk_bytes = SPMD_CHUNK_MB << 20
    reps = 3
    by_devices = {}
    mesh_label = "1x1"
    identical = True
    for n in counts:
        env = force_host_devices_env(n)
        # uniform intra-op pinning (see docstring): one compute thread per
        # device in every child
        env["XLA_FLAGS"] += " --xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1"
        # per-batch host recompute would pollute the timed reps; the child
        # does its own identity pass before timing
        env.pop("SKYPLANE_TPU_SPMD_CHECK", None)
        try:
            proc = subprocess.run(
                [sys.executable, "-c", _SPMD_CHILD, str(n), str(chunk_bytes), str(reps)],
                capture_output=True,
                text=True,
                timeout=600,
                env=env,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
        except subprocess.TimeoutExpired:
            log(f"WARN: spmd bench child for {n} device(s) hung; skipping")
            continue
        if proc.returncode != 0:
            log(f"WARN: spmd bench child for {n} device(s) failed: {proc.stderr[-300:]}")
            continue
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        by_devices[str(n)] = row["gbps"]
        identical = identical and bool(row["identical"])
        if n == counts[-1]:
            mesh_label = row["mesh"]
        log(f"spmd bench: {n} device(s) -> {row['gbps']} Gbps (mesh {row['mesh']})")
    return {
        "spmd_gbps_by_devices": by_devices,
        "spmd_mesh": mesh_label,
        "spmd_devices_available": avail,
        "spmd_identical": identical,
    }


def bench_blast() -> dict:
    """Small loopback checkpoint blast (docs/blast.md): 1 source ->
    ``SKYPLANE_BENCH_BLAST_SINKS`` peered sink daemons over a planner-placed
    relay tree (source degree 1, fanout 2), kill-free. Reports
    ``blast_egress_ratio`` — counter-measured source egress over corpus
    size, the number that must sit at ~1x regardless of sink count (a tree
    degraded to direct multicast reads ~= n_sinks and fails the
    check_bench_json gate); banked per bench round so the fan-out-vs-egress
    curve in docs/benchmark.md comes from the perf trajectory."""
    import shutil
    import tempfile
    from pathlib import Path

    from tests.integration.harness import build_chunk_requests, start_blast_fleet

    from skyplane_tpu.blast import BlastController, solve_blast_tree

    n_sinks = int(os.environ.get("SKYPLANE_BENCH_BLAST_SINKS", "4"))
    corpus_mb = int(os.environ.get("SKYPLANE_BENCH_BLAST_MB", "8"))
    chunk_bytes = 256 << 10
    payload = np.random.default_rng(13).integers(0, 256, corpus_mb << 20, dtype=np.uint8).tobytes()
    tmp = Path(tempfile.mkdtemp(prefix="skyplane_blast_bench_"))
    src_file = tmp / "ckpt.bin"
    src_file.write_bytes(payload)
    sinks = {f"sink_{i}": "local:local" for i in range(n_sinks)}
    tree = solve_blast_tree(
        "blast_src", sinks, "local:local", cost_fn=lambda a, b: 0.0, fanout=2, source_degree=1, solver="greedy"
    )
    source, sink_gws, _roots = start_blast_fleet(tmp, tree, compress="none", dedup=False, encrypt=False)
    try:
        reqs = build_chunk_requests(src_file, "/blast/ckpt.bin", chunk_bytes)
        ctl = BlastController(source, sink_gws, tree, poll_s=0.05)
        t0 = time.perf_counter()
        ctl.dispatch(reqs)
        ctl.wait(timeout=300)
        dt = time.perf_counter() - t0
        egress = ctl.source_egress_bytes()
        return {
            "blast_sinks": n_sinks,
            "blast_egress_ratio": round(egress / len(payload), 4),
            "blast_gbps": round(len(payload) * 8 / 1e9 / dt, 3),
            "blast_corpus_mb": corpus_mb,
        }
    finally:
        source.stop()
        for gw in sink_gws.values():
            gw.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def bench_raw_forward() -> dict:
    """Raw-forward fast path on the blast-interior-edge shape
    (docs/datapath-performance.md "Raw-forward fast path"): one peer-serving
    sender re-serves the SAME staged chunks to ``fanout`` tree children over
    a loopback wire — once with raw forwarding ON (first pass seals, every
    later pass splices the staged bytes kernel-side via sendfile) and once
    forced through the codec path (every pass re-reads + re-frames +
    re-fingerprints, the pre-raw behavior). Identical workload, identical
    cores; ``relay_gbps_raw`` vs ``relay_gbps_codec`` is the banked ratio
    check_bench_json.py gates (>= 3x at >= 2 cores, presence-only on
    single-vCPU runners where the consuming receiver shares the core)."""
    import shutil
    import sys as sys_mod
    import tempfile
    from pathlib import Path

    sys_mod.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests"))
    from unit.test_sender_pipeline import AckServer, drain_n, make_sender, stage_chunks

    from skyplane_tpu.gateway.operators.sender_wire import RAW_FORWARD_ENV

    n_chunks = int(os.environ.get("SKYPLANE_BENCH_RAW_CHUNKS", "16"))
    fanout = int(os.environ.get("SKYPLANE_BENCH_RAW_FANOUT", "4"))
    corpus_rng = np.random.default_rng(17)
    datas = [corpus_rng.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes() for _ in range(n_chunks)]
    total_bytes = sum(len(d) for d in datas) * fanout
    # the interior edge runs the edge's real codec: lz4 when the system
    # library is present (the seal amortizes the compression), else
    # passthrough (the seal amortizes only the fingerprint)
    from skyplane_tpu.utils import lz4ref

    codec_name = "lz4" if lz4ref.available() else "none"

    def leg(raw_on: bool):
        saved = os.environ.get(RAW_FORWARD_ENV)
        os.environ[RAW_FORWARD_ENV] = "1" if raw_on else "0"
        tmp = Path(tempfile.mkdtemp(prefix=f"skyplane_raw_bench_{int(raw_on)}_"))
        server = AckServer()
        op = None
        try:
            op, in_q, out_q, _, store = make_sender(
                tmp, server.port, dedup=False, raw_forward=raw_on, peer_serve=True,
                max_streams=1, codec_name=codec_name,
            )
            reqs = stage_chunks(store, datas)
            op.start_workers()
            t0 = time.perf_counter()
            for _ in range(fanout):  # one pass per tree child
                for req in reqs:
                    in_q.put(req)
                done = drain_n(out_q, n_chunks, timeout=120)
                assert len(done) == n_chunks, f"raw bench leg(raw={raw_on}) incomplete: {len(done)}/{n_chunks}"
            dt = time.perf_counter() - t0
            return dt, op.wire_counters()
        finally:
            if op is not None:
                op.stop_workers()
            server.close()
            shutil.rmtree(tmp, ignore_errors=True)
            if saved is None:
                os.environ.pop(RAW_FORWARD_ENV, None)
            else:
                os.environ[RAW_FORWARD_ENV] = saved

    codec_dt, codec_counters = leg(False)
    raw_dt, raw_counters = leg(True)
    return {
        "relay_gbps_raw": round(total_bytes * 8 / 1e9 / raw_dt, 3),
        "relay_gbps_codec": round(total_bytes * 8 / 1e9 / codec_dt, 3),
        "wire_raw_frames": raw_counters["wire_raw_frames"],
        "wire_raw_bytes": raw_counters["wire_raw_bytes"],
        "wire_raw_fallbacks": raw_counters["wire_raw_fallbacks"] + codec_counters["wire_raw_fallbacks"],
        "raw_chunks": n_chunks,
        "raw_fanout": fanout,
        "raw_codec": codec_name,
        "raw_cores_available": os.cpu_count() or 1,
    }


def _bench_codec(chunks, one) -> dict:
    """Time a per-chunk codec with full core-level worker parallelism.

    Best-of-N timing (N=SKYPLANE_BENCH_REPS): single-shot wall times on a
    shared-tenancy core swing ±10%, enough to flip the vs_baseline ratio;
    min-of-reps is the standard estimator for the machine's capability and is
    applied to ALL sides, so the ratios stay honest."""
    from concurrent.futures import ThreadPoolExecutor

    workers = min(8, os.cpu_count() or 1)
    one(chunks[0])  # warm
    best = float("inf")
    wire = 0
    for _ in range(max(1, BENCH_REPS)):
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=workers) as pool:
            wire = sum(pool.map(one, chunks))
        best = min(best, time.perf_counter() - t0)
    return {"seconds": best, "raw_bytes": sum(len(c) for c in chunks), "wire_bytes": wire}


def bench_baseline(chunks, one=None) -> dict:
    """zstd-3 per chunk (round-1..4 comparability baseline; native_lz
    substitute when zstandard is not installed — see pick_codecs)."""
    if one is None:
        one = pick_codecs()[2]
    return _bench_codec(chunks, one)


def bench_baseline_lz4(chunks) -> Optional[dict]:
    """REAL LZ4 frames (system liblz4 — the reference's wire codec family).
    None when the host has no liblz4; the JSON then omits the lz4 rows
    rather than substituting another codec for it."""
    from skyplane_tpu.utils import lz4ref

    if not lz4ref.available():
        log("WARN: liblz4 not present on this host; no vs_baseline_lz4 row")
        return None
    return _bench_codec(chunks, lambda c: len(lz4ref.compress(c)))


def main() -> None:
    # one process, on the backend jax gives it: a CPU run is chosen from
    # outside with JAX_PLATFORMS=cpu, and a chip that cannot be had is an
    # error, never a quiet CPU number
    from skyplane_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    devices = jax.devices()
    dev_platform = devices[0].platform
    log(f"benchmarking on platform={dev_platform} device_kind={devices[0].device_kind} n_devices={len(devices)}")

    chunks = make_corpus()
    log("corpus ready")
    ours_codec, base_label, base_one = pick_codecs()
    base = bench_baseline(chunks, base_one)
    log(f"baseline ({base_label}) done: {base['seconds']:.2f}s")
    base_lz4 = bench_baseline_lz4(chunks)
    if base_lz4:
        log(f"lz4 baseline done: {base_lz4['seconds']:.2f}s")
    # two pool sizes: the deployable gateway configuration (n_workers) is the
    # headline; 1 worker isolates per-chunk latency (VERDICT r3 #7 asked for
    # both so the "deployable VM" figure is explicit)
    deploy_workers = n_workers()
    ours = bench_ours(chunks, workers=deploy_workers, codec_name=ours_codec)
    log(f"ours done ({deploy_workers} workers): {ours['seconds']:.2f}s stats={ours['stats']}")
    gbits = ours["raw_bytes"] * 8 / 1e9
    by_workers = {str(deploy_workers): round(gbits / ours["seconds"], 3)}
    if deploy_workers != 1:
        ours_1 = bench_ours(chunks, workers=1, codec_name=ours_codec)
        by_workers["1"] = round(ours_1["raw_bytes"] * 8 / 1e9 / ours_1["seconds"], 3)
        log(f"ours done (1 worker): {ours_1['seconds']:.2f}s")

    # receiver decode path: restore throughput over the SAME corpus, encoded
    # once (north-star effective Gbps counts end-to-end restore, not just
    # sender encode — BASELINE.md)
    frames = encode_frames_for_decode(chunks, ours_codec)
    dec = bench_decode(frames)
    decode_gbps = dec["raw_bytes"] * 8 / 1e9 / dec["seconds"]
    log(f"decode done ({dec['workers']} workers): {dec['seconds']:.2f}s ({decode_gbps:.2f} Gbps)")

    # sender wire engine: serial-vs-pipelined loopback comparison + the
    # stable wire-counter schema (docs/datapath-performance.md)
    wire = bench_wire()
    log(
        f"wire bench done: serial drain {wire['serial_drain_ns_per_window'] / 1e6:.2f} ms/window, "
        f"pipelined stall {wire['wire_stall_ns_per_window'] / 1e6:.2f} ms/window, "
        f"{wire['frames_pipelined']} frames pipelined"
    )

    # trace pass: sampled loopback transfer -> per-stage latency breakdown +
    # the disabled-tracer overhead projection (docs/observability.md)
    trace_info = bench_trace(wire["pipelined_seconds"])
    log(
        f"trace bench done: {trace_info['trace_spans']} spans, stages(us)={trace_info['stage_latency_us']}, "
        f"disabled-tracer overhead {trace_info['trace_overhead_pct']:.4f}%"
    )

    # cpu-profile pass: sampling profiler over an untraced loopback transfer
    # -> per-stage CPU seconds, GIL wait, cores_effective (the single-core-
    # ceiling measurement, docs/benchmark.md; gated by check_bench_json.py)
    cpu_breakdown = bench_cpu_profile()
    log(
        f"cpu profile done: {cpu_breakdown['profile_samples']} samples @ {cpu_breakdown['profile_hz']:g} Hz, "
        f"{cpu_breakdown['cores_effective']} cores effective, "
        f"GIL wait {100.0 * cpu_breakdown['gil_wait_fraction']:.1f}%, "
        f"sampler overhead {cpu_breakdown['profile_overhead_pct']:.3f}% of one core"
    )

    # multi-process pump scaling: full-stack loopback Gbps at 1/2/4 worker
    # processes (gateway/pump.py) — the Gbps-vs-cores measurement ROADMAP
    # item 1 is judged by; gated for monotonic scaling and the 2 Gbps floor
    # where cores allow (scripts/check_bench_json.py, docs/benchmark.md)
    pump = bench_pump_scaling()
    log(
        f"pump bench done: {pump['wire_gbps_by_procs']} Gbps by procs on {pump['pump_cores_available']} core(s), "
        f"merged cores effective {pump['pump_cores_effective']}"
    )

    # SPMD device scaling: the mesh-sharded batch runner at 1/2/4/8 forced-
    # host devices (parallel/datapath_spmd.py) — ROADMAP item 1's multi-chip
    # scaling curve; byte-identity verified in every child, monotonic device
    # scaling gated by scripts/check_bench_json.py where cores allow
    spmd = bench_spmd_scaling()
    log(
        f"spmd bench done: {spmd['spmd_gbps_by_devices']} Gbps by devices "
        f"(mesh {spmd['spmd_mesh']}, {spmd['spmd_devices_available']} device(s) viable)"
    )

    # checkpoint blast: source egress vs fan-out over a peered relay tree
    # (docs/blast.md) — the ratio must sit at ~1x regardless of sink count;
    # banked per round so the fan-out-vs-egress curve rides the trajectory
    blast = bench_blast()
    log(
        f"blast bench done: {blast['blast_sinks']} sinks at {blast['blast_gbps']} Gbps, "
        f"source egress {blast['blast_egress_ratio']}x corpus"
    )

    # raw-forward fast path: sendfile re-serve vs codec re-framing over the
    # identical blast-interior-edge workload (docs/datapath-performance.md
    # "Raw-forward fast path") — the banked ratio check_bench_json.py gates
    raw_fwd = bench_raw_forward()
    log(
        f"raw-forward bench done: raw {raw_fwd['relay_gbps_raw']} Gbps vs codec "
        f"{raw_fwd['relay_gbps_codec']} Gbps ({raw_fwd['wire_raw_frames']} raw frames)"
    )

    ours_gbps = gbits / ours["seconds"]
    base_gbps = base["raw_bytes"] * 8 / 1e9 / base["seconds"]
    from skyplane_tpu.planner.pricing import get_egress_cost_per_gb

    rate_per_gb = get_egress_cost_per_gb("aws:us-east-1", "gcp:us-central1")  # the BASELINE.json route
    result = {
        "metric": (
            f"sender datapath effective throughput (CDC dedup + compress, "
            f"{sum(len(c) for c in chunks) >> 20}MiB snapshot corpus, {N_SNAPSHOTS}-snapshot chain)"
        ),
        "value": round(ours_gbps, 3),
        "unit": "Gbps",
        "vs_baseline": round(ours_gbps / base_gbps, 3),
        "baseline_gbps": round(base_gbps, 3),
        "codec_ours": _effective_codec(ours_codec),
        "codec_baseline": base_label,
        "platform": dev_platform,
        # device-count context (required on every artifact row since PR 18:
        # check_bench_json refuses rows without it): how many devices THIS
        # process's jax client saw, and the (data x seq) mesh the live batch
        # runner would shard over ("1x1" = single-device)
        "device_kind": devices[0].device_kind,
        "n_devices": len(devices),
        "mesh": _main_mesh_label(),
        "workers": deploy_workers,
        "gbps_by_workers": by_workers,
        "wire_reduction_ours": round(ours["raw_bytes"] / max(ours["wire_bytes"], 1), 2),
        "wire_reduction_baseline": round(base["raw_bytes"] / max(base["wire_bytes"], 1), 2),
        # egress $/TB of raw data actually moved (BASELINE metric's second
        # axis): wire bytes billed at the planner's AWS->GCP egress rate
        # (decimal TB, matching how cloud egress is billed)
        "egress_usd_per_tb_ours": round(rate_per_gb * 1000 * ours["wire_bytes"] / ours["raw_bytes"], 2),
        "egress_usd_per_tb_baseline": round(rate_per_gb * 1000 * base["wire_bytes"] / base["raw_bytes"], 2),
        # hot-path health counters (docs/datapath-performance.md): on the CPU
        # path they are structurally present but zero (no padding/batching);
        # on accelerators pool_hit_rate ~1.0 and batch_occupancy near 1.0 are
        # the steady-state signature the overlap-scheduled path is tuned for.
        # bench-smoke (scripts/devloop.sh) asserts these keys exist.
        "datapath_counters": {
            k: ours["stats"].get(k, 0)
            for k in (
                "pool_hit_rate",
                "pool_hits",
                "pool_misses",
                "batch_windows",
                "batch_occupancy",
                "batch_padded_rows",
                "device_wait_ns",
                "donated_batches",
                "stage_failures",
            )
        },
        # receiver decode path (parallel restore of the same corpus): the
        # other half of the end-to-end effective-Gbps story. Healthy runs
        # show store_lock_held_disk_reads == 0 (the striped store never pays
        # disk inside a lock) and store_ref_wait_ns near 0 when decode order
        # tracks frame order. bench-smoke asserts these keys exist too.
        "decode_gbps": round(decode_gbps, 3),
        "decode_workers": dec["workers"],
        "decode_counters": {k: dec["counters"].get(k, 0) for k in DECODE_COUNTER_KEYS},
        # sender wire engine (local-loopback serial-vs-pipelined comparison):
        # healthy runs show nonzero frames_pipelined and a per-window stall
        # strictly below the serial path's frame+ack drain. bench-smoke
        # asserts the keys AND the comparison (scripts/check_bench_json.py).
        "wire_counters": {k: wire.get(k, 0) for k in WIRE_COUNTER_KEYS},
        "wire_serial_seconds": wire["serial_seconds"],
        "wire_pipelined_seconds": wire["pipelined_seconds"],
        # trace-derived stage breakdown (frame/send-stall/ack-lag/decode/
        # store) + the disabled-tracer overhead projection; check_bench_json
        # gates the keys and the <2% overhead bound (docs/observability.md)
        "stage_latency_us": trace_info["stage_latency_us"],
        "trace_overhead_pct": trace_info["trace_overhead_pct"],
        "trace_spans": trace_info["trace_spans"],
        # core-time attribution (obs/profiler.py, docs/observability.md
        # "Core-time profiling"): per-stage CPU seconds over the loopback
        # wire stack, GIL wait fraction, cores effectively used, and the
        # measured sampler overhead (<2% of one core, check_bench_json.py) —
        # the baseline ROADMAP item 1's multi-core pump is judged against
        "cpu_breakdown": cpu_breakdown,
        # multi-process pump scaling (gateway/pump.py, docs/benchmark.md
        # "Gbps vs pump processes"): full-stack two-daemon loopback at
        # 1/2/4 worker processes + merged parent+worker cores-effective.
        # check_bench_json.py gates monotonic scaling and >=2 Gbps at 4
        # procs when pump_cores_available allows (graceful small-runner
        # downgrade).
        **pump,
        # checkpoint-blast fan-out (docs/blast.md): counter-measured source
        # egress over corpus size on a kill-free loopback blast — gated
        # <= 1.5x by check_bench_json.py (a degraded tree reads ~n_sinks)
        **blast,
        # raw-forward fast path (docs/datapath-performance.md): kernel-spliced
        # re-serve vs codec re-framing on the interior-edge workload; the
        # ratio gate (raw >= 3x codec, downgraded on single-vCPU runners)
        # and the wire_raw_frames floor live in check_bench_json.py
        **raw_fwd,
        # SPMD device scaling (parallel/datapath_spmd.py, docs/datapath-
        # performance.md "SPMD device data path"): batched CDC+fingerprint
        # Gbps at 1/2/4/8 forced-host devices, byte-identity verified per
        # child; check_bench_json gates monotonic scaling (0.85 tolerance)
        # and >=1.6x at 4 devices when spmd_devices_available allows
        **spmd,
    }
    if base_lz4:
        # the honest reference-codec bar (BASELINE.json names LZ4, not zstd)
        from skyplane_tpu.planner.estimator import wan_crossover_gbps

        lz4_gbps = base_lz4["raw_bytes"] * 8 / 1e9 / base_lz4["seconds"]
        red_ours = ours["raw_bytes"] / max(ours["wire_bytes"], 1)
        red_lz4 = base_lz4["raw_bytes"] / max(base_lz4["wire_bytes"], 1)
        result.update(
            {
                "baseline_lz4_gbps": round(lz4_gbps, 3),
                "vs_baseline_lz4": round(ours_gbps / lz4_gbps, 3),
                "wire_reduction_baseline_lz4": round(red_lz4, 2),
                "egress_usd_per_tb_baseline_lz4": round(rate_per_gb * 1000 * base_lz4["wire_bytes"] / base_lz4["raw_bytes"], 2),
                # WAN bandwidth below which our pipeline beats the LZ4 gateway
                # END-TO-END despite any raw-Gbps loss (estimator model).
                # null = wins at EVERY bandwidth (faster and more reduction);
                # strict JSON has no Infinity, and 0.0 already means never.
                "wan_crossover_vs_lz4_gbps": (
                    None
                    if (xover := wan_crossover_gbps(ours_gbps, red_ours, lz4_gbps, red_lz4)) == float("inf")
                    else round(xover, 2)
                ),
            }
        )
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
