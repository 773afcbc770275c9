#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the served gateway pair.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip and runs a source and a sink ``GatewayDaemon`` as
threads on loopback, built at the configuration's values. Chunks go in through
``POST /api/v1/chunk_requests`` on the source, at most 100 to a request, and
count when the sink's status log says ``complete``, read from where the last
read stopped (``lib/pair.py``). A closed loop keeps the cell's
``in_flight_chunks`` in flight; the measured span runs from chunk completion to
chunk completion (``lib/span.py``). What decides ``correct`` (``lib/check.py``)
runs once the window has closed, its reference in worker processes
(``lib/refpool.py``) that are started first of all and do nothing until then.
The last line of stdout is the result as one JSON object, printed before the
gateways are stopped and the data removed.

Everything that belongs to one cell, configuration, metric or kind of content
is a file found by its name in BENCHMARK.json: ``workloads/<cell>.json``,
``configs/<config>.json``, ``metrics/<metric>.json`` (+ ``.py`` where a reader
is code), ``generators/<name>.py``. See README.md.

Off a TPU the run fails and prints no result, unless ``--rehearse-scale N``
asks for a rehearsal: the same code at chunks N times smaller, whose line has
``correct`` false and no metrics, and which exits 1.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT)]

DEADLINE_S = 345.0  # a run exits within 360 s
TEARDOWN_S = 60.0  # stopping the gateways and removing the data, once the result is printed
LATE_S = 60.0  # how long past the close an answer is waited for
STAGGER_S = 1.0  # between the posts that fill the pipeline, where a cell states no fill_stagger_s
POLL_S = 0.1  # completion times are the sink's own stamps; a deep loop refills from its queue between polls
MARK = "bench:mark"
# the sink's segment store and REF ladder, printed under run.sink_store: what
# a cell whose store spills does there, for the whole run and after t0
SINK_STORE_COUNTERS = (
    "store_mem_evictions", "store_spill_reads", "store_promotions", "store_spill_bytes",
    "store_ref_wait_ns", "store_ref_timeouts", "decode_nacks",
)


class GatewayFault(RuntimeError):
    """A gateway put an error on its /errors list."""


class SetupUnsound(RuntimeError):
    """The set-up did not load every program the window will run: the run
    ends with no result."""


def log(msg: str) -> None:
    print(f"[run +{time.monotonic() - T_START:6.1f}s] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"benchmark_{path.parent.name}_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def whole_number(value, least: int) -> bool:
    return type(value) is int and value >= least  # True, 1.5 and "3" are not


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-scale", type=int, default=0, help="rehearsal: chunks this many times smaller; never correct")
    ap.add_argument("--control", default=None, help="also judge this control of lib/check.py, which has to fail")
    return ap.parse_args(argv)


class Cell:
    """One entry of BENCHMARK.json's workloads with the files it names."""

    def __init__(self, name: str):
        self.bench = load_json(ROOT / "BENCHMARK.json")
        entries = {w["name"]: w for w in self.bench["workloads"]}
        if name not in entries:
            raise SystemExit(f"BENCHMARK.json has no workload {name!r} (it has: {', '.join(entries)})")
        self.entry = entries[name]
        self.workload = load_json(HERE / "workloads" / f"{name}.json")
        self.config = load_json(HERE / "configs" / f"{self.entry['config']}.json")
        self.generator_file = HERE / "generators" / f"{self.workload['generator']}.py"
        self.generator = load_module(self.generator_file).Generator
        self.burst = self.workload["traffic"].get("setup_burst_chunks")  # None: the set-up chunk alone, as ever
        if self.burst is not None and not whole_number(self.burst, 2):
            raise SystemExit(f"workloads/{name}.json: setup_burst_chunks is {self.burst!r}; it is a whole number of 2 or more, or left out")
        self.setup_chunks = self.workload["traffic"].get("setup_chunks", 1)  # rows landed one at a time before the fill
        if not whole_number(self.setup_chunks, 1):
            raise SystemExit(f"workloads/{name}.json: setup_chunks is {self.setup_chunks!r}; it is a whole number of 1 or more, or left out")
        self.fill_stagger_s = self.workload["traffic"].get("fill_stagger_s", STAGGER_S)  # 0: the whole fill at once
        if not (type(self.fill_stagger_s) in (int, float) and math.isfinite(self.fill_stagger_s) and self.fill_stagger_s >= 0):
            raise SystemExit(f"workloads/{name}.json: fill_stagger_s is {self.fill_stagger_s!r}; it is a number of seconds of 0 or more, or left out")
        transfer = self.config["transfer"]
        self.store_mb = transfer.get("sink_segment_store_mb")  # None: the daemon's own bound
        if self.store_mb is not None and not (whole_number(self.store_mb, 1) and transfer["dedup"]):
            raise SystemExit(
                f"configs/{self.entry['config']}.json: sink_segment_store_mb is {self.store_mb!r}; "
                "it is a whole number of 1 or more where dedup is on, or left out"
            )

    def metrics(self, group: str):
        """The metrics of ``group`` this cell reports, each with its file."""
        out = []
        for m in self.bench[group]:
            if "workloads" in m and self.entry["name"] not in m["workloads"]:
                continue
            out.append((m, load_json(HERE / "metrics" / f"{m['name']}.json")))
        return out


def read_metric(spec: dict, facts: dict, run: dict):
    """A metric's value from the run's facts, or None where there is nothing
    to read: by the ratio its file states, or by the reader beside it."""
    if "reader" in spec:
        return load_module(HERE / "metrics" / spec["reader"]).read(facts, dict(run, metric=spec))
    ratio = spec["ratio"]
    num = facts.get(ratio["num"])
    if num is None:
        return None
    if ratio.get("den") is None:
        return num * ratio["scale"]
    den = facts.get(ratio["den"])
    if not den:
        return None
    return num / den * ratio["scale"]


def leave(rc: int) -> None:
    """End the process now, whatever its threads are doing."""
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)


def arm_deadline(seconds: float, tmp: Path, pool) -> threading.Timer:
    """A hung device call cannot be interrupted: at the deadline say so,
    end the reference's workers, remove the data and leave, with no result
    line."""

    def fire():
        log(f"FAIL: the run's deadline of {seconds:.0f}s was reached")
        pool.kill()
        shutil.rmtree(tmp, ignore_errors=True)
        leave(3)

    timer = threading.Timer(seconds, fire)
    timer.daemon = True
    timer.start()
    return timer


def tear_down(gateways, tmp: Path, limit: float) -> bool:
    """Stop the gateways and remove the run's data, within ``limit`` seconds:
    the result is printed by now, and a stop that hangs cannot take it back."""

    def work():
        try:
            for gw in gateways:
                if gw is not None:
                    gw.stop()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    t = time.monotonic()
    thread = threading.Thread(target=work, name="bench-teardown", daemon=True)
    thread.start()
    thread.join(max(limit, 0.0))
    if thread.is_alive():
        log(f"teardown passed its limit of {limit:.0f}s: leaving it")
        shutil.rmtree(tmp, ignore_errors=True)
        return False
    log(f"teardown: gateways stopped and data removed in {time.monotonic() - t:.1f}s")
    return True


def annotate_device_spans():
    """Write the program's device-category tracer spans (batch.window_wait,
    fused.dispatch, fused.readback) into the profiler's trace as host spans,
    from this side: the tracer itself stays off."""
    import jax

    from skyplane_tpu.obs import get_tracer
    from skyplane_tpu.obs.tracer import NOOP_SPAN

    def span(name, trace_id=None, cat="", args=None, force=False):
        return jax.profiler.TraceAnnotation(f"host:{name}") if cat == "device" else NOOP_SPAN

    get_tracer().span = span


def tap_runner(runner, taps: dict) -> None:
    """Keep the handle of every row the timed path submits to the device
    runner, by the row's key: its segment ends and fingerprints are what the
    reference is held against once the window has closed."""
    from lib.check import row_key

    submit = runner.submit

    def tapped(arr, padded=None):
        key = row_key(arr)
        t = time.perf_counter()
        handle = submit(arr, padded)  # a window's leader runs the batch in here
        taps[key] = (handle, time.perf_counter() - t)
        return handle

    runner.submit = tapped


def departures_from(cfg: dict, gateways, store_bytes=None) -> list:
    """Where a daemon was built at another value than the configuration's
    ``transfer`` states: a run that departs from it is no sound run.
    ``store_bytes`` is the sink's segment-store bound the run asked for, where
    the configuration states one; ``gateways`` is (source, sink)."""
    from skyplane_tpu.ops.cdc import CDCParams

    out = []
    for gw in gateways:
        d = gw.daemon
        for what, built, stated in (
            ("tls", bool(d.use_tls), bool(cfg["encrypt_socket_tls"])),
            ("e2ee", d.e2ee_key is not None, bool(cfg["encrypt_e2e"])),
            ("cdc", d.cdc_params, CDCParams(cfg["cdc_min_bytes"], cfg["cdc_avg_bytes"], cfg["cdc_max_bytes"])),
            ("batch_window", d.batch_runner.max_batch if d.batch_runner is not None else None, cfg["batch_window"]),
        ):
            if built != stated:
                out.append(f"{d.gateway_id}.{what}: built {built}, stated {stated}")
    if store_bytes is not None:  # stated only where dedup is on: the sink has a store
        d = gateways[-1].daemon
        built = d.receiver.segment_store._max_bytes
        if built != store_bytes:
            out.append(f"{d.gateway_id}.segment_store_bytes: built {built}, stated {store_bytes}")
    return out


class ChunkSource(threading.Thread):
    """Makes chunk ``i`` of the cell from (seed, i), writes it where the source
    gateway reads it and keeps its digest; stays ``ahead`` chunks ahead, from
    chunk ``start`` on (the rows before it are the set-up's)."""

    def __init__(self, generator, src_dir: Path, ahead: int, start: int = 1):
        super().__init__(name="bench-generator", daemon=True)
        self.generator = generator
        self.src_dir = src_dir
        self.start_index = start
        self.ready: queue.Queue = queue.Queue(maxsize=ahead)
        self.halt = threading.Event()

    def make(self, index: int) -> dict:
        from lib.check import bytes_digest, row_key

        arr = self.generator.setup_chunk() if index == 0 else self.generator.chunk(index)
        path = self.src_dir / f"chunk_{index:05d}.bin"
        with open(path, "wb") as f:
            f.write(arr)
        return {"index": index, "path": path, "digest": bytes_digest(arr), "key": row_key(arr), "n_bytes": len(arr)}

    def run(self):
        index = self.start_index
        while not self.halt.is_set():
            made = self.make(index)
            while not self.halt.is_set():
                try:
                    self.ready.put(made, timeout=0.2)
                    break
                except queue.Full:
                    continue
            index += 1


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = Cell(args.workload)
    if args.rehearse_scale > 0:
        os.environ.setdefault("SKYPLANE_TPU_FORCE_ACCEL_PATH", "1")

    # the reference's workers first of all: no jax in this process yet and no
    # thread; they wait on their stdin until the window has closed
    from lib import refpool

    cfg = cell.config["transfer"]
    pool = refpool.ReferencePool(
        cell.generator_file, cell.workload["content"], args.seed, max(args.rehearse_scale, 1),
        (cfg["cdc_min_bytes"], cfg["cdc_avg_bytes"], cfg["cdc_max_bytes"]), refpool.pool_size(),
    )
    log(f"reference pool: {len(pool.procs)} workers started, pids {pool.pids()} (jax imported: {'jax' in sys.modules})")
    try:
        return run_cell(args, cell, pool)
    finally:
        pool.close()


def run_cell(args, cell: Cell, pool) -> int:
    cfg = cell.config["transfer"]
    traffic = cell.workload["traffic"]
    burst = cell.burst
    rehearsal = args.rehearse_scale > 0
    phases = {}

    def phase(name: str, since: float) -> float:
        phases[name] = round(time.monotonic() - since, 3)
        return time.monotonic()

    try:
        from skyplane_tpu.utils.compile_cache import configure_compile_cache
    except ImportError as err:
        print(f"the program is not in this directory: {err}", file=sys.stderr)
        return 3
    cache_dir = configure_compile_cache()
    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if (platform != "tpu" or len(devices) < cell.entry["chips"]) and not rehearsal:
        print(
            f"jax found {len(devices)} x {platform!r} ({kind}); {args.workload} needs {cell.entry['chips']} TPU chip(s): no result",
            file=sys.stderr,
        )
        return 2
    t = phase("imports_and_device_s", T_START)

    import numpy as np

    from lib import check, pair, span as span_lib, trace as trace_lib
    from skyplane_tpu.native import datapath as native_dp
    from skyplane_tpu.ops.cdc import CDCParams
    from skyplane_tpu.ops.codecs import get_codec

    if not native_dp.available():  # builds libskydp.so once per checkout
        log("the native library is not available: the numpy host paths serve")
    t = phase("native_library_s", t)

    tmp = Path(tempfile.mkdtemp(prefix="skyplane_bench_"))
    deadline = arm_deadline(DEADLINE_S, tmp, pool)
    deadline_at = time.monotonic() + DEADLINE_S
    source = sink = None
    tracing = has_rate = correct = False
    unsound = None
    compared: dict = {}
    result: dict = {}
    try:
        src_dir, dst_dir = tmp / "source", tmp / "sink"
        src_dir.mkdir()
        dst_dir.mkdir()
        generator = cell.generator(cell.workload["content"], args.seed, max(args.rehearse_scale, 1))
        in_flight = int(traffic["in_flight_chunks"])
        n_setup = cell.setup_chunks
        chunks = ChunkSource(generator, src_dir, ahead=in_flight, start=n_setup)
        setup_made = chunks.make(0)
        t = phase("setup_chunk_made_s", t)

        cdc = (cfg["cdc_min_bytes"], cfg["cdc_avg_bytes"], cfg["cdc_max_bytes"])
        # a rehearsal cuts the store with the rows, so that it spills where the timed run does
        store_bytes = None if cell.store_mb is None else (cell.store_mb << 20) // max(args.rehearse_scale, 1)
        source, sink = pair.make_pair(
            tmp,
            compress=cfg["compress"],
            dedup=cfg["dedup"],
            encrypt=cfg["encrypt_e2e"],
            use_tls=cfg["encrypt_socket_tls"],
            num_connections=cfg["num_connections"],
            cdc_params=CDCParams(*cdc),
            sink_segment_store_bytes=store_bytes,
        )
        if store_bytes is not None:
            log(f"sink segment store: memory tier bound {store_bytes} bytes, as the configuration states")
        runner = source.daemon.batch_runner
        taps: dict = {}
        if runner is not None:
            tap_runner(runner, taps)
        if args.trace:
            annotate_device_spans()
        t = phase("pair_s", t)

        request_bytes = cfg["multipart_chunk_size_mb"] << 20
        sent: list = []
        pending: dict = {}
        landed = pair.StatusReader(sink)
        frames_seen: list = []

        def post(made_now: list) -> list:
            """Post these chunks in as few requests as ``pair.POST_BATCH``
            allows; each is pending until the sink calls it complete."""
            dst_paths = [dst_dir / made["path"].name for made in made_now]
            ids = pair.post_files(source, [(made["path"], dst, request_bytes) for made, dst in zip(made_now, dst_paths)])
            posted_at = time.time()
            out = []
            for made, dst_path, (chunk_id,) in zip(made_now, dst_paths, ids):
                s = check.Sent(
                    index=made["index"], chunk_id=chunk_id, key=made["key"], digest=made["digest"], n_bytes=made["n_bytes"],
                    src_path=made["path"], dst_path=dst_path, posted_at=posted_at,
                )
                sent.append(s)
                pending[chunk_id] = s
                out.append(s)
            landed.track(s.chunk_id for s in out)
            return out

        polls = [0]

        def poll(pending: dict) -> list:
            """Chunks of ``pending`` the sink now calls complete, in order of
            completion. A gateway that reports an error ends the run; a status
            log that dropped records before they were read ends it unsound."""
            done = landed.poll()
            frames_seen.extend(pair.decode_events(sink))
            polls[0] += 1
            if polls[0] % 10 == 0:
                for gw in (source, sink):
                    errs = pair.errors(gw)
                    if errs:
                        raise GatewayFault(f"gateway {gw.daemon.gateway_id} reports: {errs[0][:2000]}")
            out = []
            for cid, when in sorted(done.items(), key=lambda kv: kv[1]):
                s = pending.pop(cid)
                s.completed_at, s.cpu_at_completion = when, time.process_time()
                s.src_path.unlink(missing_ok=True)
                out.append(s)
            return out

        setup_rows: set = set()  # indices of the set-up chunks and the set-up bursts' chunks

        def land(made_now: list) -> None:
            """Post these chunks at once and wait until the sink calls every
            one complete and the source too: the source logs a chunk complete
            once it has taken the sink's ack and put the chunk's fingerprints
            in its index, which the sink's own ``complete`` does not imply.
            Rows of the set-up, not of the window."""
            acked = pair.StatusReader(source)
            posted = post(made_now)
            setup_rows.update(s.index for s in posted)
            acked.track(s.chunk_id for s in posted)
            while pending:
                time.sleep(POLL_S)
                poll(pending)
            while acked.waiting:
                time.sleep(POLL_S)
                acked.poll()

        t0 = first = setup_seconds = None
        at_t0: dict = {}
        feed_wait_s = 0.0
        try:
            # ---- set-up chunk: the base of the cell's content, and the row
            # that loads both device programs at the timed shape
            land([setup_made])
            t = phase("setup_chunk_landed_s", t)
            if n_setup > 1:
                # ---- the further set-up rows, one at a time in index order:
                # each is in the source's index before the next is posted, so
                # what a post finds there is the same in every run
                for index in range(1, n_setup):
                    land([chunks.make(index)])
                t = phase("setup_chunks_landed_s", t)
                log(f"set-up: {n_setup} rows landed one at a time")

            chunks.start()
            if burst:
                # ---- set-up bursts: the first loads the programs of a window
                # of several rows, the second proves that nothing is left to load
                counters = source.get("profile/compression")
                for nth in (1, 2):
                    made_now = [chunks.ready.get() for _ in range(burst)]
                    t_burst = time.monotonic()
                    land(made_now)
                    was, counters = counters, source.get("profile/compression")
                    rows, windows, compiles = (int(counters.get(k, 0)) - int(was.get(k, 0)) for k in ("batch_rows", "batch_windows", "xla_compiles"))
                    log(f"set-up burst {nth}: {burst} chunks landed in {time.monotonic() - t_burst:.2f}s, {windows} windows for {rows} rows, {compiles} compiles")
                    if nth == 1 and rows <= windows:
                        raise SetupUnsound(f"burst 1 of {burst} chunks ran no window of more than one row ({windows} windows for {rows} rows)")
                    if nth == 2 and compiles > 0:
                        raise SetupUnsound(f"burst 2 of {burst} chunks compiled {compiles} programs: a window after it would compile too")
                t = phase("setup_bursts_s", t)

            # ---- fill the pipeline; the window opens at the first completion
            if args.trace:
                trace_dir = tmp / "trace"
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
                tracing = True
                mark_wall_ns = time.time_ns()
                with jax.profiler.TraceAnnotation(MARK):
                    time.sleep(0.001)
            if cell.fill_stagger_s:
                for n in range(in_flight):
                    if n:
                        time.sleep(cell.fill_stagger_s)
                    post([chunks.ready.get()])
            else:  # the whole fill before the first poll
                post([chunks.ready.get() for _ in range(in_flight)])
            starving_since = None
            while True:
                time.sleep(POLL_S)
                for s in poll(pending):
                    if t0 is None:
                        t0, first = s.completed_at, s
                        setup_seconds = time.monotonic() - (time.time() - t0) - T_START
                        decode = sink.get("profile/decode")  # the GET drains the events: keep them
                        frames_seen.extend(decode["events"])
                        at_t0 = {"source": source.get("profile/compression"), "sink": decode["counters"], "cpu": s.cpu_at_completion}
                        thread_cpu_at_t0 = time.thread_time()
                        phase("first_window_chunk_s", t)
                        log(f"t0: first window chunk complete; set-up {setup_seconds:.2f}s, phases {phases}")
                if t0 is not None and time.time() >= t0 + args.seconds:
                    break
                ready = []
                while len(pending) + len(ready) < in_flight:
                    try:
                        ready.append(chunks.ready.get_nowait())
                    except queue.Empty:
                        if starving_since is None and t0 is not None:
                            starving_since = time.monotonic()
                        break
                    if starving_since is not None:
                        feed_wait_s += time.monotonic() - starving_since
                        starving_since = None
                if ready:
                    post(ready)
        except GatewayFault as err:
            log(f"FAIL: {err}")
        cpu_at_deadline = time.process_time()
        harness_cpu_s = None
        if t0 is not None:
            # the harness's own thread against the whole process, t0 to the close: logged, no metric
            harness_cpu_s = time.thread_time() - thread_cpu_at_t0
            log(f"harness main thread: {harness_cpu_s:.3f}s of CPU, the process {cpu_at_deadline - at_t0['cpu']:.3f}s, t0 to the close")
        chunks.halt.set()

        # ---- the window has closed
        record = None
        if args.trace:
            jax.profiler.stop_trace()
            tracing = False
        mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[: cell.entry["chips"]])
        window = [s for s in sent if s.index not in setup_rows and s is not first]
        times = [s.completed_at if s.completed_at is not None else float("inf") for s in window]
        measured = span_lib.measured_span(times, t0, args.seconds) if t0 is not None else None
        n_counted = len(measured.counted) if measured else sum(1 for x in times if t0 is not None and x <= t0 + args.seconds)
        log(f"window closed: {n_counted} completions after t0" + (f", span {measured.seconds:.3f}s" + (" (closed at t0 + seconds: trailing stall)" if measured.stalled else "") if measured else ": no rate"))

        # the reference's workers make every row that was sent, while the
        # device finishes the chunks still in flight and this thread waits for
        # each, then reads what the timed path produced (and the trace)
        obs = check.Observed(
            sent=sent, file_digests={}, device_rows={},
            row_bytes=lambda i: generator.chunk(i) if i else generator.setup_chunk(),
            counters={}, frames=[], gateway_errors=0, as_built_departures=[], cdc=cdc,
            wire_codec_id=int(get_codec(cfg["compress"]).codec_id), setup_rows=tuple(range(n_setup)),
        )
        t_ref, closed_at = time.monotonic(), time.time()
        background = ThreadPoolExecutor(max_workers=1, thread_name_prefix="bench-reference")
        making_reference = background.submit(check.compute_reference, obs, pool)
        late_until = time.monotonic() + LATE_S
        while pending and time.monotonic() < late_until:
            time.sleep(POLL_S)
            try:
                poll(pending)
            except GatewayFault as err:
                log(f"FAIL: {err}")
                break
            except pair.StatusLogLost as err:  # the reference is under way: finish, then print no result
                unsound = f"the run is not sound: {err}"
                break
        drained_s = time.monotonic() - t_ref

        # ---- what the timed path produced
        by_key = {s.key: s for s in sent}
        blocked_s = {}
        for key, (handle, submit_s) in list(taps.items()):
            s = by_key.get(key)
            if s is not None and s.completed_at is not None:
                obs.device_rows[s.index] = (np.asarray(handle.ends()), list(handle.fps()))
                blocked_s[s.index] = submit_s + handle.wait_ns / 1e9
        for s in sent:
            if s.completed_at is not None:
                obs.file_digests[s.index] = check.file_digest(s.dst_path)
        obs.counters = source.get("profile/compression")
        decode = sink.get("profile/decode")
        obs.frames = frames_seen + decode["events"]
        obs.gateway_errors = len(pair.errors(source)) + len(pair.errors(sink))
        events = source.get("events", params={"since": 0})["events"]
        obs.as_built_departures = departures_from(cfg, (source, sink), store_bytes)
        if args.trace and measured:
            record = trace_lib.extract(trace_lib.find_xplane(str(trace_dir)), rehearsal=rehearsal)  # no device plane: raises, no result
        reference = making_reference.result()
        background.shutdown()
        pool.close()
        reference["workers_ended"] = [p.returncode for p in pool.procs]
        reference_s = time.monotonic() - t_ref
        reference["started_after_close_s"] = round(reference.pop("first_started_at") - closed_at, 3)
        log(f"reference: {reference}")
        compared = check.compare(obs)
        checks_passed = check.passed(compared)
        control = None
        if args.control:
            check.CONTROLS[args.control](obs)
            control_compared = check.compare(obs)
            control = {"name": args.control, "correct": check.passed(control_compared), "compared": control_compared}

        # ---- facts the metrics read
        facts: dict = {"setup.seconds": setup_seconds}
        counted = [window[i] for i in measured.counted] if measured else []
        frames = {ev["chunk_id"]: ev for ev in obs.frames}
        if measured and all(s.chunk_id in frames for s in counted):
            cpu_end = cpu_at_deadline if measured.stalled else counted[-1].cpu_at_completion
            facts.update({
                "span.seconds": measured.seconds,
                "span.chunks": len(counted),
                "span.raw_bytes": sum(int(frames[s.chunk_id]["raw_bytes"]) for s in counted),
                "span.wire_bytes": sum(int(frames[s.chunk_id]["wire_bytes"]) for s in counted),
                "span.cpu_s": cpu_end - at_t0["cpu"],
                "span.feed_wait_s": feed_wait_s,
                "tap.rows": sum(1 for s in counted if s.index in blocked_s),
                "tap.device_wait_s": sum(blocked_s.get(s.index, 0.0) for s in counted),
            })
        for side, now in (("source", obs.counters), ("sink", decode["counters"])):
            for k, v in now.items():
                if isinstance(v, (int, float)) and isinstance(at_t0.get(side, {}).get(k), (int, float)):
                    facts[f"{side}_after_t0.{k}"] = v - at_t0[side][k]
        breakdown = None
        device = {"platform": platform, "kind": kind, "count": len(devices), "memory_peak_bytes": int(mem_peak)}
        if record is not None:
            offset = trace_lib.clock_offset_ns(record, MARK, mark_wall_ns)
            lo, hi = measured.start * 1e9 - offset, measured.end * 1e9 - offset
            busy = trace_lib.busy_ns(record, lo, hi)
            facts.update({
                "trace.window_s": (hi - lo) / 1e9,
                "trace.busy_s": busy / 1e9,
                "trace.idle_s": (hi - lo - busy) / 1e9,
            })
            device.update({"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9})
            breakdown = {
                "device_ops": trace_lib.top(trace_lib.sums_by_name(record, lo, hi)),
                "idle_gaps": trace_lib.top(trace_lib.idle_gaps(record, lo, hi)),
            }
            log(f"device programs in the span: {trace_lib.top(trace_lib.sums_by_name(record, lo, hi, key='modules'))}")
        run = {
            "device_kind": kind, "config": cell.config, "workload": cell.workload, "events": events,
            "span_row_bytes": [s.n_bytes for s in counted],
        }
        metrics = {}
        for entry, spec in cell.metrics("per_layer" if args.trace else "end_to_end"):
            try:
                value = read_metric(spec, facts, run)
            except KeyError as err:
                if not rehearsal:
                    raise
                log(f"rehearsal: {entry['name']} not read: {err}")
                value = None
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
        failed = compared["chunks_never_landed"]["value"] + compared["files_not_identical"]["value"]
        has_rate = measured is not None and "span.seconds" in facts
        correct = bool(checks_passed and has_rate and not rehearsal and platform == "tpu")
        result = {
            "correct": correct,
            "attempted": len(counted) + failed,
            "failed": failed,
            "metrics": {} if rehearsal else metrics,
            "device": device,
        }
        if rehearsal:
            result["rehearsal"] = {"scale": args.rehearse_scale, "checks_passed": checks_passed, "values": metrics}
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["run"] = {
            "workload": args.workload, "seed": args.seed, "seconds": args.seconds, "span_s": facts.get("span.seconds"),
            "completions": len(counted),
            "gaps_s": [round(b - a, 4) for a, b in zip([t0] + [s.completed_at for s in counted], [s.completed_at for s in counted])] if t0 else [],
            "stalled": bool(measured and measured.stalled), "phases": phases,
            "reference_s": round(reference_s, 3), "drained_s": round(drained_s, 3), "compile_cache": cache_dir,
            "reference_rows": len(obs.reference_rows), "rows_sent": len(sent), "setup_rows": len(setup_rows), "reference": reference,
            "device_windows_after_t0": dict(
                {k: facts.get(f"source_after_t0.batch_{k}") for k in ("rows", "windows", "padded_rows")},
                compiles=facts.get("source_after_t0.xla_compiles"),
            ),
            "harness_thread_cpu_s": harness_cpu_s, "sink_status_log_dropped": sink.daemon.api._status_log_dropped,
            "sink_store": {
                "run": {k: decode["counters"].get(k) for k in SINK_STORE_COUNTERS},
                "after_t0": {k: facts.get(f"sink_after_t0.{k}") for k in SINK_STORE_COUNTERS},
            },
        }
        if control is not None:
            result["control"] = control
        result["compared"] = compared
        log(f"span {facts.get('span.seconds')} s, {len(counted)} completions; reference {reference_s:.1f}s, all landed after {drained_s:.1f}s")
        if control is not None:
            log(f"control {control['name']}: correct={control['correct']} " + " ".join(f"{k}={v['value']}" for k, v in control["compared"].items() if v["value"] > v["limit"]))
    except SetupUnsound as err:
        unsound = f"the set-up is not sound: {err}"
    except pair.StatusLogLost as err:
        unsound = f"the run is not sound: {err}"
    except BaseException:
        tear_down((source, sink), tmp, TEARDOWN_S)
        raise
    finally:
        if tracing:
            jax.profiler.stop_trace()
    # ---- the result first, the teardown after it
    if unsound:
        log(f"FAIL: {unsound}: no result")
        rc = 5
    elif not has_rate:
        log("FAIL: fewer than two completions after t0 inside the window: no rate")
        rc = 1 if rehearsal else 4
    else:
        rc = 1 if rehearsal else 0
    if rc in (0, 1):
        print(json.dumps(result), flush=True)
        log("result line printed")
    deadline.cancel()  # what is left is bounded by the teardown's own limit
    stopped = tear_down((source, sink), tmp, min(TEARDOWN_S, deadline_at - time.monotonic()))
    if not unsound:  # the numbers compared, each beside its limit, are stderr's last lines
        for name, c in compared.items():
            print(f"compared {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
        print(f"correct: {correct}", file=sys.stderr, flush=True)
    if not stopped:
        pool.kill()
        leave(rc)
    return rc


if __name__ == "__main__":
    sys.exit(main())
