"""Micro-batching of CDC + fingerprint device work across gateway workers.

A gateway runs 16-32 sender workers (plus the receiver decode pool when
paranoid recipe verification re-fingerprints restored chunks), each
processing one chunk at a time. On an accelerator, per-chunk device calls
waste dispatch round trips and run undersized kernels; this runner groups
concurrent same-size submissions into one [B, N] batch (SURVEY §7 hard part
#2: batching with BOUNDED latency — small transfers must not wait for a
full batch).

The batched work itself is ops/fused_cdc.py: gear hash, boundary selection
and segment fingerprints run as two compiled programs per batch with two
small packed readbacks, so what crosses the host link per window is the
chunk bytes once and a few hundred KiB of metadata.

Leader-based protocol (no dedicated thread): the first worker to open a
batch window waits ``max_wait_ms`` for peers, then executes the batched
kernels for everyone and distributes results. Workers arriving later join
the open window; a full window flushes immediately (the leader's wait is a
``threading.Condition``, so it reacts to full/flushed/drained events the
moment they happen instead of on a poll tick). Because the leader pops its
window before running, the next window opens (and can dispatch) while the
previous batch is still in flight — device pipelining comes free.

Allocation-free steady state: padded bucket buffers come from a shared
``BufferPool`` (ops/bufpool.py) and are recycled as soon as the batch's
device dispatch no longer needs the host bytes; after the first few windows
per bucket the pool services every submission without touching the
allocator (pool-miss counter goes flat — asserted in tests).

Two-phase completion: segment ends are distributed to waiters as soon as
call A + host boundary selection finish (``BatchHandle.ends``), while the
fingerprint kernel and its readback are still in flight — workers overlap
recipe span assembly with the device; ``BatchHandle.fps`` then finalizes
that worker's OWN digests from the batched lanes readback, so the
per-digest host work is parallelized across workers instead of serialized
in the leader.

Enabled by DataPathProcessor when running on an accelerator with
``tpu_batch_chunks > 1``; pure CPU gateways keep the (faster for them)
numpy/native host path.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from skyplane_tpu.obs import get_tracer
from skyplane_tpu.ops.bufpool import BufferPool, bucket_size
from skyplane_tpu.ops.cdc import CDCParams
from skyplane_tpu.ops.fused_cdc import FusedCDCFP, finalize_row
from skyplane_tpu.obs import lockwitness as lockcheck


@dataclass(eq=False)  # identity semantics: dataclass __eq__ on ndarray fields
class _Entry:  # raises 'ambiguous truth value' in membership tests
    arr: np.ndarray  # padded to the bucket size
    n: int  # true length
    pooled: bool = False  # arr came from the runner's BufferPool (recycle after dispatch)
    dev: object = None  # pre-staged device buffer (async H2D at submit)
    ends_ready: threading.Event = field(default_factory=threading.Event)  # phase 1
    done: threading.Event = field(default_factory=threading.Event)  # phase 2
    ends: Optional[np.ndarray] = None
    lanes: Optional[np.ndarray] = None  # [n_slots, 8] fingerprint lanes (finalized lazily)
    fps: Optional[List[bytes]] = None  # set directly for overflow-fallback rows
    error: Optional[BaseException] = None


class BatchHandle:
    """Per-submission two-phase result. ``ends()`` unblocks when boundary
    selection lands (fingerprints may still be in flight); ``fps()`` then
    finalizes this row's digests in the CALLING worker's thread. ``wait_ns``
    accumulates the time this handle actually spent blocked on the device —
    the hot-path stall the overlap scheduling is there to hide. It leaves out
    whatever ``submit`` did before it returned the handle: the pad copy and
    staging, the leader's window wait and, for a window's leader, the whole
    batch it ran there (a leader never waits on its own handle, so a window
    of one row reads 0). All the time a chunk's worker spends on the device
    path, those included, is the ``device_path_ns`` counter of
    ``DataPathStats`` (ops/pipeline.py)."""

    def __init__(self, entry: _Entry):
        self._entry = entry
        self.wait_ns = 0

    def _wait(self, event: threading.Event) -> None:
        if not event.is_set():
            t0 = time.perf_counter_ns()
            t0_wall = time.time_ns()
            event.wait(timeout=600)
            waited = time.perf_counter_ns() - t0
            self.wait_ns += waited
            tracer = get_tracer()
            if tracer.enabled:
                # the hot-path device stall the overlap scheduling hides;
                # async track — many workers wait on one batch concurrently
                tracer.record_span("batch.device_wait", waited, t0_wall, cat="device")
        if not event.is_set():
            raise TimeoutError("device batch runner stalled")
        if self._entry.error is not None:
            raise self._entry.error

    def ends(self) -> np.ndarray:
        self._wait(self._entry.ends_ready)
        return self._entry.ends

    def fps(self) -> List[bytes]:
        e = self._entry
        self._wait(e.done)
        if e.fps is None:
            e.fps = finalize_row(e.lanes, e.ends)  # this worker's row only
            e.lanes = None
        return e.fps


class DeviceBatchRunner:
    def __init__(
        self,
        cdc_params: CDCParams = CDCParams(),
        max_batch: int = 8,
        max_wait_ms: Optional[float] = None,
        mesh=None,
        pool: Optional[BufferPool] = None,
    ):
        self.cdc_params = cdc_params
        self.max_batch = max_batch
        if max_wait_ms is None:
            # window-formation wait: how long a lone chunk waits for peers
            try:
                max_wait_ms = float(os.environ.get("SKYPLANE_TPU_BATCH_WAIT_MS", "3"))
            except ValueError:
                max_wait_ms = 3.0
        # NaN / inf / negative would stall or kill the window leader
        # (Condition.wait raises on NaN), whether it came from the env var or
        # a caller's computed value; a wait beyond a few seconds is never
        # useful, so clamp rather than obey a typo
        import math

        if not math.isfinite(max_wait_ms) or max_wait_ms < 0:
            max_wait_ms = 3.0
        self.max_wait_s = min(max_wait_ms, 5000.0) / 1000.0
        # hard ceiling on the leader's window-deferral wait (ADVICE r5): the
        # "keep the window open while the previous batch runs" optimization
        # assumes the in-flight batch finishes. If a fused call wedges,
        # _in_flight never returns to 0 and the leader would defer forever,
        # never reaching the 600s entry backstop that protects every other
        # waiter. Past the ceiling the leader flushes anyway, so a wedged
        # device batch surfaces as the existing TimeoutError.
        self.defer_ceiling_s = max(100.0 * self.max_wait_s, 120.0)
        self._lock = lockcheck.wrap(threading.Lock(), "DeviceBatchRunner._lock")
        # window-formation condition (same mutex): joiners notify on a full
        # flush, _run_batch notifies when a batch drains — the leader reacts
        # immediately instead of sleep-polling a 10 ms tick
        self._cond = threading.Condition(self._lock)
        self._open: Dict[int, List[_Entry]] = {}  # bucket size -> entries of the open window
        # batches currently executing, PER BUCKET: a lone chunk's timed flush
        # defers only while its own bucket's previous batch runs (bounded by
        # one batch duration — the FIFO floor); sustained traffic in another
        # bucket must not starve it
        self._in_flight: Dict[int, int] = {}
        # shared padded-buffer pool: submissions without a caller-provided
        # padded buffer draw from here and recycle after the batch dispatch
        self.pool = pool if pool is not None else BufferPool()
        self._counters = {
            "batch_windows": 0,
            "batch_rows": 0,
            "batch_padded_rows": 0,
            "spmd_batches": 0,
            "spmd_check_batches": 0,
        }
        self._stage_failures: Dict[int, int] = {}  # bucket -> count (first occurrence logged)
        # the first window pays the fresh XLA compile (often the single
        # largest fixed cost of a small transfer): journal it as
        # phase.first_compile so the job waterfall can name it (obs/timeline.py)
        self._saw_first_window = False
        self._zero_rows: Dict[int, np.ndarray] = {}  # bucket -> shared READ-ONLY zero pad row
        self._dev_zero_rows: Dict[int, object] = {}  # bucket -> staged device zero row
        # multi-device gateway (TPU slice): run the fused kernels sharded over
        # the mesh so every chip works the data path, not just chip 0
        # (VERDICT r1 weak #4 — the SPMD path must be the production path).
        # Boundary selection is sequential per chunk, so chunks (the batch
        # dim) are the parallel axis. Shard over ALL mesh axes when the
        # device count fits the window; otherwise shard over the data axis
        # only — never inflate the window by more than 2x (a 32-chip slice
        # must not silently turn an 8-chunk window into 32 rows the 16
        # sender workers can never fill).
        self.mesh = mesh
        self.shard_axes = None
        if mesh is not None:
            sizes = dict(mesh.shape)
            n_flat = int(np.prod(list(sizes.values())))
            data_ax = sizes.get("data", n_flat)
            if n_flat <= self.max_batch:
                self.shard_axes = tuple(sizes.keys())
                divisor = n_flat
            elif data_ax <= self.max_batch:
                self.shard_axes = ("data",)
                divisor = data_ax
                self._warn(
                    f"mesh has {n_flat} devices but the batch window is {self.max_batch}: "
                    f"sharding over the data axis only ({data_ax}); raise tpu_batch_chunks to use all chips"
                )
            else:
                self.mesh = None
                divisor = 1
                self._warn(
                    f"mesh axes {sizes} exceed the {self.max_batch}-chunk batch window; running unsharded "
                    f"— raise tpu_batch_chunks to at least the data-axis size to shard the data path"
                )
            if self.max_batch % divisor:
                new_batch = ((self.max_batch + divisor - 1) // divisor) * divisor
                self._warn(f"rounding max_batch {self.max_batch} -> {new_batch} to divide {divisor} mesh shards")
                self.max_batch = new_batch
        self._fused = FusedCDCFP(cdc_params, mesh=self.mesh, shard_axes=self.shard_axes, pool=self.pool)
        # structural bit-identity assertion for the mesh path: every sharded
        # batch is checked against the host recompute before any result
        # leaves the runner (tests, dryruns, paranoid deployments)
        self._spmd_check = os.environ.get("SKYPLANE_TPU_SPMD_CHECK", "0").strip().lower() in ("1", "on", "true", "yes")

    @staticmethod
    def _warn(msg: str) -> None:
        from skyplane_tpu.utils.logger import logger

        logger.fs.warning(msg)

    def _note_stage_failure(self, bucket: int, err: BaseException) -> None:
        """Per-chunk staging failure means a silent fall back to host upload
        at flush — fine once, a diagnosable perf bug when it's every chunk.
        Log the FIRST occurrence per bucket; count the rest (counters())."""
        with self._lock:
            n = self._stage_failures.get(bucket, 0)
            self._stage_failures[bucket] = n + 1
        if n == 0:
            self._warn(
                f"async device staging failed for bucket {bucket} ({err!r}); affected rows fall back to "
                f"host upload at flush — further occurrences for this bucket are counted, not logged"
            )

    def counters(self) -> dict:
        """Hot-path health counters, merged into DataPathStats.as_dict()."""
        with self._lock:
            c = dict(self._counters)
            c["stage_failures"] = sum(self._stage_failures.values())
        cap = c["batch_windows"] * self.max_batch
        c["batch_occupancy"] = round(c["batch_rows"] / cap, 4) if cap else 0.0
        # numeric only: merge_numeric_counters sums these across pump workers
        c["spmd_devices"] = int(np.prod(list(self.mesh.shape.values()))) if self.mesh is not None else 1
        c.update(self.pool.counters())
        c.update(self._fused.counters())
        return c

    # ---- public API ----

    def submit(self, arr: np.ndarray, padded: Optional[np.ndarray] = None) -> BatchHandle:
        """Join the current window for this chunk's bucket; returns a
        two-phase handle (see BatchHandle). When ``padded`` is omitted the
        runner pads ``arr`` into a pooled buffer and recycles it itself;
        caller-provided padded buffers are left alone (legacy path)."""
        pooled = padded is None
        # on the worker's own thread, overlapped with the row ahead on the device
        with get_tracer().span("batch.stage", cat="device", args={"bytes": len(arr)}):
            if pooled:
                n = len(arr)
                padded = self.pool.acquire(bucket_size(n))
                padded[:n] = arr
                padded[n:] = 0
            entry = _Entry(arr=padded, n=len(arr), pooled=pooled)
            # double-buffered H2D (single-device runners): upload NOW (async) so
            # the transfer overlaps the in-flight window's compute and this
            # worker's own socket pump; the flush then stacks device-resident
            # buffers. Sharded runners skip staging — device_put would pin every
            # row on chip 0 and the mesh kernels would reshard at flush, paying
            # the transfer on the critical path anyway. Staging failure is not
            # fatal — the flush falls back to a host upload for that row.
            if self.mesh is None:
                try:
                    entry.dev = self._fused.stage(padded)
                except Exception as err:  # noqa: BLE001
                    entry.dev = None
                    self._note_stage_failure(len(padded), err)
        bucket = len(padded)
        with self._lock:
            group = self._open.setdefault(bucket, [])
            group.append(entry)
            leader = len(group) == 1
            full = len(group) >= self.max_batch
            if full:
                self._open[bucket] = []
                to_run = group
                self._cond.notify_all()  # a deferring leader's window just flushed
            else:
                to_run = None
        if to_run is not None:
            self._run_batch(to_run)
        elif leader:
            # Window-formation policy (bounded latency + adaptive fill): wait
            # max_wait_ms for peers, but while a previous batch is still
            # EXECUTING keep the window open — device compute is FIFO, so this
            # window cannot start any sooner by flushing, and staggered
            # arrivals (the realistic socket-pump pattern) accumulate into a
            # full window instead of degenerating into padded windows of one
            # chunk each. The device going idle (or the window filling, via
            # the full-flush path above) notifies the condition and ends the
            # wait IMMEDIATELY, so small transfers still see only the
            # max_wait_ms floor and never a poll-tick tax on top.
            deadline = time.monotonic() + self.max_wait_s
            hard_deadline = deadline + self.defer_ceiling_s
            ceiling_flush = False
            with get_tracer().span("batch.window_wait", cat="device", args={"bucket": bucket}):
                with self._cond:
                    while True:
                        group_now = self._open.get(bucket, [])
                        # the window may already have been flushed by a 'full'
                        # flush (identity check: _Entry has eq=False by design)
                        if not any(e is entry for e in group_now):
                            break
                        now = time.monotonic()
                        if now >= deadline and (self._in_flight.get(bucket, 0) == 0 or now >= hard_deadline):
                            ceiling_flush = now >= hard_deadline and self._in_flight.get(bucket, 0) > 0
                            self._open[bucket] = []
                            to_run = group_now
                            break
                        remaining = (deadline - now) if now < deadline else (hard_deadline - now)
                        self._cond.wait(timeout=max(remaining, 0.001))
            if to_run is not None:
                if ceiling_flush:
                    # the previous batch blew the ceiling and may be wedged
                    # inside a hung fused call; a synchronous _run_batch here
                    # would wedge the LEADER in the device FIFO too. Run on a
                    # helper thread so the leader falls through to its own
                    # backstop and raises TimeoutError like every other waiter.
                    threading.Thread(
                        target=self._run_batch, args=(to_run,), name="batch-ceiling-flush", daemon=True
                    ).start()
                else:
                    self._run_batch(to_run)
        return BatchHandle(entry)

    def cdc_and_fps(self, arr: np.ndarray, padded: Optional[np.ndarray] = None) -> Tuple[np.ndarray, List[bytes]]:
        """Blocking single-phase form: (segment ends, 16-byte fingerprints)
        for one chunk. ``padded`` (the zero-padded power-of-two bucket of
        ``arr``) is optional — omitted, the runner pads from its pool."""
        handle = self.submit(arr, padded)
        return handle.ends(), handle.fps()

    # ---- batch execution (leader) ----

    def _zero_row(self, bucket: int) -> np.ndarray:
        """Shared read-only zero row for batch-dim padding (one per bucket,
        ever — np.stack copies it, so reuse is safe and allocation-free)."""
        row = self._zero_rows.get(bucket)
        if row is None:
            row = np.zeros(bucket, np.uint8)
            row.setflags(write=False)
            with self._lock:
                row = self._zero_rows.setdefault(bucket, row)
        return row

    def _dev_zero_row(self, bucket: int, like) -> object:
        """Device-resident zero row for padding staged windows (cached: the
        stacked batch copies it, the cached original is never consumed)."""
        row = self._dev_zero_rows.get(bucket)
        if row is None:
            import jax.numpy as jnp

            row = jnp.zeros_like(like)
            with self._lock:
                row = self._dev_zero_rows.setdefault(bucket, row)
        return row

    def _run_batch(self, entries: List[_Entry]) -> None:
        bucket = len(entries[0].arr)
        with self._lock:
            self._in_flight[bucket] = self._in_flight.get(bucket, 0) + 1
            first_window = not self._saw_first_window
            self._saw_first_window = True
        end_first_compile = None
        if first_window:
            # imperative begin/end (not `with`) keeps the large body below
            # un-reindented; end fires in the finally either way
            from skyplane_tpu.obs.events import PH_FIRST_COMPILE
            from skyplane_tpu.obs.timeline import phase_begin

            end_first_compile = phase_begin(PH_FIRST_COMPILE, bucket=bucket, rows=len(entries))
        n_pad_rows = 0
        try:
            # pad the batch dimension to max_batch with zero rows so XLA sees
            # ONE batch shape per bucket instead of max_batch variants (each
            # distinct B would otherwise pay a fresh multi-second compile);
            # pad rows carry n=0 and are dropped before unpacking
            rows = [e.arr for e in entries]
            lens = [e.n for e in entries]
            # batch-dim buckets {1, group}: a LONE flush (start-of-stream,
            # tail, trickle traffic) runs the ~B-times-cheaper B=1 program
            # instead of a fully padded window; all other sizes pad to a
            # multiple of the rows one dispatch carries (the whole window,
            # unless its rows are too large for one program's HBM) so XLA
            # still compiles at most two batch shapes per bucket. Sharded
            # runners always pad: a batch of 1 cannot split across the
            # mesh's batch axis.
            pad_batch = not (len(rows) == 1 and self.mesh is None)
            group = min(self.max_batch, self._fused.rows_per_dispatch(bucket))
            n_pad_rows = -len(rows) % group if pad_batch else 0
            if self.mesh is not None:
                # sharded path: one host stack; the mesh kernels distribute it
                if n_pad_rows > 0:
                    rows = rows + [self._zero_row(bucket)] * n_pad_rows
                    lens = lens + [0] * n_pad_rows
                pending = self._fused.dispatch(np.stack(rows), lens)
                if self._spmd_check:
                    # gate BEFORE ends leave the runner: a diverging shard
                    # must surface as this window's error, not as corrupt
                    # recipes three stages later
                    self._check_mesh_identity(entries, pending)
            else:
                # host-upload fallback for rows whose async staging failed:
                # passing the numpy row lets jnp.stack do the transfer inside
                # the batch dispatch — no second stage() call that could
                # re-raise and kill the whole window
                dev_rows = [e.dev if e.dev is not None else e.arr for e in entries]
                if n_pad_rows > 0:
                    rows = rows + [self._zero_row(bucket)] * n_pad_rows
                    lens = lens + [0] * n_pad_rows
                    dev_rows = dev_rows + [self._dev_zero_row(bucket, dev_rows[0])] * n_pad_rows
                pending = self._fused.dispatch(rows, lens, dev_rows=dev_rows)
                for e in entries:
                    e.dev = None  # restacked on device: let the staged row's HBM go
            # phase 1: boundary selection is final; the fingerprint kernel is
            # merely ENQUEUED. Wake every waiter so workers overlap recipe
            # span assembly with the in-flight fingerprint compute+readback.
            for e, ends, fb in zip(entries, pending.ends_rows, pending.fallback):
                if fb is not None:
                    e.ends, e.fps = fb  # overflow row: exact host recompute
                else:
                    e.ends = ends
                e.ends_ready.set()
            # the host bytes are no longer needed (device-resident / already
            # recomputed): recycle pooled buffers before the readback wait so
            # the NEXT window's submissions reuse them immediately
            self._release_pooled(entries)
            lanes = pending.lanes()  # phase 2: blocking fingerprint readback
            for i, e in enumerate(entries):
                if e.fps is None:
                    e.lanes = lanes[i]  # digests finalize lazily in the owner's thread
        except BaseException as err:  # noqa: BLE001 — every waiter must wake
            for e in entries:
                e.error = err
            self._release_pooled(entries)
        finally:
            if end_first_compile is not None:
                end_first_compile()
            with self._lock:
                self._in_flight[bucket] -= 1
                self._counters["batch_windows"] += 1
                self._counters["batch_rows"] += len(entries)
                self._counters["batch_padded_rows"] += n_pad_rows
                if self.mesh is not None:
                    self._counters["spmd_batches"] += 1
                self._cond.notify_all()  # deferring leaders: this bucket drained
            for e in entries:
                e.ends_ready.set()
                e.done.set()

    def _check_mesh_identity(self, entries: List[_Entry], pending) -> None:
        """SKYPLANE_TPU_SPMD_CHECK: assert the mesh-sharded batch is
        bit-identical to the host recompute. ``lanes()`` is cached, so the
        eager readback here makes the later phase-2 call free; verified rows
        get ``fps`` set directly, skipping lazy finalize."""
        from skyplane_tpu.ops.cdc import cdc_and_fps_host

        lanes = pending.lanes()
        for i, e in enumerate(entries):
            if pending.fallback[i] is not None:
                continue  # overflow rows already ARE the exact host recompute
            ends = pending.ends_rows[i]
            fps = finalize_row(lanes[i], ends)
            ref_ends, ref_fps = cdc_and_fps_host(e.arr[: e.n], self.cdc_params)
            if not np.array_equal(np.asarray(ends), np.asarray(ref_ends)) or list(fps) != list(ref_fps):
                raise AssertionError(
                    f"SPMD mesh batch diverged from host recompute (bucket {len(e.arr)}, row {i}, n={e.n})"
                )
            e.fps = fps
        with self._lock:
            self._counters["spmd_check_batches"] += 1

    def _release_pooled(self, entries: List[_Entry]) -> None:
        for e in entries:
            if e.pooled:
                self.pool.release(e.arr)
                e.pooled = False
