"""ServiceController: a crash-safe job control plane over a standing fleet.

The batch tool pays provision + cold start on every ``cp``; the service mode
keeps one warm dataplane up (pre-compiled FusedCDCFP, pre-dialed sender
pools, resident PersistentDedupIndex) and turns each transfer into a JOB:
submitted through the existing admission API (``POST /api/v1/jobs``, PR 6),
dispatched in well under a second because nothing provisions, tracked to
sink-measured completion, and finalized with an admission release.

Durability model (docs/service-mode.md): every state transition is
write-ahead logged to :class:`~skyplane_tpu.service.wal.ServiceWAL` BEFORE
the action it describes, so a controller SIGKILLed at any point restarts and

  * **re-adopts the live fleet** — each gateway is re-bound via its
    ``GET /api/v1/status`` probe (:func:`skyplane_tpu.api.dataplane.attach_gateway`);
    the daemons never noticed the controller die;
  * **reconciles in-flight jobs against the sink** — for every dispatched
    chunk the sink's ``chunk_status`` map is the ground truth; chunks the
    sink reports complete are marked landed (no resend), everything else is
    requeued under its ORIGINAL chunk id, so the gateway's idempotent
    re-register turns an ambiguous crash into zero duplicate side effects;
  * **replays client idempotency keys** — ``submit(spec, idem_key=k)`` for a
    key the WAL already holds returns the existing job (finished or not)
    instead of double-running it.

The controller is deliberately stepwise (``submit`` / ``poll_once`` /
``heartbeat_once`` / ``tick``): tests drive transitions one at a time, the
worker loop (service/worker.py) just calls ``tick`` forever, and the chaos
soak can kill the process between any two steps.

Fault points (docs/fault-injection.md): ``service.crash`` hard-exits the
process (``os._exit``) at the dispatch, reconcile, and compact boundaries —
the exact windows recovery must survive; ``service.journal_torn`` lives in
the WAL append itself.
"""

from __future__ import annotations

import os
import threading
import time
import uuid
from pathlib import Path
from typing import Dict, List, Optional

import requests

from skyplane_tpu.api.dataplane import BoundGateway, attach_gateway
from skyplane_tpu.chunk import Chunk, ChunkRequest
from skyplane_tpu.exceptions import SkyplaneTpuException
from skyplane_tpu.faults import get_injector
from skyplane_tpu.service.wal import (
    REC_DISPATCH,
    REC_FINALIZE,
    REC_PROGRESS,
    REC_SUBMIT,
    ServiceWAL,
)
from skyplane_tpu.service.watch import compute_sync_delta, walk_pairs
from skyplane_tpu.utils.logger import logger
from skyplane_tpu.utils.retry import retry_backoff
from skyplane_tpu.obs import lockwitness as lockcheck

#: job states (WAL-derived; "watching" jobs are standing sync-watch specs)
ST_SUBMITTED = "submitted"
ST_DISPATCHED = "dispatched"
ST_DONE = "done"
ST_FAILED = "failed"
ST_WATCHING = "watching"

#: sink chunk_status poll batching bound (http.server request-line limit)
_MAX_IDS_PER_POLL = 1500


def _crash_point(boundary: str) -> None:
    """``service.crash`` fault point: die HARD (no atexit, no flush beyond
    what the WAL already fsynced) at a named controller boundary — the
    windows the WAL exists to make survivable."""
    inj = get_injector()
    if inj.enabled and inj.fire("service.crash"):
        logger.fs.warning(f"[service] injected service.crash at {boundary} boundary — os._exit(86)")
        os._exit(86)


class ServiceJob:
    """One WAL-backed job. ``chunks`` maps chunk_id -> chunk descriptor dict
    (src_key, dest_key, offset, length); ``landed`` holds the sink-confirmed
    chunk ids."""

    __slots__ = (
        "job_id",
        "idem",
        "spec",
        "state",
        "chunks",
        "landed",
        "error",
        "submitted_at",
        "start_latency_s",
        "watch_rounds",
        "last_progress_t",
        "last_round_t",
    )

    def __init__(self, job_id: str, spec: dict, idem: Optional[str] = None):
        self.job_id = job_id
        self.idem = idem
        self.spec = spec
        self.state = ST_SUBMITTED
        self.chunks: Dict[str, dict] = {}
        self.landed: set = set()
        self.error: Optional[str] = None
        self.submitted_at = time.time()
        self.start_latency_s: Optional[float] = None
        self.watch_rounds = 0  # sync-watch specs: rounds spawned so far
        self.last_progress_t = time.monotonic()  # stall-repost clock
        # sync-watch specs: when the last round spawned, on time.monotonic()'s
        # clock, whose zero is arbitrary (boot): a watch that has run no round
        # in this process (new, or replayed from the WAL) is due now
        self.last_round_t = float("-inf")

    def pending_chunk_ids(self) -> List[str]:
        return [cid for cid in self.chunks if cid not in self.landed]

    def to_state(self) -> dict:
        return {
            "job_id": self.job_id,
            "idem": self.idem,
            "spec": self.spec,
            "state": self.state,
            "chunks": self.chunks,
            "landed": sorted(self.landed),
            "error": self.error,
            "watch_rounds": self.watch_rounds,
        }

    @staticmethod
    def from_state(d: dict) -> "ServiceJob":
        job = ServiceJob(d["job_id"], d.get("spec") or {}, d.get("idem"))
        job.state = d.get("state", ST_SUBMITTED)
        job.chunks = dict(d.get("chunks") or {})
        job.landed = set(d.get("landed") or ())
        job.error = d.get("error")
        job.watch_rounds = int(d.get("watch_rounds") or 0)
        return job


class ServiceController:
    def __init__(
        self,
        wal_dir,
        source_url: str,
        sink_url: str,
        token: Optional[str] = None,
        tenant_id: Optional[str] = None,
        chunk_bytes: int = 4 << 20,
        journal_max_bytes: int = 4 << 20,
        heartbeat_interval_s: float = 5.0,
        stall_repost_s: float = 30.0,
    ):
        self.token = token
        self.tenant_id = tenant_id
        self.chunk_bytes = int(chunk_bytes)
        self.heartbeat_interval_s = float(heartbeat_interval_s)
        self.stall_repost_s = float(stall_repost_s)
        self._source_url = source_url
        self._sink_url = sink_url
        self.source: Optional[BoundGateway] = None
        self.sink: Optional[BoundGateway] = None
        self._lock = lockcheck.wrap(threading.Lock(), "ServiceController._lock")
        self.jobs: Dict[str, ServiceJob] = {}
        self._idem: Dict[str, str] = {}  # idempotency key -> job_id
        self._last_heartbeat = 0.0
        # service counters (status snapshot + soak gates)
        self.c_jobs_submitted = 0
        self.c_jobs_recovered = 0
        self.c_chunks_requeued = 0
        self.c_heartbeats = 0
        self.c_watch_rounds = 0
        self.c_stall_reposts = 0
        self.c_fabric_exchanges = 0
        self.c_fabric_fps_exchanged = 0
        self._start_latencies: List[float] = []
        # SLO histograms on the process registry (rendered by /metrics and
        # the service API): dispatch = admission->chunk-POST done (the ~7 ms
        # warm-dispatch claim, so fine sub-10ms buckets), e2e = submit->done.
        # Registry dedupe means controllers recovered over the same WAL keep
        # accumulating into one histogram — exactly what a standing service
        # wants its SLO record to do (docs/service-mode.md).
        from skyplane_tpu.obs.metrics import get_registry

        self.dispatch_hist = get_registry().histogram(
            "service_dispatch_seconds",
            help_="warm dispatch latency: admission to chunk POST acknowledged",
            buckets=(0.001, 0.002, 0.005, 0.01, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0),
        )
        self.e2e_hist = get_registry().histogram(
            "service_e2e_seconds",
            help_="job end-to-end latency: submission to verified completion",
            buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0),
        )
        self.wal = ServiceWAL(wal_dir, journal_max_bytes=journal_max_bytes)
        self._load()

    # ---- WAL state machine ----

    def _load(self) -> None:
        """Rebuild the job table: snapshot first, then the WAL records in
        append order. Pure replay — no network; the sink reconciliation that
        turns replayed state into live truth happens in :meth:`recover`."""
        snapshot, records = self.wal.recover()
        if snapshot is not None:
            for jd in (snapshot.get("state") or {}).get("jobs", []):
                job = ServiceJob.from_state(jd)
                self.jobs[job.job_id] = job
                if job.idem:
                    self._idem[job.idem] = job.job_id
        for rec in records:
            self._apply(rec)
        self.c_jobs_recovered = sum(
            1 for j in self.jobs.values() if j.state in (ST_SUBMITTED, ST_DISPATCHED)
        )

    def _apply(self, rec: dict) -> None:
        """Apply one replayed record; idempotent against snapshot state and
        tolerant of records about jobs the snapshot already finalized."""
        t = rec.get("type")
        job_id = str(rec.get("job_id") or "")
        if t == REC_SUBMIT:
            spec = rec.get("spec") or {}
            job = ServiceJob(job_id, spec, rec.get("idem"))
            if spec.get("type") == "sync_watch":
                job.state = ST_WATCHING
                job.watch_rounds = int(rec.get("watch_rounds") or 0)
            prior = self.jobs.get(job_id)
            if prior is not None and prior.state in (ST_DONE, ST_FAILED):
                return  # snapshot already finalized this job
            self.jobs[job_id] = job
            if job.idem:
                self._idem[job.idem] = job_id
        elif t == REC_DISPATCH:
            job = self.jobs.get(job_id)
            if job is None or job.state in (ST_DONE, ST_FAILED):
                return
            for cd in rec.get("chunks") or []:
                job.chunks[cd["chunk_id"]] = cd
            job.state = ST_DISPATCHED
        elif t == REC_PROGRESS:
            job = self.jobs.get(job_id)
            if job is None:
                return
            job.landed.update(rec.get("landed") or ())
        elif t == REC_FINALIZE:
            job = self.jobs.get(job_id)
            if job is None:
                return
            job.state = ST_DONE if rec.get("status") == "done" else ST_FAILED
            job.error = rec.get("error")
        elif t == "watch_round":
            job = self.jobs.get(job_id)
            if job is not None:
                job.watch_rounds = max(job.watch_rounds, int(rec.get("round") or 0) + 1)

    def _snapshot_state(self) -> dict:
        return {"jobs": [j.to_state() for j in self.jobs.values()]}

    def _append_or_compact(self, rec: dict) -> None:
        """Append one record; compact when the WAL outgrows its bound.

        ORDERING CONTRACT: callers update the in-memory state a record
        describes BEFORE appending it. Compaction snapshots the in-memory
        job table and then truncates the WAL — if the memory update trailed
        the append, a compaction triggered by that very append would
        snapshot the stale state and destroy the only durable copy of the
        record (a lost finalize re-runs a completed job's side effects)."""
        self.wal.append(rec)
        if self.wal.needs_compaction():
            _crash_point("compact")
            with self._lock:
                state = self._snapshot_state()
            self.wal.compact(state)

    # ---- fleet adoption ----

    def attach(self) -> Dict[str, str]:
        """(Re-)bind the standing fleet via each gateway's /status probe —
        the daemons are long-lived; a restarted controller adopts them
        instead of provisioning. Raises when a gateway is unreachable or
        reports an error state."""
        self.source = attach_gateway(self._source_url, token=self.token)
        self.sink = attach_gateway(self._sink_url, token=self.token)
        return {
            "source": self.source.gateway_id,
            "sink": self.sink.gateway_id,
        }

    def recover(self) -> dict:
        """Reconcile replayed in-flight jobs against sink-measured truth and
        requeue ONLY what never landed. Idempotent: crashing inside recovery
        and re-running reaches the same state (re-registration of a chunk id
        the sink already holds is a no-op at the gateway)."""
        if self.sink is None:
            self.attach()
        _crash_point("reconcile")
        requeued = 0
        adopted: List[str] = []
        for job in list(self.jobs.values()):
            if job.state == ST_SUBMITTED:
                # submitted but never dispatched: dispatch fresh (a failure
                # here must not abort recovery of the OTHER jobs — the tick
                # loop's dispatch_pending retries it)
                try:
                    self._dispatch(job)
                except Exception as e:  # noqa: BLE001 — retried by dispatch_pending
                    logger.fs.warning(f"[service] recovery dispatch of {job.job_id} failed: {e}")
                adopted.append(job.job_id)
                continue
            if job.state != ST_DISPATCHED:
                continue
            adopted.append(job.job_id)
            # sink truth: which of this job's chunks actually landed
            landed_now = self._sink_complete(set(job.chunks))
            newly = sorted(landed_now - job.landed)
            if newly:
                job.landed.update(newly)  # memory first — see _append_or_compact
                self._append_or_compact({"type": REC_PROGRESS, "job_id": job.job_id, "landed": newly})
            pending = job.pending_chunk_ids()
            if pending:
                # requeue under the ORIGINAL chunk ids: the gateway skips ids
                # it already holds, so a chunk that was in flight (registered
                # but not yet complete) is never double-dispatched
                self._admit(job)
                self._post_chunks(job, [job.chunks[cid] for cid in pending])
                requeued += len(pending)
        self.c_chunks_requeued += requeued
        logger.fs.info(
            f"[service] recovery adopted {len(adopted)} in-flight job(s), requeued {requeued} chunk(s)"
        )
        return {"adopted_jobs": adopted, "requeued_chunks": requeued}

    # ---- submission ----

    def submit(self, spec: dict, idem_key: Optional[str] = None) -> str:
        """Submit one job. ``spec``: {"type": "copy"|"sync"|"sync_watch",
        "src", "dst", "chunk_bytes"?, "tenant_id"?, "interval_s"? (watch)}.
        An ``idem_key`` the WAL has seen returns the existing job_id without
        re-running anything — resubmission after an ambiguous crash is safe.
        """
        with self._lock:
            if idem_key and idem_key in self._idem:
                return self._idem[idem_key]
            job_id = f"svc-{uuid.uuid4().hex[:12]}"
            job = ServiceJob(job_id, spec, idem_key)
            if spec.get("type") == "sync_watch":
                job.state = ST_WATCHING
            self.jobs[job_id] = job
            if idem_key:
                self._idem[idem_key] = job_id
            self.c_jobs_submitted += 1
        self._append_or_compact(
            {"type": REC_SUBMIT, "job_id": job_id, "idem": idem_key, "spec": spec}
        )
        if job.state == ST_WATCHING:
            return job_id
        self._dispatch(job)
        return job_id

    # ---- dispatch ----

    def _chunk_requests_for(self, job: ServiceJob) -> List[dict]:
        """Chunk descriptors for the job's current source state. ``sync``
        jobs run the delta filter (size/mtime vs destination) so unchanged
        files ship zero chunks; fingerprints for the changed ones stay warm
        in the standing fleet's persistent dedup index."""
        spec = job.spec
        chunk_bytes = int(spec.get("chunk_bytes") or self.chunk_bytes)
        src, dst = Path(spec["src"]), Path(spec["dst"])
        if spec.get("type") in ("sync", "sync_watch"):
            pairs = compute_sync_delta(src, dst)
        else:
            pairs = walk_pairs(src, dst)
        descs: List[dict] = []
        for src_file, dst_file in pairs:
            size = src_file.stat().st_size
            offset = 0
            while offset < size or (size == 0 and offset == 0):
                length = min(chunk_bytes, size - offset)
                descs.append(
                    {
                        "chunk_id": uuid.uuid4().hex,
                        "src_key": str(src_file),
                        "dest_key": str(dst_file),
                        "offset": offset,
                        "length": length,
                    }
                )
                offset += length
                if size == 0:
                    break
        return descs

    def _admit(self, job: ServiceJob) -> None:
        """Admission on the source gateway (``POST /api/v1/jobs``) — 429s
        surface as SkyplaneTpuException after the retry ladder; idempotent
        re-admission doubles as the TTL-refreshing heartbeat."""
        body = {"job_id": job.job_id, "tenant_id": job.spec.get("tenant_id") or self.tenant_id}

        def _post():
            resp = self.source.control_session().post(
                f"{self.source.control_url()}/jobs", json=body, timeout=30
            )
            if resp.status_code == 429:
                raise requests.HTTPError("429 admission cap", response=resp)
            resp.raise_for_status()
            return resp

        retry_backoff(
            _post,
            max_retries=4,
            initial_backoff=0.2,
            max_backoff=2.0,
            jitter=0.5,
            deadline_s=60.0,
            exception_class=(requests.RequestException,),
        )

    def _post_chunks(self, job: ServiceJob, descs: List[dict]) -> None:
        tenant = job.spec.get("tenant_id") or self.tenant_id
        reqs = [
            ChunkRequest(
                chunk=Chunk(
                    src_key=d["src_key"],
                    dest_key=d["dest_key"],
                    chunk_id=d["chunk_id"],
                    chunk_length_bytes=d["length"],
                    file_offset_bytes=d["offset"],
                    tenant_id=tenant,
                ),
                src_region="local:local",
                dst_region="local:local",
                src_type="local",
                dst_type="local",
            ).as_dict()
            for d in descs
        ]

        def _post():
            resp = self.source.control_session().post(
                f"{self.source.control_url()}/chunk_requests", json=reqs, timeout=60
            )
            resp.raise_for_status()
            return resp

        retry_backoff(
            _post,
            max_retries=4,
            initial_backoff=0.2,
            max_backoff=2.0,
            jitter=0.5,
            deadline_s=120.0,
            exception_class=(requests.RequestException,),
        )

    def _dispatch(self, job: ServiceJob) -> None:
        """Warm dispatch: admission + WAL dispatch record + chunk POST. The
        WAL record lands BEFORE the POST (write-ahead): a crash between the
        two requeues exactly these chunk ids at recovery, and the sink's
        idempotent re-register makes the retry side-effect free.

        Journaled as phase.dispatch with scope="service" so the warm path
        lands on the same waterfall as batch-mode transfers — service-vs-
        batch overhead is one report, not two instruments
        (docs/observability.md)."""
        from skyplane_tpu.obs.events import PH_DISPATCH
        from skyplane_tpu.obs.timeline import PhaseClock

        with PhaseClock(job=job.job_id, scope="service").phase(PH_DISPATCH):
            self._dispatch_inner(job)

    def _dispatch_inner(self, job: ServiceJob) -> None:
        if self.source is None:
            self.attach()
        t0 = time.monotonic()
        try:
            descs = self._chunk_requests_for(job)
        except OSError as e:
            # an unreadable source is a PERMANENT job failure, not a
            # transient to retry every tick forever: finalize loudly; the
            # client resubmits (under a fresh idempotency key) once fixed
            self._finalize(job, "failed", error=f"source unreadable: {e}")
            return
        if not descs:
            # a sync with zero delta is complete by construction
            self._finalize(job, "done")
            job.start_latency_s = time.monotonic() - t0
            self._note_latency(job.start_latency_s)
            return
        self._admit(job)
        with self._lock:
            for d in descs:
                job.chunks[d["chunk_id"]] = d
            job.state = ST_DISPATCHED
        self._append_or_compact({"type": REC_DISPATCH, "job_id": job.job_id, "chunks": descs})
        _crash_point("dispatch")
        self._post_chunks(job, descs)
        job.start_latency_s = time.monotonic() - t0
        self._note_latency(job.start_latency_s)

    #: start-latency samples retained for the status percentiles (a standing
    #: service must not grow this list for its whole lifetime)
    MAX_LATENCY_SAMPLES = 4096

    def _note_latency(self, seconds: float) -> None:
        self.dispatch_hist.observe(seconds)
        with self._lock:
            self._start_latencies.append(seconds)
            if len(self._start_latencies) > self.MAX_LATENCY_SAMPLES:
                del self._start_latencies[: len(self._start_latencies) - self.MAX_LATENCY_SAMPLES]

    # ---- progress / finalize ----

    def _sink_complete(self, chunk_ids: set) -> set:
        """The sink's ground truth for a chunk-id set (batched polls)."""
        if not chunk_ids:
            return set()
        done: set = set()
        ids = sorted(chunk_ids)
        session = self.sink.control_session()
        for i in range(0, len(ids), _MAX_IDS_PER_POLL):
            batch = ids[i : i + _MAX_IDS_PER_POLL]
            resp = session.get(
                f"{self.sink.control_url()}/chunk_status_log",
                params={"chunk_ids": ",".join(batch)},
                timeout=30,
            )
            resp.raise_for_status()
            status = resp.json().get("chunk_status", {})
            done.update(cid for cid in batch if status.get(cid) == "complete")
        return done

    @staticmethod
    def _files_equal(a: Path, b: Path, bufsize: int = 1 << 20) -> bool:
        """Chunked byte compare — a standing controller finalizing multi-GB
        jobs must not materialize both files in RAM (and stdlib filecmp
        keeps an unbounded module-level result cache)."""
        if a.stat().st_size != b.stat().st_size:
            return False
        with open(a, "rb") as fa, open(b, "rb") as fb:
            while True:
                ba = fa.read(bufsize)
                if ba != fb.read(bufsize):
                    return False
                if not ba:
                    return True

    def _verify(self, job: ServiceJob) -> Optional[str]:
        """Byte-verify landed local outputs; returns an error string or None.
        Distinct dest files verify independently so one bad file names
        itself."""
        by_dest: Dict[str, List[dict]] = {}
        for d in job.chunks.values():
            by_dest.setdefault(d["dest_key"], []).append(d)
        for dest, descs in by_dest.items():
            src = Path(descs[0]["src_key"])
            try:
                if not self._files_equal(src, Path(dest)):
                    return f"output mismatch at {dest}"
            except OSError as e:
                return f"output unreadable at {dest}: {e}"
        return None

    def _finalize(self, job: ServiceJob, status: str, error: Optional[str] = None) -> None:
        rec = {"type": REC_FINALIZE, "job_id": job.job_id, "status": status}
        if error:
            rec["error"] = error
        if status == "done":
            self.e2e_hist.observe(max(0.0, time.time() - job.submitted_at))
        with self._lock:  # memory first — see _append_or_compact
            job.state = ST_DONE if status == "done" else ST_FAILED
            job.error = error
        self._append_or_compact(rec)
        # release the admission slot — best-effort: the TTL sweep is the
        # backstop for a gateway that missed the DELETE
        try:
            if self.source is not None:
                self.source.control_session().delete(
                    f"{self.source.control_url()}/jobs/{job.job_id}", timeout=10
                )
        except requests.RequestException as e:
            logger.fs.warning(f"[service] admission release for {job.job_id} failed: {e}")

    def poll_once(self) -> int:
        """One progress wave: batch-poll the sink for every dispatched job's
        pending chunks, WAL the newly landed, finalize fully-landed jobs
        (with byte verification). Returns chunks newly landed this wave."""
        if self.sink is None:
            return 0
        active = [j for j in self.jobs.values() if j.state == ST_DISPATCHED]
        if not active:
            return 0
        pending_by_job = {j.job_id: set(j.pending_chunk_ids()) for j in active}
        all_pending = set().union(*pending_by_job.values()) if pending_by_job else set()
        landed = self._sink_complete(all_pending)
        n = 0
        now = time.monotonic()
        for job in active:
            newly = sorted(pending_by_job[job.job_id] & landed)
            if newly:
                with self._lock:  # memory first — see _append_or_compact
                    job.landed.update(newly)
                self._append_or_compact({"type": REC_PROGRESS, "job_id": job.job_id, "landed": newly})
                job.last_progress_t = now
                n += len(newly)
            if not job.pending_chunk_ids():
                err = self._verify(job)
                self._finalize(job, "failed" if err else "done", error=err)
            elif now - job.last_progress_t > self.stall_repost_s:
                # stalled: heal the "WAL dispatch landed, POST (partially)
                # didn't" window without a restart — re-registration of a
                # chunk id the gateway already holds is a no-op, so a
                # re-POST of everything pending is always safe
                logger.fs.warning(
                    f"[service] job {job.job_id}: no progress for {self.stall_repost_s:.0f}s; "
                    f"re-posting {len(job.pending_chunk_ids())} pending chunk(s)"
                )
                try:
                    self._admit(job)
                    self._post_chunks(job, [job.chunks[c] for c in job.pending_chunk_ids()])
                    self.c_stall_reposts += 1
                except (requests.RequestException, SkyplaneTpuException) as e:
                    logger.fs.warning(f"[service] stall re-post for {job.job_id} failed: {e}")
                job.last_progress_t = now
        return n

    def heartbeat_once(self) -> int:
        """Refresh every live job's TTL clock so the gateway's job sweep
        sees it as fresh — a continuous-sync job must survive past the 24 h
        TTL as long as its controller is alive (docs/service-mode.md).
        Prefers the light ``POST /jobs/<id>/heartbeat`` route; a 404 (job
        reaped, or an older gateway without the route) falls back to the
        full idempotent re-admission, which also refreshes the clock."""
        if self.source is None:
            return 0
        live = [j for j in self.jobs.values() if j.state in (ST_SUBMITTED, ST_DISPATCHED, ST_WATCHING)]
        session = self.source.control_session()
        for job in live:
            try:
                resp = session.post(
                    f"{self.source.control_url()}/jobs/{job.job_id}/heartbeat", timeout=10
                )
                if resp.status_code == 404:
                    self._admit(job)
            except (requests.RequestException, SkyplaneTpuException) as e:
                logger.fs.warning(f"[service] heartbeat for {job.job_id} failed: {e}")
        self.c_heartbeats += 1
        self._last_heartbeat = time.time()
        return len(live)

    def fabric_exchange_once(self) -> Dict[str, int]:
        """One fleet fingerprint-summary gossip round between the bound
        gateways, piggybacked on the heartbeat cadence (docs/dedup-fabric.md):
        each gateway's recently-proved fps cross-post to the other, so sender
        dedup indexes fleet-wide treat them as durable warmth. Free when no
        fabric is configured (summaries come back empty, nothing posts)."""
        from skyplane_tpu.dedup_fabric import run_summary_exchange

        legs = []
        seen = set()
        for bg in (self.source, self.sink):
            if bg is None or bg.gateway_id in seen:
                continue
            seen.add(bg.gateway_id)
            legs.append((bg.control_url(), bg.control_session()))
        if len(legs) < 2:
            return {"pulled": 0, "posted": 0, "failed": 0, "fps": 0}
        stats = run_summary_exchange(legs)
        self.c_fabric_exchanges += 1
        self.c_fabric_fps_exchanged += stats["fps"]
        return stats

    # ---- continuous sync ----

    def run_watch_rounds(self) -> int:
        """Spawn one delta round for each watching spec whose interval
        elapsed (worker loop cadence; tests call it directly). Empty deltas
        spawn nothing. Round jobs carry deterministic idempotency keys
        (``<watch_job_id>:r<n>``) so a crash mid-round resumes THAT round."""
        spawned = 0
        now = time.monotonic()
        for job in list(self.jobs.values()):
            if job.state != ST_WATCHING:
                continue
            rnd = job.watch_rounds
            # one round in flight at a time: while the previous round's
            # child is still shipping, the delta filter would see its
            # not-yet-landed files as "changed" and spawn duplicate jobs
            # re-shipping the same bytes every tick
            if rnd > 0:
                prev = self.jobs.get(self._idem.get(f"{job.job_id}:r{rnd - 1}", ""))
                if prev is not None and prev.state in (ST_SUBMITTED, ST_DISPATCHED):
                    continue
            # the spec's interval paces the rounds (interval_s 0 = every tick)
            if now - job.last_round_t < float(job.spec.get("interval_s") or 0.0):
                continue
            src, dst = Path(job.spec["src"]), Path(job.spec["dst"])
            if not compute_sync_delta(src, dst):
                job.last_round_t = now
                continue
            child_spec = dict(job.spec)
            child_spec["type"] = "sync"
            child_id = self.submit(child_spec, idem_key=f"{job.job_id}:r{rnd}")
            with self._lock:  # memory first — see _append_or_compact
                job.watch_rounds = rnd + 1
                job.last_round_t = now
                self.c_watch_rounds += 1
            self._append_or_compact({"type": "watch_round", "job_id": job.job_id, "round": rnd})
            spawned += 1
            logger.fs.info(f"[service] watch {job.job_id} round {rnd} -> {child_id}")
        return spawned

    # ---- loop ----

    def dispatch_pending(self) -> int:
        """Retry-dispatch jobs stuck in ``submitted`` (their first dispatch
        raised: source momentarily unreadable, gateway 429/outage past the
        retry ladder). The WAL submit record is already durable, so retrying
        here is exactly what a restarted controller's recovery would do —
        the live loop just does it without the restart."""
        n = 0
        for job in list(self.jobs.values()):
            if job.state != ST_SUBMITTED:
                continue
            try:
                self._dispatch(job)
                n += 1
            except Exception as e:  # noqa: BLE001 — retried next tick; the loop must outlive one bad job
                logger.fs.warning(f"[service] dispatch retry for {job.job_id} failed: {e}")
        return n

    def tick(self) -> None:
        """One worker-loop step: stuck dispatches, progress, heartbeats (on
        their interval), watch rounds."""
        self.dispatch_pending()
        self.poll_once()
        if time.time() - self._last_heartbeat >= self.heartbeat_interval_s:
            self.heartbeat_once()
            # gossip rides the same cadence: no extra timers, and a dead
            # controller degrades gossip exactly as it degrades heartbeats
            self.fabric_exchange_once()
        self.run_watch_rounds()

    def close(self) -> None:
        self.wal.close()

    # ---- introspection ----

    def job(self, job_id: str) -> Optional[ServiceJob]:
        return self.jobs.get(job_id)

    def start_latencies(self) -> List[float]:
        with self._lock:
            return list(self._start_latencies)

    def status(self) -> dict:
        with self._lock:
            by_state: Dict[str, int] = {}
            for j in self.jobs.values():
                by_state[j.state] = by_state.get(j.state, 0) + 1
            lat = sorted(self._start_latencies)
        out = {
            "jobs_total": len(self.jobs),
            "jobs_by_state": by_state,
            "jobs_submitted": self.c_jobs_submitted,
            "jobs_recovered": self.c_jobs_recovered,
            "chunks_requeued": self.c_chunks_requeued,
            "heartbeats": self.c_heartbeats,
            "watch_rounds": self.c_watch_rounds,
            "stall_reposts": self.c_stall_reposts,
            "fabric_exchanges": self.c_fabric_exchanges,
            "fabric_fps_exchanged": self.c_fabric_fps_exchanged,
            "source_gateway": self.source.gateway_id if self.source else None,
            "sink_gateway": self.sink.gateway_id if self.sink else None,
        }
        if lat:
            out["job_start_p50_s"] = round(lat[len(lat) // 2], 4)
            out["job_start_p95_s"] = round(lat[min(len(lat) - 1, int(0.95 * len(lat)))], 4)
        # histogram-derived SLO percentiles: what the soak gate asserts (the
        # ad-hoc list above stays for continuity, the histogram is the truth)
        for key, hist, q in (
            ("dispatch_hist_p50_s", self.dispatch_hist, 0.5),
            ("dispatch_hist_p95_s", self.dispatch_hist, 0.95),
            ("e2e_hist_p50_s", self.e2e_hist, 0.5),
            ("e2e_hist_p95_s", self.e2e_hist, 0.95),
        ):
            v = hist.quantile(q)
            if v is not None:
                out[key] = round(v, 4)
        out.update(self.wal.counters())
        return out

    def timeline(self, job_id: Optional[str] = None) -> dict:
        """Per-job timeline + critical path from this process's flight
        recorder — the payload behind ``GET /api/v1/timeline`` on the
        service API (docs/observability.md "Job timelines & critical
        path"). Service-scope phase.dispatch events land here live, so a
        warm dispatch is inspectable without any fleet log on disk."""
        from skyplane_tpu.obs.events import get_recorder
        from skyplane_tpu.obs.timeline import timeline_report

        rec = get_recorder()
        events = rec.events_since(0)
        for ev in events:
            ev.setdefault("recorder", rec.recorder_id)
        report = timeline_report(events, job=job_id)
        report["job_id"] = job_id or report["timeline"].get("job") or ""
        return report
