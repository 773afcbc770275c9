"""ServiceController over the loopback harness: warm dispatch, crash-safe
recovery, idempotent resubmission, continuous sync (docs/service-mode.md)."""

from __future__ import annotations

import time

import pytest

from integration.harness import make_pair
from skyplane_tpu.service import ST_DISPATCHED, ST_DONE, ST_WATCHING, ServiceController


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """ONE standing pair for the whole module — service mode's premise is
    that the fleet outlives every job (and every controller)."""
    tmp = tmp_path_factory.mktemp("svc_fleet")
    src, dst = make_pair(tmp, compress="none", dedup=True, encrypt=False, use_tls=False, num_connections=2)
    yield tmp, src, dst
    src.stop()
    dst.stop()


def _controller(tmp, src, dst, wal_name="wal", **kw) -> ServiceController:
    c = ServiceController(
        tmp / wal_name,
        source_url=src.url("").rstrip("/"),
        sink_url=dst.url("").rstrip("/"),
        chunk_bytes=kw.pop("chunk_bytes", 256 << 10),
        **kw,
    )
    c.attach()
    return c


def _drive(c: ServiceController, job_id: str, timeout: float = 60.0) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        c.poll_once()
        if c.job(job_id).state in ("done", "failed"):
            return
        time.sleep(0.05)
    raise TimeoutError(f"job {job_id} stuck in {c.job(job_id).state}")


def test_copy_job_end_to_end_and_idempotency(fleet, tmp_path):
    tmp, src, dst = fleet
    data = tmp_path / "a.bin"
    data.write_bytes(b"payload " * 200_000)
    out = tmp_path / "out" / "a.bin"
    c = _controller(tmp_path, src, dst)
    jid = c.submit({"type": "copy", "src": str(data), "dst": str(out)}, idem_key="job-a")
    assert c.job(jid).start_latency_s < 1.0, "warm dispatch must be sub-second"
    _drive(c, jid)
    assert c.job(jid).state == ST_DONE and c.job(jid).error is None
    assert out.read_bytes() == data.read_bytes()
    # same idempotency key: the existing job returns, nothing re-runs
    assert c.submit({"type": "copy", "src": str(data), "dst": str(out)}, idem_key="job-a") == jid
    assert c.status()["jobs_submitted"] == 1
    c.close()


def test_crash_between_wal_and_post_recovers_fully(fleet, tmp_path, monkeypatch):
    """The nastiest window: the dispatch record is durable but the chunk
    POST never happened. Recovery must requeue EVERY chunk (the sink holds
    none) and finish byte-identical."""
    tmp, src, dst = fleet
    data = tmp_path / "b.bin"
    data.write_bytes(b"window " * 150_000)
    out = tmp_path / "out" / "b.bin"
    c1 = _controller(tmp_path, src, dst, wal_name="wal_crash1")
    monkeypatch.setattr(
        ServiceController, "_post_chunks", lambda self, job, descs: None, raising=True
    )
    jid = c1.submit({"type": "copy", "src": str(data), "dst": str(out)}, idem_key="job-b")
    assert c1.job(jid).state == ST_DISPATCHED
    monkeypatch.undo()
    c1.close()  # the "crash": controller gone, WAL survives, sink saw nothing

    c2 = _controller(tmp_path, src, dst, wal_name="wal_crash1")
    rec = c2.recover()
    assert rec["adopted_jobs"] == [jid]
    assert rec["requeued_chunks"] == len(c2.job(jid).chunks)
    _drive(c2, jid)
    assert out.read_bytes() == data.read_bytes()
    # idempotent resubmission after the crash maps to the SAME job
    assert c2.submit({"type": "copy", "src": str(data), "dst": str(out)}, idem_key="job-b") == jid
    c2.close()


def test_crash_mid_flight_requeues_only_unlanded(fleet, tmp_path):
    """Crash AFTER the POST: the sink lands chunks while no controller is
    alive. Recovery reconciles against sink truth — landed chunks are
    adopted, not re-sent, and re-registration of the rest is idempotent at
    the gateway (zero duplicate registrations)."""
    tmp, src, dst = fleet
    data = tmp_path / "c.bin"
    data.write_bytes(b"inflight " * 400_000)
    out = tmp_path / "out" / "c.bin"
    c1 = _controller(tmp_path, src, dst, wal_name="wal_crash2", chunk_bytes=64 << 10)
    jid = c1.submit({"type": "copy", "src": str(data), "dst": str(out)}, idem_key="job-c")
    n_chunks = len(c1.job(jid).chunks)
    c1.close()  # die immediately after dispatch; the fleet keeps pumping

    # give the standing fleet time to land (some of) the corpus ownerless
    time.sleep(1.0)
    c2 = _controller(tmp_path, src, dst, wal_name="wal_crash2")
    rec = c2.recover()
    assert rec["adopted_jobs"] == [jid]
    _drive(c2, jid)
    assert out.read_bytes() == data.read_bytes()
    # zero duplicate registrations: the sink saw each chunk id exactly once
    status = dst.get("chunk_requests", timeout=30).json()
    seen = [cr["chunk"]["chunk_id"] for cr in status["chunk_requests"]]
    job_ids = set(c2.job(jid).chunks)
    assert len([cid for cid in seen if cid in job_ids]) == n_chunks
    c2.close()


def test_stalled_post_heals_without_restart(fleet, tmp_path, monkeypatch):
    """The live-loop mirror of crash recovery: the dispatch POST fails past
    its retry ladder (gateway outage), the job stalls — and the poll loop
    re-admits + re-posts everything pending once the stall clock fires,
    with no controller restart."""
    tmp, src, dst = fleet
    data = tmp_path / "stall.bin"
    data.write_bytes(b"stall " * 100_000)
    out = tmp_path / "out" / "stall.bin"
    c = _controller(tmp_path, src, dst, wal_name="wal_stall", stall_repost_s=0.2)
    monkeypatch.setattr(ServiceController, "_post_chunks", lambda self, job, descs: None, raising=True)
    jid = c.submit({"type": "copy", "src": str(data), "dst": str(out)}, idem_key="job-stall")
    monkeypatch.undo()
    time.sleep(0.3)
    _drive(c, jid)
    assert c.c_stall_reposts >= 1, "the stall healer never fired"
    assert out.read_bytes() == data.read_bytes()
    c.close()


def test_sync_watch_rounds_ship_only_the_delta(fleet, tmp_path):
    tmp, src, dst = fleet
    srcdir = tmp_path / "tree"
    (srcdir / "sub").mkdir(parents=True)
    (srcdir / "x.bin").write_bytes(b"x" * 300_000)
    (srcdir / "sub" / "y.bin").write_bytes(b"y" * 200_000)
    dstdir = tmp_path / "mirror"
    c = _controller(tmp_path, src, dst, wal_name="wal_watch", chunk_bytes=128 << 10)
    watch_id = c.submit(
        {"type": "sync_watch", "src": str(srcdir), "dst": str(dstdir), "interval_s": 0.0},
        idem_key="watch-1",
    )
    assert c.job(watch_id).state == ST_WATCHING
    assert c.run_watch_rounds() == 1  # round 0: full tree is the delta
    round0 = c.job(c._idem[f"{watch_id}:r0"])
    _drive(c, round0.job_id)
    assert (dstdir / "x.bin").read_bytes() == (srcdir / "x.bin").read_bytes()
    assert (dstdir / "sub" / "y.bin").read_bytes() == (srcdir / "sub" / "y.bin").read_bytes()

    assert c.run_watch_rounds() == 0, "zero delta must spawn zero jobs"

    # touch ONE file: the next round ships only that file's chunks
    time.sleep(0.05)
    (srcdir / "x.bin").write_bytes(b"X" * 300_000)
    assert c.run_watch_rounds() == 1
    round1 = c.job(c._idem[f"{watch_id}:r1"])
    assert {d["src_key"] for d in round1.chunks.values()} == {str(srcdir / "x.bin")}
    _drive(c, round1.job_id)
    assert (dstdir / "x.bin").read_bytes() == b"X" * 300_000
    c.close()

    # a restarted controller resumes the watch at the next round index
    c2 = _controller(tmp_path, src, dst, wal_name="wal_watch")
    c2.recover()
    assert c2.job(watch_id).state == ST_WATCHING
    assert c2.job(watch_id).watch_rounds == 2
    c2.close()


def test_watch_paces_rounds_and_never_overlaps(fleet, tmp_path):
    """Regression: a watch must spawn at most ONE round at a time (a
    mid-flight round's un-landed files read as 'changed' — re-spawning
    every tick would duplicate the whole transfer) and must respect the
    spec's interval between rounds."""
    tmp, src, dst = fleet
    srcdir = tmp_path / "paced"
    srcdir.mkdir()
    (srcdir / "f.bin").write_bytes(b"p" * 200_000)
    c = _controller(tmp_path, src, dst, wal_name="wal_paced", chunk_bytes=64 << 10)
    watch_id = c.submit(
        {"type": "sync_watch", "src": str(srcdir), "dst": str(tmp_path / "paced_out"), "interval_s": 9999.0},
        idem_key="watch-paced",
    )
    assert c.run_watch_rounds() == 1  # round 0 spawns immediately
    # round 0 is in flight and the tree still reads as a delta: NO new round
    assert c.run_watch_rounds() == 0, "spawned a second round while round 0 was mid-flight"
    _drive(c, c._idem[f"{watch_id}:r0"])
    # round 0 landed, file touched — but the interval has not elapsed
    time.sleep(0.05)
    (srcdir / "f.bin").write_bytes(b"Q" * 200_000)
    assert c.run_watch_rounds() == 0, "ignored the watch interval"
    c.job(watch_id).last_round_t = float("-inf")  # simulate the interval elapsing
    assert c.run_watch_rounds() == 1
    c.close()


def test_missing_source_fails_loudly_not_forever(fleet, tmp_path):
    """Regression: a job whose source does not exist must finalize as
    'failed' (client-visible), not spin the dispatch retry loop forever."""
    tmp, src, dst = fleet
    c = _controller(tmp_path, src, dst, wal_name="wal_badsrc")
    jid = c.submit(
        {"type": "copy", "src": str(tmp_path / "no_such_file.bin"), "dst": str(tmp_path / "x.bin")},
        idem_key="job-badsrc",
    )
    assert c.job(jid).state == "failed"
    assert "source unreadable" in (c.job(jid).error or "")
    assert c.dispatch_pending() == 0, "a failed job must not be retried"
    c.close()


def test_heartbeat_keeps_admission_fresh(fleet, tmp_path):
    tmp, src, dst = fleet
    data = tmp_path / "hb.bin"
    data.write_bytes(b"hb" * 1000)
    c = _controller(tmp_path, src, dst, wal_name="wal_hb")
    watch_id = c.submit(
        {"type": "sync_watch", "src": str(data), "dst": str(tmp_path / "hb_out.bin"), "interval_s": 9e9},
        idem_key="watch-hb",
    )
    # first heartbeat: the watch job was never admitted (no dispatch), so the
    # light route 404s and the controller falls back to full re-admission
    assert c.heartbeat_once() >= 1
    jobs = src.get("tenants", timeout=30).json()["jobs"]
    assert watch_id in jobs, "heartbeat did not (re-)admit the standing job"
    started_0 = jobs[watch_id]["started_at"]
    # second heartbeat: the light POST /jobs/<id>/heartbeat route refreshes
    # the TTL clock without re-admission side effects
    time.sleep(0.05)
    assert c.heartbeat_once() >= 1
    jobs = src.get("tenants", timeout=30).json()["jobs"]
    assert jobs[watch_id]["started_at"] > started_0, "heartbeat route did not refresh the TTL clock"
    # unknown jobs 404 honestly (a reaped slot must not be resurrected)
    resp = src.post("jobs/never-admitted/heartbeat", timeout=10)
    assert resp.status_code == 404
    c.close()


def test_worker_loop_spool_intake(fleet, tmp_path):
    """run_service end to end: spool file -> submitted with a filename-keyed
    idempotency key -> completed; rescans are no-ops."""
    import json

    from skyplane_tpu.service.worker import run_service

    tmp, src, dst = fleet
    data = tmp_path / "spool_src.bin"
    data.write_bytes(b"spooled " * 120_000)
    out = tmp_path / "spool_out.bin"
    spool = tmp_path / "spool"
    spool.mkdir()
    (spool / "job1.json").write_text(json.dumps({"type": "copy", "src": str(data), "dst": str(out)}))
    (spool / "broken.json").write_text("{not json")
    controller = run_service(
        tmp_path / "wal_worker",
        spool,
        source_url=src.url("").rstrip("/"),
        sink_url=dst.url("").rstrip("/"),
        poll_interval_s=0.05,
        max_ticks=100,
    )
    job_id = controller._idem.get("spool:job1")
    assert job_id is not None
    assert controller.job(job_id).state == ST_DONE
    assert out.read_bytes() == data.read_bytes()
    assert controller.status()["jobs_submitted"] == 1, "spool rescans must be idempotent"
    assert (spool / "broken.rejected").exists(), "malformed specs are quarantined loudly"
    assert (tmp_path / "wal_worker" / "status.json").exists()
