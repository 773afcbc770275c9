"""Full-stack end-to-end: SkyplaneClient -> Pipeline -> planner -> local
provisioner (daemon subprocesses) -> gateway transfer -> tracker -> verify.

This is `skyplane cp` with zero cloud dependencies (BASELINE.json config #1
shape), covering the complete control plane + data plane.
"""

import hashlib
from pathlib import Path

import numpy as np
import pytest

from skyplane_tpu.api.config import TransferConfig
from skyplane_tpu.api.pipeline import Pipeline
from skyplane_tpu.api.transfer_job import CopyJob, SyncJob
from skyplane_tpu.obj_store.posix_file_interface import POSIXInterface

rng = np.random.default_rng(21)


def _fill_bucket(root: Path, n_files=3, size=256 * 1024):
    root.mkdir(parents=True, exist_ok=True)
    data = {}
    for i in range(n_files):
        payload = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        (root / f"f{i}.bin").write_bytes(payload)
        data[f"f{i}.bin"] = payload
    return data


def _make_cross_site_job(tmp_path, job_cls=CopyJob, **jkw):
    """Two distinct 'local sites' so the planner emits the full WAN path
    (read -> send -> receive -> write)."""
    src_root = tmp_path / "siteA"
    dst_root = tmp_path / "siteB"
    data = _fill_bucket(src_root)
    dst_root.mkdir()
    job = job_cls("local://siteA/", ["local://siteB/"], recursive=True, **jkw)
    job._src_iface = POSIXInterface(str(src_root), region_tag="local:siteA")
    job._dst_ifaces = [POSIXInterface(str(dst_root), region_tag="local:siteB")]
    # prefixes are bucket-relative for explicit interfaces
    job.src_path = "local:///"
    job.dst_paths = ["local:///"]
    return job, data, dst_root


def _run_pipeline(job, transfer_config):
    pipe = Pipeline(transfer_config=transfer_config)
    pipe.jobs_to_dispatch.append(job)
    dp = pipe.create_dataplane()
    with dp.auto_deprovision():
        dp.provision()
        dp.run([job])
    return dp


@pytest.mark.slow
def test_cross_site_copy_zstd(tmp_path):
    job, data, dst_root = _make_cross_site_job(tmp_path)
    cfg = TransferConfig(compress="zstd", dedup=False, multipart_threshold_mb=1024)
    _run_pipeline(job, cfg)
    for name, payload in data.items():
        got = (dst_root / name).read_bytes()
        assert hashlib.md5(got).hexdigest() == hashlib.md5(payload).hexdigest()


@pytest.mark.slow
def test_cross_site_copy_multipart(tmp_path):
    src_root = tmp_path / "siteA"
    dst_root = tmp_path / "siteB"
    src_root.mkdir()
    dst_root.mkdir()
    payload = rng.integers(0, 256, 3 << 20, dtype=np.uint8).tobytes()
    (src_root / "big.bin").write_bytes(payload)
    job = CopyJob("local://bucket/big.bin", ["local://bucket/big_copy.bin"])
    job._src_iface = POSIXInterface(str(src_root), region_tag="local:siteA")
    job._dst_ifaces = [POSIXInterface(str(dst_root), region_tag="local:siteB")]
    job.src_path = "local:///big.bin"
    job.dst_paths = ["local:///big_copy.bin"]
    cfg = TransferConfig(compress="zstd", dedup=False, multipart_threshold_mb=1, multipart_chunk_size_mb=1)
    _run_pipeline(job, cfg)
    assert (dst_root / "big_copy.bin").read_bytes() == payload


@pytest.mark.slow
def test_same_region_direct_write(tmp_path):
    """src and dst in the same region: planner writes directly, no sockets."""
    src_root = tmp_path / "site"
    dst_root = tmp_path / "site_out"
    data = _fill_bucket(src_root, n_files=2)
    dst_root.mkdir()
    job = CopyJob("local://bucket/", ["local://bucket/"], recursive=True)
    job._src_iface = POSIXInterface(str(src_root), region_tag="local:same")
    job._dst_ifaces = [POSIXInterface(str(dst_root), region_tag="local:same")]
    job.src_path = "local:///"
    job.dst_paths = ["local:///"]
    cfg = TransferConfig(compress="none", dedup=False, encrypt_e2e=False, multipart_threshold_mb=1024)
    _run_pipeline(job, cfg)
    for name, payload in data.items():
        assert (dst_root / name).read_bytes() == payload


@pytest.mark.slow
def test_sync_skips_unchanged(tmp_path):
    job, data, dst_root = _make_cross_site_job(tmp_path)
    cfg = TransferConfig(compress="zstd", dedup=False, multipart_threshold_mb=1024)
    _run_pipeline(job, cfg)
    # second sync: pre-list shows everything current -> zero pairs -> MissingObject-free no-op
    job2 = SyncJob("local://bucket/", ["local://bucket/"])
    job2._src_iface = job._src_iface
    job2._dst_ifaces = job._dst_ifaces
    job2.src_path = "local:///"
    job2.dst_paths = ["local:///"]
    filtered = [
        obj for obj in job2.src_iface.list_objects() if job2._post_filter_fn(obj)
    ]
    assert filtered == []  # nothing to re-copy


@pytest.mark.slow
def test_sync_recopies_changed_and_new_files(tmp_path):
    """Full second sync pipeline after mutating the source: only the changed
    and new objects move, and the destination converges byte-for-byte
    (reference semantics: transfer_job.py:792-865 delta filter)."""
    import time

    job, data, dst_root = _make_cross_site_job(tmp_path, job_cls=SyncJob)
    cfg = TransferConfig(compress="zstd", dedup=False, multipart_threshold_mb=1024)
    _run_pipeline(job, cfg)
    src_root = tmp_path / "siteA"
    time.sleep(1.1)  # mtime granularity: the delta filter compares mtimes
    changed = rng.integers(0, 256, 300 * 1024, dtype=np.uint8).tobytes()
    (src_root / "f1.bin").write_bytes(changed)
    added = rng.integers(0, 256, 64 * 1024, dtype=np.uint8).tobytes()
    (src_root / "new.bin").write_bytes(added)

    job2 = SyncJob("local://siteA/", ["local://siteB/"], recursive=True)
    job2._src_iface = POSIXInterface(str(src_root), region_tag="local:siteA")
    job2._dst_ifaces = [POSIXInterface(str(dst_root), region_tag="local:siteB")]
    job2.src_path = "local:///"
    job2.dst_paths = ["local:///"]
    to_copy = {o.key for o in job2.src_iface.list_objects() if job2._post_filter_fn(o)}
    assert to_copy == {"f1.bin", "new.bin"}, to_copy
    _run_pipeline(job2, cfg)
    assert (dst_root / "f1.bin").read_bytes() == changed
    assert (dst_root / "new.bin").read_bytes() == added
    assert (dst_root / "f0.bin").read_bytes() == data["f0.bin"]  # untouched


@pytest.mark.slow
def test_multicast_two_destinations(tmp_path):
    """1 source -> 2 destination regions: mux_and fan-out, per-region dest keys,
    completion requires BOTH destinations to land every chunk."""
    src_root = tmp_path / "siteA"
    d1_root = tmp_path / "siteB"
    d2_root = tmp_path / "siteC"
    data = _fill_bucket(src_root, n_files=2)
    d1_root.mkdir()
    d2_root.mkdir()
    job = CopyJob("local:///", ["local:///b/", "local:///c/"], recursive=True)
    job._src_iface = POSIXInterface(str(src_root), region_tag="local:siteA")
    job._dst_ifaces = [
        POSIXInterface(str(d1_root), region_tag="local:siteB"),
        POSIXInterface(str(d2_root), region_tag="local:siteC"),
    ]
    job.src_path = "local:///"
    job.dst_paths = ["local:///", "local:///"]
    cfg = TransferConfig(compress="zstd", dedup=False, multipart_threshold_mb=1024)
    _run_pipeline(job, cfg)
    for name, payload in data.items():
        assert (d1_root / name).read_bytes() == payload, f"dest B missing/corrupt {name}"
        assert (d2_root / name).read_bytes() == payload, f"dest C missing/corrupt {name}"


@pytest.mark.slow
def test_multi_instance_scale_out(tmp_path):
    """max_instances=2: two source + two destination gateways, chunk batches
    round-robined to the least-loaded source, mux_or connection splitting
    (reference test matrix: multi-VM case, tests/integration/test_cp.py)."""
    src_root = tmp_path / "siteA"
    dst_root = tmp_path / "siteB"
    data = _fill_bucket(src_root, n_files=4, size=192 * 1024)
    dst_root.mkdir()
    job = CopyJob("local:///", ["local:///"], recursive=True)
    job._src_iface = POSIXInterface(str(src_root), region_tag="local:siteA")
    job._dst_ifaces = [POSIXInterface(str(dst_root), region_tag="local:siteB")]
    job.src_path = "local:///"
    job.dst_paths = ["local:///"]
    cfg = TransferConfig(compress="zstd", dedup=False, multipart_threshold_mb=1024, num_connections=4)
    pipe = Pipeline(transfer_config=cfg, max_instances=2)
    pipe.jobs_to_dispatch.append(job)
    dp = pipe.create_dataplane()
    assert len(dp.topology.source_gateways()) == 2
    assert len(dp.topology.sink_gateways()) == 2
    with dp.auto_deprovision():
        dp.provision()
        dp.run([job])
    for name, payload in data.items():
        assert (dst_root / name).read_bytes() == payload


@pytest.mark.slow
def test_cross_site_dedup_through_subprocess_daemons(tmp_path):
    """Regression: dedup (which touches jax.devices() in the daemon) must work
    in SUBPROCESS gateways, which compute/local.py starts with
    JAX_PLATFORMS=cpu because the chip belongs to one process."""
    import numpy as _np

    src_root = tmp_path / "siteA"
    dst_root = tmp_path / "siteB"
    src_root.mkdir()
    dst_root.mkdir()
    pat = _np.random.default_rng(5).integers(0, 256, 1 << 19, dtype=_np.uint8).tobytes()
    payload = pat * 4 + bytes(1 << 19)
    (src_root / "f.bin").write_bytes(payload)
    job = CopyJob("local:///", ["local:///"], recursive=True)
    job._src_iface = POSIXInterface(str(src_root), region_tag="local:siteA")
    job._dst_ifaces = [POSIXInterface(str(dst_root), region_tag="local:siteB")]
    job.src_path = "local:///"
    job.dst_paths = ["local:///"]
    pipe = Pipeline(transfer_config=TransferConfig(compress="zstd", dedup=True, multipart_threshold_mb=1024))
    pipe.jobs_to_dispatch.append(job)
    stats = pipe.start()
    assert (dst_root / "f.bin").read_bytes() == payload
    assert stats and stats.get("compression_ratio", 0) > 1.5, stats


@pytest.mark.slow
def test_dead_gateway_surfaces_error(tmp_path, monkeypatch):
    """A destination daemon killed mid-transfer must fail the client with a
    GatewayException within the unreachable-streak window, not hang to the
    24h timeout."""
    from skyplane_tpu.api.tracker import TransferProgressTracker
    from skyplane_tpu.exceptions import GatewayException

    monkeypatch.setattr(TransferProgressTracker, "UNREACHABLE_STREAK_LIMIT", 5)
    src_root = tmp_path / "siteA"
    dst_root = tmp_path / "siteB"
    _fill_bucket(src_root, n_files=1, size=64 * 1024)
    dst_root.mkdir()
    job = CopyJob("local:///", ["local:///"], recursive=True)
    job._src_iface = POSIXInterface(str(src_root), region_tag="local:siteA")
    job._dst_ifaces = [POSIXInterface(str(dst_root), region_tag="local:siteB")]
    job.src_path = "local:///"
    job.dst_paths = ["local:///"]
    cfg = TransferConfig(compress="zstd", dedup=False, multipart_threshold_mb=1024)
    pipe = Pipeline(transfer_config=cfg)
    pipe.jobs_to_dispatch.append(job)
    dp = pipe.create_dataplane()
    with dp.auto_deprovision():
        dp.provision()
        # murder the destination daemon before dispatch
        for bound in dp.bound_gateways.values():
            if bound.region_tag == "local:siteB":
                bound.server.proc.kill()
        tracker = dp.run_async([job])
        tracker.join(timeout=120)
        assert not tracker.is_alive(), "tracker still running — dead gateway not detected"
        # either detection path is a win: the unreachable-streak detector, or
        # the source gateway's own fatal send error surfacing first
        assert isinstance(tracker.error, GatewayException), f"expected GatewayException, got {tracker.error!r}"


@pytest.mark.slow
def test_multi_job_single_dataplane(tmp_path):
    """Two copy jobs with different buckets share one dataplane: each job's
    chunks must route through ITS partition DAG to ITS destination bucket
    (reference matrix: pipeline multi-job case)."""
    srcA = tmp_path / "srcA"; srcB = tmp_path / "srcB"
    dstA = tmp_path / "dstA"; dstB = tmp_path / "dstB"
    dataA = _fill_bucket(srcA, n_files=2, size=128 * 1024)
    dataB = _fill_bucket(srcB, n_files=2, size=128 * 1024)
    dstA.mkdir(); dstB.mkdir()

    jobs = []
    for src_root, dst_root in ((srcA, dstA), (srcB, dstB)):
        job = CopyJob("local:///", ["local:///"], recursive=True)
        job._src_iface = POSIXInterface(str(src_root), region_tag="local:siteA")
        job._dst_ifaces = [POSIXInterface(str(dst_root), region_tag="local:siteB")]
        job.src_path = "local:///"
        job.dst_paths = ["local:///"]
        jobs.append(job)

    cfg = TransferConfig(compress="zstd", dedup=False, multipart_threshold_mb=1024, num_connections=2)
    pipe = Pipeline(transfer_config=cfg)
    pipe.jobs_to_dispatch.extend(jobs)
    dp = pipe.create_dataplane()
    # one gateway per side, TWO partitions each (one per job)
    src_gw = dp.topology.source_gateways()[0]
    partitions = [p for group in src_gw.gateway_program.to_dict()["plan"] for p in group["partitions"]]
    assert len(partitions) == 2
    with dp.auto_deprovision():
        dp.provision()
        dp.run(jobs)
    for name, payload in dataA.items():
        assert (dstA / name).read_bytes() == payload, f"job A content wrong: {name}"
        assert not (dstB / name).exists() or (dstB / name).read_bytes() != payload or name in dataB
    for name, payload in dataB.items():
        assert (dstB / name).read_bytes() == payload, f"job B content wrong: {name}"
