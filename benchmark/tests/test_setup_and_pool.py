"""The set-up bursts, the pooled reference and the order result line ->
teardown: whole rehearsal runs on the CPU backend at chunks 64 times smaller,
and the pool alone on both generators."""

import ast
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest
from lib import check, pair, reference, refpool

BENCH = Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CDC = (4096, 16384, 65536)
CONTENT = {
    "random_files": {"file_bytes": 60763889},
    "snapshot_delta": {"region_bytes": 67108864, "extent_bytes": 524288, "extents_per_region": 4},
}
SEED = 4000000007


def alive(pid: int) -> bool:
    try:
        return Path(f"/proc/{pid}/stat").read_text().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def gone(pids, within: float = 10.0) -> bool:
    until = time.monotonic() + within
    while any(alive(p) for p in pids) and time.monotonic() < until:
        time.sleep(0.1)
    return not any(alive(p) for p in pids)


def pool_pids(err: str):
    line = next(l for l in err.splitlines() if "reference pool:" in l and "pids" in l)
    return json.loads(line.split("pids ")[1].split(" (")[0]), line


def make_generator(name: str):
    import run

    return run.load_module(BENCH / "generators" / f"{name}.py").Generator(CONTENT[name], SEED, 64)


def make_pool(name: str, workers: int) -> refpool.ReferencePool:
    return refpool.ReferencePool(BENCH / "generators" / f"{name}.py", CONTENT[name], SEED, 64, CDC, workers)


# ---- the pool alone


@pytest.mark.parametrize("workers", [1, 3])
@pytest.mark.parametrize("name", sorted(CONTENT))
def test_the_pooled_reference_equals_the_serial_one_row_for_row(name, workers):
    generator = make_generator(name)
    indices = [0, 1, 2, 5, 11, 12, 40]
    pool = make_pool(name, workers)
    try:
        rows, info = pool.rows(indices)
    finally:
        pool.close()
    assert sorted(rows) == indices and info["rows"] == len(indices) and info["workers"] == workers
    for i in indices:
        ends, fps = reference.cdc_and_fingerprints(generator.chunk(i) if i else generator.setup_chunk(), *CDC)
        assert np.array_equal(rows[i][0], ends) and rows[i][0].dtype == np.int64 and rows[i][1] == fps, i
    assert gone(pool.pids(), 1.0) and all(p.returncode == 0 for p in pool.procs)


def observed_from_pool(name: str, n_rows: int):
    """An Observed whose device rows are the serial reference's and whose
    reference rows are the pool's."""
    generator = make_generator(name)
    row = lambda i: generator.chunk(i) if i else generator.setup_chunk()  # noqa: E731
    sent = [
        check.Sent(index=i, chunk_id=f"c{i}", key=str(i), digest="", n_bytes=generator.chunk_bytes, src_path=Path("."), dst_path=Path("."),
                   posted_at=0.0, completed_at=1.0)
        for i in range(n_rows)
    ]
    device_rows = {i: reference.cdc_and_fingerprints(row(i), *CDC) for i in range(n_rows)}
    segments, fewest, _ = check.expected_refs(device_rows, list(range(n_rows)))
    obs = check.Observed(
        sent=sent, file_digests={i: "" for i in range(n_rows)}, device_rows=device_rows, row_bytes=row,
        counters={"batch_rows": n_rows, "stage_failures": 0, "segments": segments, "ref_segments": fewest},
        frames=[{"chunk_id": s.chunk_id, "codec": 3, "raw_bytes": s.n_bytes, "wire_bytes": 1} for s in sent],
        gateway_errors=0, as_built_departures=[], cdc=CDC, wire_codec_id=3,
    )
    pool = make_pool(name, 2)
    try:
        info = check.compute_reference(obs, pool)
    finally:
        pool.close()
    assert info["rows"] == n_rows and sorted(obs.reference_rows) == list(range(n_rows))
    return obs


def over(compared):
    return {k for k, v in compared.items() if v["value"] > v["limit"]}


@pytest.mark.parametrize("fault, has_to_fail", [("fingerprint", "rows_fingerprints_differ"), ("end", "rows_ends_differ")])
def test_a_fault_planted_in_the_last_row_is_caught_through_the_pool(fault, has_to_fail):
    obs = observed_from_pool("snapshot_delta", 9)
    assert over(check.compare(obs)) == set()
    ends, fps = obs.device_rows[8]
    if fault == "fingerprint":
        obs.device_rows[8] = (ends, fps[:-1] + [bytes([fps[-1][0] ^ 1]) + fps[-1][1:]])
    else:
        obs.device_rows[8] = (np.concatenate([ends[:-2], [ends[-2] - 1], ends[-1:]]), fps)
    assert has_to_fail in over(check.compare(obs))


def test_workers_do_nothing_until_asked_and_end_when_the_run_goes():
    """A worker waits on its stdin; a parent that leaves through os._exit,
    as the deadline does, takes its workers with it."""
    code = (
        "import sys, os, time; sys.path[:0] = [%r]\n"
        "from pathlib import Path\n"
        "from lib import refpool\n"
        "pool = refpool.ReferencePool(Path(%r), %r, 1, 64, %r, 2)\n"
        "pool.procs[0].stdin.write(b'3\\n'); pool.procs[0].stdin.flush()  # one worker is mid-row when the parent goes\n"
        "print(pool.pids(), flush=True); time.sleep(0.3); os._exit(3)\n"
    ) % (str(BENCH), str(BENCH / "generators" / "snapshot_delta.py"), CONTENT["snapshot_delta"], list(CDC))
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=60)
    assert done.returncode == 3, done.stderr
    assert gone(json.loads(done.stdout.strip().splitlines()[-1]))


def test_the_reference_and_its_workers_import_numpy_and_nothing_of_the_program():
    allowed = set(sys.stdlib_module_names) | {"numpy", "reference"}
    for path in [BENCH / "lib" / "reference.py", BENCH / "lib" / "refpool.py", *sorted((BENCH / "generators").glob("*.py"))]:
        tree = ast.parse(path.read_text())
        names = {a.name.split(".")[0] for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
        names |= {n.module.split(".")[0] for n in ast.walk(tree) if isinstance(n, ast.ImportFrom) and n.module}
        assert names <= allowed, (path.name, names - allowed)


def test_pool_size_is_half_the_cores_and_at_most_eight(monkeypatch):
    for cores, size in [(None, 1), (1, 1), (2, 1), (13, 6), (16, 8), (96, 8)]:
        monkeypatch.setattr(os, "cpu_count", lambda cores=cores: cores)
        assert refpool.pool_size() == size


# ---- whole rehearsal runs


def with_cell(monkeypatch, traffic: dict, name: str = "test-cell.burst", transfer: dict = None, generator: str = None, content: dict = None):
    """Serve run.py a cell that is in no file: the first cell's configuration
    (with ``transfer`` over its values) and content under ``traffic``, or
    ``content`` made by ``generator``."""
    import run

    base = SPEC["workloads"][0]
    config = f"{name}-config"
    real = run.load_json

    def load_json(path: Path) -> dict:
        if path.name == "BENCHMARK.json":
            spec = real(path)
            spec["workloads"].append(dict(base, name=name, config=config))
            for m in spec["end_to_end"] + spec["per_layer"]:
                if "workloads" in m:
                    m["workloads"].append(name)
            return spec
        if path.name == f"{name}.json":
            workload = dict(real(path.with_name(f"{base['name']}.json")), name=name, config=config, traffic=traffic)
            if generator is not None:
                workload.update(generator=generator, content=content)
            return workload
        if path.name == f"{config}.json":
            cfg = real(path.with_name(f"{base['config']}.json"))
            return dict(cfg, name=config, transfer=dict(cfg["transfer"], **(transfer or {})))
        return real(path)

    monkeypatch.setattr(run, "load_json", load_json)
    return name


def rehearse(capsys, cell, trace="0", seconds="3"):
    import run

    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds", seconds, "--trace", trace, "--rehearse-scale", "64"])
    captured = capsys.readouterr()
    lines = [line for line in captured.out.strip().splitlines() if line.startswith("{")]
    return rc, (json.loads(lines[-1]) if lines else None), captured.err


def line_no(err: str, text: str) -> int:
    return next(i for i, l in enumerate(err.splitlines()) if text in l)


def test_a_burst_workload_forms_its_bursts_before_t0_and_holds_their_rows_to_the_reference(capsys, monkeypatch):
    cell = with_cell(monkeypatch, {"in_flight_chunks": 4, "setup_burst_chunks": 8})
    rc, result, err = rehearse(capsys, cell, trace="1")
    assert rc == 1 and result["rehearsal"]["checks_passed"] is True, err[-3000:]
    run = result["run"]
    assert run["setup_rows"] == 1 + 2 * 8 and "setup_bursts_s" in run["phases"]
    assert run["reference_rows"] == run["rows_sent"] >= run["setup_rows"] + 1 + run["completions"]
    assert len(run["gaps_s"]) == run["completions"]  # the window's list holds no burst row
    assert line_no(err, "set-up burst 1: 8 chunks") < line_no(err, "set-up burst 2: 8 chunks") < line_no(err, "t0: first window chunk")
    assert "0 compiles" in err.splitlines()[line_no(err, "set-up burst 2")]
    assert result["rehearsal"]["values"]["compiles_after_t0"]["value"] == 0
    assert run["reference"]["started_after_close_s"] >= 0  # no reference work before the window closed
    assert line_no(err, "window closed") < line_no(err, "reference:") < line_no(err, "result line printed") < line_no(err, "teardown:")
    assert err.splitlines()[-1] == "correct: False" and run["reference"]["workers_ended"] == [0] * run["reference"]["workers"]
    assert gone(pool_pids(err)[0], 1.0)


@pytest.mark.parametrize(
    "plant, says",
    [
        (lambda body, n: body.update(xla_compiles=body.get("xla_compiles", 0) + n), "burst 2 of 8 chunks compiled"),
        (lambda body, n: body.update(batch_windows=body["batch_rows"]), "burst 1 of 8 chunks ran no window of more than one row"),
    ],
    ids=["second_burst_compiles", "first_burst_is_lone_rows"],
)
def test_a_set_up_that_is_not_sound_ends_the_run_with_no_result_and_says_why(capsys, monkeypatch, plant, says):
    cell = with_cell(monkeypatch, {"in_flight_chunks": 2, "setup_burst_chunks": 8})
    real, asked = pair.LocalGateway.get, [0]

    def get(self, route, **kw):
        body = real(self, route, **kw)
        if route == "profile/compression":
            asked[0] += 1
            plant(body, asked[0])
        return body

    monkeypatch.setattr(pair.LocalGateway, "get", get)
    rc, result, err = rehearse(capsys, cell)
    assert rc == 5 and result is None
    assert "the set-up is not sound" in err and says in err and "t0:" not in err and "correct:" not in err
    assert gone(pool_pids(err)[0], 1.0)


@pytest.mark.parametrize("value", [1, 0, True, "8", 2.0])
def test_a_burst_of_fewer_than_two_chunks_is_refused_before_anything_starts(capsys, monkeypatch, value):
    cell = with_cell(monkeypatch, {"in_flight_chunks": 2, "setup_burst_chunks": value})
    with pytest.raises(SystemExit, match="setup_burst_chunks"):
        rehearse(capsys, cell)


def watch_posts_and_completions(monkeypatch):
    """(index, chunk id, when) of every file posted, and (gateway, chunk id)
    -> when a poll first found it complete there."""
    posts, done = [], {}
    real_post, real_poll = pair.post_files, pair.StatusReader.poll

    def post_files(source, files):
        ids = real_post(source, files)
        posts.extend((int(src_path.stem.split("_")[1]), file_ids[0], time.monotonic()) for (src_path, _, _), file_ids in zip(files, ids))
        return ids

    def poll(self):
        out = real_poll(self)
        for cid in out:
            done.setdefault((self.name, cid), time.monotonic())
        return out

    monkeypatch.setattr(pair, "post_files", post_files)
    monkeypatch.setattr(pair.StatusReader, "poll", poll)
    return posts, done


def test_set_up_rows_land_one_at_a_time_before_t0_and_are_held_to_the_reference(capsys, monkeypatch):
    cell = with_cell(monkeypatch, {"in_flight_chunks": 2, "setup_chunks": 3}, name="test-cell.setup")
    posts, done = watch_posts_and_completions(monkeypatch)
    rc, result, err = rehearse(capsys, cell)
    assert rc == 1 and result["rehearsal"]["checks_passed"] is True, err[-3000:]
    run = result["run"]
    assert run["setup_rows"] == 3 and "setup_chunks_landed_s" in run["phases"]
    assert [i for i, _, _ in posts[:5]] == [0, 1, 2, 3, 4]  # the fill numbers on from the set-up rows
    for (_, before, _), (_, _, posted) in zip(posts[:3], posts[1:4]):
        # the next row is posted once the sink has landed this one and the source has taken its ack
        assert done[("gw_dst", before)] < posted and done[("gw_src", before)] < posted
    assert run["reference_rows"] == run["rows_sent"] >= 3 + 1 + run["completions"]
    assert len(run["gaps_s"]) == run["completions"]  # no set-up row in the window
    assert line_no(err, "set-up: 3 rows landed one at a time") < line_no(err, "t0: first window chunk")
    assert gone(pool_pids(err)[0], 1.0)


@pytest.mark.parametrize("value", [0, 1.5, "3", True])
def test_set_up_chunks_other_than_a_whole_number_are_refused_before_anything_starts(capsys, monkeypatch, value):
    cell = with_cell(monkeypatch, {"in_flight_chunks": 2, "setup_chunks": value})
    with pytest.raises(SystemExit, match="setup_chunks"):
        rehearse(capsys, cell)


@pytest.mark.parametrize("value", [0, 1.5, "256"])
def test_a_store_bound_other_than_a_whole_number_is_refused_before_anything_starts(capsys, monkeypatch, value):
    cell = with_cell(monkeypatch, {"in_flight_chunks": 2}, transfer={"sink_segment_store_mb": value})
    with pytest.raises(SystemExit, match="sink_segment_store_mb"):
        rehearse(capsys, cell)


REGIONS = '''
import numpy as np


class Generator:
    """Rows 0..regions-1 are regions of their own; row i past them is region
    i % regions with one extent rewritten from (seed, i)."""

    def __init__(self, params, seed, scale=1):
        self.seed = int(seed)
        self.regions = int(params["regions"])
        self.chunk_bytes = int(params["region_bytes"]) // scale
        self.extent_bytes = int(params["extent_bytes"]) // scale

    def setup_chunk(self):
        return self.chunk(0)

    def chunk(self, i):
        out = np.random.default_rng([self.seed, 0, i % self.regions]).integers(0, 256, self.chunk_bytes, dtype=np.uint8)
        if i >= self.regions:
            rng = np.random.default_rng([self.seed, 1, i])
            at = int(rng.integers(0, self.chunk_bytes - self.extent_bytes))
            out[at : at + self.extent_bytes] = rng.integers(0, 256, self.extent_bytes, dtype=np.uint8)
        return out
'''


def test_a_store_smaller_than_the_set_up_spills_and_the_window_refs_resolve_from_disk(capsys, monkeypatch, tmp_path):
    """Three set-up regions of 1 MiB (64 MiB at the timed size) against a
    memory tier of 2 MiB (128 MB): window row i is region i % 3, so every
    window row REFs a region the store has spilled, set-up rows 1 and 2 among
    them; the union rule holds ``ref_segments`` to all three."""
    (tmp_path / "regions.py").write_text(REGIONS)
    cell = with_cell(
        monkeypatch, {"in_flight_chunks": 2, "setup_chunks": 3}, name="test-cell.spill",
        transfer={"sink_segment_store_mb": 128},
        generator=str(tmp_path / "regions"),  # absolute: joined to generators/ it stays as it is
        content={"regions": 3, "region_bytes": 67108864, "extent_bytes": 524288},
    )
    rc, result, err = rehearse(capsys, cell)
    assert rc == 1 and result["rehearsal"]["checks_passed"] is True, err[-3000:]
    assert "memory tier bound 2097152 bytes" in err and result["compared"]["as_built_departures"]["value"] == 0
    store = result["run"]["sink_store"]
    assert store["run"]["store_mem_evictions"] > 0 and store["after_t0"]["store_spill_reads"] > 0, store
    assert store["run"]["decode_nacks"] == 0 and store["run"]["store_ref_timeouts"] == 0
    assert result["run"]["completions"] >= 3  # window rows 3, 4, 5: regions 0, 1 and 2
    assert gone(pool_pids(err)[0], 1.0)


@pytest.mark.parametrize("asked, env, built", [(None, None, 4 << 30), (None, "8", 8 << 20), (3 << 20, "8", 3 << 20)])
def test_the_pair_builds_the_sink_store_at_the_bound_asked_else_the_daemons_own(tmp_path, monkeypatch, asked, env, built):
    import run
    from skyplane_tpu.ops.cdc import CDCParams

    if env is None:
        monkeypatch.delenv("SKYPLANE_TPU_SEGSTORE_MB", raising=False)
    else:
        monkeypatch.setenv("SKYPLANE_TPU_SEGSTORE_MB", env)
    source, sink = pair.make_pair(tmp_path, "tpu_zstd", True, False, False, 2, CDCParams(4096, 16384, 65536), sink_segment_store_bytes=asked)
    try:
        assert sink.daemon.receiver.segment_store._max_bytes == built
        assert source.daemon.receiver.segment_store is None
        cfg = {"encrypt_socket_tls": False, "encrypt_e2e": False, "cdc_min_bytes": 4096, "cdc_avg_bytes": 16384, "cdc_max_bytes": 65536, "batch_window": 8}
        of_store = lambda stated: [d for d in run.departures_from(cfg, (source, sink), stated) if "segment_store" in d]  # noqa: E731
        assert of_store(None) == [] and of_store(built) == []
        assert of_store(built + 1) == [f"gw_dst.segment_store_bytes: built {built}, stated {built + 1}"]
    finally:
        source.stop()
        sink.stop()


@pytest.mark.parametrize(
    "traffic, transfer",
    [(None, None), ({"setup_chunks": 1}, None), (None, {"sink_segment_store_mb": 4096}), ({"fill_stagger_s": 1.0}, None)],
    ids=["neither_key", "setup_chunks_1", "store_at_its_default", "fill_stagger_s_1"],
)
def test_a_workload_without_the_key_issues_the_posts_it_always_did_and_leaves_no_worker(capsys, monkeypatch, traffic, transfer):
    """Recorded from the parent of the PR that brought the bursts: the set-up
    chunk alone, then ``in_flight_chunks`` posts one STAGGER_S apart, then one
    post a completion, in the order of the generator's indices. One set-up
    row stated, the sink's store at the daemon's own 4 GiB, or a fill stagger
    of STAGGER_S stated, post the same; the set-up chunk and each post of the
    fill go in a request of their own."""
    import run

    cell = SPEC["workloads"][0]["name"]
    in_flight = json.loads((BENCH / "workloads" / f"{cell}.json").read_text())["traffic"]["in_flight_chunks"]
    if traffic or transfer:
        cell = with_cell(monkeypatch, dict({"in_flight_chunks": in_flight}, **(traffic or {})), name="test-cell.keys", transfer=transfer)
    events, requests, main_thread = [], [], threading.current_thread()
    real_post, real_request, real_sleep = pair.post_files, pair.LocalGateway.post, time.sleep

    def post_files(source, files):
        events.extend(("post", int(src_path.stem.split("_")[1])) for src_path, _, _ in files)
        return real_post(source, files)

    def post(self, route, body):
        requests.append(len(body))
        return real_request(self, route, body)

    def sleep(seconds):
        if threading.current_thread() is main_thread and seconds >= 0.5:  # not the polls, nor a retry's back-off
            events.append(("sleep", seconds))
        real_sleep(seconds)

    monkeypatch.setattr(pair, "post_files", post_files)
    monkeypatch.setattr(pair.LocalGateway, "post", post)
    monkeypatch.setattr(time, "sleep", sleep)
    rc, result, err = rehearse(capsys, cell)
    monkeypatch.undo()
    assert rc == 1 and result["rehearsal"]["checks_passed"] is True, err[-3000:]
    fill = [("post", 1)] + [e for n in range(2, in_flight + 1) for e in (("sleep", run.STAGGER_S), ("post", n))]
    assert events[: 1 + len(fill)] == [("post", 0)] + fill
    assert requests[: 1 + in_flight] == [1] * (1 + in_flight) and sum(requests) == len(events) - (in_flight - 1)
    rest = events[1 + len(fill) :]
    assert rest == [("post", n) for n in range(in_flight + 1, in_flight + 1 + len(rest))] and rest
    assert result["run"]["setup_rows"] == 1 and "set-up burst" not in err and "setup_bursts_s" not in result["run"]["phases"]
    assert "setup_chunks_landed_s" not in result["run"]["phases"] and "rows landed one at a time" not in err
    assert result["run"]["reference_rows"] == result["run"]["rows_sent"] == len(events) - (in_flight - 1)
    assert gone(pool_pids(err)[0], 1.0)


SMALL = {"generator": "random_files", "content": {"file_bytes": 131072}}  # 2 KiB objects at 1/64


def watch_requests_and_polls(monkeypatch):
    """In order: ("post", chunk requests in the request) for every POST to
    ``chunk_requests``, ("poll",) for every read of the sink's status log,
    and ("sleep", s) for every sleep of half a second or more on this thread."""
    events, main_thread = [], threading.current_thread()
    real_post, real_poll, real_sleep = pair.LocalGateway.post, pair.StatusReader.poll, time.sleep

    def post(self, route, body):
        if route == "chunk_requests":
            events.append(("post", len(body)))
        return real_post(self, route, body)

    def poll(self):
        if self.name == "gw_dst":
            events.append(("poll",))
        return real_poll(self)

    def sleep(seconds):
        if threading.current_thread() is main_thread and seconds >= 0.5:
            events.append(("sleep", seconds))
        real_sleep(seconds)

    monkeypatch.setattr(pair.LocalGateway, "post", post)
    monkeypatch.setattr(pair.StatusReader, "poll", poll)
    monkeypatch.setattr(time, "sleep", sleep)
    return events


def test_a_fill_stagger_of_0_posts_the_whole_fill_before_the_first_poll_in_batches_of_100(capsys, monkeypatch):
    cell = with_cell(monkeypatch, {"in_flight_chunks": 250, "fill_stagger_s": 0}, name="test-cell.fill", **SMALL)
    events = watch_requests_and_polls(monkeypatch)
    rc, result, err = rehearse(capsys, cell, seconds="10")  # the first window of several rows compiles on the CPU
    monkeypatch.undo()
    assert rc == 1 and result["rehearsal"]["checks_passed"] is True, err[-3000:]
    assert events[0] == ("post", 1)  # the set-up chunk, landed alone
    fill_at = events.index(("post", 100))
    assert all(e == ("poll",) for e in events[1:fill_at])  # the set-up chunk's polls
    assert events[fill_at : fill_at + 4] == [("post", 100), ("post", 100), ("post", 50), ("poll",)]
    assert not [e for e in events if e[0] == "sleep"]
    refills = [e[1] for e in events[fill_at + 3 :] if e[0] == "post"]
    assert refills and max(refills) <= 100 and 1 + 250 + sum(refills) == result["run"]["rows_sent"]
    assert result["run"]["completions"] >= 2 and result["run"]["sink_status_log_dropped"] == 0
    assert gone(pool_pids(err)[0], 1.0)


@pytest.mark.parametrize("value", [-1, "0", True, None, float("nan")])
def test_a_fill_stagger_other_than_a_number_of_0_or_more_is_refused_before_anything_starts(capsys, monkeypatch, value):
    cell = with_cell(monkeypatch, {"in_flight_chunks": 2, "fill_stagger_s": value})
    with pytest.raises(SystemExit, match="fill_stagger_s"):
        rehearse(capsys, cell)


def test_a_loop_deeper_than_a_url_can_list_completes_and_passes_every_check(capsys, monkeypatch):
    """2,048 in flight: the read this harness had listed every pending id in
    one GET's URL, 33 bytes an id, and ``http.server`` answers 414 past 65,536
    bytes of request line. The reader sends no id anywhere."""
    depth = 2048
    assert len("chunk_ids=" + ",".join(["0" * 32] * depth)) > 65536
    cell = with_cell(monkeypatch, {"in_flight_chunks": depth, "fill_stagger_s": 0}, name="test-cell.deep", **SMALL)
    gets, real_get = [], pair.LocalGateway.get

    def get(self, route, **kw):
        gets.append(route)
        return real_get(self, route, **kw)

    monkeypatch.setattr(pair.LocalGateway, "get", get)
    rc, result, err = rehearse(capsys, cell, seconds="10")  # the first window of several rows compiles on the CPU: a gap of 4 s
    assert rc == 1 and result["rehearsal"]["checks_passed"] is True, err[-3000:]
    run = result["run"]
    assert run["rows_sent"] >= 1 + depth and run["completions"] >= 2 and run["sink_status_log_dropped"] == 0
    assert result["compared"]["chunks_never_landed"]["value"] == 0 and result["compared"]["frames_missing"]["value"] == 0
    assert "chunk_status_log" not in gets
    assert gone(pool_pids(err)[0], 1.0)


def test_a_status_log_that_drops_records_before_they_are_read_ends_the_run_unsound_at_once(capsys, monkeypatch):
    from skyplane_tpu.gateway.gateway_daemon_api import GatewayDaemonAPI

    monkeypatch.setattr(GatewayDaemonAPI, "MAX_STATUS_LOG", 1)  # every record but the newest is dropped at once
    t = time.monotonic()
    rc, result, err = rehearse(capsys, SPEC["workloads"][0]["name"])
    assert rc == 5 and result is None
    assert "FAIL: the run is not sound: gateway gw_" in err and "records of its status log" in err and "correct:" not in err
    assert time.monotonic() - t < 60  # no wait for a completion the log no longer holds
    assert gone(pool_pids(err)[0], 1.0)


def test_a_result_line_is_printed_when_stop_hangs(capsys, monkeypatch):
    import run

    def hangs(self):
        time.sleep(30)

    def leave(rc):
        raise SystemExit(rc)

    monkeypatch.setattr(pair.LocalGateway, "stop", hangs)
    monkeypatch.setattr(run, "TEARDOWN_S", 0.5)
    monkeypatch.setattr(run, "leave", leave)
    with pytest.raises(SystemExit) as left:
        run.main(["--workload", SPEC["workloads"][0]["name"], "--seed", str(SEED), "--seconds", "3", "--trace", "0", "--rehearse-scale", "64"])
    captured = capsys.readouterr()
    assert left.value.code == 1  # a rehearsal's own code, not the deadline's
    result = json.loads(captured.out.strip().splitlines()[-1])
    assert result["rehearsal"]["checks_passed"] is True and list(result)[-1] == "compared"
    assert line_no(captured.err, "result line printed") < line_no(captured.err, "teardown passed its limit")
    assert captured.err.splitlines()[-1] == "correct: False"  # the numbers compared still end the log
    assert gone(pool_pids(captured.err)[0], 1.0)


def test_no_worker_outlives_a_run_that_a_gateway_fault_ends(capsys, monkeypatch):
    real, asked = pair.errors, [0]

    def errors(gw):
        asked[0] += 1
        return ["planted fault"] if asked[0] > 2 else real(gw)

    monkeypatch.setattr(pair, "errors", errors)
    rc, result, err = rehearse(capsys, SPEC["workloads"][0]["name"])
    assert rc != 0 and "planted fault" in err and (result is None or result["rehearsal"]["checks_passed"] is False)
    assert gone(pool_pids(err)[0], 1.0)


def test_the_pool_is_started_before_jax_and_no_worker_outlives_the_deadline():
    """A run of its own process: the pool's line says jax was not imported when
    the workers started, and a deadline that fires in the window leaves none."""
    code = (
        "import sys; sys.path[:0] = [%r]\n"
        "import run\n"
        "run.DEADLINE_S = 6.0\n"
        "sys.exit(run.main(['--workload', %r, '--seed', '7', '--seconds', '30', '--trace', '0', '--rehearse-scale', '64']))\n"
    ) % (str(BENCH), SPEC["workloads"][0]["name"])
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120, env=env)
    assert done.returncode == 3 and "deadline of 6s was reached" in done.stderr, done.stderr[-3000:]
    assert not [l for l in done.stdout.splitlines() if l.startswith("{")]
    pids, line = pool_pids(done.stderr)
    assert "(jax imported: False)" in line and gone(pids)
