"""The completion reader (``lib/pair.StatusReader``) against the read it
replaced, ``GET chunk_status_log?include_log=1``, on a recorded sink status
log replayed through the program's own status pump and control API."""

import json
import queue
import threading
from pathlib import Path
from types import SimpleNamespace

import pytest
import requests
from lib import pair

RECORDED = json.loads((Path(__file__).resolve().parent / "data" / "sink_status_log.json").read_text())


@pytest.fixture
def api(tmp_path):
    """A bare control API with the recorded sink's terminal operators, its
    HTTP server running."""
    from skyplane_tpu.gateway.chunk_store import ChunkStore
    from skyplane_tpu.gateway.gateway_daemon_api import GatewayDaemonAPI

    class Receiver:
        socket_profile_events = queue.Queue()

    api = GatewayDaemonAPI(
        chunk_store=ChunkStore(str(tmp_path / "chunks")), receiver=Receiver(), error_event=threading.Event(), error_queue=queue.Queue(),
        terminal_operators=RECORDED["terminal_operators"], handle_to_group=RECORDED["handle_to_group"],
        region="local:local", gateway_id="gw_dst", host="127.0.0.1", port=0,
    )
    api.start()
    yield api
    api.stop()


def gateway(api) -> SimpleNamespace:
    return SimpleNamespace(daemon=SimpleNamespace(api=api, gateway_id=api.gateway_id))


def include_log_read(api, chunk_ids) -> dict:
    """The harness's completion read up to this reader, word for word but for
    the session: chunk id -> the largest ``complete`` stamp of each asked-for
    chunk whose aggregate state is complete."""
    ids = sorted(chunk_ids)
    if not ids:
        return {}
    resp = requests.get(f"http://127.0.0.1:{api.port}/api/v1/chunk_status_log", params={"chunk_ids": ",".join(ids), "include_log": "1"}, timeout=30)
    resp.raise_for_status()
    body = resp.json()
    done = {c for c, state in body["chunk_status"].items() if state == "complete"}
    out = {}
    for rec in body["chunk_status_log"]:
        cid = rec["chunk_id"]
        if cid in done and rec["state"] == "complete":
            out[cid] = max(out.get(cid, 0.0), float(rec["time"]))
    return out


def feed(api, records) -> None:
    """Hand records to the API as the operators do, and pump them as the
    daemon's main loop does."""
    for rec in records:
        api.chunk_store.chunk_status_queue.put(dict(rec))
    api.pull_chunk_status_queue()


@pytest.mark.parametrize("step", [1, 3, 7, 64, 600])
def test_the_reader_gives_the_stamps_of_the_include_log_read_poll_for_poll(api, step):
    records = RECORDED["records"]
    ids = list(dict.fromkeys(r["chunk_id"] for r in records))
    reader = pair.StatusReader(gateway(api))
    reader.track(ids)
    waiting, got = set(ids), {}
    for at in range(0, len(records), step):
        feed(api, records[at : at + step])
        old = include_log_read(api, waiting)
        new = reader.poll()
        assert new == old, at
        waiting -= set(new)
        got.update(new)
    assert reader.poll() == {} and set(reader.waiting) == waiting
    # every chunk whose write the recording holds is returned once, at its write's stamp; the rest are still waited for
    written = {r["chunk_id"]: r["time"] for r in records if r["handle"] == "write" and r["state"] == "complete"}
    assert got == written and len(got) > 100 and waiting == set(ids) - set(written) != set()


def test_the_reader_returns_only_tracked_chunks_and_what_was_logged_after_its_cursor(api):
    records = RECORDED["records"][:200]
    first, second, third = list(dict.fromkeys(r["chunk_id"] for r in records))[:3]
    reader = pair.StatusReader(gateway(api))
    reader.track([first])
    feed(api, records)
    reader.track([second])  # logged before it was tracked, but after the cursor: run.py tracks a post before its next poll
    assert set(reader.poll()) == {first, second}
    reader.track([third])  # the cursor has passed its records
    assert reader.poll() == {} and set(reader.waiting) == {third}
    late = pair.StatusReader(gateway(api))
    late.track([first])
    assert late.poll() == {}  # a reader starts at the log's end


def test_records_dropped_past_the_cursor_end_the_read_at_once(api, monkeypatch):
    monkeypatch.setattr(api, "MAX_STATUS_LOG", 16)
    records = RECORDED["records"]
    reader = pair.StatusReader(gateway(api))
    reader.track(r["chunk_id"] for r in records)
    feed(api, records[:16])  # at the bound: nothing dropped
    assert len(reader.poll()) > 0
    feed(api, records[16:40])  # 24 new records, 8 of them dropped before this read
    with pytest.raises(pair.StatusLogLost, match="dropped 8 records"):
        reader.poll()
    with pytest.raises(pair.StatusLogLost):  # and stays so: no completion it may have missed is waited for
        reader.poll()


def test_a_reader_that_keeps_up_reads_on_past_the_log_bound(api, monkeypatch):
    monkeypatch.setattr(api, "MAX_STATUS_LOG", 16)
    records = RECORDED["records"]
    reader = pair.StatusReader(gateway(api))
    reader.track(r["chunk_id"] for r in records)
    got = {}
    for at in range(0, len(records), 8):
        feed(api, records[at : at + 8])
        got.update(reader.poll())
    assert api._status_log_dropped == len(records) - 16
    assert got == {r["chunk_id"]: r["time"] for r in records if r["handle"] == "write" and r["state"] == "complete"}
