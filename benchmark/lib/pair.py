"""A source and a sink ``GatewayDaemon`` in this process, on loopback.

The benchmark's own copy of what it needs from ``tests/integration/harness.py``
(``start_gateway``, ``make_pair``, ``build_chunk_requests``, status polling):
later PRs may change that file and may not change the yardstick. The daemons
are the program; everything here starts them and talks to their control API
over HTTP, as a client does, but for two reads made in this process: the
status log from a cursor (``StatusReader``) and the sink's decode events
(``decode_events``), each at a cost of what is new since the last read.
"""

from __future__ import annotations

import queue
import threading
import time
import uuid
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

import requests


@dataclass
class LocalGateway:
    daemon: object  # skyplane_tpu.gateway.gateway_daemon.GatewayDaemon
    thread: threading.Thread
    http: requests.Session

    def url(self, route: str) -> str:
        scheme = "https" if self.daemon.control_tls else "http"
        return f"{scheme}://127.0.0.1:{self.daemon.api.port}/api/v1/{route}"

    def get(self, route: str, **kw) -> dict:
        """GET a cumulative-state route (safe to ask again after a dropped
        keep-alive connection or a starved API thread)."""
        kw.setdefault("timeout", 30)
        for attempt in range(3):
            try:
                resp = self.http.get(self.url(route), **kw)
                resp.raise_for_status()
                return resp.json()
            except (requests.exceptions.ConnectionError, requests.exceptions.ReadTimeout):
                if attempt == 2:
                    raise
                time.sleep(0.2 * (attempt + 1))

    def post(self, route: str, body) -> None:
        self.http.post(self.url(route), json=body, timeout=30).raise_for_status()

    def stop(self) -> None:
        self.daemon.stop()
        self.thread.join(timeout=10)
        self.http.close()


def start_gateway(program: dict, info: Dict[str, dict], gateway_id: str, chunk_dir: str, **kw) -> LocalGateway:
    from skyplane_tpu.gateway.control_auth import control_session, suppress_insecure_warnings
    from skyplane_tpu.gateway.gateway_daemon import GatewayDaemon

    suppress_insecure_warnings()
    daemon = GatewayDaemon(
        region="local:local",
        chunk_dir=chunk_dir,
        gateway_program=program,
        gateway_info=info,
        gateway_id=gateway_id,
        control_port=0,  # ephemeral
        bind_host="127.0.0.1",
        **kw,
    )
    thread = threading.Thread(target=daemon.run, name=f"daemon-{gateway_id}", daemon=True)
    thread.start()
    gw = LocalGateway(daemon=daemon, thread=thread, http=control_session(daemon.api_token))
    for _ in range(100):  # wait for the control API to answer
        try:
            gw.get("status", timeout=1)
            break
        except requests.RequestException:
            time.sleep(0.05)
    return gw


def make_pair(
    tmp: Path, compress: str, dedup: bool, encrypt: bool, use_tls: bool, num_connections: int, cdc_params,
    sink_segment_store_bytes: Optional[int] = None,
):
    """Start (source, sink) wired source --send--> sink. Where
    ``sink_segment_store_bytes`` is given, the sink's segment store holds that
    many bytes in memory and spills the rest; else it keeps the daemon's bound
    (``SKYPLANE_TPU_SEGSTORE_MB``, else 4 GiB)."""
    from skyplane_tpu.gateway.crypto import generate_key

    key = generate_key() if encrypt else None
    sink_program = {
        "plan": [
            {
                "partitions": ["default"],
                "value": [
                    {
                        "op_type": "receive",
                        "handle": "recv",
                        "decrypt": encrypt,
                        "dedup": dedup,
                        "children": [{"op_type": "write_local", "handle": "write", "children": []}],
                    }
                ],
            }
        ]
    }
    sink = start_gateway(sink_program, {}, "gw_dst", str(tmp / "dst_chunks"), e2ee_key=key, use_tls=use_tls, cdc_params=cdc_params)
    if sink_segment_store_bytes is not None:
        # the store is still empty and the source does not exist yet: it sizes
        # its dedup index from the capacity the sink advertises when it asks
        sink.daemon.receiver.segment_store.set_bounds(max_bytes=sink_segment_store_bytes)
    info = {"gw_dst": {"public_ip": "127.0.0.1", "control_port": sink.daemon.api.port}}
    source_program = {
        "plan": [
            {
                "partitions": ["default"],
                "value": [
                    {
                        "op_type": "read_local",
                        "handle": "read",
                        "num_connections": num_connections,
                        "children": [
                            {
                                "op_type": "send",
                                "handle": "send",
                                "target_gateway_id": "gw_dst",
                                "region": "local:local",
                                "num_connections": num_connections,
                                "compress": compress,
                                "encrypt": encrypt,
                                "dedup": dedup,
                                "children": [],
                            }
                        ],
                    }
                ],
            }
        ]
    }
    source = start_gateway(source_program, info, "gw_src", str(tmp / "src_chunks"), e2ee_key=key, use_tls=use_tls, cdc_params=cdc_params)
    return source, sink


#: chunk requests in one ``POST /api/v1/chunk_requests``, as upstream's client
#: sends them (``CopyJob.dispatch``: batches of 100)
POST_BATCH = 100


def post_files(source: LocalGateway, files: Sequence[Tuple[Path, Path, int]]) -> List[List[str]]:
    """Split each local file of (src path, dst path, chunk bytes) into chunk
    requests and POST them to the source gateway, in order, at most
    ``POST_BATCH`` to a request; returns each file's chunk ids."""
    from skyplane_tpu.chunk import Chunk, ChunkRequest

    reqs, ids = [], []
    for src_path, dst_path, chunk_bytes in files:
        size = src_path.stat().st_size
        ids.append([])
        for offset in range(0, max(size, 1), chunk_bytes):
            chunk = Chunk(
                src_key=str(src_path),
                dest_key=str(dst_path),
                chunk_id=uuid.uuid4().hex,
                chunk_length_bytes=min(chunk_bytes, size - offset),
                file_offset_bytes=offset,
            )
            reqs.append(ChunkRequest(chunk=chunk, src_region="local:local", dst_region="local:local", src_type="local", dst_type="local"))
            ids[-1].append(chunk.chunk_id)
    for at in range(0, len(reqs), POST_BATCH):
        source.post("chunk_requests", [r.as_dict() for r in reqs[at : at + POST_BATCH]])
    return ids


class StatusLogLost(RuntimeError):
    """A gateway dropped records of its status log before they were read: a
    completion may be among them, and waiting for it could never end."""


class StatusReader:
    """A gateway's chunk status log, read in this process from where the last
    read stopped: each read costs the records that are new since the one
    before, not the whole log (``GET chunk_status_log?include_log=1`` copies
    and encodes all of it under the API's lock, and lists the ids it asks for
    in its URL). The cursor counts records ever logged, so the records the
    log has dropped from its head (``_status_log_dropped``) are part of it."""

    def __init__(self, gw: LocalGateway):
        self.api = gw.daemon.api
        self.name = gw.daemon.gateway_id
        with self.api._lock:
            self.cursor = self.api._status_log_dropped + len(self.api.chunk_status_log)
        self.waiting: Dict[str, float] = {}  # chunk id -> largest ``complete`` stamp read so far
        self.seen: Set[str] = set()  # waiting ids with a ``complete`` record whose chunk is not yet complete

    def track(self, chunk_ids: Iterable[str]) -> None:
        """Wait for these chunks too; records logged for them since the last
        read are still ahead of the cursor."""
        for cid in chunk_ids:
            self.waiting[cid] = 0.0

    def poll(self) -> Dict[str, float]:
        """chunk id -> the wall-clock time (``time.time()``) at which this
        gateway's LAST operator logged the chunk ``complete``, for the tracked
        chunks its status map now calls complete; each is returned once. The
        time is the largest stamp of the chunk's ``complete`` records, taken
        where the operator finished, not when we asked."""
        api = self.api
        with api._lock:
            at = self.cursor - api._status_log_dropped
            if at < 0:
                raise StatusLogLost(
                    f"gateway {self.name} dropped {-at} records of its status log (bound {api.MAX_STATUS_LOG}) "
                    f"before they were read; {len(self.waiting)} chunks were waited for"
                )
            new = api.chunk_status_log[at:]
            self.cursor += len(new)
            for rec in new:
                cid = rec["chunk_id"]
                if rec["state"] == "complete" and cid in self.waiting:
                    self.waiting[cid] = max(self.waiting[cid], float(rec["time"]))
                    self.seen.add(cid)
            # the map and the records read agree: both are as of this lock
            done = [cid for cid in self.seen if api.chunk_status.get(cid) == "complete"]
        self.seen.difference_update(done)
        return {cid: self.waiting.pop(cid) for cid in done}


def decode_events(gw: LocalGateway) -> List[dict]:
    """The sink's per-chunk decode events since the last drain, taken in this
    process from the queue ``GET profile/decode`` drains: the queue holds
    4,096 and drops the oldest, so a run of more chunks drains it as it goes."""
    q = gw.daemon.receiver.decode_profile_events
    out = []
    while True:
        try:
            out.append(q.get_nowait())
        except queue.Empty:
            return out


def errors(gw: LocalGateway) -> List[str]:
    return gw.get("errors")["errors"]
