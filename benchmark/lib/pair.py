"""A source and a sink ``GatewayDaemon`` in this process, on loopback.

The benchmark's own copy of what it needs from ``tests/integration/harness.py``
(``start_gateway``, ``make_pair``, ``build_chunk_requests``, status polling):
later PRs may change that file and may not change the yardstick. The daemons
are the program; everything here only starts them and talks to their control
API over HTTP, as a client does.
"""

from __future__ import annotations

import threading
import time
import uuid
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional

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


def post_file(source: LocalGateway, src_path: Path, dst_path: Path, chunk_bytes: int) -> List[str]:
    """Split a local file into chunk requests of ``chunk_bytes`` and POST them
    to the source gateway; returns the chunk ids."""
    from skyplane_tpu.chunk import Chunk, ChunkRequest

    size = src_path.stat().st_size
    reqs = []
    for offset in range(0, max(size, 1), chunk_bytes):
        chunk = Chunk(
            src_key=str(src_path),
            dest_key=str(dst_path),
            chunk_id=uuid.uuid4().hex,
            chunk_length_bytes=min(chunk_bytes, size - offset),
            file_offset_bytes=offset,
        )
        reqs.append(ChunkRequest(chunk=chunk, src_region="local:local", dst_region="local:local", src_type="local", dst_type="local"))
    source.post("chunk_requests", [r.as_dict() for r in reqs])
    return [r.chunk.chunk_id for r in reqs]


def completions(gw: LocalGateway, chunk_ids: Iterable[str]) -> Dict[str, float]:
    """chunk id -> the wall-clock time (``time.time()``) at which this
    gateway's LAST operator logged the chunk ``complete``, for those of
    ``chunk_ids`` its status map calls complete. The time is the status log
    record's own stamp, taken where the operator finished, not when we asked."""
    ids = sorted(chunk_ids)
    if not ids:
        return {}
    body = gw.get("chunk_status_log", params={"chunk_ids": ",".join(ids), "include_log": "1"})
    done = {c for c, state in body["chunk_status"].items() if state == "complete"}
    out: Dict[str, float] = {}
    for rec in body["chunk_status_log"]:
        cid = rec["chunk_id"]
        if cid in done and rec["state"] == "complete":
            out[cid] = max(out.get(cid, 0.0), float(rec["time"]))
    return out


def errors(gw: LocalGateway) -> List[str]:
    return gw.get("errors")["errors"]
