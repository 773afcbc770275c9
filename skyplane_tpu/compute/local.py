"""Local "cloud": gateways are daemon subprocesses on this machine.

This is the provider behind ``local:`` region tags — it gives the full
client->planner->provision->gateway->transfer stack with zero cloud
dependencies (the harness the reference lacks, SURVEY §4). Each "VM" is a
``python -m skyplane_tpu.gateway.gateway_daemon`` subprocess bound to
127.0.0.1 with an ephemeral control port.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import uuid
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from skyplane_tpu.compute.cloud_provider import CloudProvider
from skyplane_tpu.compute.server import Server, ServerState
from skyplane_tpu.utils.logger import logger


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class LocalServer(Server):
    def __init__(self, region_tag: str, instance_id: str, workdir: Path):
        super().__init__(region_tag, instance_id)
        self.workdir = workdir
        self.control_port = _free_port()
        self.proc: Optional[subprocess.Popen] = None

    def public_ip(self) -> str:
        return "127.0.0.1"

    def instance_state(self) -> ServerState:
        if self.proc is None:
            return ServerState.PENDING
        return ServerState.RUNNING if self.proc.poll() is None else ServerState.TERMINATED

    def run_command(self, command: str, timeout: int = 120) -> Tuple[str, str]:
        proc = subprocess.run(command, shell=True, capture_output=True, text=True, timeout=timeout)
        self.last_rc = proc.returncode
        return proc.stdout, proc.stderr

    def start_gateway(
        self,
        gateway_program: dict,
        gateway_info: Dict[str, dict],
        gateway_id: str,
        e2ee_key: Optional[bytes] = None,
        use_tls: bool = True,
        use_bbr: bool = True,
        docker_image: Optional[str] = None,  # local daemons run in-place
        tmpfs_gb: int = 8,
        credentials=None,
    ) -> None:
        self._record_control_credentials(gateway_info, use_tls)
        # re-starting with a new program (e.g. throughput probes) replaces the
        # old daemon — two processes cannot share the control port
        if self.proc is not None:
            self.terminate_instance()
        self.workdir.mkdir(parents=True, exist_ok=True)
        program_file = self.workdir / "program.json"
        info_file = self.workdir / "info.json"
        program_file.write_text(json.dumps(gateway_program))
        info_file.write_text(json.dumps(gateway_info))
        args = [
            sys.executable,
            "-m",
            "skyplane_tpu.gateway.gateway_daemon",
            "--region",
            self.region_tag,
            "--chunk-dir",
            str(self.workdir / "chunks"),
            "--program-file",
            str(program_file),
            "--info-file",
            str(info_file),
            "--gateway-id",
            gateway_id,
            "--control-port",
            str(self.control_port),
            "--bind-host",
            "127.0.0.1",
        ]
        if e2ee_key:
            key_file = self.workdir / "e2ee.key"
            key_file.write_bytes(e2ee_key)
            args += ["--e2ee-key-file", str(key_file)]
        if not use_tls:
            args += ["--disable-tls"]
        env = dict(os.environ)
        # object-store credential chain: local daemons inherit the client env
        # anyway, but an explicit payload (tests, mixed-cloud local topologies)
        # is staged exactly like on a remote VM — files 0600 under creds/
        if credentials is not None and not credentials.is_empty():
            creds_dir = self.workdir / "creds"
            creds_dir.mkdir(parents=True, exist_ok=True)
            creds_dir.chmod(0o700)
            for name, content in credentials.files.items():
                path = creds_dir / name
                path.write_bytes(content)
                path.chmod(0o600)
            env.update(credentials.resolved_env(str(creds_dir)))
        env.setdefault("PYTHONPATH", "")
        repo_root = str(Path(__file__).resolve().parents[2])
        env["PYTHONPATH"] = repo_root + (os.pathsep + env["PYTHONPATH"] if env["PYTHONPATH"] else "")
        # local gateways run kernels on CPU: this starts N daemons on one
        # host, and a chip belongs to one process at a time — a second daemon
        # that reached for it would fail or hang at backend start
        env.setdefault("SKYPLANE_LOCAL_GATEWAY_PLATFORM", "cpu")
        env["JAX_PLATFORMS"] = env["SKYPLANE_LOCAL_GATEWAY_PLATFORM"]
        # per-daemon log dir: N local daemons must not interleave one log file
        env["SKYPLANE_TPU_LOG_DIR"] = str(self.workdir / "logs")
        with open(self.workdir / "daemon.log", "w") as log_file:
            # Popen duplicates the fd; closing ours prevents a leak per (re)start
            self.proc = subprocess.Popen(args, stdout=log_file, stderr=subprocess.STDOUT, env=env)
        self.wait_for_gateway_ready()

    def terminate_instance(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        self.proc = None


class LocalCloudProvider(CloudProvider):
    provider_name = "local"

    def __init__(self, workroot: Optional[Path] = None):
        self.workroot = Path(workroot) if workroot else Path(tempfile.mkdtemp(prefix="skyplane_tpu_local_"))
        self.servers: List[LocalServer] = []

    def provision_instance(self, region_tag: str, vm_type: Optional[str] = None, tags: Optional[dict] = None) -> LocalServer:
        instance_id = f"local-{uuid.uuid4().hex[:8]}"
        server = LocalServer(region_tag, instance_id, self.workroot / instance_id)
        self.servers.append(server)
        logger.fs.info(f"[local] provisioned {instance_id} (control port {server.control_port})")
        return server

    def get_matching_instances(self, **kw) -> List[LocalServer]:
        return [s for s in self.servers if s.instance_state() == ServerState.RUNNING]

    def setup_global(self) -> None: ...

    def setup_region(self, region: str) -> None: ...

    def teardown_global(self) -> None:
        for s in self.servers:
            s.terminate_instance()
