"""SCP + IBM provider logic against stubbed transports (completes the
provider stub-test coverage: all five cloud providers now exercise their
request shapes without credentials).
"""

from __future__ import annotations

import base64
import hashlib
import hmac
import sys
import types

import pytest


@pytest.fixture(autouse=True)
def _modules_imported_against_a_fake_sdk_do_not_outlive_it():
    """The tests below import the S3-based backends with a fake boto3 in sys.modules. Left loaded, those modules
    make ``StorageInterface.create("aws:...")`` succeed for whatever test the same worker runs next
    (test_obj_store_interfaces.py expects MissingDependencyException on this boto3-less image)."""
    before = set(sys.modules)
    yield
    for name in set(sys.modules) - before:
        if name.startswith("skyplane_tpu.obj_store."):
            del sys.modules[name]


# ---------- SCP (HMAC-signed REST over requests) ----------


@pytest.fixture()
def scp(monkeypatch, tmp_path):
    monkeypatch.setenv("SCP_ACCESS_KEY", "AK")
    monkeypatch.setenv("SCP_SECRET_KEY", "SK")
    monkeypatch.setenv("SCP_PROJECT_ID", "P1")
    monkeypatch.setenv("SCP_IMAGE_ID", "IMG-1")
    monkeypatch.setenv("SCP_CREDENTIAL_FILE", str(tmp_path / "no_scp_credential"))

    from skyplane_tpu.compute.scp import scp_cloud_provider as mod

    calls = []

    class FakeResponse:
        def __init__(self, body):
            self._body = body
            self.content = b"{}"

        def raise_for_status(self):
            pass

        def json(self):
            return self._body

    # stateful network + server store so the full bootstrap chain
    # (vpc -> igw -> subnet -> sg -> server -> firewall) runs end to end
    state = {
        "poll": 0,
        "vpcs": [],
        "igws": [],
        "subnets": [],
        "sgs": [],
        "sg_rules": [],
        "firewalls": [],
        "fw_rules": [],
        "servers": [
            {
                "virtualServerName": "skyplane-tpu-abc",
                "virtualServerState": "RUNNING",
                "virtualServerId": "vs-9",
                "serviceZoneId": "kr-west-1",
                "natIpAddress": "8.8.8.8",
                "ipAddress": "10.0.0.9",
            },
            {"virtualServerName": "other", "virtualServerState": "RUNNING", "virtualServerId": "vs-x", "serviceZoneId": "kr-west-1"},
        ],
        "server_counter": 0,
        "fail_server": None,  # set to a state string to break provisioning
    }

    def fake_request(method, url, headers=None, json=None, timeout=None):
        calls.append((method, url, headers, json))
        path = url.split("openapi.samsungsdscloud.com", 1)[-1]
        # --- vpc ---
        if method == "POST" and path == "/vpc/v3/vpcs":
            state["vpcs"].append({"vpcId": "VPC-1", "vpcName": json["vpcName"], "vpcState": "ACTIVE", "zone": json["serviceZoneId"]})
            return FakeResponse({"resourceId": "VPC-1"})
        if method == "GET" and path.startswith("/vpc/v3/vpcs"):
            return FakeResponse({"contents": list(state["vpcs"])})
        if method == "DELETE" and path.startswith("/vpc/v3/vpcs/"):
            vid = path.rsplit("/", 1)[1]
            state["vpcs"] = [v for v in state["vpcs"] if v["vpcId"] != vid]
            return FakeResponse({})
        # --- igw ---
        if method == "POST" and path == "/internet-gateway/v2/internet-gateways":
            state["igws"].append({"internetGatewayId": "IGW-1", "vpcId": json["vpcId"], "internetGatewayState": "ATTACHED"})
            state["firewalls"].append({"firewallId": "FW-1", "objectId": "IGW-1"})
            return FakeResponse({"resourceId": "IGW-1"})
        if method == "GET" and path == "/internet-gateway/v2/internet-gateways":
            return FakeResponse({"contents": list(state["igws"])})
        if method == "DELETE" and path.startswith("/internet-gateway/v2/internet-gateways/"):
            gid = path.rsplit("/", 1)[1]
            state["igws"] = [g for g in state["igws"] if g["internetGatewayId"] != gid]
            return FakeResponse({})
        # --- subnet ---
        if method == "POST" and path == "/subnet/v2/subnets":
            state["subnets"].append(
                {"subnetId": "SUB-1", "vpcId": json["vpcId"], "subnetState": "ACTIVE", "subnetType": json["subnetType"]}
            )
            return FakeResponse({"resourceId": "SUB-1"})
        if method == "GET" and path.startswith("/subnet/v2/subnets"):
            return FakeResponse({"contents": list(state["subnets"])})
        if method == "DELETE" and path.startswith("/subnet/v2/subnets/"):
            sid = path.rsplit("/", 1)[1]
            state["subnets"] = [x for x in state["subnets"] if x["subnetId"] != sid]
            return FakeResponse({})
        # --- security group ---
        if method == "POST" and path == "/security-group/v3/security-groups":
            state["sgs"].append(
                {"securityGroupId": "SG-1", "vpcId": json["vpcId"], "securityGroupName": json["securityGroupName"], "securityGroupState": "ACTIVE"}
            )
            return FakeResponse({"resourceId": "SG-1"})
        if method == "GET" and path.startswith("/security-group/v3/security-groups"):
            return FakeResponse({"contents": list(state["sgs"])})
        if method == "DELETE" and path.startswith("/security-group/v3/security-groups/"):
            gid = path.rsplit("/", 1)[1]
            state["sgs"] = [g for g in state["sgs"] if g["securityGroupId"] != gid]
            return FakeResponse({})
        if method == "POST" and "/security-group/v2/security-groups/" in path and path.endswith("/rules"):
            state["sg_rules"].append(json)
            return FakeResponse({"resourceId": f"SGR-{len(state['sg_rules'])}"})
        # --- firewall ---
        if method == "GET" and path == "/firewall/v2/firewalls":
            return FakeResponse({"contents": list(state["firewalls"])})
        if method == "POST" and "/firewall/v2/firewalls/" in path and path.endswith("/rules"):
            state["fw_rules"].append(json)
            return FakeResponse({"resourceId": f"FWR-{len(state['fw_rules'])}"})
        # --- virtual servers ---
        if method == "POST" and path.endswith("/virtual-servers"):
            state["server_counter"] += 1
            sid = f"vs-{state['server_counter']}"
            st = state["fail_server"] or "CREATING"
            state["servers"].append(
                {
                    "virtualServerName": json["virtualServerName"],
                    "virtualServerState": st,
                    "virtualServerId": sid,
                    "serviceZoneId": json["serviceZoneId"],
                    "natIpAddress": "8.8.4.4",
                    "ipAddress": "10.2.0.9",
                }
            )
            return FakeResponse({"resourceId": sid})
        if method == "GET" and "/virtual-servers/" in path:
            sid = path.rsplit("/", 1)[1]
            srv = next((x for x in state["servers"] if x["virtualServerId"] == sid), None)
            if srv is None:
                return FakeResponse({})
            if srv["virtualServerState"] == "CREATING":
                state["poll"] += 1
                if state["poll"] >= 2:
                    srv["virtualServerState"] = "RUNNING"
            return FakeResponse(dict(srv))
        if method == "GET" and path.endswith("/virtual-servers"):
            return FakeResponse({"contents": [dict(x) for x in state["servers"]]})
        if method == "DELETE" and "/virtual-servers/" in path:
            sid = path.rsplit("/", 1)[1]
            state["servers"] = [x for x in state["servers"] if x["virtualServerId"] != sid]
            return FakeResponse({})
        return FakeResponse({})

    monkeypatch.setattr(mod.requests, "request", fake_request)
    monkeypatch.setattr(mod.time, "sleep", lambda s: None)
    mod_state = state
    return mod, calls, mod_state


def test_scp_request_signing(scp):
    mod, calls, _ = scp
    client = mod.SCPClient()
    client.request("GET", "/x")
    method, url, headers, _ = calls[0]
    # signature = HMAC-SHA256(secret, method+url+ts+access_key+project)
    msg = method + url + headers["X-Cmp-Timestamp"] + "AK" + "P1"
    want = base64.b64encode(hmac.new(b"SK", msg.encode(), hashlib.sha256).digest()).decode()
    assert headers["X-Cmp-Signature"] == want
    assert headers["X-Cmp-AccessKey"] == "AK" and headers["X-Cmp-ProjectId"] == "P1"


def test_scp_provision_waits_for_running(scp):
    mod, calls, state = scp
    provider = mod.SCPCloudProvider()
    server = provider.provision_instance("scp:kr-west-1", vm_type="s1v4m8")
    create = next(j for m, u, h, j in calls if m == "POST" and u.endswith("/virtual-servers"))
    assert create["serverType"] == "s1v4m8"
    assert create["serviceZoneId"] == "kr-west-1"
    assert create["imageId"] == "IMG-1"
    assert {"tagKey": "skyplane-tpu", "tagValue": "true"} in create["tags"]
    # the network chain was bootstrapped and wired into the VM body
    assert create["nic"] == {"natEnabled": "true", "subnetId": "SUB-1"}
    assert create["securityGroupIds"] == ["SG-1"]
    assert server.instance_id == "vs-1"
    assert server.public_ip() == "8.8.4.4"
    assert server.private_ip() == "10.2.0.9"
    # per-server firewall rules landed on the IGW's firewall
    assert len(state["fw_rules"]) == 2
    # SG got the TCP in+out rules exactly once
    assert {r["ruleDirection"] for r in state["sg_rules"]} == {"IN", "OUT"}


def test_scp_matching_instances_filters_by_name_prefix(scp):
    mod, calls, _ = scp
    provider = mod.SCPCloudProvider()
    servers = provider.get_matching_instances()
    assert [s.instance_id for s in servers] == ["vs-9"]
    servers[0].terminate_instance()
    assert any(m == "DELETE" and u.endswith("/virtual-servers/vs-9") for m, u, _, _ in calls)


def test_scp_requires_credentials(monkeypatch):
    for var in ("SCP_ACCESS_KEY", "SCP_SECRET_KEY", "SCP_PROJECT_ID"):
        monkeypatch.delenv(var, raising=False)
    from skyplane_tpu.compute.scp import scp_cloud_provider as mod

    with pytest.raises(RuntimeError, match="SCP_ACCESS_KEY"):
        mod.SCPClient()


# ---------- IBM (ibm_vpc SDK, stubbed) ----------


def test_ibm_provider_sdk_and_credential_gating(monkeypatch):
    """Construction is SDK-free (lazy imports); the gates fire on first use:
    missing credentials -> actionable RuntimeError, missing SDK -> ImportError."""
    import skyplane_tpu.compute.ibmcloud.ibm_cloud_provider as mod

    provider = mod.IBMCloudProvider()  # must not import ibm_vpc
    monkeypatch.delenv("IBM_API_KEY", raising=False)
    monkeypatch.setitem(sys.modules, "ibm_cloud_sdk_core", None)
    monkeypatch.setitem(sys.modules, "ibm_cloud_sdk_core.authenticators", None)
    monkeypatch.setitem(sys.modules, "ibm_vpc", None)
    with pytest.raises((RuntimeError, ImportError)):
        provider.vpc_client("us-south")


# ---------- SCP object storage management plane (signed bucket lifecycle) ----------


@pytest.fixture()
def scp_obs(monkeypatch):
    """SCPInterface against a scripted signed-REST transport + fake boto3."""
    monkeypatch.setenv("SCP_ACCESS_KEY", "AK")
    monkeypatch.setenv("SCP_SECRET_KEY", "SK")
    monkeypatch.setenv("SCP_PROJECT_ID", "P1")
    monkeypatch.setenv("SCP_OBS_ENDPOINT", "https://obs.example")

    # the S3 data-plane base imports boto3/botocore at module scope
    boto3_mod = types.ModuleType("boto3")
    boto3_mod.client = lambda *a, **k: None
    botocore_mod = types.ModuleType("botocore")
    botocore_exc = types.ModuleType("botocore.exceptions")
    botocore_exc.ClientError = type("ClientError", (Exception,), {})
    botocore_mod.exceptions = botocore_exc
    monkeypatch.setitem(sys.modules, "boto3", boto3_mod)
    monkeypatch.setitem(sys.modules, "botocore", botocore_mod)
    monkeypatch.setitem(sys.modules, "botocore.exceptions", botocore_exc)

    from skyplane_tpu.obj_store.scp_interface import SCPInterface

    calls = []
    state = {"buckets": [], "bucket_counter": 0}

    class FakeResponse:
        def __init__(self, body):
            self._body = body
            self.content = b"{}"

        def raise_for_status(self):
            pass

        def json(self):
            return self._body

    def fake_request(method, url, headers=None, json=None, timeout=None):
        calls.append((method, url, headers, json))
        if method == "GET" and "/object-storage/v4/buckets?objectStorageBucketName=" in url:
            name = url.rsplit("=", 1)[1]
            return FakeResponse(
                {"contents": [b for b in state["buckets"] if b["objectStorageBucketName"] == name]}
            )
        if method == "GET" and "/project/v3/projects/P1" in url:
            return FakeResponse(
                {"serviceZones": [{"serviceZoneName": "kr-west-1", "serviceZoneId": "ZONE-1"}]}
            )
        if method == "GET" and "/object-storage/v4/object-storages?serviceZoneId=ZONE-1" in url:
            return FakeResponse({"contents": [{"objectStorageId": "OBS-1"}]})
        if method == "POST" and url.endswith("/object-storage/v4/buckets"):
            state["bucket_counter"] += 1
            state["buckets"].append(
                {
                    "objectStorageBucketName": json["objectStorageBucketName"],
                    "objectStorageBucketId": f"BUCKET-{state['bucket_counter']}",
                }
            )
            return FakeResponse({})
        if method == "DELETE" and "/object-storage/v4/buckets/" in url:
            bucket_id = url.rsplit("/", 1)[1]
            state["buckets"] = [b for b in state["buckets"] if b["objectStorageBucketId"] != bucket_id]
            return FakeResponse({})
        raise AssertionError(f"unexpected request {method} {url}")

    import skyplane_tpu.compute.scp.scp_cloud_provider as scp_mod

    monkeypatch.setattr(scp_mod.requests, "request", fake_request)
    return SCPInterface("mybucket"), calls, state


def test_scp_obs_create_bucket_signed_flow(scp_obs):
    iface, calls, state = scp_obs
    iface.create_bucket("scp:kr-west-1")
    assert state["buckets"] and state["buckets"][0]["objectStorageBucketName"] == "mybucket"
    # resolution chain: bucket lookup -> zone -> object-storage id -> create
    urls = [u for _, u, _, _ in calls]
    assert any("/project/v3/projects/P1" in u for u in urls)
    assert any("serviceZoneId=ZONE-1" in u for u in urls)
    post = next((m, u, h, j) for m, u, h, j in calls if m == "POST")
    assert post[3]["objectStorageId"] == "OBS-1" and post[3]["serviceZoneId"] == "ZONE-1"
    # every management call carries the X-Cmp HMAC signature headers
    for _, _, headers, _ in calls:
        assert headers["X-Cmp-AccessKey"] == "AK" and headers["X-Cmp-Signature"]
    # idempotent: a second create sees the bucket and issues no second POST
    n_posts = sum(1 for m, *_ in calls if m == "POST")
    iface.create_bucket("scp:kr-west-1")
    assert sum(1 for m, *_ in calls if m == "POST") == n_posts


def test_scp_obs_bucket_exists_and_delete_by_id(scp_obs):
    iface, calls, state = scp_obs
    assert iface.bucket_exists() is False
    iface.create_bucket("scp:kr-west-1")
    assert iface.bucket_exists() is True
    iface.delete_bucket()
    assert state["buckets"] == []
    assert any(m == "DELETE" and u.endswith("/BUCKET-1") for m, u, _, _ in calls)
    # deleting an absent bucket is a no-op, not an error
    iface.delete_bucket()


def test_scp_obs_requires_management_creds(monkeypatch):
    monkeypatch.setenv("SCP_OBS_ENDPOINT", "https://obs.example")
    monkeypatch.delenv("SCP_PROJECT_ID", raising=False)
    monkeypatch.setenv("SCP_ACCESS_KEY", "AK")
    monkeypatch.setenv("SCP_SECRET_KEY", "SK")
    boto3_mod = types.ModuleType("boto3")
    botocore_mod = types.ModuleType("botocore")
    botocore_exc = types.ModuleType("botocore.exceptions")
    botocore_exc.ClientError = type("ClientError", (Exception,), {})
    botocore_mod.exceptions = botocore_exc
    monkeypatch.setitem(sys.modules, "boto3", boto3_mod)
    monkeypatch.setitem(sys.modules, "botocore", botocore_mod)
    monkeypatch.setitem(sys.modules, "botocore.exceptions", botocore_exc)
    from skyplane_tpu.exceptions import BadConfigException
    from skyplane_tpu.obj_store.scp_interface import SCPInterface

    iface = SCPInterface("b")
    with pytest.raises(BadConfigException, match="management credentials"):
        iface.create_bucket("scp:kr-west-1")


def test_scp_make_vpc_idempotent(scp):
    mod, calls, state = scp
    provider = mod.SCPCloudProvider()
    net1 = provider.network.make_vpc("kr-west-1")
    assert net1 == {"vpc_id": "VPC-1", "subnet_id": "SUB-1", "sg_id": "SG-1", "igw_id": "IGW-1"}
    n_posts = sum(1 for m, u, _, _ in calls if m == "POST")
    # second call finds the valid VPC and creates nothing new
    net2 = provider.network.make_vpc("kr-west-1")
    assert net2 == net1
    assert sum(1 for m, u, _, _ in calls if m == "POST") == n_posts


def test_scp_partial_provision_cleanup(scp):
    mod, calls, state = scp
    provider = mod.SCPCloudProvider()
    state["fail_server"] = "ERROR"
    n_before = len(state["servers"])
    with pytest.raises(RuntimeError, match="ERROR"):
        provider.provision_instance("scp:kr-west-1")
    # the half-created server was deleted again
    assert len(state["servers"]) == n_before
    assert any(m == "DELETE" and "/virtual-servers/" in u for m, u, _, _ in calls)


def test_scp_teardown_region_sweeps_network(scp):
    mod, calls, state = scp
    provider = mod.SCPCloudProvider()
    provider.provision_instance("scp:kr-west-1")
    counts = provider.teardown_region("kr-west-1")
    # tagged servers (pre-seeded vs-9 + the provisioned one) and the chain
    assert counts["servers"] == 2
    assert counts == {"servers": 2, "security_groups": 1, "subnets": 1, "igws": 1, "vpcs": 1}
    assert state["vpcs"] == [] and state["subnets"] == [] and state["igws"] == [] and state["sgs"] == []
    # untagged server survives
    assert [s["virtualServerId"] for s in state["servers"]] == ["vs-x"]
    names = [(m, u.split("openapi.samsungsdscloud.com", 1)[-1].split("/")[1]) for m, u, _, _ in calls if m == "DELETE"]
    # dependency order: servers first, vpc last
    kinds = [k for _, k in names]
    assert kinds.index("virtual-server") < kinds.index("vpc")
    assert kinds.index("subnet") < kinds.index("vpc") and kinds.index("internet-gateway") < kinds.index("vpc")


def test_scp_http_trace_is_0600_and_rotates(monkeypatch, tmp_path):
    """SKYPLANE_TPU_HTTP_TRACE writes API request/response BODIES: the file
    must be 0600 like every other file under the config root, and must
    rotate at the size cap instead of appending unbounded (ADVICE r5)."""
    import os
    import stat

    from skyplane_tpu.compute.scp import scp_cloud_provider as mod

    monkeypatch.setenv("SKYPLANE_TPU_HTTP_TRACE", "1")
    monkeypatch.setattr("skyplane_tpu.config_paths.config_root", tmp_path)

    class FakeResp:
        status_code = 200
        content = b"{}"

        def json(self):
            return {}

    trace = tmp_path / "scp_trace.jsonl"
    mod.SCPClient._trace("GET", "/x", None, FakeResp())
    assert trace.exists()
    assert stat.S_IMODE(os.stat(trace).st_mode) == 0o600
    # a pre-existing loose-permission trace is tightened on the next append
    os.chmod(trace, 0o644)
    mod.SCPClient._trace("GET", "/y", None, FakeResp())
    assert stat.S_IMODE(os.stat(trace).st_mode) == 0o600
    assert len(trace.read_text().splitlines()) == 2
    # over the cap: current file rotates to .1 and a fresh one starts
    monkeypatch.setattr(mod.SCPClient, "TRACE_MAX_BYTES", 64)
    mod.SCPClient._trace("GET", "/z", None, FakeResp())
    rotated = tmp_path / "scp_trace.jsonl.1"
    assert rotated.exists() and len(rotated.read_text().splitlines()) == 2
    assert len(trace.read_text().splitlines()) == 1  # only the post-rotate record
    assert stat.S_IMODE(os.stat(trace).st_mode) == 0o600


def test_scp_object_data_retry_and_uploadid_strip(monkeypatch):
    """SCP OBS endpoint quirks (reference scp_interface.py:324-369, :413,
    :419-433): download retries broadly, upload retries client errors
    (incl. checksum mismatch) but not local file errors; upload ids arrive
    whitespace-padded."""
    monkeypatch.setenv("SCP_OBS_ENDPOINT", "https://obs.example")
    monkeypatch.setenv("SCP_ACCESS_KEY", "AK")
    monkeypatch.setenv("SCP_SECRET_KEY", "SK")
    monkeypatch.setenv("SCP_PROJECT_ID", "P1")
    # self-contained fake boto3/botocore (same pattern as the bucket tests):
    # the S3 data-plane base imports them at module scope, and this test must
    # pass in isolation on the boto3-less env
    boto3_mod = types.ModuleType("boto3")
    boto3_mod.client = lambda *a, **k: None
    botocore_mod = types.ModuleType("botocore")
    botocore_exc = types.ModuleType("botocore.exceptions")
    botocore_exc.ClientError = type("ClientError", (Exception,), {})
    botocore_exc.BotoCoreError = type("BotoCoreError", (Exception,), {})
    botocore_mod.exceptions = botocore_exc
    monkeypatch.setitem(sys.modules, "boto3", boto3_mod)
    monkeypatch.setitem(sys.modules, "botocore", botocore_mod)
    monkeypatch.setitem(sys.modules, "botocore.exceptions", botocore_exc)

    from skyplane_tpu.exceptions import ChecksumMismatchException
    from skyplane_tpu.obj_store.s3_interface import S3Interface
    from skyplane_tpu.obj_store.scp_interface import SCPInterface

    iface = SCPInterface("bkt")
    iface.DATA_RETRY_SLEEP_S = 0.0  # keep the test instant

    attempts = {"n": 0}

    def flaky_download(*a, **k):
        attempts["n"] += 1
        if attempts["n"] < 3:
            raise ConnectionResetError("connection reset by OBS")
        return "mime"

    monkeypatch.setattr(S3Interface, "download_object", flaky_download)
    assert iface.download_object("k", "/tmp/x") == "mime"
    assert attempts["n"] == 3  # two transient failures absorbed

    # download: plain OSError is a LOCAL file error (ENOSPC writing the
    # chunk), not endpoint flakiness — it must propagate on the first
    # attempt, matching the upload path's contract (ADVICE r5)
    def disk_full(*a, **k):
        attempts["n"] += 1
        raise OSError(28, "No space left on device")

    attempts["n"] = 0
    monkeypatch.setattr(S3Interface, "download_object", disk_full)
    with pytest.raises(OSError) as exc_info:
        iface.download_object("k", "/tmp/x")
    assert exc_info.value.errno == 28
    assert attempts["n"] == 1  # no 10x1s retry delaying the real traceback

    # upload: a transiently corrupted part (checksum mismatch) heals on retry
    def corrupt_then_ok(*a, **k):
        attempts["n"] += 1
        if attempts["n"] < 2:
            raise ChecksumMismatchException("scp://bkt/obj")

    attempts["n"] = 0
    monkeypatch.setattr(S3Interface, "upload_object", corrupt_then_ok)
    iface.upload_object("/tmp/src", "obj")
    assert attempts["n"] == 2

    # upload: local file errors are NOT endpoint flakiness — no retry
    def missing_file(*a, **k):
        attempts["n"] += 1
        raise FileNotFoundError("/tmp/deleted-chunk")

    attempts["n"] = 0
    monkeypatch.setattr(S3Interface, "upload_object", missing_file)
    with pytest.raises(FileNotFoundError):
        iface.upload_object("/tmp/deleted-chunk", "obj")
    assert attempts["n"] == 1

    # whitespace-padded upload id is stripped at creation
    monkeypatch.setattr(S3Interface, "initiate_multipart_upload", lambda self, k, m=None: "  upl-123 \n")
    assert iface.initiate_multipart_upload("obj") == "upl-123"
