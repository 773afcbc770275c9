"""Hardware-aware codec routing (ops/pipeline.effective_codec_name).

Gateways without an accelerator substitute plain zstd for a configured
``tpu_zstd`` at operator construction — wire-legal (codec id travels per
chunk) and measured equal-reduction-but-faster on CPU (docs/benchmark.md
round 5). These tests pin the decision table and the env opt-out.
"""

from __future__ import annotations

import pytest

from skyplane_tpu.ops import backend
from skyplane_tpu.ops.pipeline import effective_codec_name


@pytest.fixture()
def cpu_backend(monkeypatch):
    monkeypatch.delenv("SKYPLANE_TPU_KEEP_TPU_CODEC", raising=False)
    monkeypatch.setattr(backend, "_is_accelerator", False)


@pytest.fixture()
def accel_backend(monkeypatch):
    monkeypatch.delenv("SKYPLANE_TPU_KEEP_TPU_CODEC", raising=False)
    monkeypatch.setattr(backend, "_is_accelerator", True)


def test_tpu_zstd_routes_to_zstd_on_cpu(cpu_backend):
    assert effective_codec_name("tpu_zstd") == "zstd"


def test_tpu_zstd_kept_on_accelerator(accel_backend):
    assert effective_codec_name("tpu_zstd") == "tpu_zstd"


def test_other_codecs_never_substituted(cpu_backend):
    # 'tpu' (blockpack-only) stays: its cheap suppression is the point on
    # any backend; everything else passes through untouched
    for name in ("tpu", "zstd", "none", "native_lz", "lz4"):
        assert effective_codec_name(name) == name


def test_env_opt_out_preserves_container_coverage(cpu_backend, monkeypatch):
    monkeypatch.setenv("SKYPLANE_TPU_KEEP_TPU_CODEC", "1")
    assert effective_codec_name("tpu_zstd") == "tpu_zstd"


def test_processor_stays_codec_faithful(cpu_backend):
    # the processor itself must NOT substitute (dryrun host/device wire
    # parity depends on it) — routing happens one layer up, in the daemon
    from skyplane_tpu.ops.pipeline import DataPathProcessor

    proc = DataPathProcessor(codec_name="tpu_zstd", dedup=False)
    assert proc.codec.name == "tpu_zstd"


def test_sender_operator_routes_at_construction(cpu_backend, tmp_path):
    # the ACTUAL substitution site: GatewaySenderOperator's processor must
    # come up on zstd when the host has no accelerator — pins the
    # effective_codec_name() wrapper at the operator call site
    import queue
    import threading

    from skyplane_tpu.gateway.chunk_store import ChunkStore
    from skyplane_tpu.gateway.gateway_queue import GatewayQueue
    from skyplane_tpu.gateway.operators.gateway_operator import GatewaySenderOperator

    op = GatewaySenderOperator(
        handle="send",
        region="local:test",
        input_queue=GatewayQueue(),
        output_queue=None,
        error_event=threading.Event(),
        error_queue=queue.Queue(),
        chunk_store=ChunkStore(str(tmp_path / "chunks")),
        target_gateway_id="gw_dst",
        target_host="127.0.0.1",
        target_control_port=1,
        codec_name="tpu_zstd",
        dedup=False,
        use_tls=False,
    )
    assert op.processor.codec.name == "zstd"


def test_a_pair_that_keeps_tpu_zstd_gathers_the_literals_of_every_chunk_that_holds_one(monkeypatch, tmp_path):
    """Under the opt-out a CPU pair runs the container codec: every chunk that
    holds a literal hands its literals to blockpack as spans of the chunk
    (``literal_gathers``), none is joined in Python (``literal_joins``), and a
    chunk sent again, every segment a REF, counts in neither."""
    pytest.importorskip("zstandard")
    import numpy as np

    from skyplane_tpu.chunk import Codec
    from skyplane_tpu.native import datapath as native_dp
    from tests.integration.harness import dispatch_file, make_pair, wait_complete

    if not native_dp.available():
        pytest.skip("the native library does not build here: the numpy fallback lays the literals down")
    monkeypatch.setenv("SKYPLANE_TPU_KEEP_TPU_CODEC", "1")
    chunk = 256 << 10
    rng = np.random.default_rng(43)
    text = ((rng.integers(0, 256, (64, 8), dtype=np.uint8) & 0x3F) | 0x20)[rng.integers(0, 64, chunk // 8)].ravel()
    first = np.concatenate([rng.integers(0, 256, chunk, dtype=np.uint8), np.zeros(chunk, np.uint8), text]).tobytes()
    (tmp_path / "src").mkdir()
    (tmp_path / "out").mkdir()
    (tmp_path / "src" / "first.bin").write_bytes(first)
    (tmp_path / "src" / "again.bin").write_bytes(first[:chunk])  # the first chunk's bytes once more
    src, dst = make_pair(tmp_path, compress="tpu_zstd", dedup=True, encrypt=False, use_tls=False)
    try:
        ids = dispatch_file(src, tmp_path / "src" / "first.bin", tmp_path / "out" / "first.bin", chunk_bytes=chunk)
        wait_complete(dst, ids)
        wait_complete(src, ids)  # acked: its fingerprints are in the sender's index
        again = dispatch_file(src, tmp_path / "src" / "again.bin", tmp_path / "out" / "again.bin", chunk_bytes=chunk)
        wait_complete(dst, again)
        counters = src.get("profile/compression", timeout=10).json()
        frames = dst.get("profile/decode", timeout=10).json()["events"]
        assert src.daemon.operators and all(
            op.processor.codec.name == "tpu_zstd" for op in src.daemon.operators if getattr(op, "processor", None) is not None
        )
    finally:
        src.stop()
        dst.stop()
    assert (tmp_path / "out" / "first.bin").read_bytes() == first
    assert (tmp_path / "out" / "again.bin").read_bytes() == first[:chunk]
    assert counters["chunks"] == 4
    assert counters["literal_gathers"] == 3 and counters["literal_joins"] == 0
    assert counters["blockpack_ns"] > 0 and counters["pool_outstanding"] == 0
    assert {ev["codec"] for ev in frames} == {int(Codec.TPU_BLOCK_ZSTD)}
