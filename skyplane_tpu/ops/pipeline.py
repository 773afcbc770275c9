"""The host side of the data path: ``DataPathProcessor``.

``DataPathProcessor`` is the host orchestration the gateway operators call
per chunk: content-defined chunking and segment fingerprints (the two device
programs of ops/fused_cdc.py through the shared ``DeviceBatchRunner`` on an
accelerator, the host kernels otherwise), dedup recipe assembly, codec
encode/decode, and end-to-end fingerprints. Input sizes are padded to
power-of-two buckets so XLA compiles a handful of shapes, not one per chunk.
"""

from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

from skyplane_tpu.chunk import Codec, WireProtocolHeader
from skyplane_tpu.exceptions import ChecksumMismatchException, CodecException
from skyplane_tpu.obs import get_tracer
from skyplane_tpu.obs.stage import Stage, StageCounters
from skyplane_tpu.ops.bufpool import MIN_BUCKET, BufferPool, bucket_size
from skyplane_tpu.ops.cdc import CDCParams, cdc_segment_ends
from skyplane_tpu.ops.codecs import CodecSpec, get_codec, get_codec_by_id, timed_encoder
from skyplane_tpu.ops.dedup import PooledChunk, SegmentStore, SenderDedupIndex, build_recipe, parse_recipe

# canonical home is ops/bufpool.py (the pool keys on it); kept under the old
# name here because this is where every data-path caller historically looked
_bucket_size = bucket_size


@dataclass
class ProcessedPayload:
    """Sender-side result for one chunk."""

    wire_bytes: bytes
    codec: Codec
    is_compressed: bool
    is_recipe: bool
    raw_len: int
    fingerprint: str  # 32 hex chars, end-to-end identity of the raw bytes
    n_segments: int = 0
    n_ref_segments: int = 0
    literal_bytes: int = 0  # pre-codec literal bytes shipped (dedup mode)
    literal_blob_bytes: int = 0  # the same literals after the codec: the recipe's encoded blob
    new_fingerprints: list = field(default_factory=list)  # commit to index AFTER delivery
    ref_fingerprints: list = field(default_factory=list)  # discard from index on unresolvable-ref nack


class DataPathStats(StageCounters):
    """Cumulative sender-side accounting (feeds /profile/compression).

    observe() is called for EVERY chunk from every worker of an operator pool
    sharing one processor; a single mutex here measurably serializes 16-32
    workers whose actual work (numpy/zstd/XLA) releases the GIL. Counters are
    therefore SHARDED per thread (:class:`StageCounters`): each worker
    increments its own dict (plain GIL-atomic int ops, no lock), and
    ``as_dict()`` merges the shards. The merge may interleave with in-flight
    increments — each counter is individually monotonic and exact once
    traffic quiesces, which is all a monitoring surface needs; the old
    whole-snapshot consistency bought nothing but contention. The stages of
    the processor and of its sender (``recipe.build``, ``wire.seal``) count
    through :meth:`add`.

    External per-subsystem counters (buffer pool, batch runner, donation) are
    merged in via registered source callables, with a zero-filled default set
    so the key schema is stable whether or not those subsystems are active
    (bench-smoke and dashboard queries rely on the keys always existing).
    """

    _KEYS = (
        "chunks",
        "raw_bytes",
        "wire_bytes",
        "segments",
        "ref_segments",
        "literal_bytes",
        "literal_blob_bytes",
        "device_wait_ns",
        "device_path_ns",
        "recipe_ns",
        "recipe_encode_ns",
        "blockpack_ns",
        "zstd_ns",
        "seal_ns",
        "literal_gathers",
        "literal_joins",
    )
    EXTERNAL_ZERO = {
        "pool_hits": 0,
        "pool_misses": 0,
        "pool_hit_rate": 0.0,
        "pool_recycled": 0,
        "pool_dropped": 0,
        "pool_evicted_bytes": 0,
        "pool_idle_bytes": 0,
        "pool_outstanding": 0,
        "batch_windows": 0,
        "batch_rows": 0,
        "batch_padded_rows": 0,
        "batch_occupancy": 0.0,
        "stage_failures": 0,
        "donated_batches": 0,
        "fused_rows": 0,
        "fused_gap_ns": 0,
        "fused_gap_cpu_ns": 0,
        "overflow_rows": 0,
        "xla_compiles": 0,
        "xla_compile_ns": 0,
    }

    def __init__(self):
        super().__init__(self._KEYS)
        self._sources: List[Callable[[], dict]] = []

    def observe(self, p: ProcessedPayload, device_path_ns: int = 0, timings: Optional[dict] = None) -> None:
        """One chunk done. ``device_path_ns``: wall time its worker spent on
        CDC + fingerprints, from submission to finalized digests — the pad
        copy and staging, the window wait, a leader's whole batch, a
        follower's waits, ``finalize_row`` (on a gateway with no accelerator,
        the host kernels). ``timings`` is ``build_recipe``'s:
        ``recipe_encode_ns``, the join and the codec inside ``recipe_ns``
        (``build_recipe`` whole: dedup-index lookups, literal join, codec;
        counted by the ``recipe.build`` stage), and inside that
        ``blockpack_ns`` and ``zstd_ns``, the steps of the
        codec that ran (a step it does not have stays 0; ``blockpack_ns``
        holds the pass that lays the literals down from the chunk, where the
        join sat outside it), and ``literal_gathers`` / ``literal_joins``: 1
        for a chunk that holds a literal, as the native pass laid its literals
        down or Python joined them (``codecs.timed_encoder``). ``literal_bytes``:
        raw bytes of the segments that went as literals, so what dedup left
        (0 with dedup off: no recipe, no literal); ``literal_blob_bytes``:
        what the codec made of them. Added together with ``chunks``, their
        denominator, so a scrape between chunks sees whole chunks."""
        d = self._shard()
        d["chunks"] += 1
        d["raw_bytes"] += p.raw_len
        d["wire_bytes"] += len(p.wire_bytes)
        d["segments"] += p.n_segments
        d["ref_segments"] += p.n_ref_segments
        d["literal_bytes"] += p.literal_bytes
        d["literal_blob_bytes"] += p.literal_blob_bytes
        d["device_path_ns"] += device_path_ns
        for k in ("recipe_encode_ns", "blockpack_ns", "zstd_ns", "literal_gathers", "literal_joins"):
            d[k] += (timings or {}).get(k, 0)

    def observe_device_wait(self, ns: int) -> None:
        """Time this worker spent BLOCKED on the device (phase waits in the
        batch runner) — the stall the overlap scheduling exists to hide.
        Only a follower's waits on its handle: what ``submit`` itself took
        (a leader's whole batch) is in ``device_path_ns``, not here."""
        if ns:
            self._shard()["device_wait_ns"] += int(ns)

    def add_source(self, fn: Callable[[], dict]) -> None:
        """Register an external counter provider merged into as_dict()."""
        with self._lock:
            self._sources.append(fn)

    def as_dict(self) -> dict:
        with self._lock:
            sources = list(self._sources)
        out = self.totals()
        out["compression_ratio"] = out["raw_bytes"] / out["wire_bytes"] if out["wire_bytes"] else 1.0
        merged = dict(self.EXTERNAL_ZERO)
        for fn in sources:
            merged.update(fn())
        out.update(merged)
        return out


def effective_codec_name(codec_name: str) -> str:
    """The codec a gateway should RUN for a configured codec name, decided
    where the hardware is known (the daemon, at operator construction).

    ``tpu_zstd`` on a host with no accelerator maps to plain ``zstd``:
    blockpack's zero/const suppression is the DEVICE path's job, and on CPU
    zstd alone measures the same wire reduction (6.13x on the bench corpus —
    zstd swallows zero pages natively) with the ~0.8 GB/s blockpack pass
    over the literal stream removed (round-5 bench: 1.11x -> 1.32x vs the
    zstd-3 baseline). The codec id travels per chunk in the wire header, so
    mixed TPU/CPU gateways interoperate and the substitution is visible on
    the wire and in /profile/compression. ``tpu`` (blockpack-only) is NOT
    substituted — its cheap suppression is the point on any backend.
    SKYPLANE_TPU_KEEP_TPU_CODEC=1 opts out (tests exercising the container
    format on CPU-pinned hosts).
    """
    import os

    if codec_name != "tpu_zstd" or os.environ.get("SKYPLANE_TPU_KEEP_TPU_CODEC") == "1":
        return codec_name
    from skyplane_tpu.ops.backend import on_accelerator

    if on_accelerator():
        return codec_name
    from skyplane_tpu.utils.logger import logger

    logger.fs.info("no accelerator: gateway runs codec 'zstd' for configured 'tpu_zstd' (wire-header visible)")
    return "zstd"


class _PhasedCDC:
    """Two-phase CDC result: ``ends`` (segment boundaries) are final at
    construction; ``fps()`` blocks until the segment fingerprints land.
    ``wait_ns`` reports the device-blocked time once fps() returned."""

    __slots__ = ("ends", "_fps_fn", "_wait_ns_fn")

    def __init__(self, ends, fps_fn, wait_ns_fn=None):
        self.ends = ends
        self._fps_fn = fps_fn
        self._wait_ns_fn = wait_ns_fn

    def fps(self):
        return self._fps_fn()

    @property
    def wait_ns(self) -> int:
        return self._wait_ns_fn() if self._wait_ns_fn is not None else 0


class DataPathProcessor:
    """Per-connection host orchestrator for the TPU data path.

    Encode path (sender): CDC -> segment fingerprints -> dedup recipe ->
    codec; or plain codec when dedup is off. Decode path (receiver) is the
    exact inverse, driven by wire-header codec/flags — no out-of-band config
    needed (SURVEY §7 wire-compat requirement).
    """

    def __init__(
        self,
        codec_name: str = "tpu_zstd",
        dedup: bool = True,
        cdc_params: CDCParams = CDCParams(),
        verify_checksums: bool = True,
        batch_runner=None,
        paranoid_verify: bool = False,
    ):
        self.codec: CodecSpec = get_codec(codec_name)
        self.dedup = dedup
        self.cdc_params = cdc_params
        self.verify_checksums = verify_checksums
        # shared DeviceBatchRunner: micro-batches CDC+fingerprint device work
        # across the operator's worker pool on accelerators
        self.batch_runner = batch_runner
        # paranoid: receivers re-run CDC over RESTORED recipe chunks and check
        # the end-to-end chunk fingerprint — catches even a poisoned segment
        # store or a fingerprint collision, at the cost of re-hashing
        self.paranoid_verify = paranoid_verify
        self._fused = None  # lazy FusedCDCFP for the unbatched accelerator path
        # padded-bucket buffer reuse: share the runner's pool when batching
        # (the runner recycles after dispatch), else own one for the
        # unbatched device path
        self.bufpool = batch_runner.pool if batch_runner is not None else BufferPool()
        # paranoid-verify accounting (decode side): total recipe chunks
        # re-fingerprinted, and how many went through the shared batch runner
        # (micro-batched device calls) instead of a per-chunk dispatch.
        # Plain GIL increments — monitoring-grade, like the store counters.
        self._verify_total = 0
        self._verify_batched = 0
        self.stats = DataPathStats()
        self._t_recipe = Stage(self.stats.add, "recipe_ns", "recipe.build")
        if batch_runner is not None:
            # the runner's counters() already folds in its pool + fused stats
            self.stats.add_source(batch_runner.counters)
        else:
            self.stats.add_source(self.bufpool.counters)
            self.stats.add_source(lambda: self._fused.counters() if self._fused is not None else {})

    # ---- fingerprints ----

    @staticmethod
    def _on_accelerator() -> bool:
        from skyplane_tpu.ops.backend import on_accelerator

        return on_accelerator()

    def _segment_fps(self, arr: np.ndarray, ends: np.ndarray) -> List[bytes]:
        """8-lane segment fingerprints -> 16-byte digests on HOST kernels
        (native Horner when built, numpy otherwise). Accelerator callers go
        through FusedCDCFP instead (_cdc_and_fps), which computes boundaries
        and fingerprints in batched device dispatches."""
        from skyplane_tpu.ops.fingerprint import segment_fingerprints_host_batch

        return segment_fingerprints_host_batch(arr, ends)

    def _cdc_and_fps_phased(self, arr: np.ndarray) -> "_PhasedCDC":
        """CDC boundaries + segment fingerprints with ONE device dispatch and
        ONE small packed readback on accelerators (ops/fused_cdc.py).

        Two-phase contract: the returned handle's ``.ends`` are final
        immediately; ``.fps()`` may block until the fingerprint readback
        lands. Callers do boundary-dependent work (recipe span assembly)
        between the two so host work overlaps the in-flight device batch.
        Host and unbatched paths degenerate to both-ready-now.
        """
        if self.batch_runner is not None and getattr(self.batch_runner, "remote", False):
            # pump worker with parent-routed batches: the proxy ships the
            # chunk to the parent daemon's (possibly mesh-sharded) runner
            # over the CtrlChannel. Checked BEFORE on_accelerator(): the
            # worker itself pins a CPU backend precisely because the parent
            # owns the device.
            assert self.batch_runner.cdc_params == self.cdc_params, "batch runner CDC params diverge from processor"
            handle = self.batch_runner.submit(arr)
            return _PhasedCDC(handle.ends(), handle.fps, wait_ns_fn=lambda: handle.wait_ns)
        if not self._on_accelerator():
            from skyplane_tpu.ops.cdc import cdc_and_fps_host

            ends, fps = cdc_and_fps_host(arr, self.cdc_params)
            return _PhasedCDC(ends, lambda: fps)
        if self.batch_runner is not None:
            # the runner chunks with ITS params; both paths must agree or the
            # same bytes would fingerprint differently depending on routing
            assert self.batch_runner.cdc_params == self.cdc_params, "batch runner CDC params diverge from processor"
            handle = self.batch_runner.submit(arr)
            return _PhasedCDC(handle.ends(), handle.fps, wait_ns_fn=lambda: handle.wait_ns)
        if self._fused is None:
            from skyplane_tpu.ops.fused_cdc import FusedCDCFP

            self._fused = FusedCDCFP(self.cdc_params, pool=self.bufpool)
        bucket = _bucket_size(len(arr))
        if len(arr) == bucket:
            # exact-bucket chunk: pass the caller's bytes through untouched
            # (read-only np.frombuffer views are fine — the device upload copies)
            ends, fps = self._fused(arr[None, :], [len(arr)])[0]
            return _PhasedCDC(ends, lambda: fps)
        padded = self.bufpool.acquire(bucket)
        try:
            padded[: len(arr)] = arr
            padded[len(arr) :] = 0
            ends, fps = self._fused(padded[None, :], [len(arr)])[0]
        finally:
            self.bufpool.release(padded)
        return _PhasedCDC(ends, lambda: fps)

    def _cdc_and_fps(self, arr: np.ndarray):
        """Blocking single-phase form of :meth:`_cdc_and_fps_phased`."""
        phased = self._cdc_and_fps_phased(arr)
        return phased.ends, phased.fps()

    def _chunk_fingerprint(self, seg_fps: List[bytes], raw_len: int) -> str:
        h = hashlib.blake2b(b"".join(seg_fps) + raw_len.to_bytes(8, "little"), digest_size=16)
        return h.hexdigest()

    # ---- encode ----

    def process(
        self, data: bytes, index: Optional[SenderDedupIndex] = None, trace_id: Optional[str] = None
    ) -> ProcessedPayload:
        """``trace_id`` (the chunk id) keys the sampling of the ``recipe.build``
        span and of ``codec.blockpack`` / ``codec.zstd`` inside it, as it does
        for the framer's ``wire.frame`` around this call."""
        raw_len = len(data)
        device_path_ns = 0
        timings: dict = {}
        if self.dedup and index is not None and raw_len > 0:
            arr = np.frombuffer(data, np.uint8)
            t = time.perf_counter_ns()
            phased = self._cdc_and_fps_phased(arr)
            device_path_ns = time.perf_counter_ns() - t
            # boundary-dependent assembly runs BETWEEN the phases: spans are
            # final once ends land, so they're cut while the fingerprint
            # readback of this worker's batch is still in flight
            ends_l = np.asarray(phased.ends).tolist()
            # memoryview slices: no segment's bytes are copied here (the
            # codec reads the literals from ``data`` by their spans)
            mv = memoryview(data)
            spans = []
            start = 0
            for end in ends_l:
                spans.append(mv[start:end])
                start = end
            t = time.perf_counter_ns()
            seg_fps = phased.fps()
            device_path_ns += time.perf_counter_ns() - t
            self.stats.observe_device_wait(phased.wait_ns)
            segments = list(zip(seg_fps, spans))
            tracer = get_tracer()
            encode = timed_encoder(
                self.codec, timings, lambda name: tracer.span(name, trace_id=trace_id, cat="sender"), pool=self.bufpool
            )
            with self._t_recipe(trace_id):
                wire, n_ref, lit_bytes, new_fps, ref_fps = build_recipe(segments, index, encode, timings, chunk=data)
            payload = ProcessedPayload(
                wire_bytes=wire,
                codec=self.codec.codec_id,
                is_compressed=self.codec.codec_id != Codec.NONE,
                is_recipe=True,
                raw_len=raw_len,
                fingerprint=self._chunk_fingerprint(seg_fps, raw_len),
                n_segments=len(segments),
                n_ref_segments=n_ref,
                literal_bytes=lit_bytes,
                literal_blob_bytes=timings["literal_blob_bytes"],
                new_fingerprints=new_fps,
                ref_fingerprints=ref_fps,
            )
        else:
            wire = timed_encoder(self.codec, {}, pool=self.bufpool)(data, [(0, raw_len)])
            if len(wire) >= raw_len and self.codec.codec_id != Codec.NONE:
                # incompressible chunk: ship raw (receiver dispatches on header codec)
                wire, codec_id = data, Codec.NONE
            else:
                codec_id = self.codec.codec_id
            fp = hashlib.blake2b(data, digest_size=16).hexdigest()
            payload = ProcessedPayload(
                wire_bytes=wire,
                codec=codec_id,
                is_compressed=codec_id != Codec.NONE,
                is_recipe=False,
                raw_len=raw_len,
                fingerprint=fp,
            )
        self.stats.observe(payload, device_path_ns, timings)
        return payload

    # ---- decode ----

    def verify_counters(self) -> dict:
        """Paranoid-verify counters, merged into the receiver's decode schema."""
        return {"verify_total": self._verify_total, "verify_batched": self._verify_batched}

    def restore(
        self,
        payload: bytes,
        header: WireProtocolHeader,
        store: Optional[SegmentStore] = None,
        ref_wait_timeout: float = 60.0,
        pooled: bool = False,
        ref_stats: Optional[dict] = None,
    ):
        """Wire payload -> raw chunk bytes, driven by the wire header.

        With ``pooled`` (the gateway receiver's decode pool), recipe payloads
        assemble into a pooled buffer and a :class:`PooledChunk` is returned —
        the caller writes ``.view`` out and calls ``.release()``. Non-recipe
        payloads (and ``pooled=False``) return plain ``bytes``. ``ref_stats``
        is ``parse_recipe``'s: what its literal pass and its REF pass did.
        """
        codec = get_codec_by_id(header.codec)
        if header.is_recipe:
            if store is None:
                raise CodecException("recipe payload but no SegmentStore configured")
            data = parse_recipe(
                payload,
                store,
                codec.decode,
                ref_wait_timeout=ref_wait_timeout,
                verify_literals=self.verify_checksums,
                out_pool=self.bufpool if pooled else None,
                expected_raw_len=header.raw_data_len,
                ref_stats=ref_stats,
                trace_id=header.chunk_id,
                force=header.is_traced,
                blob_out_len=codec.decode_out_len,
                blob_span=get_tracer().span("decode.blob", trace_id=header.chunk_id, cat="receiver", force=header.is_traced),
            )
        else:
            data = codec.decode(payload)
            if not isinstance(data, bytes):
                data = bytes(data)  # a codec may hand back a view of an array; this path returns ``bytes``
        view = data.view if isinstance(data, PooledChunk) else data
        try:
            if len(view) != header.raw_data_len:
                raise ChecksumMismatchException(
                    f"chunk {header.chunk_id}: raw length {len(view)} != header {header.raw_data_len}"
                )
            if self.verify_checksums and not header.is_recipe and header.fingerprint != "0" * 32:
                got = hashlib.blake2b(view, digest_size=16).hexdigest()
                if got != header.fingerprint:
                    raise ChecksumMismatchException(f"chunk {header.chunk_id}: fingerprint mismatch")
            if self.paranoid_verify and header.is_recipe and header.fingerprint != "0" * 32:
                # full end-to-end recipe verification: re-chunk the restored bytes
                # (deterministic CDC) and rebuild the chunk fingerprint the sender
                # embedded in the header — any wrong REF substitution surfaces here.
                # Concurrent decode workers sharing a batch runner micro-batch
                # these device calls instead of dispatching one blocking call each.
                self._verify_total += 1
                if self.batch_runner is not None and self._on_accelerator():
                    self._verify_batched += 1
                arr = np.frombuffer(view, np.uint8)
                _, seg_fps = self._cdc_and_fps(arr)
                got = self._chunk_fingerprint(seg_fps, len(view))
                if got != header.fingerprint:
                    raise ChecksumMismatchException(
                        f"chunk {header.chunk_id}: paranoid recipe verification failed (restored bytes re-fingerprint differently)"
                    )
        except BaseException:
            if isinstance(data, PooledChunk):
                data.release()  # failed verification must not leak the buffer
            raise
        return data
