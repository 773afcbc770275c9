#!/usr/bin/env python
"""Validate a bench output file: exactly one well-formed JSON result line
with the full perf-counter schema (docs/datapath-performance.md).

Three result shapes are recognized, dispatched on the ``metric`` field:

  * bench.py results (the default encode/decode/wire schema);
  * scripts/soak_multijob.py results (``metric: multijob_gbps``): the
    multi-tenant soak — per-tenant Gbps split, the fairness ratio gate
    (max/min <= fairness_bound for equal weights), bounded index RSS, and
    per-tenant accounting keys (docs/multitenancy.md);
  * scripts/soak_chaos.py results (``metric: chaos_gbps``): the chaos soak —
    faults actually injected across >=5 armed points, byte-for-byte corpus
    integrity, seed-replay determinism, zero leaked scheduler tokens / pool
    buffers, bounded fd growth, and bounded recovery time
    (docs/fault-injection.md);
  * scripts/monitor_smoke.py results (``metric: fleet_telemetry``): the fleet
    telemetry smoke — a 2-hop relay transfer collector-merged into one
    timeline, the flight-recorder fleet log complete and ordered, bottleneck
    attribution reconciling within 10%, and collector overhead < 2% per poll
    cycle (docs/observability.md);
  * scripts/soak_service.py results (``metric: service_jobs``): the
    always-on service soak — one standing fleet, >=50 sequential + >=8
    concurrent warm jobs (p50 start < 1 s, warm dedup > cold), continuous
    sync delta rounds, and a SIGKILLed controller recovered from the WAL
    with byte-identical output, zero acked-chunk loss, zero duplicate sink
    registrations, and idempotent resubmission (docs/service-mode.md);
  * scripts/soak_blast.py results (``metric: blast_soak``): the checkpoint-
    blast fan-out soak — 1 source -> >=8 peered sinks over a planner-placed
    relay tree, one relay hard-killed mid-blast and healed (replacement +
    retarget + re-drive), every sink byte-identical, source egress
    counter-measured at <= 1.5x the corpus, zero acked-chunk loss, zero
    duplicate sink registrations (docs/blast.md);
  * scripts/soak_dedup_fabric.py results (``metric: fabric_soak``): the
    dedup-fabric soak — two gateway pairs sync overlapping corpora through
    one consistent-hash ring; the warm re-send probe must hit >=90%
    cross-gateway REFs with >=1 peer fetch served, a cross-shard NACK rate
    under the PR-13 literal-resend tolerance, byte-identical outputs, and
    bounded fd growth (docs/dedup-fabric.md).

Exit 0 iff the result parses and every required key is present; used by the
bench-smoke, multijob-smoke, and chaos-smoke steps in scripts/devloop.sh so a
schema, fairness, or recovery regression is caught in seconds on CPU.
"""

from __future__ import annotations

import json
import sys

REQUIRED_TOP = (
    "metric",
    "value",
    "unit",
    "vs_baseline",
    "platform",
    "device_kind",
    "datapath_counters",
    "decode_gbps",
    "decode_counters",
    "wire_counters",
    "stage_latency_us",
    "trace_overhead_pct",
    "cpu_breakdown",
    "wire_gbps_by_procs",
    "pump_cores_available",
    "pump_cores_effective",
    # checkpoint-blast fan-out (docs/blast.md): counter-measured source
    # egress over corpus size on a small loopback blast, banked per round
    "blast_egress_ratio",
    "blast_sinks",
    # raw-forward fast path (docs/datapath-performance.md): kernel-spliced
    # re-serve vs codec re-framing on the interior-edge workload
    "relay_gbps_raw",
    "relay_gbps_codec",
    "wire_raw_frames",
    "wire_raw_fallbacks",
    "raw_chunks",
    "raw_fanout",
    "raw_cores_available",
    # device-count context: every bench/MULTICHIP artifact row carries the
    # attached device count and (data x seq) mesh label since PR 18
    "n_devices",
    "mesh",
    # SPMD device scaling (parallel/datapath_spmd.py, docs/datapath-
    # performance.md "SPMD device data path"): mesh-sharded batch-runner Gbps
    # by forced-host device count, byte-identity verified in every child
    "spmd_gbps_by_devices",
    "spmd_mesh",
    "spmd_devices_available",
    "spmd_identical",
)
#: bench/soak acceptance bound: source egress may exceed 1x the corpus only
#: by healing re-sends and in-flight re-frames (docs/blast.md)
MAX_BLAST_EGRESS_RATIO = 1.5
#: raw-forward acceptance ratio: kernel-spliced re-serve vs codec re-framing
#: over the identical interior-edge workload, at equal cores. Demonstrable
#: only when the consuming receiver can move off the sender's core, so the
#: ratio gate arms at >= MIN_RAW_CORES cores; single-vCPU runners downgrade
#: to schema + raw-beats-codec sanity (docs/datapath-performance.md).
MIN_RAW_RELAY_RATIO = 3.0
MIN_RAW_CORES = 2
#: the acceptance floor for the blast soak's fan-out scale
MIN_BLAST_SINKS = 8
# trace-derived per-stage latency breakdown (bench.py TRACE_STAGES /
# docs/observability.md): a future perf PR proves WHERE it moved time
REQUIRED_STAGES = ("frame", "send_stall", "ack_lag", "decode", "store")
# acceptance bound: with tracing DISABLED the instrumentation may tax the
# loopback wire bench by at most this much (ISSUE 5 acceptance criteria)
MAX_TRACE_OVERHEAD_PCT = 2.0
# core-time attribution (bench.py bench_cpu_profile / obs/profiler.py,
# docs/observability.md "Core-time profiling"): per-stage CPU seconds over
# the loopback wire stack + the GIL-probe wait fraction + cores effectively
# used — the single-core-ceiling baseline ROADMAP item 1 is judged against
REQUIRED_CPU_BREAKDOWN = (
    "stage_cpu_s",
    "gil_wait_fraction",
    "cores_effective",
    "profile_hz",
    "profile_samples",
    "profile_samples_dropped",
    "profile_overhead_pct",
)
REQUIRED_CPU_STAGES = (
    "frame",
    "send_stall",
    "ack_lag",
    "decode",
    "store",
    "device_wait",
    "codec",
    "crypto",
    "framing",
    "other",
)
# acceptance bound (ISSUE 12): the sampler's measured steady-state cost at
# the configured rate may consume at most this share of ONE core
MAX_PROFILE_OVERHEAD_PCT = 2.0
# multi-process pump scaling (gateway/pump.py, docs/benchmark.md "Gbps vs
# pump processes"): the proc counts bench.py sweeps, the measurement-noise
# tolerance on the monotonicity requirement, the throughput floor at 4 procs
# on runners with >= 4 cores, and the cores-effective floor that proves the
# single-core ceiling actually broke (ISSUE 13 acceptance)
PUMP_PROC_KEYS = ("1", "2", "4")
PUMP_MONOTONIC_TOLERANCE = 0.85
MIN_PUMP_GBPS_AT_4 = 2.0
MIN_PUMP_CORES_EFFECTIVE = 1.5
# SPMD device scaling (parallel/datapath_spmd.py, docs/datapath-performance.md
# "SPMD device data path"): the mesh-sharded batch runner swept at 1/2/4/8
# forced-host devices must scale — monotonic within measurement tolerance,
# and >= 1.6x at 4 devices vs 1 on runners with the cores to force them.
# Small runners (spmd_devices_available < 2) downgrade gracefully to the
# schema + byte-identity checks, same pattern as the pump core gates: a
# 1-core container cannot demonstrate device scaling.
SPMD_MONOTONIC_TOLERANCE = 0.85
MIN_SPMD_SPEEDUP_AT_4 = 1.6
# MULTICHIP dryrun artifact row (__graft_entry__.dryrun_multichip)
REQUIRED_MULTICHIP = (
    "metric",
    "n_devices",
    "mesh",
    "prod_chunk_mb",
    "prod_batch",
    "ref_segments",
    "bit_identical",
)
REQUIRED_COUNTERS = (
    "pool_hit_rate",
    "pool_hits",
    "pool_misses",
    "batch_windows",
    "batch_occupancy",
    "batch_padded_rows",
    "device_wait_ns",
    "donated_batches",
    "stage_failures",
)
# receiver decode-path section (mirrors bench.py DECODE_COUNTER_KEYS)
REQUIRED_DECODE_COUNTERS = (
    "store_mem_hits",
    "store_spill_reads",
    "store_lock_held_disk_reads",
    "store_stripe_contention",
    "store_ref_wait_ns",
    "pool_hit_rate",
    "verify_total",
    "verify_batched",
    # parse_recipe's literal pass (PR 30): segments per call is how often the batch engages
    "literal_pass_ns",
    "literal_segments_verified",
    "literal_verify_calls",
)
# sender wire-engine section (mirrors bench.py WIRE_COUNTER_KEYS /
# operators/sender_wire.py SENDER_WIRE_COUNTER_ZERO)
REQUIRED_WIRE_COUNTERS = (
    "frames_pipelined",
    "wire_stall_ns",
    "ack_lag_ns",
    "wire_inflight_bytes",
    "streams_open",
    "windows",
    "wire_stall_ns_per_window",
    "serial_drain_ns_per_window",
)


# multi-tenant soak result (scripts/soak_multijob.py)
REQUIRED_MULTIJOB = (
    "metric",
    "value",
    "unit",
    "n_jobs",
    "tenant_gbps",
    "gbps_max_min_ratio",
    "fairness_bound",
    "index_rss_bytes",
    "process_open_fds_start",
    "process_open_fds_end",
    "tenant_counters",
)
# every tenant's accounting entry must carry these keys
REQUIRED_TENANT_KEYS = ("chunks_registered", "bytes_registered", "bytes_delivered")

# dedup-fabric soak result (scripts/soak_dedup_fabric.py / docs/dedup-fabric.md)
REQUIRED_FABRIC = (
    "metric",
    "value",
    "unit",
    "fabric_members",
    "fabric_gossip_fps",
    "fabric_overlap_segments",
    "fabric_overlap_refs",
    "fabric_overlap_ref_rate",
    "fabric_warm_segments",
    "fabric_warm_refs",
    "fabric_warm_hit_rate",
    "fabric_warm_hit_floor",
    "fabric_source_literals_warm",
    "fabric_peer_fetch_hits",
    "fabric_peer_fetch_timeouts",
    "fabric_pushes_sent",
    "fabric_lands",
    "fabric_land_rejects",
    "fabric_cross_shard_nacks",
    "fabric_cross_shard_nack_rate",
    "fabric_nack_rate_bound",
    "fabric_byte_identical",
    "fabric_warm_seconds",
    "process_open_fds_start",
    "process_open_fds_end",
)

# chaos soak result (scripts/soak_chaos.py / docs/fault-injection.md)
REQUIRED_CHAOS = (
    "metric",
    "value",
    "unit",
    "n_jobs",
    "chaos_seed",
    "chaos_plan",
    "chaos_points_armed",
    "chaos_points_fired",
    "chaos_faults_injected",
    "chaos_faults_total",
    "chaos_integrity_ok",
    "chaos_determinism_ok",
    "chaos_metrics_exported",
    "chaos_slowdown_x",
    "chaos_slowdown_bound",
    "chaos_bound_seconds",
    "chaos_sched_tokens_leaked",
    "chaos_pool_buffers_leaked",
    "chaos_fd_growth",
    "chaos_torn_records_dropped",
    "baseline_seconds",
    "chaos_seconds",
    # runtime lock-order witness (SKYPLANE_TPU_LOCKCHECK=1, obs/lockwitness.py):
    # observed acquisition-order graph must stay acyclic, overhead gated <5%
    "lockcheck_enabled",
    "lockcheck_acyclic",
    "lockcheck_locks",
    "lockcheck_edges",
    "lockcheck_acquisitions",
    "lockcheck_overhead_pct",
    # gateway-death scenario (requeue-to-survivor, docs/provisioning.md)
    "gateway_death_ok",
    "gateway_death_detected",
    "gateway_death_requeued_chunks",
    "gateway_death_detect_seconds",
    "gateway_death_sched_tokens_leaked",
    # capacity-repair scenarios (docs/provisioning.md "Repair & drain"):
    # replacement provisioning, graceful spot drain, applied replans
    "replacement_ok",
    "replacement_provisioned",
    "replacement_resharded_chunks",
    "replacement_recovery_ratio",
    "replacement_detect_to_ready_seconds",
    "drain_ok",
    "drain_seconds",
    "drain_deadline_s",
    "drain_remaining_chunks",
    "drain_acked_chunks_lost",
    "drain_admission_rejected",
    "replan_applied_ok",
    "replan_applied_events",
    "replan_retargeted_ops",
    "replan_stream_retargets",
    # multi-process pump scenario (gateway/pump.py, docs/fault-injection.md
    # pump.worker_crash): worker killed mid-transfer -> respawn + uncounted
    # requeue, byte-identical corpus, zero acked-chunk loss, zero duplicate
    # registrations at the sink
    "pump_ok",
    "pump_procs",
    "pump_worker_deaths",
    "pump_respawns",
    "pump_requeued_chunks",
    "pump_byte_identical",
    "pump_acked_chunks_lost",
    "pump_duplicate_registrations",
    "pump_seconds",
    # dedup-fabric scenario (docs/dedup-fabric.md): with fabric.peer_fetch
    # dropping every fetch, the warm cross-gateway re-send must heal through
    # NACK -> literal resend, byte-identical, with zero peer-fetch hits
    "fabric_ok",
    "fabric_faults_fired",
    "fabric_nacks",
    "fabric_peer_fetch_hits",
    "fabric_byte_identical",
    "fabric_seconds",
)
#: post-recovery completion rate must reach this fraction of the pre-kill
#: rate once the replacement joins ("within 20%" of pre-kill throughput)
MIN_REPLACEMENT_RECOVERY_RATIO = 0.8
#: the acceptance floor: a chaos run proves nothing unless it injected faults
#: across at least this many distinct points of the stack
MIN_CHAOS_POINTS = 5
#: acceptance bound for the runtime lock-order witness: with
#: SKYPLANE_TPU_LOCKCHECK=1 the instrumented-lock tax on the chaos run
#: (deterministic per-acquire cost x observed acquisitions) stays under this
MAX_LOCKCHECK_OVERHEAD_PCT = 5.0

# fleet-telemetry smoke result (scripts/monitor_smoke.py / docs/observability.md):
# a loopback 2-hop relay transfer scraped by the TelemetryCollector — merged
# multi-gateway timeline, tailed flight-recorder fleet log, bottleneck
# attribution reconciliation, and the collector's own overhead
REQUIRED_FLEET = (
    "metric",
    "value",
    "unit",
    "fleet_gateways",
    "fleet_trace_events",
    "fleet_gateway_rows",
    "fleet_multihop_chunks",
    "fleet_events_tailed",
    "fleet_lifecycle_events",
    "fleet_fault_events",
    "fleet_events_in_order",
    "fleet_log_path",
    "fleet_log_lines",
    "fleet_stage_latency_us",
    "fleet_profile_gateways",
    "fleet_gil_wait_fraction",
    "fleet_reconcile_pct",
    "fleet_stale_gateways",
    "collector_scrapes",
    "collector_overhead_pct",
    "collector_poll_interval_s",
)
# the bottleneck report's stage axis (obs/collector.py BOTTLENECK_STAGES)
REQUIRED_FLEET_STAGES = ("frame", "send_stall", "ack_lag", "decode", "store", "device_wait")
#: fleet-vs-local stage attribution must reconcile within this bound
#: (ISSUE 9 acceptance: bottleneck totals vs bench-style stage means)
MAX_FLEET_RECONCILE_PCT = 10.0
#: the collector's CPU cost per poll cycle, as % of the poll interval
MAX_COLLECTOR_OVERHEAD_PCT = 2.0


# timeline / critical-path attribution result (scripts/bench_e2e.py
# --timeline-only, scripts/report_overhead.py — docs/observability.md "Job
# timelines & critical path"): a >=3-size loopback sweep, each run fully
# sampled into a fleet event log, fitted to wall = overhead + bytes/rate
REQUIRED_TIMELINE = (
    "metric",
    "unit",
    "timeline_sizes_bytes",
    "timeline_samples",
    "e2e_fixed_overhead_s",
    "e2e_fit_rate_bytes_per_s",
    "e2e_fit_r2",
    "timeline_critical_path_s",
    "timeline_wall_s",
    "timeline_coverage",
    "timeline_fixed_s",
    "timeline_scaled_s",
    "timeline_largest_fixed_phase",
    "timeline_phase_count",
)
#: the solved critical path must explain the timeline wall-clock to within
#: 10% (the ISSUE-20 acceptance bound) — below this the DAG is dropping
#: intervals; above 1.0 (plus float slack) it is double-counting overlap
MIN_TIMELINE_COVERAGE = 0.90
MAX_TIMELINE_COVERAGE = 1.001
#: banked fixed-overhead baseline: the paper's ~2 s provisioned-path figure.
#: The loopback sweep has no provisioning/TLS/WAN, so it must come in WELL
#: under it — a loopback fit drifting past the bound means the client path
#: itself regressed (dispatch serialization, drain poll, collector stalls)
MAX_E2E_FIXED_OVERHEAD_S = 2.0
MIN_TIMELINE_SIZES = 3


def check_timeline(result: dict) -> int:
    missing = [k for k in REQUIRED_TIMELINE if k not in result]
    if missing:
        print(f"timeline-smoke: result missing keys: {', '.join(missing)}", file=sys.stderr)
        return 1
    sizes = result["timeline_sizes_bytes"]
    if not isinstance(sizes, list) or len(sizes) < MIN_TIMELINE_SIZES or len(set(sizes)) < 2:
        print(
            f"timeline-smoke: fit needs >={MIN_TIMELINE_SIZES} sizes (>=2 distinct), got {sizes!r}",
            file=sys.stderr,
        )
        return 1
    overhead = result["e2e_fixed_overhead_s"]
    if not isinstance(overhead, (int, float)) or overhead < 0:
        print(f"timeline-smoke: e2e_fixed_overhead_s {overhead!r} is not a non-negative number", file=sys.stderr)
        return 1
    if overhead > MAX_E2E_FIXED_OVERHEAD_S:
        print(
            f"timeline-smoke: fixed overhead {overhead}s regressed past the banked "
            f"{MAX_E2E_FIXED_OVERHEAD_S}s baseline — the loopback client path got slower",
            file=sys.stderr,
        )
        return 1
    cp, wall = result["timeline_critical_path_s"], result["timeline_wall_s"]
    cov = result["timeline_coverage"]
    if not all(isinstance(v, (int, float)) and v > 0 for v in (cp, wall, cov)):
        print(f"timeline-smoke: non-positive path/wall/coverage: {cp!r}/{wall!r}/{cov!r}", file=sys.stderr)
        return 1
    if cov < MIN_TIMELINE_COVERAGE or cov > MAX_TIMELINE_COVERAGE:
        print(
            f"timeline-smoke: critical path {cp}s explains {100 * cov:.1f}% of wall {wall}s "
            f"(required {100 * MIN_TIMELINE_COVERAGE:.0f}-{100 * MAX_TIMELINE_COVERAGE:.1f}%) — "
            "the DAG is dropping intervals or double-counting overlap",
            file=sys.stderr,
        )
        return 1
    if not result["timeline_largest_fixed_phase"]:
        print("timeline-smoke: no largest fixed-cost phase attributed (empty waterfall?)", file=sys.stderr)
        return 1
    if result["timeline_phase_count"] < 2:
        print(
            f"timeline-smoke: only {result['timeline_phase_count']} phase interval(s) sampled — "
            "the lifecycle instrumentation did not fire",
            file=sys.stderr,
        )
        return 1
    fx, sc = result["timeline_fixed_s"], result["timeline_scaled_s"]
    if not all(isinstance(v, (int, float)) and v >= 0 for v in (fx, sc)):
        print(f"timeline-smoke: bad fixed/scaled split: {fx!r}/{sc!r}", file=sys.stderr)
        return 1
    if abs((fx + sc) - cp) > max(0.01, 0.01 * cp):
        print(
            f"timeline-smoke: fixed {fx}s + scaled {sc}s != critical path {cp}s — "
            "the attribution split does not reconcile",
            file=sys.stderr,
        )
        return 1
    print(
        f"timeline-smoke OK: {len(sizes)}-size sweep, fixed overhead {overhead}s "
        f"(baseline {MAX_E2E_FIXED_OVERHEAD_S}s), critical path {cp}s = {100 * cov:.1f}% of wall "
        f"{wall}s, largest fixed cost '{result['timeline_largest_fixed_phase']}' "
        f"(fixed {fx}s | byte-scaled {sc}s)"
    )
    return 0


# always-on service soak result (scripts/soak_service.py /
# docs/service-mode.md): one standing fleet, >=50 sequential + >=8
# concurrent warm jobs, a SIGKILLed controller recovered from the WAL
REQUIRED_SERVICE = (
    "metric",
    "value",
    "unit",
    "service_seq_jobs",
    "service_concurrent_jobs",
    "service_job_start_p50_s",
    "service_job_start_p95_s",
    "service_dispatch_hist_p50_s",
    "service_dispatch_hist_p95_s",
    "service_start_bound_s",
    "service_dedup_hit_cold",
    "service_dedup_hit_warm",
    "service_heartbeats",
    "service_watch_rounds",
    "service_watch_delta_only",
    "service_watch_byte_identical",
    "service_controller_killed",
    "service_recovery_seconds",
    "service_recovery_bound_s",
    "service_recovered",
    "service_byte_identical",
    "service_acked_chunks_lost",
    "service_duplicate_registrations",
    "service_requeued_chunks",
    "service_torn_records_dropped",
    "service_crash_fault_fired",
    "service_resubmit_noop",
    "service_dispatch_gap_ok",
    "process_open_fds_start",
    "process_open_fds_end",
    "service_rss_start_bytes",
    "service_rss_end_bytes",
)
#: acceptance floors (ISSUE 14): the soak proves nothing below these
MIN_SERVICE_SEQ_JOBS = 50
MIN_SERVICE_CONC_JOBS = 8
#: fd/RSS must stay flat across the >=50-job soak (leak gates)
MAX_SERVICE_FD_GROWTH = 64
MAX_SERVICE_RSS_GROWTH_BYTES = 256 << 20


def check_service(result: dict) -> int:
    missing = [k for k in REQUIRED_SERVICE if k not in result]
    if missing:
        print(f"service-smoke: result missing keys: {', '.join(missing)}", file=sys.stderr)
        return 1
    if result["service_seq_jobs"] < MIN_SERVICE_SEQ_JOBS:
        print(
            f"service-smoke: only {result['service_seq_jobs']} sequential jobs "
            f"(acceptance floor {MIN_SERVICE_SEQ_JOBS})",
            file=sys.stderr,
        )
        return 1
    if result["service_concurrent_jobs"] < MIN_SERVICE_CONC_JOBS:
        print(
            f"service-smoke: only {result['service_concurrent_jobs']} concurrent jobs "
            f"(acceptance floor {MIN_SERVICE_CONC_JOBS})",
            file=sys.stderr,
        )
        return 1
    p50 = result["service_job_start_p50_s"]
    if not isinstance(p50, (int, float)) or p50 <= 0 or p50 >= result["service_start_bound_s"]:
        print(
            f"service-smoke: warm-job start p50 {p50!r}s breaches the "
            f"{result['service_start_bound_s']}s bound — the standing fleet is not warm",
            file=sys.stderr,
        )
        return 1
    # the histogram-derived p50 (skyplane_service_dispatch_seconds) must agree:
    # the soak gate and a production dashboard read the SAME series, so a
    # dispatch-path latency regression cannot hide behind ad-hoc timing
    hp50 = result["service_dispatch_hist_p50_s"]
    if not isinstance(hp50, (int, float)) or hp50 <= 0 or hp50 >= result["service_start_bound_s"]:
        print(
            f"service-smoke: histogram-derived warm-dispatch p50 {hp50!r}s breaches the "
            f"{result['service_start_bound_s']}s bound (service_dispatch_seconds series)",
            file=sys.stderr,
        )
        return 1
    cold, warm = result["service_dedup_hit_cold"], result["service_dedup_hit_warm"]
    if not isinstance(warm, (int, float)) or warm <= cold:
        print(
            f"service-smoke: warm dedup hit rate {warm!r} does not beat cold {cold!r} — "
            "the persistent index is not staying warm across jobs",
            file=sys.stderr,
        )
        return 1
    if result["service_heartbeats"] < 1:
        print("service-smoke: no TTL heartbeats observed (reap-vs-heartbeat untested)", file=sys.stderr)
        return 1
    if result["service_watch_rounds"] < 2 or result["service_watch_delta_only"] is not True:
        print(
            f"service-smoke: continuous sync failed — rounds={result['service_watch_rounds']} "
            f"delta_only={result['service_watch_delta_only']}",
            file=sys.stderr,
        )
        return 1
    if result["service_watch_byte_identical"] is not True:
        print("service-smoke: sync-watch mirror NOT byte-identical", file=sys.stderr)
        return 1
    if result["service_controller_killed"] is not True:
        print("service-smoke: the controller was never SIGKILLed mid-job (vacuous run)", file=sys.stderr)
        return 1
    if result["service_recovered"] is not True or result["service_byte_identical"] is not True:
        print(
            f"service-smoke: recovery failed — recovered={result['service_recovered']} "
            f"byte_identical={result['service_byte_identical']}",
            file=sys.stderr,
        )
        return 1
    if result["service_recovery_seconds"] > result["service_recovery_bound_s"]:
        print(
            f"service-smoke: recovery took {result['service_recovery_seconds']}s, over the "
            f"{result['service_recovery_bound_s']}s bound",
            file=sys.stderr,
        )
        return 1
    if result["service_acked_chunks_lost"] != 0:
        print(
            f"service-smoke: {result['service_acked_chunks_lost']} acked chunk(s) LOST across the kill",
            file=sys.stderr,
        )
        return 1
    if result["service_duplicate_registrations"] != 0:
        print(
            f"service-smoke: {result['service_duplicate_registrations']} duplicate sink "
            "registration(s) — recovery re-dispatched under fresh chunk ids",
            file=sys.stderr,
        )
        return 1
    if result["service_torn_records_dropped"] < 1:
        print("service-smoke: the torn WAL tail was never exercised (vacuous)", file=sys.stderr)
        return 1
    if result["service_crash_fault_fired"] is not True:
        print("service-smoke: service.crash never fired during recovery (vacuous)", file=sys.stderr)
        return 1
    if result["service_resubmit_noop"] is not True:
        print("service-smoke: post-recovery resubmission was NOT idempotent", file=sys.stderr)
        return 1
    if result["service_dispatch_gap_ok"] is not True:
        print(
            "service-smoke: the WAL->POST crash-window scenario failed (requeue from the "
            "dispatch record broke)",
            file=sys.stderr,
        )
        return 1
    if result["service_requeued_chunks"] < 1:
        print("service-smoke: recovery requeued zero chunks (vacuous crash window)", file=sys.stderr)
        return 1
    fd_growth = result["process_open_fds_end"] - result["process_open_fds_start"]
    if fd_growth > MAX_SERVICE_FD_GROWTH:
        print(f"service-smoke: fd count grew by {fd_growth} across the soak (descriptor leak)", file=sys.stderr)
        return 1
    rss_growth = result["service_rss_end_bytes"] - result["service_rss_start_bytes"]
    if rss_growth > MAX_SERVICE_RSS_GROWTH_BYTES:
        print(
            f"service-smoke: RSS grew by {rss_growth / (1 << 20):.0f} MiB across the soak "
            f"(bound {MAX_SERVICE_RSS_GROWTH_BYTES >> 20} MiB)",
            file=sys.stderr,
        )
        return 1
    print(
        f"service-smoke OK: {result['service_seq_jobs']} sequential + "
        f"{result['service_concurrent_jobs']} concurrent jobs on one standing fleet, "
        f"warm start p50 {p50}s/p95 {result['service_job_start_p95_s']}s (bound "
        f"{result['service_start_bound_s']}s), dedup cold {cold} -> warm {warm}; "
        f"controller SIGKILLed mid-job and recovered in {result['service_recovery_seconds']}s "
        f"(byte-identical, 0 acked lost, 0 duplicate registrations, "
        f"{result['service_requeued_chunks']} chunk(s) requeued from the WAL, "
        f"{result['service_torn_records_dropped']} torn record(s) dropped, crash-in-recovery + "
        f"idempotent resubmission proven); continuous sync: {result['service_watch_rounds']} "
        f"round(s), delta-only, byte-identical; fd growth {fd_growth}, "
        f"RSS growth {rss_growth / (1 << 20):.0f} MiB"
    )
    return 0


def check_fleet(result: dict) -> int:
    missing = [k for k in REQUIRED_FLEET if k not in result]
    stages = result.get("fleet_stage_latency_us")
    if not isinstance(stages, dict):
        missing.append("fleet_stage_latency_us(dict)")
    else:
        missing += [f"fleet_stage_latency_us.{k}" for k in REQUIRED_FLEET_STAGES if k not in stages]
    if missing:
        print(f"monitor-smoke: result missing keys: {', '.join(missing)}", file=sys.stderr)
        return 1
    if result["fleet_gateways"] < 3:
        print(f"monitor-smoke: only {result['fleet_gateways']} gateways scraped; a 2-hop relay needs 3", file=sys.stderr)
        return 1
    if result["fleet_gateway_rows"] < 3:
        print(
            f"monitor-smoke: merged timeline shows {result['fleet_gateway_rows']} gateway rows "
            "(need source+relay+destination)",
            file=sys.stderr,
        )
        return 1
    if result["fleet_multihop_chunks"] < 1:
        print("monitor-smoke: no chunk stitched across the full source->relay->destination path", file=sys.stderr)
        return 1
    if result["fleet_lifecycle_events"] < 2 or result["fleet_fault_events"] < 1:
        print(
            f"monitor-smoke: fleet log incomplete — {result['fleet_lifecycle_events']} lifecycle "
            f"event(s), {result['fleet_fault_events']} fault event(s)",
            file=sys.stderr,
        )
        return 1
    if result["fleet_events_in_order"] is not True:
        print("monitor-smoke: fleet event log is not in seq order per recorder", file=sys.stderr)
        return 1
    if result["fleet_log_lines"] < result["fleet_events_tailed"]:
        print(
            f"monitor-smoke: JSONL fleet log holds {result['fleet_log_lines']} lines but "
            f"{result['fleet_events_tailed']} events were tailed",
            file=sys.stderr,
        )
        return 1
    # core-time scrape proof (ISSUE 12): the combined telemetry scrape must
    # have carried at least one profiler summary, with a sane GIL fraction
    if result["fleet_profile_gateways"] < 1:
        print("monitor-smoke: no gateway's scrape carried a profiler summary (?profile=1 path broken)", file=sys.stderr)
        return 1
    gil = result["fleet_gil_wait_fraction"]
    if not isinstance(gil, (int, float)) or gil < 0.0 or gil > 1.0:
        print(f"monitor-smoke: implausible fleet_gil_wait_fraction {gil!r} (must be 0..1)", file=sys.stderr)
        return 1
    rec = result["fleet_reconcile_pct"]
    if not isinstance(rec, (int, float)) or rec < 0 or rec > MAX_FLEET_RECONCILE_PCT:
        print(
            f"monitor-smoke: bottleneck stage attribution diverges {rec!r}% from the local trace "
            f"(bound {MAX_FLEET_RECONCILE_PCT}%) — the merge/dedupe dropped or duplicated spans",
            file=sys.stderr,
        )
        return 1
    overhead = result["collector_overhead_pct"]
    if not isinstance(overhead, (int, float)) or overhead < 0 or overhead >= MAX_COLLECTOR_OVERHEAD_PCT:
        print(
            f"monitor-smoke: collector overhead {overhead!r}% breaches the "
            f"{MAX_COLLECTOR_OVERHEAD_PCT}% budget per poll cycle",
            file=sys.stderr,
        )
        return 1
    print(
        f"monitor-smoke OK: {result['fleet_gateways']} gateways, {result['fleet_gateway_rows']} timeline rows, "
        f"{result['fleet_multihop_chunks']} chunk(s) full-path stitched, "
        f"{result['fleet_events_tailed']} fleet events ({result['fleet_fault_events']} fault, "
        f"{result['fleet_lifecycle_events']} lifecycle) in order, reconcile {rec}%, "
        f"collector overhead {overhead}%/cycle"
    )
    return 0


def check_chaos(result: dict) -> int:
    missing = [k for k in REQUIRED_CHAOS if k not in result]
    if missing:
        print(f"chaos-smoke: result missing keys: {', '.join(missing)}", file=sys.stderr)
        return 1
    if result["chaos_faults_total"] <= 0 or not result["chaos_faults_injected"]:
        print("chaos-smoke: no faults were injected — the chaos run was vacuous", file=sys.stderr)
        return 1
    if result["chaos_points_armed"] < MIN_CHAOS_POINTS or result["chaos_points_fired"] < MIN_CHAOS_POINTS:
        print(
            f"chaos-smoke: {result['chaos_points_fired']} fired / {result['chaos_points_armed']} armed "
            f"fault points; acceptance needs >= {MIN_CHAOS_POINTS} distinct points firing",
            file=sys.stderr,
        )
        return 1
    if result["chaos_integrity_ok"] is not True:
        print("chaos-smoke: destination corpus NOT byte-identical under faults (CORRUPTION)", file=sys.stderr)
        return 1
    if result["chaos_determinism_ok"] is not True:
        print("chaos-smoke: fault firing sequence did not replay from the seed", file=sys.stderr)
        return 1
    if result["chaos_metrics_exported"] is not True:
        print("chaos-smoke: faults_injected counters missing from /api/v1/metrics", file=sys.stderr)
        return 1
    if result["chaos_sched_tokens_leaked"] != 0:
        print(
            f"chaos-smoke: {result['chaos_sched_tokens_leaked']} scheduler tokens leaked through recovery",
            file=sys.stderr,
        )
        return 1
    if result["chaos_pool_buffers_leaked"] != 0:
        print(f"chaos-smoke: {result['chaos_pool_buffers_leaked']} pool buffers leaked", file=sys.stderr)
        return 1
    if result["chaos_fd_growth"] > 64:
        print(f"chaos-smoke: fd count grew by {result['chaos_fd_growth']} (descriptor leak)", file=sys.stderr)
        return 1
    if result["gateway_death_ok"] is not True:
        print(
            "chaos-smoke: gateway-death scenario failed — "
            f"detected={result.get('gateway_death_detected')} "
            f"requeued={result.get('gateway_death_requeued_chunks')} "
            f"tracker_error={result.get('gateway_death_tracker_error')} "
            f"tokens_leaked={result.get('gateway_death_sched_tokens_leaked')}",
            file=sys.stderr,
        )
        return 1
    if result["replacement_ok"] is not True:
        print(
            "chaos-smoke: replacement scenario failed — "
            f"provisioned={result.get('replacement_provisioned')} "
            f"resharded={result.get('replacement_resharded_chunks')} "
            f"ratio={result.get('replacement_recovery_ratio')} "
            f"tracker_error={result.get('replacement_tracker_error')}",
            file=sys.stderr,
        )
        return 1
    if result["replacement_resharded_chunks"] <= 0:
        print("chaos-smoke: replacement joined the fleet but carried zero re-sharded chunks (idle)", file=sys.stderr)
        return 1
    ratio = result["replacement_recovery_ratio"]
    if not isinstance(ratio, (int, float)) or ratio < MIN_REPLACEMENT_RECOVERY_RATIO:
        print(
            f"chaos-smoke: post-replacement throughput recovered to only {ratio!r}x the pre-kill rate "
            f"(floor {MIN_REPLACEMENT_RECOVERY_RATIO})",
            file=sys.stderr,
        )
        return 1
    if result["drain_ok"] is not True:
        print(
            "chaos-smoke: drain scenario failed — "
            f"seconds={result.get('drain_seconds')} (deadline {result.get('drain_deadline_s')}) "
            f"remaining={result.get('drain_remaining_chunks')} "
            f"acked_lost={result.get('drain_acked_chunks_lost')} "
            f"admission_rejected={result.get('drain_admission_rejected')} "
            f"error={result.get('drain_error')}",
            file=sys.stderr,
        )
        return 1
    if result["drain_acked_chunks_lost"] != 0:
        print(f"chaos-smoke: drain lost {result['drain_acked_chunks_lost']} acked chunk(s)", file=sys.stderr)
        return 1
    if result["drain_seconds"] is None or result["drain_seconds"] > result["drain_deadline_s"]:
        print(
            f"chaos-smoke: drain took {result['drain_seconds']}s, over its deadline {result['drain_deadline_s']}s",
            file=sys.stderr,
        )
        return 1
    if result["replan_applied_ok"] is not True or result["replan_applied_events"] < 1:
        print(
            "chaos-smoke: applied-replan scenario failed — "
            f"applied={result.get('replan_applied_events')} "
            f"retargeted={result.get('replan_retargeted_ops')} "
            f"stream_retargets={result.get('replan_stream_retargets')} "
            f"tracker_error={result.get('replan_tracker_error')} "
            f"byte_identical={result.get('replan_byte_identical')}",
            file=sys.stderr,
        )
        return 1
    if result["replan_stream_retargets"] < 1:
        print("chaos-smoke: replan applied but no wire stream performed a cutover reset", file=sys.stderr)
        return 1
    if result["pump_ok"] is not True:
        print(
            "chaos-smoke: pump worker-crash scenario failed — "
            f"deaths={result.get('pump_worker_deaths')} respawns={result.get('pump_respawns')} "
            f"byte_identical={result.get('pump_byte_identical')} "
            f"acked_lost={result.get('pump_acked_chunks_lost')} "
            f"dup_registrations={result.get('pump_duplicate_registrations')} "
            f"error={result.get('pump_error')}",
            file=sys.stderr,
        )
        return 1
    if result["pump_worker_deaths"] < 1 or result["pump_respawns"] < 1:
        print(
            f"chaos-smoke: pump scenario was vacuous — {result['pump_worker_deaths']} death(s), "
            f"{result['pump_respawns']} respawn(s); the crash fault never fired",
            file=sys.stderr,
        )
        return 1
    if result["pump_acked_chunks_lost"] != 0 or result["pump_duplicate_registrations"] != 0:
        print(
            f"chaos-smoke: pump accounting broke — {result['pump_acked_chunks_lost']} acked chunk(s) lost, "
            f"{result['pump_duplicate_registrations']} duplicate sink registration(s)",
            file=sys.stderr,
        )
        return 1
    if result["fabric_ok"] is not True:
        print(
            "chaos-smoke: dedup-fabric scenario failed — "
            f"faults_fired={result.get('fabric_faults_fired')} "
            f"nacks={result.get('fabric_nacks')} "
            f"peer_fetch_hits={result.get('fabric_peer_fetch_hits')} "
            f"byte_identical={result.get('fabric_byte_identical')} "
            f"error={result.get('fabric_error')}",
            file=sys.stderr,
        )
        return 1
    if result["fabric_faults_fired"] < 1 or result["fabric_nacks"] < 1:
        print(
            f"chaos-smoke: fabric scenario was vacuous — {result['fabric_faults_fired']} fault(s) "
            f"fired, {result['fabric_nacks']} NACK(s); the drop never forced the heal path",
            file=sys.stderr,
        )
        return 1
    overhead = result["lockcheck_overhead_pct"]
    if not isinstance(overhead, (int, float)) or overhead < 0 or overhead >= MAX_LOCKCHECK_OVERHEAD_PCT:
        print(
            f"chaos-smoke: lock-witness overhead {overhead!r}% breaches the "
            f"{MAX_LOCKCHECK_OVERHEAD_PCT}% budget (SKYPLANE_TPU_LOCKCHECK)",
            file=sys.stderr,
        )
        return 1
    if result["lockcheck_enabled"]:
        if result["lockcheck_acyclic"] is not True:
            print(
                "chaos-smoke: observed lock-acquisition-order graph has a CYCLE (or a swallowed "
                "LockOrderViolation) — see /api/v1/profile/locks witness output",
                file=sys.stderr,
            )
            return 1
        if result["lockcheck_acquisitions"] <= 0:
            print(
                "chaos-smoke: SKYPLANE_TPU_LOCKCHECK=1 but the witness observed zero acquisitions "
                "— the wrap() shims are not on the hot path (vacuous lockcheck run)",
                file=sys.stderr,
            )
            return 1
    if result["chaos_seconds"] > result["chaos_bound_seconds"]:
        print(
            f"chaos-smoke: recovery took {result['chaos_seconds']}s, over the bound "
            f"{result['chaos_bound_seconds']}s ({result['chaos_slowdown_x']}x the fault-free baseline)",
            file=sys.stderr,
        )
        return 1
    print(
        f"chaos-smoke OK: seed {result['chaos_seed']}, {result['chaos_faults_total']} faults over "
        f"{result['chaos_points_fired']}/{result['chaos_points_armed']} points, integrity+determinism proven, "
        f"{result['chaos_seconds']}s vs baseline {result['baseline_seconds']}s "
        f"(bound {result['chaos_bound_seconds']}s), {result['chaos_torn_records_dropped']} torn journal "
        f"record(s) recovered, zero token/buffer leaks, fd growth {result['chaos_fd_growth']}; "
        f"repair loop: replacement ready {result['replacement_detect_to_ready_seconds']}s after detection "
        f"({result['replacement_resharded_chunks']} chunk(s) re-sharded, recovery {ratio}x pre-kill), "
        f"drain {result['drain_seconds']}s/{result['drain_deadline_s']}s with 0 acked chunks lost, "
        f"{result['replan_applied_events']} replan(s) applied over {result['replan_stream_retargets']} stream cutover(s); "
        f"pump: {result['pump_worker_deaths']} worker crash(es) absorbed in {result['pump_seconds']}s "
        f"({result['pump_respawns']} respawn(s), {result['pump_requeued_chunks']} chunk(s) requeued, byte-identical); "
        f"fabric: {result['fabric_faults_fired']} dropped peer fetch(es) healed via "
        f"{result['fabric_nacks']} NACK(s), byte-identical"
        + (
            f"; lockcheck: {result['lockcheck_acquisitions']} acquisitions over "
            f"{result['lockcheck_locks']} locks, {result['lockcheck_edges']} order edge(s) acyclic, "
            f"overhead {overhead}%"
            if result["lockcheck_enabled"]
            else "; lockcheck: disabled"
        )
    )
    return 0


# blast fan-out soak result (scripts/soak_blast.py / docs/blast.md)
REQUIRED_BLAST = (
    "metric",
    "value",
    "unit",
    "blast_sinks",
    "blast_fanout",
    "blast_chunks",
    "blast_corpus_bytes",
    "blast_relay_killed",
    "blast_healed",
    "blast_byte_identical",
    "blast_source_egress_bytes",
    "blast_egress_ratio",
    "blast_requeued_chunks",
    "blast_acked_chunks_lost",
    "blast_duplicate_registrations",
    "blast_peer_serve_faults",
    "blast_events_ok",
    "blast_seconds",
    "blast_ok",
)


def check_blast(result: dict) -> int:
    missing = [k for k in REQUIRED_BLAST if k not in result]
    if missing:
        print(f"blast-smoke: result missing keys: {', '.join(missing)}", file=sys.stderr)
        return 1
    if result["blast_sinks"] < MIN_BLAST_SINKS:
        print(
            f"blast-smoke: only {result['blast_sinks']} sinks; acceptance needs >= {MIN_BLAST_SINKS}",
            file=sys.stderr,
        )
        return 1
    if result["blast_byte_identical"] is not True:
        print("blast-smoke: sinks NOT byte-identical (CORRUPTION)", file=sys.stderr)
        return 1
    if result["blast_relay_killed"] is not True or result["blast_healed"] is not True:
        print(
            "blast-smoke: relay-death drill was vacuous — "
            f"killed={result.get('blast_relay_killed')} healed={result.get('blast_healed')} "
            f"error={result.get('blast_error')}",
            file=sys.stderr,
        )
        return 1
    ratio = result["blast_egress_ratio"]
    if not isinstance(ratio, (int, float)) or ratio <= 0 or ratio > MAX_BLAST_EGRESS_RATIO:
        print(
            f"blast-smoke: source egress ratio {ratio!r} breaches the {MAX_BLAST_EGRESS_RATIO}x bound "
            "(counter-measured skyplane_egress_bytes_total / corpus bytes)",
            file=sys.stderr,
        )
        return 1
    if result["blast_acked_chunks_lost"] != 0 or result["blast_duplicate_registrations"] != 0:
        print(
            f"blast-smoke: accounting broke — {result['blast_acked_chunks_lost']} acked chunk(s) lost, "
            f"{result['blast_duplicate_registrations']} duplicate sink registration(s)",
            file=sys.stderr,
        )
        return 1
    if result["blast_peer_serve_faults"] < 1:
        print(
            "blast-smoke: the armed relay.peer_serve plan never fired — the injected-drop "
            "absorption drill was vacuous (scale the corpus back up)",
            file=sys.stderr,
        )
        return 1
    if result["blast_events_ok"] is not True:
        print("blast-smoke: blast.* flight-recorder lifecycle events missing", file=sys.stderr)
        return 1
    if result["blast_ok"] is not True:
        print(f"blast-smoke: soak self-check failed — error={result.get('blast_error')}", file=sys.stderr)
        return 1
    print(
        f"blast-smoke OK: 1 source -> {result['blast_sinks']} sinks (fanout {result['blast_fanout']}, "
        f"{result['blast_chunks']} chunks, {result['blast_corpus_bytes'] >> 20} MiB), relay killed mid-blast and "
        f"healed ({result['blast_requeued_chunks']} chunk(s) re-driven), byte-identical everywhere, "
        f"source egress {ratio}x corpus (bound {MAX_BLAST_EGRESS_RATIO}), "
        f"{result['blast_peer_serve_faults']} peer-serve fault(s) absorbed, {result['blast_seconds']}s"
    )
    return 0


def check_multijob(result: dict) -> int:
    missing = [k for k in REQUIRED_MULTIJOB if k not in result]
    if missing:
        print(f"multijob-smoke: result missing keys: {', '.join(missing)}", file=sys.stderr)
        return 1
    tenant_gbps = result["tenant_gbps"]
    if not isinstance(tenant_gbps, dict) or len(tenant_gbps) < 2:
        print(f"multijob-smoke: tenant_gbps must map >=2 tenants, got {tenant_gbps!r}", file=sys.stderr)
        return 1
    if len(tenant_gbps) != result["n_jobs"]:
        print(
            f"multijob-smoke: {len(tenant_gbps)} tenant entries but n_jobs={result['n_jobs']}",
            file=sys.stderr,
        )
        return 1
    counters = result["tenant_counters"]
    bad = [
        f"tenant_counters[{t}].{k}"
        for t in tenant_gbps
        for k in REQUIRED_TENANT_KEYS
        if k not in (counters.get(t) or {})
    ]
    if bad:
        print(f"multijob-smoke: missing per-tenant keys: {', '.join(bad[:8])}", file=sys.stderr)
        return 1
    # acceptance gate: equal-weight tenants split throughput fairly
    ratio = result["gbps_max_min_ratio"]
    bound = result["fairness_bound"]
    if not isinstance(ratio, (int, float)) or ratio <= 0 or ratio > bound:
        print(
            f"multijob-smoke: per-tenant Gbps max/min ratio {ratio!r} breaches the fairness bound {bound}",
            file=sys.stderr,
        )
        return 1
    # leak gates: bounded index RSS, no descriptor growth beyond slack
    if result["index_rss_bytes"] < 0:
        print(f"multijob-smoke: implausible index_rss_bytes {result['index_rss_bytes']!r}", file=sys.stderr)
        return 1
    fd_growth = result["process_open_fds_end"] - result["process_open_fds_start"]
    if fd_growth > 64:
        print(f"multijob-smoke: fd count grew by {fd_growth} across the soak (descriptor leak)", file=sys.stderr)
        return 1
    print(
        f"multijob-smoke OK: {result['n_jobs']} jobs, {result['value']} {result['unit']} aggregate, "
        f"per-tenant max/min {ratio} (bound {bound}), index RSS {result['index_rss_bytes']:.0f}B, "
        f"fd growth {fd_growth}"
    )
    return 0


def check_fabric(result: dict) -> int:
    missing = [k for k in REQUIRED_FABRIC if k not in result]
    if missing:
        print(f"fabric-smoke: result missing keys: {', '.join(missing)}", file=sys.stderr)
        return 1
    if result["fabric_byte_identical"] is not True:
        print("fabric-smoke: a phase output was NOT byte-identical to its corpus", file=sys.stderr)
        return 1
    # vacuous-run guards: the probe must have actually exercised the fabric
    if result["fabric_warm_segments"] < 1 or result["fabric_gossip_fps"] < 1:
        print(
            f"fabric-smoke: vacuous run — warm_segments={result['fabric_warm_segments']}, "
            f"gossip_fps={result['fabric_gossip_fps']}",
            file=sys.stderr,
        )
        return 1
    if result["fabric_peer_fetch_hits"] < 1:
        print(
            "fabric-smoke: zero peer fetches served — the ring never resolved a REF miss "
            f"(lands={result['fabric_lands']}, pushes={result['fabric_pushes_sent']})",
            file=sys.stderr,
        )
        return 1
    # acceptance gate (ISSUE 19): cross-gateway warm-hit rate >= 90%
    rate = result["fabric_warm_hit_rate"]
    floor = result["fabric_warm_hit_floor"]
    if not isinstance(rate, (int, float)) or rate < floor:
        print(
            f"fabric-smoke: warm-hit rate {rate!r} under the {floor} floor "
            f"({result['fabric_source_literals_warm']} source literal(s) on the warm probe)",
            file=sys.stderr,
        )
        return 1
    # acceptance gate: cross-shard NACK rate under the PR-13 tolerance
    nack_rate = result["fabric_cross_shard_nack_rate"]
    bound = result["fabric_nack_rate_bound"]
    if not isinstance(nack_rate, (int, float)) or nack_rate > bound:
        print(
            f"fabric-smoke: cross-shard NACK rate {nack_rate!r} over the {bound} bound "
            f"({result['fabric_cross_shard_nacks']} NACK(s) / {result['fabric_warm_refs']} warm REF(s))",
            file=sys.stderr,
        )
        return 1
    if result["fabric_land_rejects"] > 0:
        print(
            f"fabric-smoke: {result['fabric_land_rejects']} pushed segment(s) failed content "
            "verification at the ring owner",
            file=sys.stderr,
        )
        return 1
    fd_growth = result["process_open_fds_end"] - result["process_open_fds_start"]
    if fd_growth > 64:
        print(f"fabric-smoke: fd count grew by {fd_growth} across the soak (descriptor leak)", file=sys.stderr)
        return 1
    print(
        f"fabric-smoke OK: warm-hit {rate} (floor {floor}, {result['fabric_warm_refs']}/"
        f"{result['fabric_warm_segments']} REFs), {result['fabric_peer_fetch_hits']} peer fetch(es) served, "
        f"overlap REF rate {result['fabric_overlap_ref_rate']}, NACK rate {nack_rate} (bound {bound}), "
        f"byte-identical, {result['value']} {result['unit']} warm, fd growth {fd_growth}"
    )
    return 0


def _gate_spmd(result, tag: str):
    """SPMD device-scaling gate, shared by the full bench artifact and the
    standalone ``spmd_scaling`` row (devloop spmd-smoke). Returns the
    human-readable note for the OK line on pass, or None after printing the
    failure (caller returns 1). Gates arm progressively with
    spmd_devices_available — the pump-gate downgrade pattern."""
    spmd_g = result.get("spmd_gbps_by_devices")
    if not isinstance(spmd_g, dict) or "1" not in spmd_g:
        print(f"{tag}: spmd_gbps_by_devices must be a dict holding the 1-device point, got {spmd_g!r}", file=sys.stderr)
        return None
    bad = {k: v for k, v in spmd_g.items() if not isinstance(v, (int, float)) or v <= 0}
    if bad:
        print(f"{tag}: implausible spmd throughput(s): {bad}", file=sys.stderr)
        return None
    if result.get("spmd_identical") is not True:
        print(f"{tag}: spmd sweep is not byte-identical to the host kernels (spmd_identical={result.get('spmd_identical')!r})", file=sys.stderr)
        return None
    avail = result.get("spmd_devices_available")
    if not isinstance(avail, (int, float)) or avail < 1:
        print(f"{tag}: implausible spmd_devices_available {avail!r}", file=sys.stderr)
        return None
    note = f"(devices_available={avail}: scaling gates downgraded)"
    if avail >= 2:
        if "2" not in spmd_g:
            print(f"{tag}: spmd sweep missing the 2-device point on a {avail}-device runner", file=sys.stderr)
            return None
        if spmd_g["2"] < SPMD_MONOTONIC_TOLERANCE * spmd_g["1"]:
            print(
                f"{tag}: spmd throughput regressed 1->2 devices ({spmd_g['1']} -> {spmd_g['2']} Gbps) "
                f"on a {avail}-device runner",
                file=sys.stderr,
            )
            return None
        note = f"(devices_available={avail}: 4-device gates downgraded)"
    if avail >= 4:
        if "4" not in spmd_g:
            print(f"{tag}: spmd sweep missing the 4-device point on a {avail}-device runner", file=sys.stderr)
            return None
        if spmd_g["4"] < SPMD_MONOTONIC_TOLERANCE * spmd_g["2"]:
            print(
                f"{tag}: spmd throughput regressed 2->4 devices ({spmd_g['2']} -> {spmd_g['4']} Gbps) "
                f"on a {avail}-device runner",
                file=sys.stderr,
            )
            return None
        speedup = spmd_g["4"] / spmd_g["1"]
        if speedup < MIN_SPMD_SPEEDUP_AT_4:
            print(
                f"{tag}: spmd speedup at 4 devices is {round(speedup, 2)}x vs 1 device "
                f"({spmd_g['1']} -> {spmd_g['4']} Gbps), below the {MIN_SPMD_SPEEDUP_AT_4}x acceptance floor",
                file=sys.stderr,
            )
            return None
        note = f"(mesh {result.get('spmd_mesh')}, {round(speedup, 2)}x at 4 devices)"
    return note


def _mesh_label_ok(mesh, n_devices) -> bool:
    """A mesh label is "<data>x<seq>" whose product equals the device count."""
    if not isinstance(mesh, str):
        return False
    parts = mesh.split("x")
    if len(parts) != 2 or not all(p.isdigit() for p in parts):
        return False
    return int(parts[0]) * int(parts[1]) == n_devices


def check_spmd(result) -> int:
    """Standalone SPMD scaling row (devloop spmd-smoke: bench_spmd_scaling()
    exported as one ``{"metric": "spmd_scaling", ...}`` line)."""
    missing = [
        k
        for k in ("spmd_gbps_by_devices", "spmd_mesh", "spmd_devices_available", "spmd_identical")
        if k not in result
    ]
    if missing:
        print(f"spmd-smoke: result missing keys: {', '.join(missing)}", file=sys.stderr)
        return 1
    note = _gate_spmd(result, "spmd-smoke")
    if note is None:
        return 1
    print(f"spmd-smoke OK: {result['spmd_gbps_by_devices']} Gbps by devices {note}")
    return 0


def check_multichip(result) -> int:
    """MULTICHIP dryrun artifact row (__graft_entry__.dryrun_multichip):
    every row must carry the device-count context (n_devices + mesh — on
    every bench/MULTICHIP artifact row since PR 18) and prove the
    production-shape mesh run bit-identical to the host pipeline."""
    missing = [k for k in REQUIRED_MULTICHIP if k not in result]
    if missing:
        print(f"multichip-smoke: result missing keys: {', '.join(missing)}", file=sys.stderr)
        return 1
    n = result["n_devices"]
    if not isinstance(n, int) or n < 1:
        print(f"multichip-smoke: implausible n_devices {n!r}", file=sys.stderr)
        return 1
    if not _mesh_label_ok(result["mesh"], n):
        print(
            f"multichip-smoke: mesh label {result['mesh']!r} is not a (data x seq) factorization of "
            f"{n} device(s)",
            file=sys.stderr,
        )
        return 1
    if result["bit_identical"] is not True:
        print("multichip-smoke: mesh data path is not bit-identical to the host pipeline", file=sys.stderr)
        return 1
    if not isinstance(result["ref_segments"], int) or result["ref_segments"] <= 0:
        print(
            f"multichip-smoke: near-duplicate produced {result['ref_segments']!r} REF segments "
            "(dedup inactive on the mesh path?)",
            file=sys.stderr,
        )
        return 1
    print(
        f"multichip-smoke OK: mesh {result['mesh']} over {n} device(s), "
        f"{result['prod_batch']}x{result['prod_chunk_mb']}MiB production batch bit-identical, "
        f"{result['ref_segments']} REF segments on the near-dup"
    )
    return 0


def main(argv) -> int:
    if len(argv) != 2:
        print("usage: check_bench_json.py <bench-output-file>", file=sys.stderr)
        return 2
    try:
        lines = [ln for ln in open(argv[1]).read().splitlines() if ln.strip()]
    except OSError as e:
        print(f"bench-smoke: cannot read output: {e}", file=sys.stderr)
        return 1
    if not lines:
        print("bench-smoke: bench.py produced no output line", file=sys.stderr)
        return 1
    results = []
    for ln in lines:
        try:
            parsed = json.loads(ln)
        except json.JSONDecodeError:
            print(f"bench-smoke: non-JSON stdout line: {ln[:200]!r}", file=sys.stderr)
            return 1
        if isinstance(parsed, dict) and "metric" in parsed:
            results.append(parsed)
    if len(results) != 1:
        print(f"bench-smoke: expected exactly ONE result line, found {len(results)}", file=sys.stderr)
        return 1
    result = results[0]
    if result.get("metric") == "multijob_gbps":
        return check_multijob(result)
    if result.get("metric") == "chaos_gbps":
        return check_chaos(result)
    if result.get("metric") == "fleet_telemetry":
        return check_fleet(result)
    if result.get("metric") == "service_jobs":
        return check_service(result)
    if result.get("metric") == "timeline_overhead":
        return check_timeline(result)
    if result.get("metric") == "blast_soak":
        return check_blast(result)
    if result.get("metric") == "fabric_soak":
        return check_fabric(result)
    if result.get("metric") == "spmd_scaling":
        return check_spmd(result)
    if result.get("metric") == "multichip":
        return check_multichip(result)
    missing = [k for k in REQUIRED_TOP if k not in result]
    counters = result.get("datapath_counters")
    if not isinstance(counters, dict):
        missing.append("datapath_counters(dict)")
    else:
        missing += [f"datapath_counters.{k}" for k in REQUIRED_COUNTERS if k not in counters]
    dec = result.get("decode_counters")
    if not isinstance(dec, dict):
        missing.append("decode_counters(dict)")
    else:
        missing += [f"decode_counters.{k}" for k in REQUIRED_DECODE_COUNTERS if k not in dec]
    wire = result.get("wire_counters")
    if not isinstance(wire, dict):
        missing.append("wire_counters(dict)")
    else:
        missing += [f"wire_counters.{k}" for k in REQUIRED_WIRE_COUNTERS if k not in wire]
    stages = result.get("stage_latency_us")
    if not isinstance(stages, dict):
        missing.append("stage_latency_us(dict)")
    else:
        missing += [f"stage_latency_us.{k}" for k in REQUIRED_STAGES if k not in stages]
    cpu = result.get("cpu_breakdown")
    if not isinstance(cpu, dict):
        missing.append("cpu_breakdown(dict)")
    else:
        missing += [f"cpu_breakdown.{k}" for k in REQUIRED_CPU_BREAKDOWN if k not in cpu]
        cpu_stages = cpu.get("stage_cpu_s")
        if not isinstance(cpu_stages, dict):
            missing.append("cpu_breakdown.stage_cpu_s(dict)")
        else:
            missing += [f"cpu_breakdown.stage_cpu_s.{k}" for k in REQUIRED_CPU_STAGES if k not in cpu_stages]
    if missing:
        print(f"bench-smoke: result missing keys: {', '.join(missing)}", file=sys.stderr)
        return 1
    if not isinstance(result["value"], (int, float)) or result["value"] <= 0:
        print(f"bench-smoke: implausible throughput value {result['value']!r}", file=sys.stderr)
        return 1
    if not isinstance(result["decode_gbps"], (int, float)) or result["decode_gbps"] <= 0:
        print(f"bench-smoke: implausible decode throughput {result['decode_gbps']!r}", file=sys.stderr)
        return 1
    # acceptance gate for the pipelined sender wire engine: the continuous
    # stream must actually pipeline, and its per-window transmit-idle time
    # must beat the serial path's frame+ack drain on the loopback bench
    if not wire["frames_pipelined"]:
        print("bench-smoke: wire engine reported zero frames_pipelined (stream did not overlap)", file=sys.stderr)
        return 1
    if wire["wire_stall_ns_per_window"] >= wire["serial_drain_ns_per_window"]:
        print(
            f"bench-smoke: pipelined stall {wire['wire_stall_ns_per_window']}ns/window is not "
            f"below the serial drain {wire['serial_drain_ns_per_window']}ns/window",
            file=sys.stderr,
        )
        return 1
    # observability acceptance gate: the no-op span path (tracing disabled)
    # must cost < MAX_TRACE_OVERHEAD_PCT of loopback wire-bench throughput —
    # measured directly from the disabled span's per-call cost so the gate
    # is deterministic, not wall-clock noise between two runs
    overhead = result["trace_overhead_pct"]
    if not isinstance(overhead, (int, float)) or overhead < 0 or overhead >= MAX_TRACE_OVERHEAD_PCT:
        print(
            f"bench-smoke: disabled-tracer overhead {overhead!r}% breaches the "
            f"{MAX_TRACE_OVERHEAD_PCT}% instrumentation budget",
            file=sys.stderr,
        )
        return 1
    # core-time attribution gates (ISSUE 12): the profile must hold real
    # samples, a sane GIL fraction, a positive core count, and a measured
    # sampler cost under the always-on budget
    if cpu["profile_samples"] <= 0:
        print("bench-smoke: cpu_breakdown holds zero profile samples (sampler never ran)", file=sys.stderr)
        return 1
    gil = cpu["gil_wait_fraction"]
    if not isinstance(gil, (int, float)) or gil < 0.0 or gil > 1.0:
        print(f"bench-smoke: implausible gil_wait_fraction {gil!r} (must be 0..1)", file=sys.stderr)
        return 1
    cores = cpu["cores_effective"]
    if not isinstance(cores, (int, float)) or cores <= 0.0:
        print(f"bench-smoke: implausible cores_effective {cores!r}", file=sys.stderr)
        return 1
    p_overhead = cpu["profile_overhead_pct"]
    if not isinstance(p_overhead, (int, float)) or p_overhead < 0 or p_overhead >= MAX_PROFILE_OVERHEAD_PCT:
        print(
            f"bench-smoke: sampling-profiler overhead {p_overhead!r}% breaches the "
            f"{MAX_PROFILE_OVERHEAD_PCT}% always-on budget (one-core share at "
            f"{cpu.get('profile_hz')!r} Hz)",
            file=sys.stderr,
        )
        return 1
    # multi-process pump scaling gates (ISSUE 13, docs/benchmark.md): every
    # swept proc count must report a positive Gbps; on runners with the
    # cores to show it, scaling must be monotonic (within measurement
    # tolerance), clear the 2 Gbps floor at 4 procs, and the merged
    # parent+worker profile must prove > 1.5 cores effectively used.
    # Small runners (pump_cores_available < 4) downgrade gracefully to the
    # schema + sanity checks — a 1-core container cannot demonstrate scaling.
    pump_g = result["wire_gbps_by_procs"]
    if not isinstance(pump_g, dict):
        print(f"bench-smoke: wire_gbps_by_procs must be a dict, got {pump_g!r}", file=sys.stderr)
        return 1
    missing_pump = [k for k in PUMP_PROC_KEYS if k not in pump_g]
    if missing_pump:
        print(f"bench-smoke: wire_gbps_by_procs missing proc counts: {missing_pump}", file=sys.stderr)
        return 1
    bad_pump = {k: pump_g[k] for k in PUMP_PROC_KEYS if not isinstance(pump_g[k], (int, float)) or pump_g[k] <= 0}
    if bad_pump:
        print(f"bench-smoke: implausible pump throughput(s): {bad_pump}", file=sys.stderr)
        return 1
    pump_cores = result["pump_cores_available"]
    pump_note = f"(cores_available={pump_cores}: scaling gates downgraded)"
    if isinstance(pump_cores, (int, float)) and pump_cores >= 2:
        if pump_g["2"] < PUMP_MONOTONIC_TOLERANCE * pump_g["1"]:
            print(
                f"bench-smoke: pump throughput regressed 1->2 procs ({pump_g['1']} -> {pump_g['2']} Gbps) "
                f"on a {pump_cores}-core runner",
                file=sys.stderr,
            )
            return 1
        pump_note = f"(cores_available={pump_cores}: 4-proc gates downgraded)"
    if isinstance(pump_cores, (int, float)) and pump_cores >= 4:
        if pump_g["4"] < PUMP_MONOTONIC_TOLERANCE * pump_g["2"]:
            print(
                f"bench-smoke: pump throughput regressed 2->4 procs ({pump_g['2']} -> {pump_g['4']} Gbps) "
                f"on a {pump_cores}-core runner",
                file=sys.stderr,
            )
            return 1
        if pump_g["4"] < MIN_PUMP_GBPS_AT_4:
            print(
                f"bench-smoke: pump throughput at 4 procs is {pump_g['4']} Gbps, below the "
                f"{MIN_PUMP_GBPS_AT_4} Gbps acceptance floor (cores_available={pump_cores})",
                file=sys.stderr,
            )
            return 1
        eff = result["pump_cores_effective"]
        if not isinstance(eff, (int, float)) or eff <= MIN_PUMP_CORES_EFFECTIVE:
            print(
                f"bench-smoke: merged pump cores_effective {eff!r} does not clear the "
                f"{MIN_PUMP_CORES_EFFECTIVE} floor — the single-core ceiling did not break",
                file=sys.stderr,
            )
            return 1
        pump_note = f"(cores_available={pump_cores}, cores_effective={result['pump_cores_effective']})"
    # checkpoint-blast fan-out gate (docs/blast.md): the bench's small
    # loopback blast is kill-free, so source egress must sit at ~1x the
    # corpus — the 1.5x bound here catches a tree that degraded to direct
    # multicast (ratio ~= n_sinks) long before the full soak runs
    blast_ratio = result["blast_egress_ratio"]
    if not isinstance(blast_ratio, (int, float)) or blast_ratio <= 0 or blast_ratio > MAX_BLAST_EGRESS_RATIO:
        print(
            f"bench-smoke: blast egress ratio {blast_ratio!r} over {result['blast_sinks']} sinks breaches "
            f"the {MAX_BLAST_EGRESS_RATIO}x bound (counter-measured; docs/blast.md)",
            file=sys.stderr,
        )
        return 1
    # raw-forward fast path gates (docs/datapath-performance.md "Raw-forward
    # fast path"): the identical interior-edge workload must actually splice
    # (wire_raw_frames covers every re-serve pass) with zero fallbacks, and
    # on runners with a core for the consuming receiver the spliced legs
    # must beat codec re-framing by MIN_RAW_RELAY_RATIO. Single-vCPU
    # runners can only show the copy win diluted by the shared core, so
    # they downgrade to raw > codec.
    raw_g, codec_g = result["relay_gbps_raw"], result["relay_gbps_codec"]
    for key, val in (("relay_gbps_raw", raw_g), ("relay_gbps_codec", codec_g)):
        if not isinstance(val, (int, float)) or val <= 0:
            print(f"bench-smoke: implausible raw-forward throughput {key}={val!r}", file=sys.stderr)
            return 1
    min_raw_frames = result["raw_chunks"] * (result["raw_fanout"] - 1)
    if result["wire_raw_frames"] < min_raw_frames:
        print(
            f"bench-smoke: raw-forward leg spliced only {result['wire_raw_frames']} frames "
            f"(every re-serve pass must go raw: floor {min_raw_frames})",
            file=sys.stderr,
        )
        return 1
    if result["wire_raw_fallbacks"]:
        print(
            f"bench-smoke: {result['wire_raw_fallbacks']} raw->codec fallbacks on a healthy loopback",
            file=sys.stderr,
        )
        return 1
    raw_cores = result["raw_cores_available"]
    if isinstance(raw_cores, (int, float)) and raw_cores >= MIN_RAW_CORES:
        if raw_g < MIN_RAW_RELAY_RATIO * codec_g:
            print(
                f"bench-smoke: raw-forward re-serve at {raw_g} Gbps does not clear "
                f"{MIN_RAW_RELAY_RATIO}x the codec path ({codec_g} Gbps) on a {raw_cores}-core runner",
                file=sys.stderr,
            )
            return 1
        raw_note = f"({round(raw_g / codec_g, 2)}x codec at {raw_cores} cores)"
    else:
        if raw_g <= codec_g:
            print(
                f"bench-smoke: raw-forward re-serve ({raw_g} Gbps) did not beat the codec path "
                f"({codec_g} Gbps) even on a shared core",
                file=sys.stderr,
            )
            return 1
        raw_note = f"(cores_available={raw_cores}: ratio gate downgraded, {round(raw_g / codec_g, 2)}x codec)"
    # device-count context (PR 18): every bench row names its device count
    # and (data x seq) mesh; "1x1" is the unsharded single-device label
    n_dev = result["n_devices"]
    if not isinstance(n_dev, int) or n_dev < 1:
        print(f"bench-smoke: implausible n_devices {n_dev!r}", file=sys.stderr)
        return 1
    if not _mesh_label_ok(result["mesh"], n_dev) and result["mesh"] != "1x1":
        print(
            f"bench-smoke: mesh label {result['mesh']!r} is not a (data x seq) factorization of "
            f"{n_dev} device(s)",
            file=sys.stderr,
        )
        return 1
    # SPMD device-scaling gates (ISSUE 18, docs/datapath-performance.md
    # "SPMD device data path"): positive Gbps at every swept device count,
    # byte-identity vs the host kernels, monotonic scaling within tolerance
    # where cores allow, and the 1.6x floor at 4 devices
    spmd_note = _gate_spmd(result, "bench-smoke")
    if spmd_note is None:
        return 1
    print(
        f"bench-smoke OK: {result['value']} {result['unit']} encode, "
        f"{result['decode_gbps']} {result['unit']} decode on {result['platform']} "
        f"({result['device_kind']}); wire: {wire['frames_pipelined']} frames pipelined, "
        f"stall {wire['wire_stall_ns_per_window']}ns/window vs serial drain {wire['serial_drain_ns_per_window']}ns/window; "
        f"trace overhead {overhead}%; cpu profile: {cpu['profile_samples']} samples, "
        f"{cores} cores effective, GIL wait {round(100.0 * gil, 1)}%, sampler overhead {p_overhead}%; "
        f"pump: {pump_g} Gbps by procs {pump_note}; "
        f"blast: {blast_ratio}x source egress over {result['blast_sinks']} sinks; "
        f"raw-forward: {raw_g} vs {codec_g} Gbps, {result['wire_raw_frames']} frames spliced {raw_note}; "
        f"devices: {n_dev} (mesh {result['mesh']}); "
        f"spmd: {result['spmd_gbps_by_devices']} Gbps by devices {spmd_note}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
