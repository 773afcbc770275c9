#!/bin/bash
# CPU gates for a development loop: lint, the tier-1 suite, and each smoke
# (bench schema, monitor, soaks, chaos, pump, raw-forward, spmd) with its
# check_*_json.py gate. Everything here runs with JAX_PLATFORMS=cpu; the chip
# is exercised separately, by `python chip_smoke.py` in one process.
#
# Usage: bash scripts/devloop.sh
set -u
cd "$(dirname "$0")/.."
LOGDIR=/tmp/devlogs
mkdir -p "$LOGDIR"

# Static-analysis gate (CPU-only, cheap — content-hash cached, so an
# unchanged tree costs milliseconds): same pass tier-1 runs in
# tests/unit/test_static_analysis.py. --check-suppressions makes a stale
# `# sklint: disable` fail this step loudly instead of rotting in place.
# Emits the machine-readable findings report for BENCH/soak tooling;
# failures are logged LOUDLY but do not block the later steps — the
# pytest gate is what blocks a merge.
JAX_PLATFORMS=cpu python -m skyplane_tpu.analysis skyplane_tpu \
  --check-suppressions \
  --json "$LOGDIR/lint_findings.json" >"$LOGDIR/lint.out" 2>&1
LINT_RC=$?
if [ "$LINT_RC" -ne 0 ]; then
  echo "[devloop] LINT FAILURES (rc=$LINT_RC) — fix or suppress before merging; see $LOGDIR/lint.out" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] lint clean; report at $LOGDIR/lint_findings.json" >>"$LOGDIR/devloop.log"
fi

# Provisioning-test gate (CPU-only, seconds, zero network): the stubbed-SDK
# control-plane suite — AWS instance-profile attach, GCP service-account
# scopes, Azure identity + UnsupportedProviderError, start_gateway
# credential staging, the provisioning state machine's retry/fallback
# ladder, the pricing-grid MILP pin test, and the replan monitor
# (docs/provisioning.md). Like lint: failures are logged LOUDLY but do not
# block the later steps — the pytest gate is what blocks a merge.
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
  tests/unit/test_provision_lifecycle.py tests/unit/test_pricing_grid.py tests/unit/test_replan.py \
  tests/unit/test_aws_provider_stubbed.py tests/unit/test_gcp_provider_stubbed.py \
  tests/unit/test_azure_provider_stubbed.py \
  >"$LOGDIR/provision_tests.out" 2>&1
PROVISION_RC=$?
if [ "$PROVISION_RC" -ne 0 ]; then
  echo "[devloop] PROVISION-TEST FAILURES (rc=$PROVISION_RC) — control-plane contracts regressed; see $LOGDIR/provision_tests.out" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] provision-tests clean; report at $LOGDIR/provision_tests.out" >>"$LOGDIR/devloop.log"
fi

# Bench-smoke gate (CPU-only, seconds): bench.py on a tiny corpus — the
# sender encode bench, the receiver decode bench (decode_gbps +
# decode_counters), and the loopback sender wire bench (wire_counters:
# serial-vs-pipelined drain comparison) — then validate the JSON result line
# and ALL THREE perf-counter schemas plus the device-provenance field
# (docs/datapath-performance.md). Catches a malformed result, a dropped
# counter key, or a wire engine that stopped pipelining BEFORE a multi-hour
# real bench run discovers it. Like lint: failures are logged LOUDLY but do not block
# the later steps.
JAX_PLATFORMS=cpu \
  SKYPLANE_BENCH_CHUNK_MB=1 SKYPLANE_BENCH_SNAPSHOTS=2 SKYPLANE_BENCH_SNAP_CHUNKS=2 SKYPLANE_BENCH_REPS=1 \
  SKYPLANE_BENCH_DECODE_WORKERS=4 SKYPLANE_BENCH_PUMP_MB=4 SKYPLANE_BENCH_BLAST_MB=2 \
  SKYPLANE_BENCH_TRACE_OUT="$LOGDIR/trace_smoke.json" \
  SKYPLANE_BENCH_PROFILE_OUT="$LOGDIR/profile_smoke.speedscope.json" \
  python bench.py >"$LOGDIR/bench_smoke.out" 2>"$LOGDIR/bench_smoke.err"
BENCH_RC=$?
if [ "$BENCH_RC" -eq 0 ]; then
  python scripts/check_bench_json.py "$LOGDIR/bench_smoke.out" >>"$LOGDIR/devloop.log" 2>&1
  BENCH_RC=$?
fi
if [ "$BENCH_RC" -ne 0 ]; then
  echo "[devloop] BENCH-SMOKE FAILURE (rc=$BENCH_RC) — bench.py output malformed or counter keys missing; see $LOGDIR/bench_smoke.err" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] bench-smoke clean; result at $LOGDIR/bench_smoke.out" >>"$LOGDIR/devloop.log"
fi

# Trace-smoke gate (CPU-only, part of the same bench run): the fully-sampled
# loopback transfer inside bench.py exports Chrome trace-event JSON
# (SKYPLANE_BENCH_TRACE_OUT above); validate schema, span nesting, and the
# sender<->receiver chunk-id stitching (docs/observability.md). Catches a
# tracer/export/flag-propagation regression before anyone opens Perfetto on
# a multi-hour run and finds an empty or unstitched trace.
python scripts/check_trace_json.py "$LOGDIR/trace_smoke.json" >>"$LOGDIR/devloop.log" 2>&1
TRACE_RC=$?
if [ "$TRACE_RC" -ne 0 ]; then
  echo "[devloop] TRACE-SMOKE FAILURE (rc=$TRACE_RC) — exported trace invalid; see $LOGDIR/trace_smoke.json" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] trace-smoke clean; trace at $LOGDIR/trace_smoke.json" >>"$LOGDIR/devloop.log"
fi

# Profile-smoke gate (CPU-only, part of the same bench run): bench.py's
# cpu-profile pass runs the sampling profiler (obs/profiler.py) over a
# fully-sampled loopback transfer and exports speedscope JSON
# (SKYPLANE_BENCH_PROFILE_OUT above); validate the export schema here
# (scripts/check_speedscope_json.py: frames table, sampled profiles,
# in-range indices, nonzero sample weight). The cpu_breakdown keys and the
# <2% sampler-overhead gate already ride the bench-smoke check above
# (scripts/check_bench_json.py REQUIRED_CPU_BREAKDOWN /
# MAX_PROFILE_OVERHEAD_PCT). Catches a profiler-export regression before an
# operator drops an empty flame graph on speedscope mid-incident.
python scripts/check_speedscope_json.py "$LOGDIR/profile_smoke.speedscope.json" \
  --min-samples 16 >>"$LOGDIR/devloop.log" 2>&1
PROFILE_RC=$?
if [ "$PROFILE_RC" -ne 0 ]; then
  echo "[devloop] PROFILE-SMOKE FAILURE (rc=$PROFILE_RC) — speedscope export invalid or sampler never ran; see $LOGDIR/profile_smoke.speedscope.json" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] profile-smoke clean; speedscope at $LOGDIR/profile_smoke.speedscope.json" >>"$LOGDIR/devloop.log"
fi

# Monitor-smoke gate (CPU-only, seconds): the fleet telemetry plane end to
# end (scripts/monitor_smoke.py, docs/observability.md) — a fully-sampled
# loopback 2-hop relay transfer (src -> relay -> dst) with one armed fault,
# scraped live by the TelemetryCollector: the merged multi-gateway timeline
# must pass check_trace_json --multihop (same chunk on source, relay AND
# destination rows, sender hops 0+1), the flight-recorder fleet log must hold
# the transfer lifecycle plus the fault firing in seq order, and the
# bottleneck attribution must reconcile with the local trace within 10% with
# collector overhead < 2%/cycle (fleet branch of check_bench_json.py). Like
# the other smokes: failures are logged LOUDLY but do not block profiling.
JAX_PLATFORMS=cpu SKYPLANE_MONITOR_TRACE_OUT="$LOGDIR/monitor_trace.json" \
  python scripts/monitor_smoke.py >"$LOGDIR/monitor_smoke.out" 2>"$LOGDIR/monitor_smoke.err"
MONITOR_RC=$?
if [ "$MONITOR_RC" -eq 0 ]; then
  python scripts/check_bench_json.py "$LOGDIR/monitor_smoke.out" >>"$LOGDIR/devloop.log" 2>&1
  MONITOR_RC=$?
fi
if [ "$MONITOR_RC" -eq 0 ]; then
  python scripts/check_trace_json.py "$LOGDIR/monitor_trace.json" --multihop >>"$LOGDIR/devloop.log" 2>&1
  MONITOR_RC=$?
fi
if [ "$MONITOR_RC" -ne 0 ]; then
  echo "[devloop] MONITOR-SMOKE FAILURE (rc=$MONITOR_RC) — collector merge, multihop stitching, fleet log, or bottleneck gates regressed; see $LOGDIR/monitor_smoke.err" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] monitor-smoke clean; result at $LOGDIR/monitor_smoke.out, merged trace at $LOGDIR/monitor_trace.json" >>"$LOGDIR/devloop.log"
fi

# Timeline-smoke gate (CPU-only, ~1 min): the job-timeline / critical-path
# attribution engine (obs/timeline.py, docs/observability.md "Job timelines
# & critical path") — bench_e2e.py --timeline-only sweeps a loopback tracker
# transfer across 3 corpus sizes, each fully sampled into a fleet event log,
# and banks e2e_fixed_overhead_s (the wall = overhead + bytes/rate fit) plus
# timeline_critical_path_s. The timeline branch of check_bench_json.py gates
# the keys present, the critical path explaining 90-100% of the timeline
# wall, a named largest fixed-cost phase, and the fixed overhead under the
# banked 2.0 s baseline. Like the other smokes: failures are logged LOUDLY
# but do not block the later steps.
JAX_PLATFORMS=cpu python scripts/bench_e2e.py --timeline-only \
  --timeline-sizes-mb 1,2,4 >"$LOGDIR/timeline_smoke.out" 2>"$LOGDIR/timeline_smoke.err"
TIMELINE_RC=$?
if [ "$TIMELINE_RC" -eq 0 ]; then
  python scripts/check_bench_json.py "$LOGDIR/timeline_smoke.out" >>"$LOGDIR/devloop.log" 2>&1
  TIMELINE_RC=$?
fi
if [ "$TIMELINE_RC" -ne 0 ]; then
  echo "[devloop] TIMELINE-SMOKE FAILURE (rc=$TIMELINE_RC) — critical-path coverage, overhead fit, or attribution keys regressed; see $LOGDIR/timeline_smoke.err" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] timeline-smoke clean; result at $LOGDIR/timeline_smoke.out" >>"$LOGDIR/devloop.log"
fi

# Multijob-smoke gate (CPU-only, ~1 min): >= 8 concurrent tenants over the
# loopback stack (scripts/soak_multijob.py) — per-tenant Gbps split must stay
# within the 2x fairness bound for equal weights, index RSS bounded, no fd
# growth, and the per-tenant accounting keys present (docs/multitenancy.md).
# Validated by the multijob branch of check_bench_json.py. Like lint/bench:
# failures are logged LOUDLY but do not block the later steps.
JAX_PLATFORMS=cpu SKYPLANE_SOAK_JOBS=8 SKYPLANE_SOAK_MB_PER_JOB=2 \
  python scripts/soak_multijob.py >"$LOGDIR/multijob_smoke.out" 2>"$LOGDIR/multijob_smoke.err"
MULTIJOB_RC=$?
if [ "$MULTIJOB_RC" -eq 0 ]; then
  python scripts/check_bench_json.py "$LOGDIR/multijob_smoke.out" >>"$LOGDIR/devloop.log" 2>&1
  MULTIJOB_RC=$?
fi
if [ "$MULTIJOB_RC" -ne 0 ]; then
  echo "[devloop] MULTIJOB-SMOKE FAILURE (rc=$MULTIJOB_RC) — fairness split, tenant keys, or leak gates regressed; see $LOGDIR/multijob_smoke.err" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] multijob-smoke clean; result at $LOGDIR/multijob_smoke.out" >>"$LOGDIR/devloop.log"
fi

# Service-smoke gate (CPU-only, ~1-2 min): the always-on replication service
# (skyplane_tpu/service/, docs/service-mode.md) — one standing loopback
# fleet, >= 50 sequential + >= 8 concurrent warm jobs (p50 start gated < 1 s,
# warm dedup hit rate gated > cold), continuous-sync delta rounds, then the
# crash lab: a worker controller SIGKILLed mid-job, its WAL tail torn, a
# service.crash fault fired inside recovery itself — and the restarted
# controller must finish byte-identical with zero acked-chunk loss, zero
# duplicate sink registrations, a deterministic WAL->POST-window requeue,
# and an idempotent resubmission (service branch of check_bench_json.py).
# Like the other smokes: failures are logged LOUDLY but do not block
# the later steps.
JAX_PLATFORMS=cpu SKYPLANE_SERVICE_SEQ_JOBS=50 SKYPLANE_SERVICE_CONC_JOBS=8 \
  python scripts/soak_service.py >"$LOGDIR/service_smoke.out" 2>"$LOGDIR/service_smoke.err"
SERVICE_RC=$?
if [ "$SERVICE_RC" -eq 0 ]; then
  python scripts/check_bench_json.py "$LOGDIR/service_smoke.out" >>"$LOGDIR/devloop.log" 2>&1
  SERVICE_RC=$?
fi
if [ "$SERVICE_RC" -ne 0 ]; then
  echo "[devloop] SERVICE-SMOKE FAILURE (rc=$SERVICE_RC) — warm-start, dedup-warmth, or WAL-recovery gates regressed; see $LOGDIR/service_smoke.err" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] service-smoke clean; result at $LOGDIR/service_smoke.out" >>"$LOGDIR/devloop.log"
fi

# Blast-smoke gate (CPU-only, ~1 min): the checkpoint-blast fan-out soak
# (scripts/soak_blast.py, docs/blast.md) at smoke scale — 1 source -> 8
# peered sink daemons over a planner-placed relay tree, the first relay
# hard-killed mid-blast with the relay.peer_serve fault armed. Gates
# (blast branch of check_bench_json.py): every sink byte-identical, the
# tree healed (replacement + retarget + re-drive), source egress
# counter-measured <= 1.5x the corpus, zero acked-chunk loss, zero
# duplicate sink registrations, blast.* lifecycle events recorded. Like
# the other smokes: failures are logged LOUDLY but do not block profiling.
JAX_PLATFORMS=cpu SKYPLANE_BLAST_SINKS=8 SKYPLANE_BLAST_MB=16 \
  python scripts/soak_blast.py >"$LOGDIR/blast_smoke.out" 2>"$LOGDIR/blast_smoke.err"
BLAST_RC=$?
if [ "$BLAST_RC" -eq 0 ]; then
  python scripts/check_bench_json.py "$LOGDIR/blast_smoke.out" >>"$LOGDIR/devloop.log" 2>&1
  BLAST_RC=$?
fi
if [ "$BLAST_RC" -ne 0 ]; then
  echo "[devloop] BLAST-SMOKE FAILURE (rc=$BLAST_RC) — fan-out integrity, egress ratio, or healing gates regressed; see $LOGDIR/blast_smoke.err" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] blast-smoke clean; result at $LOGDIR/blast_smoke.out" >>"$LOGDIR/devloop.log"
fi

# Fabric-smoke gate (CPU-only, ~1 min): the fleet-wide dedup fabric
# (skyplane_tpu/dedup_fabric/, docs/dedup-fabric.md) — two src->dst pairs
# whose receivers form one consistent-hash ring sync overlapping corpora:
# write-through placement, one gossip round, then the warm probe (corpus A
# re-sent through pair B) which must hit >= 90% cross-gateway REFs with >= 1
# peer fetch actually served, a cross-shard NACK rate under the PR-13
# literal-resend tolerance, byte-identical outputs, and bounded fd growth
# (fabric branch of check_bench_json.py). The fabric.peer_fetch fault rung
# rides the chaos smoke below. Like the other smokes: failures are logged
# LOUDLY but do not block the later steps.
JAX_PLATFORMS=cpu SKYPLANE_FABRIC_MB=4 SKYPLANE_FABRIC_UNIQUE_MB=1 \
  python scripts/soak_dedup_fabric.py >"$LOGDIR/fabric_smoke.out" 2>"$LOGDIR/fabric_smoke.err"
FABRIC_RC=$?
if [ "$FABRIC_RC" -eq 0 ]; then
  python scripts/check_bench_json.py "$LOGDIR/fabric_smoke.out" >>"$LOGDIR/devloop.log" 2>&1
  FABRIC_RC=$?
fi
if [ "$FABRIC_RC" -ne 0 ]; then
  echo "[devloop] FABRIC-SMOKE FAILURE (rc=$FABRIC_RC) — warm-hit, peer-fetch, NACK-rate, or integrity gates regressed; see $LOGDIR/fabric_smoke.err" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] fabric-smoke clean; result at $LOGDIR/fabric_smoke.out" >>"$LOGDIR/devloop.log"
fi

# Chaos-smoke gate (CPU-only, ~1-2 min): the deterministic fault-injection soak
# plus the capacity-repair scenarios (docs/provisioning.md "Repair & drain"):
# gateway death -> requeue-to-survivor, kill-one-of-two -> replacement
# provisioned + re-sharded with throughput recovery gated >= 0.8x pre-kill,
# preempt notice -> graceful drain under its deadline with zero acked-chunk
# loss, and an injected ack-lag-dominant hop -> replan APPLIED over a clean
# stream cutover (replacement_*/drain_*/replan_* keys required by the chaos
# branch of check_bench_json.py)
# (scripts/soak_chaos.py, fixed seed, small corpus) — >= 5 distinct fault
# points fire across the sender wire path / receiver framing / decode pool /
# scheduler / control API / persistent journal, and the run must finish with
# byte-identical outputs, seed-replay determinism, zero leaked tokens/buffers,
# and bounded recovery time (docs/fault-injection.md). Validated by the chaos
# branch of check_bench_json.py. Like the other smokes: failures are logged
# LOUDLY but do not block the later steps.
JAX_PLATFORMS=cpu SKYPLANE_CHAOS_JOBS=4 SKYPLANE_CHAOS_MB_PER_JOB=2 \
  python scripts/soak_chaos.py --seed 1337 >"$LOGDIR/chaos_smoke.out" 2>"$LOGDIR/chaos_smoke.err"
CHAOS_RC=$?
if [ "$CHAOS_RC" -eq 0 ]; then
  python scripts/check_bench_json.py "$LOGDIR/chaos_smoke.out" >>"$LOGDIR/devloop.log" 2>&1
  CHAOS_RC=$?
fi
if [ "$CHAOS_RC" -ne 0 ]; then
  echo "[devloop] CHAOS-SMOKE FAILURE (rc=$CHAOS_RC) — fault recovery, integrity, or leak gates regressed; see $LOGDIR/chaos_smoke.err" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] chaos-smoke clean; result at $LOGDIR/chaos_smoke.out" >>"$LOGDIR/devloop.log"
fi

# Lockcheck gate (CPU-only, ~2-3 min): the runtime lock-order witness
# (SKYPLANE_TPU_LOCKCHECK=1, obs/lockwitness.py, docs/debugging.md "deadlock
# triage") armed over (a) the tier-1 integration suite and (b) a chaos-smoke
# rerun. Every wrapped lock records into the observed acquisition-order
# graph and RAISES with both witness stacks the moment an acquisition would
# close a cycle — so any run that merely *permits* an ABBA deadlock fails
# loudly here instead of hanging a fleet at 3am. The chaos rerun must stay
# byte-identical with an acyclic observed graph and measured witness
# overhead < 5% (lockcheck_* keys in the chaos branch of
# check_bench_json.py). Like the other smokes: failures are logged LOUDLY
# but do not block the later steps.
JAX_PLATFORMS=cpu SKYPLANE_TPU_LOCKCHECK=1 python -m pytest -q -p no:cacheprovider \
  tests/integration >"$LOGDIR/lockcheck_tests.out" 2>&1
LOCKTEST_RC=$?
if [ "$LOCKTEST_RC" -ne 0 ]; then
  echo "[devloop] LOCKCHECK-TESTS FAILURE (rc=$LOCKTEST_RC) — a lock-order violation (or regression) under the witness; see $LOGDIR/lockcheck_tests.out" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] lockcheck integration tests clean; report at $LOGDIR/lockcheck_tests.out" >>"$LOGDIR/devloop.log"
fi
JAX_PLATFORMS=cpu SKYPLANE_TPU_LOCKCHECK=1 SKYPLANE_CHAOS_JOBS=4 SKYPLANE_CHAOS_MB_PER_JOB=2 \
  python scripts/soak_chaos.py --seed 1337 >"$LOGDIR/lockcheck_smoke.out" 2>"$LOGDIR/lockcheck_smoke.err"
LOCKCHECK_RC=$?
if [ "$LOCKCHECK_RC" -eq 0 ]; then
  python scripts/check_bench_json.py "$LOGDIR/lockcheck_smoke.out" >>"$LOGDIR/devloop.log" 2>&1
  LOCKCHECK_RC=$?
fi
if [ "$LOCKCHECK_RC" -ne 0 ]; then
  echo "[devloop] LOCKCHECK-SMOKE FAILURE (rc=$LOCKCHECK_RC) — lock-order cycle, witness overhead, or chaos gates regressed under SKYPLANE_TPU_LOCKCHECK=1; see $LOGDIR/lockcheck_smoke.err" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] lockcheck-smoke clean; result at $LOGDIR/lockcheck_smoke.out" >>"$LOGDIR/devloop.log"
fi

# Pump-smoke gate (CPU-only, minutes): the tier-1 integration suite rerun
# with the multi-process byte pump armed (SKYPLANE_TPU_PUMP_PROCS=2,
# gateway/pump.py, docs/datapath-performance.md "Multi-process pump") — the
# full data plane must behave identically when receiver decode and sender
# framing/wire work shard across spawn-context worker processes: fd-passed
# sockets, control-channel chunk accounting, worker telemetry muxing. A
# regression here (stranded chunk, double accounting, worker wedge) is the
# class of bug only the end-to-end suite catches. Like the other smokes:
# failures are logged LOUDLY but do not block the later steps.
JAX_PLATFORMS=cpu SKYPLANE_TPU_PUMP_PROCS=2 python -m pytest -q -m 'not slow' -p no:cacheprovider \
  tests/integration >"$LOGDIR/pump_tests.out" 2>&1
PUMP_RC=$?
if [ "$PUMP_RC" -ne 0 ]; then
  echo "[devloop] PUMP-SMOKE FAILURE (rc=$PUMP_RC) — integration suite regressed under SKYPLANE_TPU_PUMP_PROCS=2; see $LOGDIR/pump_tests.out" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] pump-smoke clean; report at $LOGDIR/pump_tests.out" >>"$LOGDIR/devloop.log"
fi

# Raw-smoke gate (CPU-only, ~1 min): the raw-forward fast path
# (docs/datapath-performance.md "Raw-forward fast path"). Two halves:
# (a) the raw-forward unit suite — byte-identical sendfile-vs-codec wire
# output, the RawSendError fallback truth table, sealed-cache refcount/GC,
# and the copy-free vectored send; (b) the integration suite rerun with the
# SKYPLANE_TPU_RAW_FORWARD=0 kill switch — the codec path must stand alone
# when raw forwarding is disabled in the field, with nothing keyed on the
# sealed cache. (The default-ON raw path already rides every other smoke
# and tier-1.) Like the other smokes: failures are logged LOUDLY but do not block
# the later steps.
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
  tests/unit/test_raw_forward.py >"$LOGDIR/raw_tests.out" 2>&1
RAW_RC=$?
if [ "$RAW_RC" -eq 0 ]; then
  JAX_PLATFORMS=cpu SKYPLANE_TPU_RAW_FORWARD=0 python -m pytest -q -m 'not slow' -p no:cacheprovider \
    tests/integration >"$LOGDIR/raw_killswitch_tests.out" 2>&1
  RAW_RC=$?
fi
if [ "$RAW_RC" -ne 0 ]; then
  echo "[devloop] RAW-SMOKE FAILURE (rc=$RAW_RC) — raw-forward unit suite or the RAW_FORWARD=0 kill-switch rerun regressed; see $LOGDIR/raw_tests.out / $LOGDIR/raw_killswitch_tests.out" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] raw-smoke clean; reports at $LOGDIR/raw_tests.out, $LOGDIR/raw_killswitch_tests.out" >>"$LOGDIR/devloop.log"
fi

# SPMD-smoke gate (CPU-only, ~1 min): the mesh-sharded device data path
# (parallel/datapath_spmd.py, docs/datapath-performance.md "SPMD device data
# path") — bench_spmd_scaling() sweeps the batched CDC+fingerprint runner at
# 1/2/4/8 forced-host devices (capped at the runner's core count), each child
# byte-identity-checked against the host kernels before its timed reps. The
# spmd_scaling branch of check_bench_json.py gates monotonic device scaling
# (0.85 tolerance) and the 1.6x floor at 4 devices, auto-armed at
# spmd_devices_available >= 2 and gracefully downgraded on 1-device runners.
# Like the other smokes: failures are logged LOUDLY but do not block the
# later steps.
JAX_PLATFORMS=cpu SKYPLANE_BENCH_SPMD_MB=1 python -c \
  'import json, bench; print(json.dumps({"metric": "spmd_scaling", **bench.bench_spmd_scaling()}))' \
  >"$LOGDIR/spmd_smoke.out" 2>"$LOGDIR/spmd_smoke.err"
SPMD_RC=$?
if [ "$SPMD_RC" -eq 0 ]; then
  python scripts/check_bench_json.py "$LOGDIR/spmd_smoke.out" >>"$LOGDIR/devloop.log" 2>&1
  SPMD_RC=$?
fi
if [ "$SPMD_RC" -ne 0 ]; then
  echo "[devloop] SPMD-SMOKE FAILURE (rc=$SPMD_RC) — mesh scaling, byte-identity, or schema gates regressed; see $LOGDIR/spmd_smoke.err" >>"$LOGDIR/devloop.log"
else
  echo "[devloop] spmd-smoke clean; result at $LOGDIR/spmd_smoke.out" >>"$LOGDIR/devloop.log"
fi
