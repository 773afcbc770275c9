"""The reduction from a trace record to numbers: on a made-up record, and on
a small one recorded on the chip (tests/data/trace_tpu_v5e.json)."""

import json
from pathlib import Path

import pytest
from lib import trace

DEV = "/device:TPU:0"
RECORD = {
    "device_ops": [
        ["fusion.1", "jit_a", 100, 50, DEV],  # 100-150
        ["fusion.2", "jit_a", 140, 30, DEV],  # 140-170 overlaps the first
        ["fusion.1", "jit_b", 200, 100, DEV],  # 200-300
        ["copy.3", "", 400, 50, DEV],  # 400-450
    ],
    "modules": [["jit_a", 100, 70, DEV], ["jit_b", 200, 100, DEV]],
    "host_spans": [["bench:mark", 90, 1], ["host:fused.dispatch", 170, 25], ["host:batch.window_wait", 300, 95], ["host:fused.readback", 310, 10]],
}


def test_busy_is_the_union_of_the_op_intervals():
    assert trace.busy_ns(RECORD, 0, 500) == 70 + 100 + 50
    # cut to a window: events are clipped at its edges
    assert trace.busy_ns(RECORD, 120, 250) == 50 + 50
    assert trace.busy_ns(RECORD, 1000, 2000) == 0


def test_sums_by_name_carry_the_program_where_it_is_known():
    sums = trace.sums_by_name(RECORD, 0, 500)
    assert sums == {"jit_a/fusion.1": 50, "jit_a/fusion.2": 30, "jit_b/fusion.1": 100, "copy.3": 50}
    assert trace.sums_by_name(RECORD, 0, 500, key="modules") == {"jit_a": 70, "jit_b": 100}
    assert trace.top(sums, 2) == [["jit_b/fusion.1", 100 / 1e9], ["jit_a/fusion.1", 50 / 1e9]]


def test_idle_gaps_go_to_the_host_span_that_covers_most_of_each():
    gaps = trace.idle_gaps(RECORD, 100, 450)
    assert gaps == {"host:fused.dispatch": 30, "host:batch.window_wait": 100}
    assert sum(gaps.values()) == (450 - 100) - trace.busy_ns(RECORD, 100, 450)
    assert trace.idle_gaps(RECORD, 0, 100) == {trace.UNATTRIBUTED: 100}


def test_busy_is_averaged_over_the_devices_that_ran_anything():
    two = dict(RECORD, device_ops=RECORD["device_ops"] + [["fusion.1", "jit_a", 100, 20, "/device:TPU:1"]])
    assert trace.busy_ns(two, 0, 500) == (220 + 20) / 2


def test_the_clock_is_set_by_the_mark():
    assert trace.clock_offset_ns(RECORD, "bench:mark", 1_000_090) == 1_000_000
    with pytest.raises(LookupError):
        trace.clock_offset_ns(RECORD, "bench:absent", 0)


def test_ops_are_named_by_the_program_whose_interval_holds_them():
    ops = [["fusion.1", "", 120, 10, DEV], ["fusion.1", "", 250, 10, DEV], ["copy", "", 390, 5, DEV], ["fusion.1", "known", 120, 10, DEV]]
    trace.attribute_modules(ops, [["jit_b", 200, 100, DEV], ["jit_a", 100, 70, DEV]])
    assert [op[1] for op in ops] == ["jit_a", "jit_b", "", "known"]


def test_op_names_lose_their_shapes():
    assert trace.op_name("%fusion.2 = u32[67108864]{0:T(1024)} fusion(u32[262144]{0:T(1024)} %p)") == "fusion.2"
    assert trace.op_name("fusion.7") == "fusion.7"


RECORDED = Path(__file__).parent / "data" / "trace_tpu_v5e.json"


def test_recorded_chip_trace_reduces_to_what_was_read_from_it():
    rec = json.loads(RECORDED.read_text())
    lo, hi = rec["span"]
    busy = trace.busy_ns(rec, lo, hi)
    assert rec["from_device_plane"] and trace.devices_of(rec) == ["/device:TPU:0"]
    assert 0 < busy <= hi - lo
    expect = rec["expect"]  # written beside the record when it was cut from the run's trace
    assert busy == pytest.approx(expect["busy_ns"], rel=1e-9)
    assert 100 * (1 - busy / (hi - lo)) == pytest.approx(expect["idle_share_percent"], rel=1e-9)
    sums = trace.sums_by_name(rec, lo, hi)
    assert trace.top(sums, 1)[0][0] == expect["top_op"]
    assert sum(sums.values()) >= busy  # operations nest, so their sum is no less than their union
    modules = trace.sums_by_name(rec, lo, hi, key="modules")
    assert sum(modules.values()) == pytest.approx(expect["module_ns"], rel=1e-9) and sum(modules.values()) <= busy * 1.001
    assert sum(trace.idle_gaps(rec, lo, hi).values()) == pytest.approx(hi - lo - busy, rel=1e-6)


def test_a_profile_with_no_device_plane_yields_no_record_but_in_a_rehearsal(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jax.jit(lambda x: (x * 2 + 1).sum())(jnp.arange(1 << 16)).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    with pytest.raises(LookupError, match="no device metric"):
        trace.extract(path)
    rec = trace.extract(path, rehearsal=True)
    assert rec["from_device_plane"] is False and rec["device_ops"]
