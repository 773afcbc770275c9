"""Every data file loads, every name and unit keeps to the contract's
characters, and BENCHMARK.json names only files that are there."""

import json
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
DATA_FILES = sorted(p for d in ("configs", "workloads", "metrics") for p in (BENCH / d).glob("*.json"))


@pytest.mark.parametrize("path", DATA_FILES, ids=lambda p: f"{p.parent.name}/{p.name}")
def test_data_file_loads_and_is_named_after_itself(path):
    data = json.loads(path.read_text())
    assert data["name"] == path.stem and NAME.match(data["name"])
    if path.parent.name == "metrics":
        assert UNIT.match(data["unit"]) and data["source"] in SOURCES and data["better"] in ("lower", "higher")
        assert ("ratio" in data) != ("reader" in data)
        if "reader" in data:
            assert (path.parent / data["reader"]).is_file()
    if path.parent.name == "workloads":
        assert (BENCH / "configs" / f"{data['config']}.json").is_file()
        assert (BENCH / "generators" / f"{data['generator']}.py").is_file()
        traffic = data["traffic"]
        assert set(traffic) <= {"in_flight_chunks", "setup_burst_chunks", "setup_chunks"} and traffic["in_flight_chunks"] >= 1
        burst = traffic.get("setup_burst_chunks", 2)  # optional: the set-up bursts of run.py
        assert type(burst) is int and burst >= 2
        setup = traffic.get("setup_chunks", 1)  # optional: rows landed one at a time before the fill
        assert type(setup) is int and setup >= 1
    if path.parent.name == "configs":
        assert 1 <= len(data["source"]) <= 200 and data["guarantees"] and isinstance(data["reduced"], dict)


def test_benchmark_json_names_files_that_exist_and_agree_with_them():
    assert SPEC["command"] == ["python3", "benchmark/run.py"] and SPEC["paths"] == ["benchmark"]
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        data = json.loads((BENCH.parent / c["file"]).read_text())
        assert data["source"] == c["source"] and sorted(data["reduced"]) == sorted(c["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}" and w["config"] in configs and len(w["why"]) <= 200
        assert json.loads((BENCH / "workloads" / f"{w['name']}.json").read_text())["config"] == w["config"]
    ends = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in ends
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        data = json.loads((BENCH / "metrics" / f"{m['name']}.json").read_text())
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert (data["unit"], data["source"], data["better"]) == (m["unit"], m["source"], m["better"])
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["per_layer"]:
        assert m["moves"] in ends
        assert json.loads((BENCH / "metrics" / f"{m['name']}.json").read_text())["layer"] == m["layer"]
    for m in SPEC["end_to_end"]:
        assert 0 < m["bound"] <= 0.25


def test_run_py_knows_no_cell_configuration_or_metric_by_name():
    text = (BENCH / "run.py").read_text()
    names = [w["name"] for w in SPEC["workloads"]] + [c["name"] for c in SPEC["configs"]]
    names += [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    whole = lambda n: re.search(rf"(?<![A-Za-z0-9_.-]){re.escape(n)}(?![A-Za-z0-9_-])", text)  # noqa: E731
    assert [n for n in names if whole(n)] == []


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_are_errors():
    from lib import roofline

    assert roofline.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")
    assert roofline.least_bytes(64 << 20, 4096, 16384) == (64 << 20) + 4 * (8 * 4096 + 1) + 36 * (16384 + 2)
