"""The snapshot-chain configuration and its generator: the stated change
model is what the generator realises, exactly and in every chunk; the
generator knows nothing of the program; the cell's files say what the
configuration says; and a rehearsal of the cell passes its checks while each
control does not."""

import ast
import json
from fractions import Fraction
from pathlib import Path

import numpy as np
import pytest
from run import Cell

BENCH = Path(__file__).resolve().parents[1]
CELL = "snapshot-chain.incremental"
SEED = 3_000_000_027


def generator(scale=1, seed=SEED):
    cell = Cell(CELL)
    return cell.generator(cell.workload["content"], seed, scale)


@pytest.fixture(scope="module")
def offsets_of_100_chunks():
    g = generator()
    return g, np.array([g.offsets(i) for i in range(1, 101)])


def test_same_seed_and_index_give_the_same_bytes_and_the_same_extents():
    a, b = generator(16), generator(16)
    assert np.array_equal(a.setup_chunk(), b.setup_chunk()) and np.array_equal(a.chunk(5), b.chunk(5))
    assert np.array_equal(a.offsets(5), b.offsets(5)) and not np.array_equal(a.offsets(5), a.offsets(6))
    assert a.chunk(0) is a.setup_chunk() and not a.setup_chunk().flags.writeable


def test_changed_share_of_a_region_is_one_thirty_second_exactly():
    g = generator()
    assert Fraction(g.extents * g.extent_bytes, g.chunk_bytes) == Fraction(1, 32)
    assert (g.chunk_bytes, g.extent_bytes, g.extents) == (67_108_864, 524_288, 4)


@pytest.mark.parametrize("index", [1, 2, 3, 17])
def test_a_generation_is_the_base_with_exactly_its_extents_rewritten(index):
    g = generator(16)
    base, row, offsets = g.setup_chunk(), g.chunk(index), g.offsets(index)
    assert len(row) == len(base) == g.chunk_bytes and len(offsets) == g.extents
    inside = np.zeros(len(base), bool)
    for at in offsets.tolist():
        assert 0 <= at <= len(base) - g.extent_bytes and not inside[at : at + g.extent_bytes].any()  # in range, non-overlapping
        inside[at : at + g.extent_bytes] = True
    assert inside.sum() == g.extents * g.extent_bytes == len(base) // 32
    differs = row != base
    assert not differs[~inside].any()
    assert differs[inside].mean() > 0.99  # a fresh random byte keeps the old value once in 256


def test_extents_do_not_overlap_in_any_of_100_chunks(offsets_of_100_chunks):
    g, offsets = offsets_of_100_chunks
    assert (np.diff(offsets, axis=1) >= g.extent_bytes).all()
    assert offsets.min() >= 0 and offsets.max() <= g.chunk_bytes - g.extent_bytes


def test_offsets_are_byte_granular_and_aligned_to_nothing(offsets_of_100_chunks):
    _, offsets = offsets_of_100_chunks
    assert (offsets % 2 == 1).any(), "every offset is even: aligned to a power of two"
    # half of uniform byte offsets are odd, a quarter are multiples of 4
    assert 0.4 < (offsets % 2 == 1).mean() < 0.6 and 0.15 < (offsets % 4 == 0).mean() < 0.35


def test_offsets_cover_the_region_uniformly(offsets_of_100_chunks):
    g, offsets = offsets_of_100_chunks
    counts, _ = np.histogram(offsets, bins=8, range=(0, g.chunk_bytes))
    assert counts.min() >= 25 and counts.max() <= 75  # 400 offsets, 50 a bin


def test_the_generator_imports_numpy_and_nothing_of_the_program():
    tree = ast.parse((BENCH / "generators" / "snapshot_delta.py").read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"__future__", "numpy", "pathlib"}  # pathlib: where the schedule lies
    words = (BENCH / "generators" / "snapshot_delta.py").read_text().lower()
    assert not [w for w in ("skyplane", "gear", "cdc", "anchor", "boundar", "segment", "fingerprint") if w in words]


def test_the_configuration_states_what_the_issue_asks_and_guarantees_what_the_control_does():
    cfg = json.loads((BENCH / "configs" / "snapshot-chain.json").read_text())
    bulk = json.loads((BENCH / "configs" / "bulk-files.json").read_text())
    assert {"source", "deployment", "transfer", "content", "guarantees", "reduced", "assumed"} <= set(cfg)
    assert cfg["guarantees"] == bulk["guarantees"] and cfg["transfer"] == bulk["transfer"]
    content = cfg["content"]
    assert content["region_bytes"] == cfg["transfer"]["multipart_chunk_size_mb"] << 20  # a row fills its bucket
    assert content["extents_per_region"] * content["extent_bytes"] * 32 == content["region_bytes"]
    assert set(cfg["assumed"]) == {"changed_fraction", "extent_bytes", "extents_per_region", "mix"}
    assert set(cfg["reduced"]) == {"corpus_bytes", "volume_regions", "gateways", "in_flight_chunks"}


def test_the_cell_hands_the_generator_the_configurations_sizes_and_nothing_else():
    cell = Cell(CELL)
    content = cell.workload["content"]
    assert set(content) == {"region_bytes", "extent_bytes", "extents_per_region", "corpus_seed", "schedule"}
    assert (BENCH / content["schedule"]).is_file()
    assert all(content[k] == cell.config["content"][k] for k in content)
    assert cell.workload["traffic"] == {"in_flight_chunks": 2} and cell.entry["chips"] == 1


def test_new_metrics_are_data_files_and_the_cell_reports_them():
    cell = Cell(CELL)
    reported = {entry["name"]: spec for entry, spec in cell.metrics("per_layer")}
    for name in ("ref_segment_share", "literal_share", "sink_ref_resolve_s_per_gib", "source_encode_s_per_gib"):
        assert "ratio" in reported[name] and "reader" not in reported[name]
    assert {e["name"] for e, _ in cell.metrics("end_to_end")} == {"goodput_gbps", "wire_reduction", "setup_s"}


# ---- a whole rehearsal of the cell on the CPU backend, chunks 64 times smaller


@pytest.mark.parametrize("control", ["fp_4_lanes", "cdc_avg_halved", "restore_flips_byte"])
def test_a_rehearsal_of_the_cell_compares_all_zero_and_the_control_is_not_correct(capsys, control):
    import run

    rc = run.main(["--workload", CELL, "--seed", "4000000027", "--seconds", "3", "--trace", "0", "--rehearse-scale", "64", "--control", control])
    captured = capsys.readouterr()
    result = json.loads([line for line in captured.out.strip().splitlines() if line.startswith("{")][-1])
    assert rc == 1 and result["correct"] is False and result["metrics"] == {}  # a rehearsal is never correct
    assert result["rehearsal"]["checks_passed"] is True, captured.err[-3000:]
    assert {k: v["value"] for k, v in result["compared"].items() if v["value"] != 0} == {}
    assert result["control"]["name"] == control and result["control"]["correct"] is False
    assert any(v["value"] > v["limit"] for v in result["control"]["compared"].values())
