"""The volume-seed configuration and its generator: the stated block mix is
what the generator realises, exactly and in every region; a region is a
function of (seed, index) alone; the generator knows nothing of the program;
the cell's files say what the configuration says; regions cost alike on the
wire; and a rehearsal of the cell passes its checks while each control does
not."""

import ast
import hashlib
import json
import statistics
import time
from pathlib import Path

import numpy as np
import pytest
from lib import reference
from run import Cell

BENCH = Path(__file__).resolve().parents[1]
CELL = "volume-seed.full"
SEED = 3_600_000_027
COUNTS = {"zero": 32, "text": 45, "records": 32, "random": 19}
CUT = (4096, 16384, 65536)  # the configuration's cdc_min / avg / max
ZERO_SEGMENT = hashlib.blake2b(bytes(CUT[2]), digest_size=16).digest()


def generator(scale=1, seed=SEED):
    cell = Cell(CELL)
    return cell.generator(cell.workload["content"], seed, scale)


def typical_record(extent: np.ndarray):
    """The commonest 64-byte row of an extent, column by column, and the share of the extent's bytes that equal it."""
    rows = extent.reshape(-1, 64)
    typical = np.array([np.bincount(col, minlength=256).argmax() for col in rows.T], np.uint8)
    return typical, float((rows == typical).mean())


def classify(extent: np.ndarray) -> str:
    """The type an extent's bytes show, from the configuration's words alone."""
    if not extent.any():
        return "zero"
    if ((extent >= 0x20) & (extent < 0x40)).all():
        return "text"
    # records: most bytes still equal the extent's one record; random bytes do once in 256
    return "records" if typical_record(extent)[1] > 0.9 else "random"


@pytest.mark.parametrize("index", [0, 1, 2, 40])
def test_every_region_holds_exactly_the_stated_extents_of_each_type(index):
    g = generator()
    assert (g.chunk_bytes, g.extent_bytes) == (67_108_864, 524_288)
    row = g.chunk(index) if index else g.setup_chunk()
    assert len(row) == g.chunk_bytes
    shown = [classify(e) for e in row.reshape(-1, g.extent_bytes)]
    assert shown == g.layout(index)
    assert {t: shown.count(t) for t in COUNTS} == COUNTS


def test_the_order_of_the_extents_differs_by_region_and_by_seed():
    g = generator(64)
    assert g.layout(1) != g.layout(2) and g.layout(1) != generator(64, SEED + 1).layout(1)
    assert sorted(g.layout(1)) == sorted(g.layout(2))


def test_a_region_is_a_function_of_seed_and_index_alone():
    a, b = generator(16), generator(16)
    late = [a.chunk(i) for i in (7, 3, 0)]  # a has made other regions first, in another order
    assert np.array_equal(b.chunk(3), late[1]) and np.array_equal(b.setup_chunk(), late[2]) and np.array_equal(b.chunk(7), late[0])
    assert np.array_equal(a.chunk(3), late[1])  # and gives the same bytes when asked again
    assert not np.array_equal(a.chunk(3), a.chunk(4)) and not np.array_equal(a.chunk(3), generator(16, SEED + 1).chunk(3))


def text_words(g, i: int) -> np.ndarray:
    row = g.chunk(i).reshape(-1, g.extent_bytes)
    return np.concatenate([e for e, t in zip(row, g.layout(i)) if t == "text"]).view(np.uint64)


def test_text_is_words_of_one_vocabulary_and_the_stream_differs_by_region_and_seed():
    g, other = generator(16), generator(16, SEED + 1)
    words = {i: text_words(g, i) for i in (1, 2)}
    vocabulary = np.union1d(words[1], words[2])
    assert 500 < len(vocabulary) <= 512  # 512 words, drawn uniformly: nearly all seen in 368,640 draws
    assert not np.array_equal(words[1][:4096], words[2][:4096])
    # the cell states vocabulary_seed: another --seed, the same words in another stream
    theirs = text_words(other, 1)
    assert not np.array_equal(theirs[:4096], words[1][:4096]) and len(np.setdiff1d(theirs, vocabulary)) < 8


def test_without_a_vocabulary_seed_the_vocabulary_is_the_seeds():
    cell = Cell(CELL)
    content = {k: v for k, v in cell.workload["content"].items() if k != "vocabulary_seed"}
    a, b = cell.generator(content, SEED, 16), cell.generator(content, SEED + 1, 16)
    assert len(np.intersect1d(text_words(a, 1), text_words(b, 1))) < 8  # another seed, another vocabulary
    assert np.array_equal(text_words(a, 1), text_words(cell.generator(content, SEED, 16), 1))
    stated = cell.generator(dict(content, vocabulary_seed=SEED), SEED + 1, 16)
    assert len(np.setdiff1d(text_words(stated, 1), np.union1d(text_words(a, 1), text_words(a, 2)))) < 8  # SEED's words


def test_records_are_one_record_tiled_with_a_byte_in_32_edited():
    g = generator()
    row = g.chunk(1).reshape(-1, g.extent_bytes)
    extents = [e for e, t in zip(row, g.layout(1)) if t == "records"]
    records = set()
    for e in extents[:8]:
        typical, unedited = typical_record(e)
        records.add(typical.tobytes())
        # extent_bytes // 32 draws with replacement: 1 - exp(-1/32) of the positions hit, one in 256 to no effect
        assert 0.028 < 1 - unedited < 0.033
    assert len(records) == 8  # a record of its own per extent


def test_the_generator_imports_numpy_and_nothing_of_the_program():
    text = (BENCH / "generators" / "volume_blocks.py").read_text()
    imported = set()
    for node in ast.walk(ast.parse(text)):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"__future__", "numpy"}
    assert not [w for w in ("skyplane", "gear", "cdc", "anchor", "boundar", "segment", "fingerprint") if w in text.lower()]


def test_a_region_is_made_in_under_half_a_second():
    g = generator()
    g.chunk(1)
    t = time.perf_counter()
    g.chunk(2)
    assert time.perf_counter() - t < 0.5


def test_the_configuration_states_what_the_issue_asks_and_guarantees_what_the_control_does():
    cfg = json.loads((BENCH / "configs" / "volume-seed.json").read_text())
    bulk = json.loads((BENCH / "configs" / "bulk-files.json").read_text())
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    assert {"source", "source_recalled", "deployment", "transfer", "content", "guarantees", "reduced", "assumed"} <= set(cfg)
    assert cfg["guarantees"] == bulk["guarantees"] and cfg["transfer"] == bulk["transfer"]
    content = cfg["content"]
    assert content["region_bytes"] == cfg["transfer"]["multipart_chunk_size_mb"] << 20  # a row fills its bucket
    assert content["extents_by_type"] == COUNTS and sum(COUNTS.values()) * content["extent_bytes"] == content["region_bytes"]
    assert content["extent_bytes"] >= 2 * cfg["transfer"]["cdc_max_bytes"]  # a zero extent holds a whole longest segment
    assert set(cfg["reduced"]) == {"corpus_bytes", "volume_regions", "gateways", "in_flight_chunks"}
    (entry,) = [c for c in bench["configs"] if c["name"] == "volume-seed"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200 and entry["reduced"] == list(cfg["reduced"])


def test_the_cell_hands_the_generator_the_configurations_content_and_nothing_else():
    cell = Cell(CELL)
    content = cell.workload["content"]
    assert set(content) == {"region_bytes", "extent_bytes", "extents_by_type", "vocabulary_seed"}
    assert all(content[k] == cell.config["content"][k] for k in content)
    assert cell.workload["traffic"] == {"in_flight_chunks": 2} and cell.entry["chips"] == 1 and cell.burst is None


def test_new_metrics_are_data_files_and_only_the_new_cell_reports_them():
    new = ("source_blockpack_s_per_gib", "source_zstd_s_per_gib", "sink_blob_decode_s_per_gib", "codec_ratio", "overflow_rows")
    reported = {entry["name"]: spec for entry, spec in Cell(CELL).metrics("per_layer")}
    for name in new:
        assert "ratio" in reported[name] and "reader" not in reported[name]
    assert not [p.name for p in (BENCH / "metrics").glob("*.py") if p.stem in new]
    for other in ("bulk-files.copy", "snapshot-chain.incremental"):
        theirs = {entry["name"] for entry, _ in Cell(other).metrics("per_layer")}
        assert not theirs & set(new) and theirs <= set(reported)  # the new cell reports everything the others do
    assert {e["name"] for e, _ in Cell(CELL).metrics("end_to_end")} == {"goodput_gbps", "wire_reduction", "setup_s"}


@pytest.mark.parametrize("name, facts, want", [
    ("codec_ratio", {"source_after_t0.literal_bytes": 7e6, "source_after_t0.literal_blob_bytes": 2e6}, 3.5),
    ("overflow_rows", {"source_after_t0.overflow_rows": 0}, 0.0),
    ("source_zstd_s_per_gib", {"source_after_t0.zstd_ns": 2e9, "source_after_t0.raw_bytes": 1 << 30}, 2.0),
    # a parent that has no such counter offers no such fact: the metric is left out, nothing raises
    ("codec_ratio", {"source_after_t0.literal_bytes": 7e6}, None),
    ("overflow_rows", {}, None),
    ("sink_blob_decode_s_per_gib", {"sink_after_t0.decode_raw_bytes": 1 << 30}, None),
])
def test_a_new_metric_reads_its_counter_and_is_left_out_where_there_is_none(name, facts, want):
    import run

    got = run.read_metric(json.loads((BENCH / "metrics" / f"{name}.json").read_text()), facts, {})
    assert got == want if want is None else got == pytest.approx(want)


# ---- what a region is on the wire, counted with the plain reference's cut and zstd


def wire_cost(row: np.ndarray, held: set) -> dict:
    """A region cut by ``lib/reference.py`` and sent under exact dedup
    against ``held`` (digests of the segments the sink has): 7 bytes of
    recipe head, 25 an entry, and the literals through zstd at the shipped
    level. The program's blockpack step and the seal are left out: they add
    alike to every region."""
    import zstandard

    ends = reference.select_boundaries(reference.candidates(row, int(np.log2(CUT[1]))), len(row), CUT[0], CUT[2]).tolist()
    literals, refs, own = [], 0, set()
    for a, b in zip([0] + ends[:-1], ends):
        digest = hashlib.blake2b(row[a:b], digest_size=16).digest()
        if digest in held or digest in own:
            refs += 1
        else:
            own.add(digest)
            literals.append(row[a:b])
    literal_bytes = sum(len(x) for x in literals)
    blob = zstandard.ZstdCompressor(level=-2).compress(np.concatenate(literals).tobytes())
    return {"segments": len(ends), "refs": refs, "literal_bytes": literal_bytes, "blob": len(blob), "wire": 7 + 25 * len(ends) + len(blob), "own": own}


@pytest.fixture(scope="module")
def forty_regions():
    """Regions 0..40 of one seed, the set-up region first; 1.7 s a region."""
    g = generator()
    setup = wire_cost(g.setup_chunk(), set())
    return setup, [wire_cost(g.chunk(i), setup["own"]) for i in range(1, 41)]


def test_the_set_up_region_holds_whole_zero_segments_and_later_regions_ref_them(forty_regions):
    setup, later = forty_regions
    assert ZERO_SEGMENT in setup["own"] and setup["refs"] > 200  # its own repeats: every whole zero segment but the first
    for cost in later:
        assert 200 < cost["refs"] < 256  # 32 zero extents are 256 longest segments, less one or two a run of zeros
        assert not (cost["own"] - {ZERO_SEGMENT}) & setup["own"]  # nothing else of a region is in another


def test_regions_cost_alike_on_the_wire(forty_regions, capsys):
    _, later = forty_regions
    wire = [c["wire"] for c in later]
    mean, sd = statistics.mean(wire), statistics.stdev(wire)
    with capsys.disabled():
        print(
            f"\nvolume-seed, 40 regions of seed {SEED}: wire bytes mean {mean:.0f} sd {sd:.0f} ({100 * sd / mean:.3f}%); "
            f"segments {min(c['segments'] for c in later)}-{max(c['segments'] for c in later)}, "
            f"REFs {min(c['refs'] for c in later)}-{max(c['refs'] for c in later)}, "
            f"literal share {100 * statistics.mean(c['literal_bytes'] for c in later) / 67108864:.2f}%, "
            f"zstd alone {statistics.mean(c['literal_bytes'] / c['blob'] for c in later):.3f}x, "
            f"reduction {67108864 / mean:.3f}x"
        )
    assert sd < 0.005 * mean
    assert 2.5 < 67108864 / mean < 6.0


# ---- a whole rehearsal of the cell on the CPU backend, chunks 16 times smaller


@pytest.mark.parametrize("control", ["fp_4_lanes", "cdc_avg_halved", "restore_flips_byte"])
def test_a_rehearsal_of_the_cell_compares_all_zero_and_the_control_is_not_correct(capsys, control):
    import run

    rc = run.main(["--workload", CELL, "--seed", "4000000036", "--seconds", "3", "--trace", "0", "--rehearse-scale", "16", "--control", control])
    captured = capsys.readouterr()
    result = json.loads([line for line in captured.out.strip().splitlines() if line.startswith("{")][-1])
    assert rc == 1 and result["correct"] is False and result["metrics"] == {}  # a rehearsal is never correct
    assert result["rehearsal"]["checks_passed"] is True, captured.err[-3000:]
    assert {k: v["value"] for k, v in result["compared"].items() if v["value"] != 0} == {}
    assert result["run"]["reference_rows"] == result["run"]["rows_sent"]
    assert result["control"]["name"] == control and result["control"]["correct"] is False
    assert any(v["value"] > v["limit"] for v in result["control"]["compared"].values())
