"""The generators: chunk i is a function of (seed, i); all chunks of a cell
have one structure (here: one length); --seed changes bytes (or, where the
content states a corpus seed, their order), not structure."""

import json
from pathlib import Path

import numpy as np
import pytest
from run import Cell

BENCH = Path(__file__).resolve().parents[1]
CELLS = [w["name"] for w in json.loads((BENCH.parent / "BENCHMARK.json").read_text())["workloads"]]
SCALE = 16


def make(cell_name, seed):
    cell = Cell(cell_name)
    return cell, cell.generator(cell.workload["content"], seed, SCALE)


@pytest.mark.parametrize("cell_name", CELLS)
def test_same_seed_and_index_give_the_same_bytes(cell_name):
    _, a = make(cell_name, 3_000_000_001)
    _, b = make(cell_name, 3_000_000_001)
    assert np.array_equal(a.setup_chunk(), b.setup_chunk())
    assert np.array_equal(a.chunk(3), b.chunk(3))
    assert not np.array_equal(a.chunk(3), a.chunk(4))
    assert len(a.chunk(3)) == a.chunk_bytes == len(a.setup_chunk())


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_seed_changes_the_bytes(cell_name):
    _, a = make(cell_name, 1)
    _, b = make(cell_name, 2)
    assert len(a.chunk(1)) == len(b.chunk(1)) == len(a.chunk(2)) == len(b.setup_chunk())
    assert not np.array_equal(a.chunk(1), b.chunk(1))
    one_corpus = "corpus_seed" in Cell(cell_name).workload["content"]  # one volume for every seed: the seed gives the order
    assert np.array_equal(a.setup_chunk(), b.setup_chunk()) == one_corpus
