"""The schedule of ``snapshot-chain.incremental``: every seed sends the same
generations in whole blocks, in another order; the blocks cost alike on the
wire; and the cut that priced them is the plain reference's."""

import sys
from pathlib import Path

import numpy as np
import pytest
from run import Cell

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH / "schedules"))

import make_blocks  # noqa: E402
from lib import reference  # noqa: E402

CELL = "snapshot-chain.incremental"


def generator(seed, scale=16):
    cell = Cell(CELL)
    return cell.generator(cell.workload["content"], seed, scale)


@pytest.fixture(scope="module")
def blocks():
    cell = Cell(CELL)
    return cell.generator.__init__.__globals__["load_blocks"](BENCH / cell.workload["content"]["schedule"])


def test_the_schedule_lists_every_generation_once_in_blocks_of_four(blocks):
    assert blocks.shape == (1024, 4) and sorted(blocks.ravel().tolist()) == list(range(1, 4097))


@pytest.mark.parametrize("seed", [1, 3_300_000_001, 2**31 + 12345])
def test_a_seed_sends_whole_blocks_and_every_generation_once(blocks, seed):
    g = generator(seed)
    sent = [g.generation(i) for i in range(1, blocks.size + 1)]
    assert sorted(sent) == list(range(1, blocks.size + 1))
    rows = {frozenset(row) for row in blocks.tolist()}
    assert all(frozenset(sent[i : i + 4]) in rows for i in range(0, len(sent), 4))
    assert [g.generation(i) for i in (blocks.size + 1, blocks.size + 7)] == [blocks.size + 1, blocks.size + 7]  # past the schedule: no repeat


def test_seeds_share_the_volume_and_the_generations_and_differ_in_order():
    a, b = generator(11), generator(12)
    assert np.array_equal(a.setup_chunk(), b.setup_chunk())
    order_a, order_b = [[g.generation(i) for i in range(1, 41)] for g in (a, b)]
    assert order_a != order_b and len(set(order_a)) == 40
    i, j = 3, next(j for j in range(1, 4097) if b.generation(j) == a.generation(3))
    assert np.array_equal(a.chunk(i), b.chunk(j)) and np.array_equal(a.offsets(i), b.offsets(j))
    assert not np.array_equal(a.chunk(1), b.chunk(1))


def test_content_without_a_corpus_seed_comes_from_the_seed_in_natural_order():
    cell = Cell(CELL)
    content = {k: v for k, v in cell.workload["content"].items() if k not in ("corpus_seed", "schedule")}
    a, b = cell.generator(content, 5, 16), cell.generator(content, 6, 16)
    assert not np.array_equal(a.setup_chunk(), b.setup_chunk()) and [a.generation(i) for i in (1, 2, 9)] == [1, 2, 9]


@pytest.mark.parametrize("k", [1, 2, 3, 4, 77])
def test_the_local_cut_that_prices_a_generation_is_the_whole_reference(k):
    g, cut = make_blocks.load_generator(CELL, scale=16)
    costs = make_blocks.Costs(g, cut)
    base_fps = set(reference.cdc_and_fingerprints(g.setup_chunk(), *cut)[1])
    ends, fps = reference.cdc_and_fingerprints(g.chunk(k), *cut)
    assert np.array_equal(costs.ends(k)[0], ends)
    starts = [0] + ends[:-1].tolist()
    new = sum(e - s for s, e, fp in zip(starts, ends.tolist(), fps) if fp not in base_fps)
    assert costs.cost(k) == new + make_blocks.RECIPE_ENTRY_BYTES * len(ends)
    assert new >= g.extents * g.extent_bytes  # every rewritten byte is new, and what is cut with it


def test_balanced_blocks_use_every_index_once_and_cost_alike():
    cost = np.random.default_rng(7).normal(2_300_000, 45_000, 400).astype(np.int64)
    rows = make_blocks.balanced_blocks(cost, 4, rounds=4000)
    assert rows.shape == (100, 4) and sorted(rows.ravel().tolist()) == list(range(400))
    sums = cost[rows].sum(1)
    assert (sums.max() - sums.min()) / sums.mean() < 2e-3 < cost.std() * 2 / cost.mean()  # 4 random ones differ by ~4%


def test_two_blocks_of_the_committed_schedule_cost_what_its_header_says_at_full_size(blocks):
    header = (BENCH / Cell(CELL).workload["content"]["schedule"]).read_text().splitlines()[1]
    stated = float(header.split("a block ")[1].split(" ")[0])
    g, cut = make_blocks.load_generator(CELL)
    costs = make_blocks.Costs(g, cut)
    for row in (blocks[0], blocks[-1]):
        assert abs(sum(costs.cost(int(k)) for k in row) - stated) < 1e-4 * stated
