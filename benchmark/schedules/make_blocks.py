#!/usr/bin/env python3
"""Make a schedule for ``generators/snapshot_delta.py``: the generations 1..G
of a cell's content in blocks that each carry the same bytes to the other side.

    python3 benchmark/schedules/make_blocks.py <cell> <generations> <block> > benchmark/schedules/<name>.csv

What a generation costs on the wire is what exact dedup against the base
leaves of it: the bytes of every piece that the configuration's cut
(``lib/reference.py``, the plain reference) gives the generation and not the
base, plus ``RECIPE_ENTRY_BYTES`` a piece. It differs from generation to
generation by where the extents' edges fall inside the base's pieces (sd 1.9%
of the mean at 4 extents of 512 KiB in 64 MiB), so a run's wire reduction
would differ by which generations it happened to send. The blocks take that
out: every block's cost is the same to a few parts in 100,000, and the
generator sends whole blocks in an order the seed gives.

A generation is cut here only where it differs from the base: candidates are
kept from the base outside an extent's reach (its bytes and the hash's window
after them) and hashed anew inside it, and the boundaries are selected over
the whole row. ``benchmark/tests/test_schedules.py`` holds that to the whole reference.

Made offline, once, for a cell's content and its configuration's cut; a cell
with another region, extent or cut wants a schedule of its own. Nothing here
is run by ``run.py``.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from lib import reference  # noqa: E402

RECIPE_ENTRY_BYTES = 25  # what the recipe spends on a piece, literal or reference (PERF.md, section 4)
ROUNDS = 200_000


def load_generator(cell: str, scale: int = 1):
    workload = json.loads((BENCH / "workloads" / f"{cell}.json").read_text())
    config = json.loads((BENCH / "configs" / f"{workload['config']}.json").read_text())
    content = {k: v for k, v in workload["content"].items() if k != "schedule"}  # chunk k is generation k
    spec = importlib.util.spec_from_file_location("schedule_generator", BENCH / "generators" / f"{workload['generator']}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    t = config["transfer"]
    return module.Generator(content, 0, scale), (t["cdc_min_bytes"], t["cdc_avg_bytes"], t["cdc_max_bytes"])


class Costs:
    """What each generation of a generator's content costs on the wire."""

    def __init__(self, generator, cut):
        self.g, (self.min_bytes, avg_bytes, self.max_bytes) = generator, cut
        self.bits = max(1, int(np.log2(avg_bytes)))
        self.base = generator.setup_chunk()
        self.cands = reference.candidates(self.base, self.bits)
        ends = reference.select_boundaries(self.cands, len(self.base), self.min_bytes, self.max_bytes).tolist()
        self.pieces = set(zip([0] + ends[:-1], ends))

    def ends(self, k: int):
        """The generation's boundaries and the extents it rewrote."""
        n, size, halo = len(self.base), self.g.extent_bytes, reference.GEAR_WINDOW - 1
        keep, fresh_cands, extents = np.ones(len(self.cands), bool), [], []
        for at, fresh in self.g.rewrites(k):
            lo, hi = max(0, at - halo), min(n, at + size + halo)
            h = reference.gear_hash(np.concatenate([self.base[lo:at], fresh, self.base[at + size : hi]]))[at - lo :]
            fresh_cands.append(np.flatnonzero((h >> np.uint32(32 - self.bits)) == 0) + at)
            keep &= ~((self.cands >= at) & (self.cands < hi))
            extents.append((at, at + size))
        cands = np.sort(np.concatenate([self.cands[keep]] + fresh_cands))
        return reference.select_boundaries(cands, n, self.min_bytes, self.max_bytes), extents

    def cost(self, k: int) -> int:
        ends, extents = self.ends(k)
        ends = ends.tolist()
        new = sum(e - s for s, e in zip([0] + ends[:-1], ends) if (s, e) not in self.pieces or any(s < b and e > a for a, b in extents))
        return new + RECIPE_ENTRY_BYTES * len(ends)


def balanced_blocks(cost: np.ndarray, block: int, rounds: int = ROUNDS) -> np.ndarray:
    """Indices into ``cost`` in rows of ``block`` whose sums are as alike as
    swaps between the dearest row and another can make them."""
    rows = len(cost) // block
    ranked = np.argsort(cost, kind="stable")[: rows * block].reshape(block, rows)
    ranked[1::2] = ranked[1::2, ::-1]  # dear with cheap
    blocks = np.ascontiguousarray(ranked.T)
    rng = np.random.default_rng(0)
    for it in range(rounds):
        sums = cost[blocks].sum(1)
        hi = int(sums.argmax())
        lo = int(rng.integers(rows)) if it % 2 else int(sums.argmin())
        gap = sums[hi] - sums[lo]
        if hi == lo or gap <= 0:
            continue
        after = np.abs(gap - 2 * (cost[blocks[hi]][:, None] - cost[blocks[lo]][None, :]))
        a, b = np.unravel_index(after.argmin(), after.shape)
        if after[a, b] < gap:
            blocks[hi, a], blocks[lo, b] = blocks[lo, b], blocks[hi, a]
    return blocks


def main(argv) -> int:
    cell, generations, block = argv[0], int(argv[1]), int(argv[2])
    generator, cut = load_generator(cell)
    costs = Costs(generator, cut)
    cost = np.array([costs.cost(k) for k in range(1, generations + 1)], dtype=np.int64)
    blocks = balanced_blocks(cost, block)
    sums = cost[blocks].sum(1)
    print(f"# made by schedules/make_blocks.py {cell} {generations} {block}: generations 1..{blocks.size} of corpus seed {generator.corpus_seed} in blocks of {block}")
    print(f"# cut {cut}; a generation costs {cost.mean():.0f} bytes on the wire (sd {cost.std():.0f}, {cost.min()}-{cost.max()}); a block {sums.mean():.0f} (sd {sums.std():.1f}, {sums.min()}-{sums.max()})")
    for row in blocks + 1:
        print(",".join(str(int(k)) for k in row))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
