"""Generations of one volume region: the base, and the base with a stated
share of it rewritten.

The set-up chunk is the base region, ``region_bytes`` random bytes from
(corpus seed, 0), made once and kept. Generation *k* is a copy of the base in
which exactly ``extents_per_region`` extents of ``extent_bytes`` are
overwritten with fresh random bytes from (corpus seed, *k*). The extents'
offsets are drawn per generation, uniform over byte offsets, non-overlapping
and aligned to nothing; ``offsets(i)`` gives what chunk *i* realised. Every
generation differs from the base alone, never from another generation.
Parameters (the cell's ``content``): ``region_bytes``, ``extent_bytes``,
``extents_per_region``, and optionally ``corpus_seed`` and ``schedule``.

Where the content states no ``corpus_seed``, the corpus seed is ``--seed``
and chunk *i* is generation *i*. Where it states one, the volume and its
generations are the same for every ``--seed`` and the seed gives the order
in which they are sent: ``schedule`` names a file under ``benchmark/`` that
lists the generations 1..G in blocks, one block a line, made so that every
block carries the same new bytes to the other side
(``schedules/make_blocks.py``); the seed shuffles the blocks and each block
within itself, and chunk *i* is the *i*-th generation of that order. Every
seed then sends the same set of generations, in another order, and any run
of chunks holds whole blocks but for its two ends. Past the last block
chunk *i* is generation *i*, so no generation is ever sent twice.

The generator knows the change model and nothing of the program that will
cut these bytes: it imports numpy only (and pathlib, to find the schedule,
which it reads as a list of numbers).
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]


def random_bytes(rng: np.random.Generator, n: int) -> np.ndarray:
    # integers() fills without the interpreter lock; bytes() holds it
    words = rng.integers(0, 1 << 32, -(-n // 4), dtype=np.uint32)
    return words.view(np.uint8)[:n]


def load_blocks(path: Path) -> np.ndarray:
    """The schedule's blocks, one a row: the generations 1..G, each once."""
    blocks = np.loadtxt(path, dtype=np.int64, delimiter=",", comments="#", ndmin=2)
    if sorted(blocks.ravel().tolist()) != list(range(1, blocks.size + 1)):
        raise ValueError(f"{path}: not the generations 1..{blocks.size}, each once")
    return blocks


class Generator:
    def __init__(self, params: dict, seed: int, scale: int = 1):
        self.seed = int(seed)
        self.corpus_seed = int(params.get("corpus_seed", seed))
        self.chunk_bytes = int(params["region_bytes"]) // scale
        self.extent_bytes = int(params["extent_bytes"]) // scale
        self.extents = int(params["extents_per_region"])
        self._base = None
        self._order = np.empty(0, np.int64)
        if "schedule" in params:
            rng = np.random.default_rng([self.seed, self.corpus_seed])
            blocks = load_blocks(BENCH / params["schedule"])
            self._order = rng.permuted(blocks[rng.permutation(len(blocks))], axis=1).ravel()

    def setup_chunk(self) -> np.ndarray:
        if self._base is None:
            self._base = random_bytes(np.random.default_rng([self.corpus_seed, 0]), self.chunk_bytes)
            self._base.setflags(write=False)
        return self._base

    def generation(self, i: int) -> int:
        """Which generation chunk ``i`` is (``i`` from 1)."""
        return int(self._order[i - 1]) if i <= len(self._order) else i

    def _offsets(self, rng: np.random.Generator) -> np.ndarray:
        # k sorted draws from the bytes the extents leave free, the j-th moved
        # up by j extents: every placement of k extents that do not overlap is
        # as likely as any other
        free = self.chunk_bytes - self.extents * self.extent_bytes
        gaps = np.sort(rng.integers(0, free + 1, self.extents))
        return gaps + np.arange(self.extents) * self.extent_bytes

    def rewrites(self, k: int) -> list:
        """(offset, fresh bytes) of each extent generation ``k`` rewrites, ascending."""
        rng = np.random.default_rng([self.corpus_seed, k])
        return [(at, random_bytes(rng, self.extent_bytes)) for at in self._offsets(rng).tolist()]

    def offsets(self, i: int) -> np.ndarray:
        """Byte offsets of the extents chunk ``i`` rewrites, ascending."""
        return self._offsets(np.random.default_rng([self.corpus_seed, self.generation(i)]))

    def chunk(self, i: int) -> np.ndarray:
        if i == 0:
            return self.setup_chunk()
        out = self.setup_chunk().copy()
        for at, fresh in self.rewrites(self.generation(i)):
            out[at : at + self.extent_bytes] = fresh
        return out
