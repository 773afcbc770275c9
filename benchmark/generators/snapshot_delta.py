"""Generations of one volume region: the base, and the base with a stated
share of it rewritten.

The set-up chunk is the base region, ``region_bytes`` random bytes from
(``--seed``, 0), made once and kept. Chunk *i* is that region in generation
*i*: a copy of the base in which exactly ``extents_per_region`` extents of
``extent_bytes`` are overwritten with fresh random bytes from (``--seed``,
*i*). The extents' offsets are drawn per chunk, uniform over byte offsets,
non-overlapping and aligned to nothing; ``offsets(i)`` gives what was
realised. Every generation differs from the base alone, never from another
generation. Parameters (the cell's ``content``): ``region_bytes``,
``extent_bytes``, ``extents_per_region``.

The generator knows the change model and nothing of the program that will
cut these bytes: it imports numpy only.
"""

from __future__ import annotations

import numpy as np


def random_bytes(rng: np.random.Generator, n: int) -> np.ndarray:
    # integers() fills without the interpreter lock; bytes() holds it
    words = rng.integers(0, 1 << 32, -(-n // 4), dtype=np.uint32)
    return words.view(np.uint8)[:n]


class Generator:
    def __init__(self, params: dict, seed: int, scale: int = 1):
        self.seed = int(seed)
        self.chunk_bytes = int(params["region_bytes"]) // scale
        self.extent_bytes = int(params["extent_bytes"]) // scale
        self.extents = int(params["extents_per_region"])
        self._base = None

    def setup_chunk(self) -> np.ndarray:
        if self._base is None:
            self._base = random_bytes(np.random.default_rng([self.seed, 0]), self.chunk_bytes)
            self._base.setflags(write=False)
        return self._base

    def _rng(self, i: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, i])

    def _offsets(self, rng: np.random.Generator) -> np.ndarray:
        # k sorted draws from the bytes the extents leave free, the j-th moved
        # up by j extents: every placement of k extents that do not overlap is
        # as likely as any other
        free = self.chunk_bytes - self.extents * self.extent_bytes
        gaps = np.sort(rng.integers(0, free + 1, self.extents))
        return gaps + np.arange(self.extents) * self.extent_bytes

    def offsets(self, i: int) -> np.ndarray:
        """Byte offsets of the extents generation ``i`` rewrites, ascending."""
        return self._offsets(self._rng(i))

    def chunk(self, i: int) -> np.ndarray:
        if i == 0:
            return self.setup_chunk()
        out = self.setup_chunk().copy()
        rng = self._rng(i)
        for at in self._offsets(rng).tolist():
            out[at : at + self.extent_bytes] = random_bytes(rng, self.extent_bytes)
        return out
