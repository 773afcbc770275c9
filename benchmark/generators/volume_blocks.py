"""Regions of a volume seen for the first time: a mix of block types in whole
extents, nothing sent twice.

A region is ``region_bytes`` long and made of extents of ``extent_bytes``,
each of one type. ``extents_by_type`` says how many extents of each type a
region holds, exactly and in every region, the set-up region (region 0) too;
their order is a permutation drawn from (``--seed``, *i*). The types:

``zero``     bytes of value 0: free space as a volume image reads it
``text``     a stream of 8-byte words drawn uniformly from a vocabulary of 512
             words, each byte ``(r & 0x3F) | 0x20``; the stream from
             (``--seed``, *i*), the vocabulary from ``vocabulary_seed`` where
             the content states one and from ``--seed`` where it does not
``records``  per extent one 64-byte record of random bytes tiled over the
             extent, then ``extent_bytes // 32`` byte positions, drawn
             uniformly with replacement, overwritten with random bytes
``random``   random bytes

Chunk *i* is region *i*, a function of (``--seed``, *i*) alone; no region is
a copy or a later state of another, so what a chunk shares with the chunks
before it is what any two regions of a volume share: here, runs of zeros.
Parameters (the cell's ``content``): ``region_bytes``, ``extent_bytes``,
``extents_by_type``, and optionally ``vocabulary_seed``.

Why a cell states ``vocabulary_seed``: how far the codec shrinks text depends
on which 512 words the vocabulary holds, by sd 1.4% from one vocabulary to
the next, and not on which of them a region draws (sd 0.05% from region to
region). With the vocabulary drawn from ``--seed`` what a run ships differs
from seed to seed by sd 0.4%, the same in every chunk of the run, so no
number of chunks averages it out. With one vocabulary for every seed the
seeds differ in everything else (order of the extents, word stream, records,
edits, random bytes) and ship alike.

The generator knows the block mix and nothing of the program that will cut
these bytes: it imports numpy only. It fills a region through calls that
release the interpreter lock (``Generator.integers``, ``take``, slice
copies): ``random_files.py`` says why.
"""

from __future__ import annotations

import numpy as np

TYPES = ("zero", "text", "records", "random")
WORD_BYTES = 8
VOCABULARY_WORDS = 512
RECORD_BYTES = 64
EDITS_PER_EXTENT_DIVISOR = 32  # one edited byte position drawn for every 32 bytes of a records extent
# a third number beside (seed, i): numpy seeds [seed] and [seed, 0] alike, so
# the vocabulary's stream and region 0's each carry a mark of their own
_REGION, _VOCABULARY = 1, 2


def random_bytes(rng: np.random.Generator, n: int) -> np.ndarray:
    # integers() fills without the interpreter lock; bytes() holds it
    words = rng.integers(0, 1 << 32, -(-n // 4), dtype=np.uint32)
    return words.view(np.uint8)[:n]


class Generator:
    def __init__(self, params: dict, seed: int, scale: int = 1):
        self.seed = int(seed)
        self.chunk_bytes = int(params["region_bytes"]) // scale
        self.extent_bytes = int(params["extent_bytes"]) // scale
        counts = params["extents_by_type"]
        if sorted(counts) != sorted(TYPES):
            raise ValueError(f"extents_by_type names {sorted(counts)}; the types are {sorted(TYPES)}")
        self._types = np.repeat(np.arange(len(TYPES)), [int(counts[t]) for t in TYPES])
        if len(self._types) * self.extent_bytes != self.chunk_bytes:
            raise ValueError(f"{len(self._types)} extents of {self.extent_bytes} bytes are not a region of {self.chunk_bytes}")
        if self.extent_bytes % RECORD_BYTES:
            raise ValueError(f"an extent of {self.extent_bytes} bytes is not whole {RECORD_BYTES}-byte records")
        vocabulary_seed = int(params.get("vocabulary_seed", seed))
        letters = random_bytes(np.random.default_rng([vocabulary_seed, 0, _VOCABULARY]), VOCABULARY_WORDS * WORD_BYTES)
        self._vocabulary = ((letters & 0x3F) | 0x20).view(np.uint64)

    def layout(self, i: int) -> list:
        """The type of each extent of region ``i``, in order."""
        return [TYPES[t] for t in self._layout(np.random.default_rng([self.seed, i, _REGION]))]

    def _layout(self, rng: np.random.Generator) -> np.ndarray:
        return rng.permutation(self._types)

    def setup_chunk(self) -> np.ndarray:
        return self.chunk(0)

    def chunk(self, i: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, i, _REGION])
        types = self._layout(rng)
        out = np.zeros(self.chunk_bytes, np.uint8)
        extents = out.reshape(len(types), self.extent_bytes)
        words = self.extent_bytes // WORD_BYTES
        for row in np.flatnonzero(types == TYPES.index("text")):
            np.take(self._vocabulary, rng.integers(0, VOCABULARY_WORDS, words, dtype=np.uint16), out=extents[row].view(np.uint64))
        edits = self.extent_bytes // EDITS_PER_EXTENT_DIVISOR
        for row in np.flatnonzero(types == TYPES.index("records")):
            extents[row].reshape(-1, RECORD_BYTES)[:] = random_bytes(rng, RECORD_BYTES)
            at = rng.integers(0, self.extent_bytes, edits)
            extents[row][at] = rng.integers(0, 256, edits, dtype=np.uint8)
        for row in np.flatnonzero(types == TYPES.index("random")):
            extents[row] = random_bytes(rng, self.extent_bytes)
        return out
