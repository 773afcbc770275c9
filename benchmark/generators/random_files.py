"""Files of already-compressed media: incompressible, nothing repeats.

Every chunk is one whole file of ``file_bytes`` random bytes from
(``--seed``, *i*); the set-up chunk is file 0. Parameters (the cell's
``content``): ``file_bytes``.
"""

from __future__ import annotations

import numpy as np


class Generator:
    def __init__(self, params: dict, seed: int, scale: int = 1):
        self.seed = int(seed)
        self.chunk_bytes = int(params["file_bytes"]) // scale

    def setup_chunk(self) -> np.ndarray:
        return self.chunk(0)

    def chunk(self, i: int) -> np.ndarray:
        # integers() fills without the interpreter lock; bytes() holds it for
        # the whole chunk (0.1 s at 58 MiB), which stalled the gateways'
        # threads each time the generator thread made a chunk
        rng = np.random.default_rng([self.seed, i])
        words = rng.integers(0, 1 << 32, -(-self.chunk_bytes // 4), dtype=np.uint32)
        return words.view(np.uint8)[: self.chunk_bytes]
