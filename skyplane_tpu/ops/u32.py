"""uint32 arithmetic in GF(2^31 - 1) for TPU-resident hashing.

TPUs have no native 64-bit integer lanes, so all field arithmetic is built
from uint32 ops with 16-bit limb decomposition. The Mersenne prime
``M31 = 2^31 - 1`` makes reduction a pair of shift-adds (2^31 ≡ 1).

These primitives back the polynomial fingerprints in ops/fingerprint.py; a
numpy mirror (``*_np``) is provided for property tests against Python ints.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

M31 = (1 << 31) - 1  # 2147483647, Mersenne prime


def fold31(x):
    """Reduce x < 2^32 into [0, M31] using 2^31 ≡ 1 (one extra fold for the edge)."""
    x = (x >> 31) + (x & M31)
    x = (x >> 31) + (x & M31)
    return jnp.where(x == M31, jnp.uint32(0), x.astype(jnp.uint32))


def addmod31(a, b):
    """(a + b) mod M31 for canonical a, b < M31 (sum < 2^32 so uint32 is safe)."""
    return fold31(a.astype(jnp.uint32) + b.astype(jnp.uint32))


def mulmod31(a, b):
    """(a * b) mod M31 for a, b < 2^31 using 16-bit limbs (no 64-bit ops).

    a*b = a1*b1<<32 + (a1*b0 + a0*b1)<<16 + a0*b0, then each part is folded
    with 2^31 ≡ 1:
      t1<<32 ≡ 2*t1            (t1 < 2^30)
      t2<<16 ≡ u + v<<16       where t2 = u<<15 | v   (t2 < 2^32)
      t3     ≡ t3>>31 + t3&M31 (t3 < 2^32)
    """
    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    a1, a0 = a >> 16, a & 0xFFFF
    b1, b0 = b >> 16, b & 0xFFFF
    t1 = a1 * b1  # < 2^30
    t2 = a1 * b0 + a0 * b1  # < 2^32
    t3 = a0 * b0  # < 2^32
    p1 = fold31(t1 << 1)
    u, v = t2 >> 15, t2 & 0x7FFF
    p2 = addmod31(fold31(u), fold31(v << 16))
    p3 = fold31(t3)
    return addmod31(addmod31(p1, p2), p3)


def powmod31_table(base: int, n: int) -> np.ndarray:
    """Host-side table [base^0, ..., base^(n-1)] mod M31, built by size-doubling."""
    out = np.zeros(max(n, 1), dtype=np.uint64)
    out[0] = 1
    m = 1
    while m < n:
        step = out[:m] * ((out[m - 1] * base) % M31)  # base^m * base^i, fits u64
        take = min(m, n - m)
        out[m : m + take] = step[:take] % M31
        m *= 2
    return out[:n].astype(np.uint32)


def powmod31_table_device(bases, n: int):
    """[len(bases), n] table base^j mod M31 built INSIDE a traced program, on
    the device, by the same size-doubling (a few dozen small ops a call). The
    seed passes an optimization barrier: without it the compiler folds the
    build into constants embedded in the program (40 MiB of them in the 64
    MiB fingerprint program), which every load of the program then pays."""
    out = jax.lax.optimization_barrier(jnp.ones((len(bases), 1), jnp.uint32))
    m = 1
    while m < n:
        step = jnp.asarray([pow(int(b), m, M31) for b in bases], jnp.uint32)[:, None]  # base^m
        out = jnp.concatenate([out, mulmod31(out, step)], axis=1)
        m *= 2
    return out[:, :n]


# ---- numpy mirrors for property testing ----


def mulmod31_np(a, b):
    return np.uint32((np.uint64(a) * np.uint64(b)) % np.uint64(M31))


def addmod31_np(a, b):
    return np.uint32((np.uint64(a) + np.uint64(b)) % np.uint64(M31))
