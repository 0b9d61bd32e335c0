"""The one traffic generator, and the cell words every table starts from.

A traffic mix is a JSON file of parameters (`traffic/<mix>.json`):

  mix            {op name: share}; each lane's op is drawn independently
                 with these shares, as YCSB's CoreWorkload draws each op;
  keys           "zipf" or "uniform" over the cell's key space;
  theta          the Zipf exponent (YCSB's 0.99);
  pool_batches   distinct batches drawn before the window; the window
                 runs them in order and starts again from the first;
  warmup_batches batches run before the window (set-up), from the pool.

Zipf ranks are drawn as YCSB's ZipfianGenerator draws them (Gray et al.,
SIGMOD 1994), over the key space, one independent uniform variate per
lane.  Ranks are spread over the key space by an odd multiply and an
offset drawn from the seed, a bijection, so hot keys do not sit side by
side.
"""

from __future__ import annotations

import functools

import numpy as np

_GOLDEN = 0x9E3779B9


# ---------------------------------------------------------------------------
# Zipf ranks (copied from chip_smoke.zipf_ranks, which draws them as YCSB)
# ---------------------------------------------------------------------------

@functools.cache
def zeta(n_items: int, theta: float) -> float:
    """sum_{i=1..n} i^-theta, in blocks so that a large n needs no array
    of its size."""
    total, block = 0.0, 1 << 22
    for lo in range(1, n_items + 1, block):
        i = np.arange(lo, min(lo + block, n_items + 1), dtype=np.float64)
        total += float(np.sum(i ** -theta))
    return total


class Zipf:
    """Zipf(theta) ranks in [0, n_items), rank 0 the hottest."""

    def __init__(self, n_items: int, theta: float):
        self.n, self.theta = n_items, theta
        self.zetan = zeta(n_items, theta)
        self.zeta2 = 1.0 + 0.5 ** theta
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = ((1 - (2.0 / n_items) ** (1 - theta))
                    / (1 - self.zeta2 / self.zetan))

    def rank(self, u: np.ndarray) -> np.ndarray:
        """YCSB's rank for uniform variates u in [0, 1)."""
        uz = u * self.zetan
        tail = (self.n * (self.eta * u - self.eta + 1) ** self.alpha
                ).astype(np.int64)
        ranks = np.where(uz < 1.0, 0, np.where(uz < self.zeta2, 1, tail))
        return np.minimum(ranks, self.n - 1)

    def draw(self, rng: np.random.Generator, lanes: int) -> np.ndarray:
        """One batch of ranks, each drawn independently."""
        return self.rank(rng.random(lanes))


def spread(ranks: np.ndarray, n_items: int, mul: int, off: int) -> np.ndarray:
    """Ranks onto [0, n_items) (a power of two) by an odd multiply and an
    offset: a bijection."""
    mask = np.uint64(n_items - 1)
    return ((ranks.astype(np.uint64) * np.uint64(mul) + np.uint64(off))
            & mask).astype(np.int64)


# ---------------------------------------------------------------------------
# Batches
# ---------------------------------------------------------------------------

class Batches:
    """A pool of op batches drawn from one seed.

    kind   int32[B, lanes]  program op codes (the system's `KINDS`)
    index  int64[B, lanes]  key index in [0, space)
    value  uint32[B, lanes, width]  the value each lane writes (every lane
           gets one; lanes that write nothing ignore it)
    """

    def __init__(self, kind, index, value):
        self.kind, self.index, self.value = kind, index, value

    def __len__(self) -> int:
        return self.kind.shape[0]


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, stream])


def draw(traffic: dict, *, space: int, lanes: int, width: int, seed: int,
         codes: dict) -> Batches:
    """The pool of `traffic["pool_batches"]` batches for one seed.  `codes`
    maps the mix's op names onto the system's op codes."""
    if space & (space - 1):
        raise ValueError(f"key space {space} is not a power of two")
    rng = rng_for(seed, 1)
    n_b = int(traffic["pool_batches"])
    names = sorted(traffic["mix"])
    unknown = set(names) - set(codes)
    if unknown:
        raise ValueError(f"op names {sorted(unknown)} not in {sorted(codes)}")
    shares = np.asarray([traffic["mix"][m] for m in names], np.float64)
    if abs(shares.sum() - 1.0) > 1e-9:
        raise ValueError(f"mix shares sum to {shares.sum()}, not 1")
    kind = np.asarray([codes[m] for m in names], np.int32)[
        rng.choice(len(names), size=(n_b, lanes), p=shares)]
    if traffic["keys"] == "zipf":
        zipf = Zipf(space, float(traffic["theta"]))
        mul = int(rng.integers(0, 1 << 31)) * 2 + 1
        off = int(rng.integers(0, space))
        index = np.stack([spread(zipf.draw(rng, lanes), space, mul, off)
                          for _ in range(n_b)])
    elif traffic["keys"] == "uniform":
        index = rng.integers(0, space, (n_b, lanes), dtype=np.int64)
    else:
        raise ValueError(f"keys {traffic['keys']!r}: zipf or uniform")
    value = rng.integers(0, 1 << 32, (n_b, lanes, width), dtype=np.uint32)
    return Batches(kind, index, value)


# ---------------------------------------------------------------------------
# Cell words: what a table of the cell's size holds before the first op.
# ---------------------------------------------------------------------------

def _seed_words(seed: int) -> tuple[int, int]:
    seed = int(seed) & 0xFFFFFFFFFFFFFFFF
    return seed & 0xFFFFFFFF, ((seed >> 32) * _GOLDEN + 0x632BE5AB) & 0xFFFFFFFF


def _fmix32(x, xp):
    """murmur3's finalizer on uint32 arrays of numpy or jax.numpy."""
    x = x ^ (x >> 16)
    x = x * xp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * xp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def cell_words(seed: int, cells, k: int, xp=np):
    """uint32[len(cells), k]: word j of cell c is a hash of (seed, c*k+j).
    `xp` is numpy (the reference) or jax.numpy (the table on the device):
    the two give the same words."""
    s0, s1 = _seed_words(seed)
    c = xp.asarray(cells).astype(xp.uint32)[:, None]
    x = c * xp.uint32(k) + xp.arange(k, dtype=xp.uint32)[None, :]
    return _fmix32(_fmix32(x ^ xp.uint32(s0), xp) + xp.uint32(s1), xp)
