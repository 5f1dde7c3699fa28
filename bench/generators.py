"""Seeded generators of the benchmark's graphs and request streams.

Everything here is numpy on the host and a pure function of its seed:

* :func:`rmat_edges` -- a Graph500-style R-MAT edge list with an exact
  number of distinct non-loop edges over any number of vertices (ids past
  it are drawn again), vertex ids permuted from the seed;
* :func:`rmat_vertices` -- vertex draws from the same skewed marginal;
* :func:`zipf_weights` -- tenant popularity.

The op kinds are restated here so that the benchmark owes nothing to the
code under test.
"""
from __future__ import annotations

import numpy as np

ADD_EDGE, REM_EDGE, ADD_VERTEX, REM_VERTEX = 0, 1, 2, 3


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream...): a run's seed may
    exceed 32 bits, which ``SeedSequence`` takes as it is."""
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) & ((1 << 64) - 1), *[int(s) for s in stream]]))


def _rmat_bits(rng, n: int, scale: int, abcd) -> tuple:
    """Unpermuted R-MAT (u, v) draws: at each of ``scale`` levels a
    quadrant is picked with probabilities (a, b, c, d)."""
    a, b, c, _ = abcd
    u = np.zeros(n, np.int64)
    v = np.zeros(n, np.int64)
    for _ in range(scale):
        r = rng.random(n, dtype=np.float32)
        down = r >= a + b                       # quadrants c and d
        right = ((r >= a) & (r < a + b)) | (r >= a + b + c)  # b and d
        u = (u << 1) | down
        v = (v << 1) | right
    return u, v


def rmat_scale(n: int) -> int:
    """Levels of R-MAT bits that cover ``n`` vertex ids."""
    return max(1, (int(n) - 1).bit_length())


def vertex_perm(seed: int, n: int, stream: int = 0) -> np.ndarray:
    """The seed's relabelling of ``n`` R-MAT vertex ids onto vertex slots
    ``0..n-1`` (hubs land anywhere); each graph of a configuration is its
    own ``stream``."""
    return rng_for(seed, 1, stream).permutation(int(n))


def _rmat_ids(rng, count: int, n: int, abcd) -> tuple:
    """``count`` raw R-MAT (u, v) draws with both ids under ``n``: draws
    at ``rmat_scale(n)`` levels, and those naming an id past ``n`` are
    drawn again, so the skew of the ids that exist is kept."""
    scale = rmat_scale(n)
    us, vs, have = [], [], 0
    while have < count:
        u, v = _rmat_bits(rng, max(1024, int((count - have) * 1.3)), scale,
                          abcd)
        ok = (u < n) & (v < n)
        us.append(u[ok])
        vs.append(v[ok])
        have += int(ok.sum())
    return np.concatenate(us)[:count], np.concatenate(vs)[:count]


def rmat_edges(seed: int, n: int, n_edges: int, abcd, stream: int = 0
               ) -> tuple:
    """Exactly ``n_edges`` distinct non-loop directed edges ``(src, dst)``
    (int32) over the ``n`` vertices ``0..n-1``, in the order they were
    first drawn.  Draws continue in rounds until enough distinct edges
    exist, then the first ``n_edges`` are kept, so the result is a pure
    function of the arguments."""
    if n_edges > n * (n - 1) // 2:
        raise ValueError(f"{n_edges} distinct edges do not fit {n} "
                         f"vertices under R-MAT skew")
    rng = rng_for(seed, 2, stream)
    perm = vertex_perm(seed, n, stream)
    keys = np.zeros(0, np.int64)
    order = np.zeros(0, np.int64)
    drawn = 0
    while keys.size < n_edges:
        u, v = _rmat_ids(rng, max(1024, int((n_edges - keys.size) * 1.3)),
                         n, abcd)
        new = u * n + v
        new = new[u != v]
        keys = np.concatenate([keys, new])
        order = np.concatenate([order, drawn + np.arange(new.size)])
        drawn += new.size
        keys, first = np.unique(keys, return_index=True)
        order = order[first]
    pick = np.argsort(order, kind="stable")[:n_edges]
    k = keys[pick]
    return (perm[k // n].astype(np.int32), perm[k % n].astype(np.int32))


def rmat_vertices(rng, count: int, abcd, perm: np.ndarray) -> tuple:
    """``count`` skewed (u, v) endpoint pairs from the R-MAT marginal over
    the ``perm.size`` vertices of one graph."""
    u, v = _rmat_ids(rng, count, perm.size, abcd)
    return perm[u].astype(np.int32), perm[v].astype(np.int32)


def zipf_weights(n: int, s: float) -> np.ndarray:
    """Popularity of ranks 1..n under Zipf(s), summing to 1."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return w / w.sum()

