"""Graph500 Kronecker generator (the specification's reference code).

A copy of the ``kronecker_generator`` of the Graph500 benchmark
specification (graph500.org, "Graph Generation"): ``edgefactor * 2^scale``
edge tuples, each endpoint built bit by bit from the initiator
``A, B, C, D = 0.57, 0.19, 0.19, 0.05``, then the vertex labels randomly
permuted and the tuples shuffled.  The benchmark then keeps the graph the
kernel sees: self-loops and duplicate tuples dropped, edges undirected.

``generate(params, seed)`` is the entry the harness calls; ``params`` is
the configuration file's ``graph`` object.
"""

from __future__ import annotations

import numpy as np

A, B, C = 0.57, 0.19, 0.19        # D = 1 - A - B - C = 0.05


def kronecker_tuples(scale: int, edgefactor: int,
                     rng: np.random.Generator) -> np.ndarray:
    """int64[M, 2] edge tuples, ``M = edgefactor * 2^scale``, as the
    specification's generator draws them (labels permuted, rows shuffled)."""
    n = 1 << scale
    m = edgefactor * n
    ij = np.zeros((2, m), np.int64)
    ab = A + B
    c_norm = C / (1.0 - ab)
    a_norm = A / ab
    for ib in range(scale):
        ii_bit = rng.random(m) > ab
        jj_bit = rng.random(m) > np.where(ii_bit, c_norm, a_norm)
        ij[0] += ii_bit.astype(np.int64) << ib
        ij[1] += jj_bit.astype(np.int64) << ib
    perm = rng.permutation(n)
    ij = perm[ij]
    ij = ij[:, rng.permutation(m)]
    return ij.T


def simple_edges(tuples: np.ndarray) -> np.ndarray:
    """Undirected simple graph of the tuples: int64[E, 2] with lo < hi,
    sorted, self-loops and duplicates dropped."""
    lo = tuples.min(axis=1)
    hi = tuples.max(axis=1)
    keep = lo != hi
    pairs = np.stack([lo[keep], hi[keep]], axis=1)
    return np.unique(pairs, axis=0)


def generate(params: dict, seed: int):
    """(n, edges int64[E, 2]) of the configuration's Kronecker graph."""
    scale = int(params["scale"])
    rng = np.random.default_rng(seed)
    tuples = kronecker_tuples(scale, int(params["edgefactor"]), rng)
    return 1 << scale, simple_edges(tuples)
