"""Road-network stand-in: a seeded perturbed planar grid.

The 9th DIMACS Implementation Challenge (Shortest Paths) road graphs,
such as ``USA-road-d.NY`` (264,346 nodes, 733,846 arcs, mean degree
2.78, degrees 1-8), are planar-like meshes of low degree and large
diameter.  No download: the graph is a ``side`` x ``side``
4-neighbour lattice with each edge dropped with probability ``drop``
(0.30 gives NY's mean degree), its largest connected component kept and
the vertex labels permuted.  Arc lengths are dropped: DSPC counts
unweighted shortest paths, so distances are hops.

``generate(params, seed)`` is the entry the harness calls; ``params`` is
the configuration file's ``graph`` object.
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components


def lattice_edges(side: int) -> np.ndarray:
    """int64[E, 2] of the ``side`` x ``side`` 4-neighbour grid, vertex
    ``r * side + c``, each edge once with lo < hi."""
    ids = np.arange(side * side, dtype=np.int64).reshape(side, side)
    across = np.stack([ids[:, :-1].ravel(), ids[:, 1:].ravel()], axis=1)
    down = np.stack([ids[:-1, :].ravel(), ids[1:, :].ravel()], axis=1)
    return np.concatenate([across, down])


def largest_component(n: int, edges: np.ndarray) -> np.ndarray:
    """The vertices of the largest connected component, ascending."""
    adj = coo_matrix((np.ones(len(edges)), (edges[:, 0], edges[:, 1])),
                     shape=(n, n))
    _, comp = connected_components(adj, directed=False)
    return np.flatnonzero(comp == np.bincount(comp).argmax())


def generate(params: dict, seed: int):
    """(n, edges int64[E, 2]) of the configuration's road graph: lo < hi,
    sorted, unique."""
    side = int(params["side"])
    drop = float(params["drop"])
    rng = np.random.default_rng(seed)
    edges = lattice_edges(side)
    edges = edges[rng.random(len(edges)) >= drop]
    keep = largest_component(side * side, edges)
    label = np.full(side * side, -1, np.int64)
    label[keep] = rng.permutation(keep.size)
    edges = label[edges]
    edges = edges[(edges >= 0).all(axis=1)]
    edges = np.sort(edges, axis=1)
    return int(keep.size), np.unique(edges, axis=0)
