"""Top-k friend recommendation from SPC-count features.

The paper's second motivating application: on a social graph, the
standard potential-friend signal for a user ``u`` is the number of
*common friends* with each non-friend ``x`` -- which is exactly the
shortest-path count ``sigma(u, x)`` whenever ``d(u, x) == 2``.  One
``one_to_all`` dispatch over the pinned snapshot therefore yields the
full candidate set (every vertex at distance 2) *and* its ranking
signal at once; no adjacency structure is consulted.

Beyond the classic heuristic, :func:`recommendation_features` exposes a
per-candidate feature row built entirely from snapshot state --

    [d(u, x), sigma(u, x), size[x], min(sum(cnt[x]), 2^63 - 1)]

(distance, path count, label-row occupancy and the row's count mass,
the latter two cheap popularity/coverage proxies read off the label
rows) -- which ``examples/analytics_spc.py`` feeds through
the repo's GNN + embedding-bag stack: the first end-to-end "model
consumes the dynamic index" scenario.  :func:`common_neighbor_ids`
recovers the actual common-friend id list (two mask rows ANDed) for
``embedding_bag`` pooling.

Oracle: :func:`recommend_numpy` recomputes the ranking from raw
adjacency sets (no index), for the differential tests.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional

import jax
import numpy as np

from repro.core import query as Q
from repro.core.counts import COUNT_MAX
from repro.core.graph import INF
from repro.core.labels import SPCIndex


@dataclasses.dataclass(frozen=True)
class Recommendation:
    """One ranked candidate: ``score`` is the common-friend count
    (sigma at distance 2)."""
    vertex: int
    score: int
    dist: int


@partial(jax.jit, static_argnames=())
def _one_to_all(idx: SPCIndex, u) -> tuple:
    return Q.one_to_all(idx, u)


def recommend(idx: SPCIndex, u: int, *, k: int = 16) -> List[Recommendation]:
    """Top-k friends-of-friends of ``u`` by common-friend count,
    deterministically tie-broken by vertex id."""
    dist, cnt = _one_to_all(idx, u)
    dist = np.asarray(dist)[:idx.n]
    cnt = np.asarray(cnt)[:idx.n]
    cand = np.flatnonzero(dist == 2)
    if cand.size == 0:
        return []
    order = np.lexsort((cand, -cnt[cand]))[:k]
    return [Recommendation(int(cand[i]), int(cnt[cand[i]]), 2)
            for i in order]


def recommendation_features(idx: SPCIndex, u: int,
                            candidates: np.ndarray) -> np.ndarray:
    """float32 [C, 4] feature rows ``[dist, sigma, size, count mass]``
    for ``candidates``, all off the pinned snapshot (disconnected
    candidates get dist = -1, sigma = 0; the count mass is the row's
    count sum clamped at 2^63 - 1, summed on the host in Python ints)."""
    dist, cnt = _one_to_all(idx, u)
    dist = np.asarray(dist)
    cnt = np.asarray(cnt)
    c = np.asarray(candidates, dtype=np.int64)
    d = dist[c].astype(np.float32)
    d[dist[c] >= INF] = -1.0
    return np.stack(
        [d,
         cnt[c].astype(np.float32),
         np.asarray(idx.size)[c].astype(np.float32),
         np.minimum(np.asarray(idx.cnt)[c].astype(object).sum(axis=1),
                    COUNT_MAX).astype(np.int64).astype(np.float32)],
        axis=1)


def common_neighbor_ids(idx: SPCIndex, u: int, x: int) -> np.ndarray:
    """Ids of the common friends of ``u`` and ``x`` (for embedding-bag
    pooling), recovered from two one_to_all rows."""
    du, _ = _one_to_all(idx, u)
    dx, _ = _one_to_all(idx, x)
    both = (np.asarray(du)[:idx.n] == 1) & (np.asarray(dx)[:idx.n] == 1)
    return np.flatnonzero(both)


def recommend_numpy(n: int, edges, u: int, *,
                    k: int = 16) -> List[Recommendation]:
    """Brute-force oracle: common-friend counts from adjacency sets."""
    adj = [set() for _ in range(n)]
    for a, b in edges:
        adj[a].add(b)
        adj[b].add(a)
    scores = {}
    for x in range(n):
        if x == u or x in adj[u]:
            continue
        common = len(adj[u] & adj[x])
        if common:
            scores[x] = common
    ranked = sorted(scores.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    return [Recommendation(x, s, 2) for x, s in ranked]
