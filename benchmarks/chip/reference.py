"""Plain reference: breadth-first search with shortest-path counting.

The benchmark's yardstick for ``correct``.  It imports nothing of the
program and reads nothing the program made: it takes the edge list the
benchmark generated from the seed (plus the events the benchmark itself
submitted) and answers ``(dist, count)`` by a level-synchronous BFS over a
CSR adjacency.

Semantics are the service's: ``dist(s, s) = 0`` with one path; a pair
with no path answers ``(UNREACHED, 0)``.  Counts follow the count
contract: a pair's count is right if and only if it equals
``min(true count, 2^63 - 1)``; ``INT64_MAX`` reads as "at least that
many", and every count below it is exact.  The reference never wraps: a
level's sums are taken in float64 where they are exact (under 2^52), and
in Python ints, clamped at ``INT64_MAX``, where they are not.  Clamping
each vertex's count keeps the contract through the next level, since for
counts x >= 0, ``min(sum(min(x_i, S)), S) = min(sum(x_i), S)``.

``counts="bfloat16"`` is the control: the same BFS with every level's
path counts rounded to bfloat16.  Put in the program's place it must come
out as not correct (see ``checks.py``).  ``counts="float32"`` is the
precision the query kernel counts in without its int64 fallback; it is
read beside the control, and on these graphs it is exact (PERF.md).
Both are held to the same contract: a rounded count at or past 2^63
reads ``INT64_MAX``.
"""

from __future__ import annotations

import numpy as np

#: The service's "no path" distance (``repro.core.graph.INF``, 1 << 28).
UNREACHED = 1 << 28


class Adjacency:
    """Undirected CSR adjacency of an edge list over ``n`` vertices."""

    def __init__(self, n: int, edges) -> None:
        e = np.asarray(edges, np.int64).reshape(-1, 2)
        src = np.concatenate([e[:, 0], e[:, 1]])
        dst = np.concatenate([e[:, 1], e[:, 0]])
        order = np.argsort(src, kind="stable")
        self.n = n
        self.indices = dst[order]
        self.indptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=n), out=self.indptr[1:])

    def neighbours(self, frontier: np.ndarray):
        """(parent index into frontier, neighbour) for every edge out of
        the frontier."""
        lo = self.indptr[frontier]
        deg = self.indptr[frontier + 1] - lo
        owner = np.repeat(np.arange(frontier.size), deg)
        offs = np.arange(deg.sum()) - np.repeat(np.cumsum(deg) - deg, deg)
        return owner, self.indices[lo[owner] + offs]


def _round_bf16(x: np.ndarray) -> np.ndarray:
    """float32 -> nearest bfloat16 (ties to even), kept as float32."""
    bits = np.asarray(x, np.float32).view(np.uint32)
    bias = np.uint32(0x7FFF) + ((bits >> 16) & np.uint32(1))
    return ((bits + bias) & np.uint32(0xFFFF0000)).view(np.float32)


#: Count precisions ``bfs_counts`` accumulates in.
COUNTS = ("int64", "float32", "bfloat16")
#: The count contract's ceiling: "at least this many shortest paths".
INT64_MAX = 2 ** 63 - 1
#: float64 sums of non-negative integers under this are exact: no
#: partial sum reached 2^53.
_F64_EXACT = 2.0 ** 52


def _exact_sums(contrib: np.ndarray, to: np.ndarray,
                big: np.ndarray) -> np.ndarray:
    """int64 ``min(sum of contrib[to == v], INT64_MAX)`` for each vertex
    ``v`` of ``big``, summed in Python ints."""
    acc = dict.fromkeys(big.tolist(), 0)
    use = np.isin(to, big)
    for v, c in zip(to[use].tolist(), contrib[use].tolist()):
        acc[v] += c
    return np.asarray([min(acc[k], INT64_MAX) for k in big.tolist()],
                      np.int64)


def bfs_counts(adj: Adjacency, source: int, *, counts: str = "int64"):
    """(dist int64[n], count int64[n]) from ``source``; unreached vertices
    get ``(UNREACHED, 0)``.  Counts are ``min(true count, INT64_MAX)``;
    ``counts`` other than ``"int64"`` rounds every level's path counts to
    that precision first."""
    if counts not in COUNTS:
        raise ValueError(f"unknown count precision {counts!r}")
    n = adj.n
    dist = np.full(n, UNREACHED, np.int64)
    rounded = counts != "int64"
    cnt = np.zeros(n, np.float32 if rounded else np.int64)
    dist[source] = 0
    cnt[source] = 1
    frontier = np.asarray([source], np.int64)
    level = 0
    while frontier.size:
        owner, nb = adj.neighbours(frontier)
        fresh = dist[nb] >= level + 1
        owner, nb = owner[fresh], nb[fresh]
        if not nb.size:
            break
        contrib = cnt[frontier[owner]]
        # every count reached is at least 1, so the next level is
        # where the sums are not 0
        sums = np.bincount(nb, weights=contrib.astype(np.float64),
                           minlength=n)
        nxt = np.flatnonzero(sums)
        sums = sums[nxt]
        if rounded:
            with np.errstate(over="ignore"):
                level_counts = sums.astype(np.float32)
            cnt[nxt] = (_round_bf16(level_counts) if counts == "bfloat16"
                        else level_counts)
        else:
            exact = sums < _F64_EXACT
            level_counts = np.where(exact, sums, 0).astype(np.int64)
            big = np.flatnonzero(~exact)
            if big.size:
                level_counts[big] = _exact_sums(contrib, nb, nxt[big])
            cnt[nxt] = level_counts
        dist[nxt] = level + 1
        frontier = nxt
        level += 1
    if rounded:
        fits = cnt < 2.0 ** 63
        out = np.full(n, INT64_MAX, np.int64)
        out[fits] = cnt[fits].astype(np.int64)
        return dist, out
    return dist, cnt


class EdgeSet:
    """The reference's own copy of the graph under the benchmark's
    events: a set of ``(lo, hi)`` pairs."""

    def __init__(self, n: int, edges) -> None:
        self.n = n
        self.edges = {(int(min(a, b)), int(max(a, b))) for a, b in edges}

    def apply(self, op: str, a: int, b: int) -> None:
        key = (min(a, b), max(a, b))
        if op == "+":
            self.edges.add(key)
        else:
            self.edges.discard(key)

    def adjacency(self) -> Adjacency:
        return Adjacency(self.n, sorted(self.edges))
