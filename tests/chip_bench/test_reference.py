"""The benchmark's plain reference and its control."""

import collections

import numpy as np
import pytest

from benchmarks.chip import reference as ref


def queue_bfs(n, edges, s):
    """Textbook BFS with path counting, one vertex at a time."""
    adj = [[] for _ in range(n)]
    for a, b in edges:
        adj[a].append(b)
        adj[b].append(a)
    dist = [ref.UNREACHED] * n
    cnt = [0] * n
    dist[s], cnt[s] = 0, 1
    q = collections.deque([s])
    while q:
        v = q.popleft()
        for w in adj[v]:
            if dist[w] == ref.UNREACHED:
                dist[w], cnt[w] = dist[v] + 1, cnt[v]
                q.append(w)
            elif dist[w] == dist[v] + 1:
                cnt[w] += cnt[v]
    return np.asarray(dist), np.asarray(cnt)


@pytest.mark.parametrize("seed", range(4))
def test_bfs_counts_match_a_queue_bfs(seed):
    rng = np.random.default_rng(seed)
    n = 60
    pairs = {tuple(sorted(p)) for p in rng.integers(0, n, (150, 2))
             if p[0] != p[1]}
    edges = sorted(pairs)
    adj = ref.Adjacency(n, edges)
    for s in range(0, n, 7):
        d, c = ref.bfs_counts(adj, s)
        wd, wc = queue_bfs(n, edges, s)
        np.testing.assert_array_equal(d, wd)
        np.testing.assert_array_equal(c, wc)


def test_counts_of_a_grid_are_binomials():
    """On a k x k grid, corner to (i, j) has C(i + j, i) shortest paths."""
    from math import comb

    k = 12
    vid = lambda i, j: i * k + j  # noqa: E731
    edges = [(vid(i, j), vid(i + 1, j)) for i in range(k - 1)
             for j in range(k)] + [(vid(i, j), vid(i, j + 1))
                                   for i in range(k) for j in range(k - 1)]
    d, c = ref.bfs_counts(ref.Adjacency(k * k, edges), 0)
    for i in range(k):
        for j in range(k):
            assert d[vid(i, j)] == i + j
            assert c[vid(i, j)] == comb(i + j, i)
    _, cb = ref.bfs_counts(ref.Adjacency(k * k, edges), 0,
                           counts="bfloat16")
    # bfloat16 holds 8 significant bits: exact to 256, rounded beyond
    assert cb[vid(4, 4)] == comb(8, 4)          # 70
    assert cb[vid(11, 11)] != comb(22, 11)      # 705,432
    # float32 holds 24: every count of this grid is under 2^24
    _, cf = ref.bfs_counts(ref.Adjacency(k * k, edges), 0,
                           counts="float32")
    np.testing.assert_array_equal(cf, c)


def test_float32_counts_round_only_past_2_to_the_24():
    k = 18
    vid = lambda i, j: i * k + j  # noqa: E731
    edges = [(vid(i, j), vid(i + 1, j)) for i in range(k - 1)
             for j in range(k)] + [(vid(i, j), vid(i, j + 1))
                                   for i in range(k) for j in range(k - 1)]
    adj = ref.Adjacency(k * k, edges)
    _, c = ref.bfs_counts(adj, 0)
    _, cf = ref.bfs_counts(adj, 0, counts="float32")
    small = c < 2 ** 24
    np.testing.assert_array_equal(cf[small], c[small])
    assert np.any(cf[~small] != c[~small])
    with pytest.raises(ValueError):
        ref.bfs_counts(adj, 0, counts="int32")


def test_bf16_rounding_ties_to_even():
    x = np.asarray([256, 257, 258, 259, 260, 1.0, 3.0], np.float32)
    np.testing.assert_array_equal(ref._round_bf16(x),
                                  [256, 256, 258, 260, 260, 1.0, 3.0])


def test_edge_set_follows_events():
    es = ref.EdgeSet(5, [(0, 1), (1, 2)])
    es.apply("+", 4, 3)
    es.apply("-", 1, 0)
    assert es.edges == {(1, 2), (3, 4)}
    d, c = ref.bfs_counts(es.adjacency(), 0)
    assert d[0] == 0 and c[0] == 1
    assert d[1] == ref.UNREACHED and c[1] == 0
