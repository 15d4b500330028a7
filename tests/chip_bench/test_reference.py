"""The benchmark's plain reference and its control, held to the count
contract: a count is right if and only if it equals
``min(true count, 2^63 - 1)``."""

import collections
from math import comb

import numpy as np
import pytest

from benchmarks.chip import checks
from benchmarks.chip import reference as ref


def queue_bfs(n, edges, s, clamp=False):
    """Textbook BFS with path counting, one vertex at a time, in Python
    ints; ``clamp`` returns the contract's counts, ``min(count,
    INT64_MAX)``."""
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
    if clamp:
        cnt = [min(c, ref.INT64_MAX) for c in cnt]
    return np.asarray(dist), np.asarray(cnt)


def grid(k, drop=0.0, rng=None):
    """Edges of a k x k grid, each kept with probability ``1 - drop``,
    and the vertex id of ``(i, j)``."""
    vid = lambda i, j: i * k + j  # noqa: E731
    edges = [(vid(i, j), vid(i + 1, j)) for i in range(k - 1)
             for j in range(k)] + [(vid(i, j), vid(i, j + 1))
                                   for i in range(k) for j in range(k - 1)]
    if drop:
        edges = [e for e in edges if rng.random() >= drop]
    return edges, vid


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


@pytest.mark.parametrize("k", [12, 40])
def test_counts_of_a_grid_are_binomials(k):
    """On a k x k grid, corner to (i, j) has C(i + j, i) shortest paths:
    under 2^24 at k = 12, up to about 2^75 at k = 40, where the counts at
    or past 2^63 - 1 read 2^63 - 1 and the rest are exact."""
    edges, vid = grid(k)
    adj = ref.Adjacency(k * k, edges)
    d, c = ref.bfs_counts(adj, 0)
    assert c.dtype == np.int64 and c.min() >= 1
    for i in range(k):
        for j in range(k):
            assert d[vid(i, j)] == i + j
            assert c[vid(i, j)] == min(comb(i + j, i), ref.INT64_MAX)
    saturated = c == ref.INT64_MAX
    assert saturated.any() == (comb(2 * k - 2, k - 1) >= ref.INT64_MAX)
    _, cb = ref.bfs_counts(adj, 0, counts="bfloat16")
    # bfloat16 holds 8 significant bits: exact to 256, rounded beyond
    assert cb[vid(4, 4)] == comb(8, 4)          # 70
    assert cb[vid(11, 11)] != comb(22, 11)      # 705,432
    # float32 holds 24: exact on every count under 2^24, which is every
    # count at k = 12; both read 2^63 - 1 where the count does
    _, cf = ref.bfs_counts(adj, 0, counts="float32")
    small = c < 2 ** 24
    assert small.all() == (k == 12)
    np.testing.assert_array_equal(cf[small], c[small])
    np.testing.assert_array_equal(cf[saturated], c[saturated])
    np.testing.assert_array_equal(cb[saturated], c[saturated])


@pytest.mark.parametrize("seed", range(4))
def test_counts_past_int64_match_a_clamped_queue_bfs(seed):
    """On perturbed grids, whose counts pass 2^63, the reference equals a
    Python-int queue BFS clamped at 2^63 - 1 from every source tried."""
    rng = np.random.default_rng(seed)
    k = 64
    edges, vid = grid(k, drop=0.1, rng=rng)
    adj = ref.Adjacency(k * k, edges)
    sources = [vid(0, 0), vid(k - 1, k - 1), vid(0, k - 1)]
    sources += [int(v) for v in rng.choice(k * k, 3, replace=False)]
    saturated = 0
    for s in sources:
        d, c = ref.bfs_counts(adj, s)
        wd, wc = queue_bfs(k * k, edges, s, clamp=True)
        np.testing.assert_array_equal(d, wd)
        np.testing.assert_array_equal(c, wc.astype(np.int64))
        saturated += int(np.sum(c == ref.INT64_MAX))
    assert saturated > 0


def _grid_pairs(k):
    """Every pair from the corner of a k x k grid, with the reference's
    answers and the true counts in Python ints."""
    edges, _ = grid(k)
    adj = ref.Adjacency(k * k, edges)
    d, c = ref.bfs_counts(adj, 0)
    true = queue_bfs(k * k, edges, 0)[1]
    s = np.zeros(k * k, np.int64)
    t = np.arange(k * k)
    return adj, s, t, d, c, true


def test_check_pairs_holds_answers_to_the_count_contract():
    adj, s, t, d, c, true = _grid_pairs(40)
    sample = np.asarray([0])
    got = checks.check_pairs(s, t, d, c, adj, sample)
    assert got == {"checked": 1600, "wrong": 0, "saturated": int(
        sum(x >= ref.INT64_MAX for x in true))}
    assert got["saturated"] > 0
    # int64 arithmetic that wraps: the true count mod 2^64, as int64
    wrapped = np.asarray([(x + 2 ** 63) % 2 ** 64 - 2 ** 63 for x in true],
                         np.int64)
    assert checks.check_pairs(s, t, d, wrapped, adj, sample)["wrong"] > 0
    # one count off below the ceiling is wrong, at the ceiling it is not
    off = c.copy()
    off[np.argmax(c < ref.INT64_MAX - 1)] -= 1
    assert checks.check_pairs(s, t, d, off, adj, sample)["wrong"] == 1
    low = c.copy()
    low[c == ref.INT64_MAX] = ref.INT64_MAX - 1
    assert checks.check_pairs(s, t, d, low, adj, sample)["wrong"] == \
        got["saturated"]


def test_float32_counts_round_only_past_2_to_the_24():
    edges, _ = grid(18)
    adj = ref.Adjacency(18 * 18, edges)
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
