"""Recommendation (common-friend ranking off one ``one_to_all``
dispatch) vs the adjacency-set oracle, plus the feature rows the GNN
example consumes."""

import numpy as np
import pytest

from repro.analytics import (common_neighbor_ids, recommend, recommend_numpy,
                             recommendation_features)
from repro.core import refimpl as R
from repro.core.counts import COUNT_MAX
from repro.core.dynamic import DynamicSPC
from repro.core.graph import INF
from repro.core.labels import from_ref
from repro.data import graph_stream, random_graph_edges


def _adj(n, edges):
    adj = [set() for _ in range(n)]
    for a, b in edges:
        adj[a].add(b)
        adj[b].add(a)
    return adj


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_recommend_matches_oracle_under_stream(seed):
    n = 20
    edges = random_graph_edges(n, 40, seed=seed)
    spc = DynamicSPC(n, edges, l_cap=24)
    current = set(edges)
    for op, a, b in graph_stream(edges, n, 6, 4, seed=seed + 30):
        spc.apply_events([(op, a, b)])
        e = (min(a, b), max(a, b))
        current.add(e) if op == "+" else current.discard(e)
    for u in range(0, n, 3):
        got = recommend(spc.index, u, k=8)
        want = recommend_numpy(n, sorted(current), u, k=8)
        assert [(r.vertex, r.score, r.dist) for r in got] \
            == [(r.vertex, r.score, r.dist) for r in want], u


def test_recommendation_features_rows():
    # path 0-1-2 plus isolated 3: candidate at d=2, and a disconnected one
    edges = [(0, 1), (1, 2)]
    spc = DynamicSPC(4, edges, l_cap=8)
    feats = recommendation_features(spc.index, 0, np.asarray([2, 3]))
    assert feats.shape == (2, 4) and feats.dtype == np.float32
    d, sigma = feats[:, 0], feats[:, 1]
    assert (d[0], sigma[0]) == (2.0, 1.0)
    assert (d[1], sigma[1]) == (-1.0, 0.0)  # disconnected sentinel


def test_count_mass_feature_clamps_a_saturated_row():
    """The fourth feature is the row's count sum in Python ints, clamped
    at 2^63 - 1: a row of two 2^62 counts plus one reads 2^63 - 1 (an
    int64 sum would wrap negative), a small row its exact sum."""
    ref = R.RefSPCIndex(3)
    ref.labels[0] = [(0, 0, 1)]
    ref.labels[1] = [(0, 1, 2 ** 62), (1, 0, 2 ** 62 + 1)]
    ref.labels[2] = [(0, 1, 5), (2, 0, 1)]
    feats = recommendation_features(from_ref(ref, l_cap=4), 0,
                                    np.asarray([1, 2]))
    assert feats[0, 3] == np.float32(COUNT_MAX)
    assert feats[1, 3] == np.float32(6)
    np.testing.assert_array_equal(feats[:, 2], [2.0, 2.0])   # row sizes


def test_features_sigma_equals_common_friend_count():
    n = 16
    edges = random_graph_edges(n, 34, seed=3)
    spc = DynamicSPC(n, edges, l_cap=24)
    adj = _adj(n, edges)
    u = 0
    recs = recommend(spc.index, u, k=16)
    cand = np.asarray([r.vertex for r in recs])
    if cand.size == 0:
        pytest.skip("no distance-2 candidates in this draw")
    feats = recommendation_features(spc.index, u, cand)
    for row, x in zip(feats, cand.tolist()):
        assert row[0] == 2.0
        assert int(row[1]) == len(adj[u] & adj[x])


def test_common_neighbor_ids_matches_adjacency():
    n = 16
    edges = random_graph_edges(n, 34, seed=4)
    spc = DynamicSPC(n, edges, l_cap=24)
    adj = _adj(n, edges)
    for u, x in [(0, 5), (1, 9), (2, 2), (3, 14)]:
        got = common_neighbor_ids(spc.index, u, x).tolist()
        assert got == sorted(adj[u] & adj[x]), (u, x)


def test_recommend_no_candidates():
    # a clique: everyone is already a friend -> nothing at distance 2
    n = 5
    edges = [(a, b) for a in range(n) for b in range(a + 1, n)]
    spc = DynamicSPC(n, edges, l_cap=12)
    assert recommend(spc.index, 0) == []
    assert recommend_numpy(n, edges, 0) == []


def test_recommend_deterministic_tie_break():
    # star: every leaf pair has exactly 1 common friend -> id order
    edges = [(0, i) for i in range(1, 6)]
    spc = DynamicSPC(6, edges, l_cap=12)
    got = recommend(spc.index, 1, k=3)
    assert [r.vertex for r in got] == [2, 3, 4]
    assert all(r.score == 1 and r.dist == 2 for r in got)
    assert int(INF) > 0  # sanity: sentinel imported, stays positive
