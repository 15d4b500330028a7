"""Exactness decided from the kernel's own fp32 answer.

The serving read path runs the Pallas kernel (interpret mode here) on the
whole batch and re-answers in int64 exactly the real rows whose fp32
count reads 2^23 or more (``kernels/spc_query/ops.exact_query_split``).
These tests hold every answer to the count contract -- a count is right
iff it equals ``min(true count, 2^63 - 1)`` -- at the edges of fp32
(just under and over 2^23 and 2^24, 2^24 + 1, a sum that overflows to
+inf, saturated labels), and on grids against the benchmark's plain BFS
reference (``benchmarks/chip/reference.py``): every patched row read
2^23 or more in the kernel, and every row left unpatched is exact.
"""

import pathlib
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import refimpl as R
from repro.core.counts import COUNT_MAX
from repro.core.dynamic import DynamicSPC
from repro.core.graph import INF
from repro.core.labels import from_ref
from repro.kernels.spc_query import ops
from repro.serve import QueryEngine

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import reference  # noqa: E402
from benchmarks.chip.graphs import road  # noqa: E402

#: Vertices 0..3 are hubs; ``S`` and ``T`` reach each of them at distance
#: 1, so the pair (S, T) sums one product per hub, at distance 2.
S, T, N = 8, 9, 10

#: name -> (counts of S on hubs 0.., counts of T on the same hubs)
CASES = {
    "2^23-1": ([2 ** 23 - 1], [1]),
    "2^23": ([2 ** 23], [1]),
    "2^23+1": ([2 ** 23 + 1], [1]),
    "2^24-1": ([2 ** 24 - 1], [1]),
    "2^24": ([2 ** 24], [1]),
    "2^24+1": ([2 ** 24 + 1], [1]),          # fp32 reads 2^24
    "sum_2^23-1": ([2 ** 22, 2 ** 21, 2 ** 21 - 1], [1, 1, 1]),
    "sum_3*2^22": ([4095, 2049], [2048, 2048]),
    "sum_2^24+1": ([2 ** 23, 2 ** 23, 1], [1, 1, 1]),
    "product_2^62-1": ([2 ** 31 + 1], [2 ** 31 - 1]),
    "saturated": ([COUNT_MAX], [1]),
    "saturated_product": ([COUNT_MAX], [2]),
    "sum_to_inf": ([COUNT_MAX] * 4, [COUNT_MAX] * 4),   # 4 * 2^126
}


def _case_index(cs, ct):
    ref = R.RefSPCIndex(N)
    for v in range(N):
        ref.labels[v] = [(v, 0, 1)]
    ref.labels[S] = [(h, 1, c) for h, c in enumerate(cs)] + [(S, 0, 1)]
    ref.labels[T] = [(h, 1, c) for h, c in enumerate(ct)] + [(T, 0, 1)]
    want = min(sum(a * b for a, b in zip(cs, ct)), COUNT_MAX)
    return from_ref(ref, l_cap=8), want


def _spy_patches(monkeypatch):
    """Record the row indices every ``merge_patch`` dispatch patches."""
    patched, real = [], ops.merge_patch

    def spy(rows, iex, d, c):
        patched.append(np.asarray(iex))
        return real(rows, iex, d, c)

    monkeypatch.setattr(ops, "merge_patch", spy)
    return patched


@pytest.mark.parametrize("name", list(CASES))
def test_rows_at_the_edges_of_fp32_read_exactly(name, monkeypatch):
    """The row (S, T) beside two small rows, through the engine (bucket
    padded) and through ``index_query_batch``: the contract's answer on
    both, a patch iff the true count is 2^23 or more, and the kernel's
    raw count exact under 2^24 and at least 2^23 (or inf) past it."""
    idx, want = _case_index(*CASES[name])
    patched = _spy_patches(monkeypatch)
    s, t = [0, S, T], [0, T, 5]
    eng = QueryEngine()
    d, c = eng.query_batch(idx, s, t, route="pallas")
    assert c.dtype == jnp.int64
    assert [(int(x), int(y)) for x, y in zip(d, c)] == [
        (0, 1), (2, want), (int(INF), 0)]
    big = want >= ops.PATCH_AT
    assert eng.stats.routes == {("pallas+merge" if big else "pallas"): 1}
    assert eng.stats.route_pairs == ({"pallas": 2, "merge": 1} if big
                                     else {"pallas": 3})
    assert [set(p.tolist()) for p in patched] == ([{1}] if big else [])
    d2, c2 = ops.index_query_batch(idx, jnp.asarray(s), jnp.asarray(t),
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(c))
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(d))
    _, raw = ops.index_query_batch(idx, jnp.asarray([S]), jnp.asarray([T]),
                                   interpret=True, exact=False)
    raw = float(raw[0])
    if want < ops.EXACT_COUNT_MAX:
        assert raw == want
    else:
        assert not raw < ops.PATCH_AT
    assert np.isinf(raw) == (name == "sum_to_inf")


def _full_lattice(side: int):
    return side * side, road.lattice_edges(side)


def _road_grid(side: int, seed: int):
    return road.generate({"side": side, "drop": 0.30}, seed)


def _served(dyn, edges, sources):
    """Every (source, target) pair of ``sources``, internal ids, with the
    reference's (dist, count) for each."""
    adj = reference.Adjacency(dyn.n, edges)
    s = np.repeat(np.asarray(sources), dyn.n)
    t = np.tile(np.arange(dyn.n), len(sources))
    want = [reference.bfs_counts(adj, int(a)) for a in sources]
    d_ref = np.concatenate([w[0] for w in want])
    c_ref = np.concatenate([w[1] for w in want])
    to = dyn.order.to_internal
    return (jnp.asarray(to(s), jnp.int32), jnp.asarray(to(t), jnp.int32),
            d_ref, c_ref)


def test_grid_patches_exactly_the_rows_read_past_2_23(monkeypatch):
    """A full 24 x 24 lattice (corner counts to C(46, 23) ~ 2^42.9) from
    three sources to every target: the rows patched are the rows whose
    raw kernel count reads 2^23 or more, every other row's raw count is
    exact, and every answer is the reference's."""
    n, edges = _full_lattice(24)
    dyn = DynamicSPC(n, [tuple(e) for e in edges.tolist()], l_cap=None,
                     construct_batch=32, vertex_order="degree")
    s, t, d_ref, c_ref = _served(dyn, edges, [0, 23, 12 * 24 + 5])
    patched = _spy_patches(monkeypatch)
    d, c, route, merged = ops.exact_query_split(dyn.index, s, t,
                                                interpret=True)
    _, raw = ops.rows_query_pallas(*ops.prep_rows(dyn.index, s, t),
                                   interpret=True)
    raw = np.asarray(raw)
    hot = np.flatnonzero(~(raw < ops.PATCH_AT))
    (iex,) = patched
    assert route == "pallas+merge" and merged == hot.size
    assert set(iex.tolist()) == set(hot.tolist())
    cold = np.setdiff1d(np.arange(raw.size), hot)
    assert cold.size and hot.size
    np.testing.assert_array_equal(raw[cold].astype(np.int64), c_ref[cold])
    assert (c_ref[hot] >= ops.PATCH_AT).all()
    np.testing.assert_array_equal(np.asarray(d), d_ref)
    np.testing.assert_array_equal(np.asarray(c), c_ref)
    # the patch is what makes some of them exact: fp32 cannot hold them
    assert (raw[hot].astype(np.float64) != c_ref[hot]).any()


def test_small_count_graph_never_dispatches_the_merge(monkeypatch):
    """A road grid of side 24 (counts under 2^20): reader batches of
    every bucket take the kernel alone, with the reference's answers."""
    n, edges = _road_grid(24, seed=1)
    dyn = DynamicSPC(n, [tuple(e) for e in edges.tolist()], l_cap=None,
                     construct_batch=32, vertex_order="degree")
    s, t, d_ref, c_ref = _served(dyn, edges, [0, n // 2])
    assert c_ref.max() < ops.PATCH_AT
    patched = _spy_patches(monkeypatch)
    eng = QueryEngine()
    for lo, hi in ((0, 5), (5, 64), (64, 300), (300, s.size)):
        d, c = eng.query_batch(dyn.index, np.asarray(s[lo:hi]),
                               np.asarray(t[lo:hi]), route="pallas")
        np.testing.assert_array_equal(np.asarray(d), d_ref[lo:hi])
        np.testing.assert_array_equal(np.asarray(c), c_ref[lo:hi])
    assert patched == []
    assert eng.stats.routes == {"pallas": 4}
    assert eng.stats.route_pairs == {"pallas": s.size}
