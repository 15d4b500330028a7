"""The count contract on graphs whose path counts pass int64.

A count is right if and only if it equals ``min(true count, 2^63 - 1)``
(``repro.core.counts``).  On a full 36 x 36 grid the corner-to-corner
count is C(70, 35) ~ 2^66.6; on a seeded perturbed grid counts stay
small but exactness bounds mix.  Every read route of the built index --
the int64 merge, the Pallas kernel (interpret mode) on the rows its
bound proves exact, and the mixed split -- and the index after inserts
and deletes through ``hyb_spc_batch`` must equal a Python-int BFS
clamped at 2^63 - 1.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import counts as C
from repro.core import refimpl as R
from repro.core.counts import COUNT_MAX
from repro.core.dynamic import DynamicSPC
from repro.core.graph import INF
from repro.core.labels import (SPCIndex, bulk_remove, bulk_upsert,
                               empty_index, from_ref, recompute_cnt_sum)
from repro.core.query import batched_query_merge
from repro.kernels.spc_query.ops import (EXACT_COUNT_MAX, exact_query_split,
                                         gather_rows_with_bounds,
                                         rows_query_pallas)

SIDE = 36
FAR = 2 * (SIDE - 1)          # corner-to-corner hops


def lattice(side: int):
    """(ids[side, side], edges int[E, 2]) of the 4-neighbour grid."""
    ids = np.arange(side * side).reshape(side, side)
    edges = np.concatenate([
        np.stack([ids[:, :-1].ravel(), ids[:, 1:].ravel()], axis=1),
        np.stack([ids[:-1].ravel(), ids[1:].ravel()], axis=1)])
    return ids, edges


def full_grid():
    """The full grid, labels permuted with corner (0, 0) as vertex 0, built
    in id order: the corner is the top hub, so rows far from it carry
    labels whose counts saturate, and the rest of the order is random."""
    rng = np.random.default_rng(5)
    ids, edges = lattice(SIDE)
    perm = rng.permutation(SIDE * SIDE)
    k = int(np.flatnonzero(perm == 0)[0])
    perm[[0, k]] = perm[[k, 0]]
    relabel = perm[ids]
    events = [("+", relabel[0, 1], relabel[1, 0]),      # no shortcut
              ("-", relabel[10, 10], relabel[10, 11]),
              ("+", relabel[30, 31], relabel[31, 30]),
              ("-", relabel[20, 5], relabel[21, 5])]
    sources = [relabel[0, 0], relabel[-1, -1], relabel[17, 18]]
    return relabel, perm[edges], "id", events, sources


def perturbed_grid():
    """The grid with 30% of its edges dropped (seeded), labels permuted,
    built in degree order; components may be disconnected."""
    rng = np.random.default_rng(11)
    ids, edges = lattice(SIDE)
    keep = rng.random(len(edges)) >= 0.30
    perm = rng.permutation(SIDE * SIDE)
    relabel = perm[ids]
    dropped, kept = perm[edges[~keep]], perm[edges[keep]]
    events = [("+", *dropped[0]), ("-", *kept[7]), ("+", *dropped[1]),
              ("-", *kept[100])]
    sources = list(rng.choice(SIDE * SIDE, 3, replace=False))
    return relabel, kept, "degree", events, sources


GRAPHS = {"full": full_grid, "perturbed": perturbed_grid}


def as_pairs(edges):
    return [(int(a), int(b)) for a, b in edges]


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def built(request):
    layout, edges, order, events, sources = GRAPHS[request.param]()
    n = SIDE * SIDE
    dyn = DynamicSPC(n, as_pairs(edges), l_cap=None, construct_batch=32,
                     vertex_order=order)
    ref = R.RefGraph(n, as_pairs(edges))
    truth = {int(s): R.bfs_spc(ref, int(s)) for s in sources}
    return dict(name=request.param, layout=layout, edges=edges, dyn=dyn,
                ref=ref, events=[(op, int(a), int(b)) for op, a, b in events],
                truth=truth)


def _internal(dyn, v):
    return jnp.asarray(dyn.order.to_internal(np.asarray(v)), jnp.int32)


def _pairs_with_bounds(b):
    """(s, t, bound) over all targets of the graph's sources."""
    dyn, n = b["dyn"], b["dyn"].n
    s = np.repeat(np.asarray(list(b["truth"])), n)
    t = np.tile(np.arange(n), len(b["truth"]))
    _, bounds = gather_rows_with_bounds(dyn.index, _internal(dyn, s),
                                        _internal(dyn, t))
    return s, t, np.asarray(bounds)


def _check(b, s, t, d, c):
    """Answers against the clamped reference; an unreachable pair reads
    the engine's INF and count 0."""
    d_ref = np.array([b["truth"][int(a)][0][v] for a, v in zip(s, t)])
    c_ref = np.array([b["truth"][int(a)][1][v] for a, v in zip(s, t)])
    d_ref = np.where(d_ref >= R.INF, int(INF), d_ref)
    np.testing.assert_array_equal(np.asarray(d).astype(np.int64), d_ref)
    np.testing.assert_array_equal(np.asarray(c).astype(np.int64), c_ref)


def test_far_corner_reads_count_max():
    """C(70, 35) > 2^63 - 1: the far corner reads the contract's edge, not
    a wrapped count."""
    layout, edges, order, _, _ = full_grid()
    a, z = int(layout[0, 0]), int(layout[-1, -1])
    d_ref, c_ref = R.bfs_spc(R.RefGraph(SIDE * SIDE, as_pairs(edges)), a)
    assert (int(d_ref[z]), int(c_ref[z])) == (FAR, COUNT_MAX)
    dyn = DynamicSPC(SIDE * SIDE, as_pairs(edges), l_cap=None,
                     construct_batch=32, vertex_order=order)
    assert dyn.query(a, z) == (FAR, COUNT_MAX)


@pytest.mark.parametrize("route", ["merge", "pallas", "mixed"])
def test_read_routes_match_clamped_reference(built, route):
    dyn = built["dyn"]
    s, t, bounds = _pairs_with_bounds(built)
    exact = np.flatnonzero(bounds < EXACT_COUNT_MAX)
    inexact = np.flatnonzero(bounds >= EXACT_COUNT_MAX)
    if route == "merge":
        d, c = dyn.query_batch(s, t, route="merge")
        _check(built, s, t, d, c)
        if built["name"] == "full":
            assert (np.asarray(c) == COUNT_MAX).sum() >= 2
        return
    assert exact.size and inexact.size
    if route == "pallas":
        sel = exact[:: max(1, exact.size // 128)][:128]
        rows, _ = gather_rows_with_bounds(
            dyn.index, _internal(dyn, s[sel]), _internal(dyn, t[sel]))
        d, c = rows_query_pallas(*rows, interpret=True)
        _check(built, s[sel], t[sel], d, c)
        return
    sel = np.concatenate([exact[:: max(1, exact.size // 64)][:64],
                          inexact[:: max(1, inexact.size // 64)][:64]])
    d, c, how, merged = exact_query_split(
        dyn.index, _internal(dyn, s[sel]), _internal(dyn, t[sel]),
        interpret=True)
    assert how == "pallas+merge" and merged == inexact[:: max(
        1, inexact.size // 64)][:64].size
    _check(built, s[sel], t[sel], d, c)


def test_cnt_sum_saturates_after_build(built):
    idx = built["dyn"].index
    np.testing.assert_array_equal(np.asarray(idx.cnt_sum),
                                  np.asarray(recompute_cnt_sum(idx.cnt)))
    saturated = int((np.asarray(idx.cnt_sum) == COUNT_MAX).sum())
    assert (saturated > 0) == (built["name"] == "full")


def test_updates_keep_the_contract(built):
    """Inserts and deletes through ``hyb_spc_batch``: the cached row sums
    stay the saturating sums, and reads of every event endpoint and of
    the sources match a BFS of the final graph."""
    src = built["dyn"]
    dyn = DynamicSPC.from_state_dict(src.n, src.state_dict())
    ref = built["ref"].copy()
    dyn.apply_events(built["events"], batch_size=8)
    for op, a, b in built["events"]:
        (ref.add_edge if op == "+" else ref.remove_edge)(a, b)
    idx = dyn.index
    np.testing.assert_array_equal(np.asarray(idx.cnt_sum),
                                  np.asarray(recompute_cnt_sum(idx.cnt)))
    assert (np.asarray(idx.cnt_sum) == COUNT_MAX).any() == (
        built["name"] == "full")
    n = dyn.n
    reads = sorted({v for _, a, b in built["events"] for v in (a, b)}
                   | set(built["truth"]))
    for v in reads:
        d, c = dyn.query_batch(np.full(n, v), np.arange(n), route="merge")
        _check({"truth": {v: R.bfs_spc(ref, v)}}, np.full(n, v),
               np.arange(n), d, c)


# -- the saturating helpers and the cached row sums ---------------------------
BIG = [0, 1, 3, 2 ** 31 - 1, 2 ** 31, 2 ** 32 + 5, 2 ** 40, 3 ** 25,
       2 ** 62, COUNT_MAX - 1, COUNT_MAX]


@pytest.mark.parametrize("op", ["add", "mul"])
def test_saturating_add_and_mul(op):
    a, b = np.meshgrid(np.asarray(BIG, np.int64), np.asarray(BIG, np.int64))
    fn, py = {"add": (C.sat_add, lambda x, y: x + y),
              "mul": (C.sat_mul, lambda x, y: x * y)}[op]
    got = np.asarray(jax.jit(fn)(a, b))
    want = [[min(py(int(x), int(y)), COUNT_MAX) for x, y in zip(r, q)]
            for r, q in zip(a, b)]
    np.testing.assert_array_equal(got, np.asarray(want, np.int64))


@pytest.mark.parametrize("fits", [True, False])
def test_relax_sums_both_branches(fits):
    """Per-destination sums, the plain ``segment_sum`` where the guard
    proves them safe and the split sums where it does not, both equal
    the clamped Python-int sums."""
    rng = np.random.default_rng(3)
    top = 2 ** 20 if fits else COUNT_MAX
    x = rng.integers(0, top, size=64, dtype=np.int64, endpoint=True)
    x[:5] = top
    dst = rng.integers(0, 6, size=64).astype(np.int32)
    got = np.asarray(jax.jit(C.relax_sums, static_argnums=(2, 4))(
        jnp.asarray(x), jnp.asarray(dst), 6, jnp.max(x), 64))
    want = [min(sum(int(v) for v, d in zip(x, dst) if d == k), COUNT_MAX)
            for k in range(6)]
    assert bool(C.sums_fit(jnp.max(x), 64)) == fits
    np.testing.assert_array_equal(got, np.asarray(want, np.int64))


def _row_index(counts) -> SPCIndex:
    """An index of 8 vertices whose row 0 holds ``counts`` on hubs 1.."""
    idx = empty_index(8, 8)
    k = len(counts)
    hub = idx.hub.at[0, :k].set(jnp.arange(1, k + 1, dtype=jnp.int32))
    dist = idx.dist.at[0, :k].set(1)
    cnt = idx.cnt.at[0, :k].set(jnp.asarray(counts, jnp.int64))
    return SPCIndex(hub=hub, dist=dist, cnt=cnt,
                    size=idx.size.at[0].set(k),
                    cnt_sum=recompute_cnt_sum(cnt),
                    overflow=idx.overflow, n=8)


@pytest.mark.parametrize("edit", ["remove", "replace", "insert"])
@pytest.mark.parametrize("counts", [[2 ** 62, 2 ** 62, 5],
                                    [7, 2 ** 40, 5]])
def test_cnt_sum_upkeep_at_saturation(edit, counts):
    """A row whose sum sits at 2^63 - 1 is summed again when it loses or
    replaces a label; an unsaturated one keeps its exact running sum."""
    idx = _row_index(counts)
    assert (int(idx.cnt_sum[0]) == COUNT_MAX) == (counts[0] == 2 ** 62)
    mask = jnp.zeros(9, bool).at[0].set(True)
    if edit == "remove":
        out = jax.jit(bulk_remove)(idx, 2, mask)
        row = [counts[0], counts[2]]
    else:
        h = 2 if edit == "replace" else 5
        out = jax.jit(bulk_upsert)(idx, h, jnp.full(9, 1, jnp.int32),
                                   jnp.full(9, 9, jnp.int64), mask)
        row = ([counts[0], 9, counts[2]] if edit == "replace"
               else counts + [9])
    assert int(out.cnt_sum[0]) == min(sum(row), COUNT_MAX)
    np.testing.assert_array_equal(np.asarray(out.cnt_sum),
                                  np.asarray(recompute_cnt_sum(out.cnt)))


def test_plain_reference_clamps_at_count_max():
    """``refimpl``: a chain of 70 diamonds has 2^70 shortest paths end to
    end.  Its BFS and its index (``hp_spc``, Python-int labels) answer
    2^63 - 1 there and exact counts below; ``from_ref`` clamps the labels
    it stores, and the JAX merge over them agrees."""
    k = 70
    junction = [3 * i for i in range(k + 1)]
    edges = [(j, j + m) for j in junction[:-1] for m in (1, 2)]
    edges += [(j + m, j + 3) for j in junction[:-1] for m in (1, 2)]
    g = R.RefGraph(3 * k + 1, edges)
    ref = R.hp_spc(g)
    R.check_espc(g, ref, [(0, t) for t in range(g.n)])
    assert ref.query(0, 3 * k) == (2 * k, COUNT_MAX)
    assert ref.query(0, 3 * 62) == (2 * 62, 2 ** 62)
    idx = from_ref(ref)
    t = jnp.arange(g.n)
    d, c = batched_query_merge(idx, jnp.zeros_like(t), t)
    _, c_ref = R.bfs_spc(g, 0)
    np.testing.assert_array_equal(np.asarray(c), c_ref)
