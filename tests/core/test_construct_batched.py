"""Differential tests for batched (PSPC-style) index construction.

``build_index_batched`` must produce an index *identical* to the
sequential ``build_index`` on the same (relabeled) graph for every
``hub_batch`` -- the lockstep schedule with rank-masked in-batch pruning
is a pure reordering of the same work -- and both must answer queries
matching the ``bfs_spc`` reference oracle.  The multi-device sharded
variant runs in a subprocess with forced host devices (CI's ``-m slow``
distributed step), mirroring ``test_dist_update.py``; a single-device
mesh differential keeps the sharded multi-relax code path in tier-1.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as G
from repro.core import refimpl as R
from repro.core.construct import (build_index, build_index_batched,
                                  provision_l_cap)
from repro.core.labels import to_ref
from repro.core.order import (graph_ordering, ordering_from_state,
                              relabel_graph, vertex_ordering)
from repro.core.query import batched_query
from repro.data import random_graph_edges

HUB_BATCHES = (1, 4, 32)


def _graphs():
    """(name, n, edges): random, power-law, disconnected."""
    return [
        ("random", 30, random_graph_edges(30, 60, seed=11, power_law=False)),
        ("powerlaw", 40, random_graph_edges(40, 100, seed=12,
                                            power_law=True)),
        ("disconnected", 14, [(0, 1), (1, 2), (2, 0), (5, 6), (6, 7),
                              (9, 10), (12, 13)]),
    ]


def _check_oracle(idx, n, edges):
    rg = R.RefGraph(n, edges)
    pairs = [(s, t) for s in range(n) for t in range(n)]
    d, c = batched_query(idx, jnp.asarray([p[0] for p in pairs]),
                         jnp.asarray([p[1] for p in pairs]))
    truth = {s: R.bfs_spc(rg, s) for s in range(n)}
    for i, (s, t) in enumerate(pairs):
        dist, cnt = truth[s]
        if int(cnt[t]) == 0:
            assert int(c[i]) == 0 and int(d[i]) >= (1 << 28), (s, t)
        else:
            assert (int(d[i]), int(c[i])) == (int(dist[t]), int(cnt[t])), \
                (s, t)


@pytest.mark.parametrize("name,n,edges",
                         _graphs(), ids=[g[0] for g in _graphs()])
def test_batched_equals_sequential_and_oracle(name, n, edges):
    g = G.from_edges(n, edges)
    seq = build_index(g, n + 2)
    assert int(seq.overflow) == 0
    want = to_ref(seq).labels
    for hb in HUB_BATCHES:
        bat = build_index_batched(g, n + 2, hub_batch=hb)
        assert int(bat.overflow) == 0
        assert to_ref(bat).labels == want, (name, hb)
    _check_oracle(bat, n, edges)


def test_overflow_retry_from_pre_round_snapshot():
    """A tiny starting capacity must regrow mid-build (per hub round,
    from the pre-round snapshot) and still land on the sequential
    result -- never fail or lose committed labels."""
    n = 30
    edges = random_graph_edges(n, 60, seed=11, power_law=False)
    g = G.from_edges(n, edges)
    seq = build_index(g, n + 2)
    regrown = []
    bat = build_index_batched(g, 2, hub_batch=4,
                              on_regrow=regrown.append)
    assert int(bat.overflow) == 0
    assert regrown, "l_cap=2 must overflow at least once on this graph"
    assert bat.l_cap > 2
    assert to_ref(bat).labels == to_ref(seq).labels


def test_provision_l_cap_degree_stats():
    n = 40
    g = G.from_edges(n, random_graph_edges(n, 100, seed=12, power_law=True))
    cap = provision_l_cap(g)
    assert 4 <= cap <= n + 1
    assert cap & (cap - 1) == 0  # power of two (compile-cache friendly)
    # provisioned default (l_cap=None) builds without the caller passing
    # a capacity and still matches sequential-to-success
    bat = build_index_batched(g, hub_batch=8)
    assert int(bat.overflow) == 0
    lcap = 8
    while True:
        seq = build_index(g, lcap)
        if int(seq.overflow) == 0:
            break
        lcap *= 2
    assert to_ref(bat).labels == to_ref(seq).labels


def test_degree_order_deterministic_and_differential():
    """order="degree": stable sort (ties by id), byte-identical state
    dicts across two builds, round-trip through from_state_dict, and
    batched == sequential on the relabeled graph."""
    from repro.core.dynamic import DynamicSPC

    n = 30
    edges = random_graph_edges(n, 80, seed=13, power_law=True)
    g = G.from_edges(n, edges)

    o = graph_ordering(g, "degree")
    deg = np.asarray(G.degrees(g))[:n]
    dv = deg[o.vertex_of]
    assert all(dv[i] >= dv[i + 1] for i in range(n - 1))  # descending degree
    ties = [i for i in range(n - 1) if dv[i] == dv[i + 1]]
    assert all(o.vertex_of[i] < o.vertex_of[i + 1] for i in ties)  # id ties
    assert np.array_equal(o.rank_of[o.vertex_of], np.arange(n))

    gr = relabel_graph(g, o)
    seq = build_index(gr, n + 2)
    bat = build_index_batched(g, n + 2, hub_batch=8, order="degree")
    assert to_ref(bat).labels == to_ref(seq).labels

    a = DynamicSPC(n, edges, l_cap=n + 2, construct_batch=8,
                   vertex_order="degree")
    b = DynamicSPC(n, edges, l_cap=n + 2, construct_batch=8,
                   vertex_order="degree")
    sa, sb = a.state_dict(), b.state_dict()
    assert "order.vertex_of" in sa
    assert sorted(sa) == sorted(sb)
    for k in sa:
        assert np.asarray(sa[k]).tobytes() == np.asarray(sb[k]).tobytes(), k

    # round trip: restored service answers external-id queries identically
    r = DynamicSPC.from_state_dict(n, sa)
    assert not r.order.identity
    ident = DynamicSPC(n, edges, l_cap=n + 2)
    for s in range(n):
        assert r.query(s, 0) == ident.query(s, 0) == a.query(s, 0), s

    # a corrupted permutation leaf must be rejected, not silently used
    bad = dict(sa)
    bad["order.vertex_of"] = jnp.zeros(n, jnp.int32)
    with pytest.raises(ValueError, match="permutation"):
        DynamicSPC.from_state_dict(n, bad)


def test_vertex_ordering_identity_and_validation():
    o = vertex_ordering(5, [(0, 1)], "id")
    assert o.identity and o.to_internal(3) == 3 and o.to_external(3) == 3
    with pytest.raises(ValueError, match="unknown vertex order"):
        vertex_ordering(5, [], "betweenness")
    od = vertex_ordering(3, [(0, 1), (1, 2)], "degree")
    assert list(od.vertex_of) == [1, 0, 2]  # deg 2 first, ties by id
    with pytest.raises(ValueError, match="out of range"):
        od.to_internal(3)
    with pytest.raises(ValueError, match="permutation"):
        ordering_from_state(np.zeros(3, np.int32))


def test_dynamic_spc_construct_batch_parity():
    """DynamicSPC's build -- the batched builder at its default hub
    batch and at construct_batch=8, from a label capacity that has to
    grow -- equals the sequential build_index oracle, and so does
    rebuild() after updates."""
    from repro.core.dynamic import DynamicSPC

    n = 20
    edges = random_graph_edges(n, 40, seed=14)
    a = DynamicSPC(n, edges, l_cap=2)
    b = DynamicSPC(n, edges, l_cap=n + 2, construct_batch=8)
    assert a.stats.label_regrows >= 1 and a.stats.build_relax_rounds > 0
    want = to_ref(build_index(a.graph, n + 2)).labels
    assert to_ref(a.index).labels == to_ref(b.index).labels == want
    have = {tuple(sorted(e)) for e in edges}
    u, v = next((u, v) for u in range(n) for v in range(u + 1, n)
                if (u, v) not in have)
    ops = [("+", u, v), ("-", edges[0][0], edges[0][1])]
    b.apply_events(ops, batch_size=4)
    # rebuild() must match a fresh sequential build of the updated
    # graph (the incremental index may retain prunable labels a
    # from-scratch build drops, so compare rebuild-vs-rebuild, not
    # rebuild-vs-incremental)
    b.rebuild()
    fresh = build_index(b.graph, int(b.index.l_cap))
    assert to_ref(b.index).labels == to_ref(fresh).labels


def test_mesh_single_device_batched_differential():
    """Tier-1 coverage of the sharded multi-relax path (1-device mesh):
    updater.build_index_batched on the padded graph must equal the
    replicated sequential builder, including after a capacity re-pad
    (``pad_graph_for`` regression: cap_e stays shard-divisible)."""
    from jax.sharding import Mesh

    from repro.core.distributed import make_distributed_updater

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("model",))
    upd = make_distributed_updater(mesh, "model")
    n = 24
    edges = random_graph_edges(n, 50, seed=15, power_law=True)
    g = upd.pad(G.from_edges(n, edges))
    assert g.cap_e % upd.num_shards == 0
    seq = build_index(g, n + 2)
    bat = upd.build_index_batched(g, n + 2, hub_batch=8)
    assert to_ref(bat).labels == to_ref(seq).labels
    # regrow under the mesh: tiny l_cap forces the per-round retry on
    # the padded graph
    bat2 = upd.build_index_batched(g, 2, hub_batch=8)
    assert int(bat2.overflow) == 0
    assert to_ref(bat2).labels == to_ref(seq).labels


def test_pad_graph_for_repad_regression():
    """Re-padding after a capacity grow keeps cap_e shard-divisible and
    the padded slots inert (dump-row convention)."""
    from repro.core.distributed import pad_graph_for

    n = 9
    g = G.from_edges(n, [(0, 1), (1, 2), (2, 3)], cap_e=16)
    for shards in (3, 4, 5, 7):
        gp = pad_graph_for(g, shards)
        assert gp.cap_e % shards == 0
        assert gp.cap_e >= g.cap_e
        src = np.asarray(gp.src)
        assert (src[int(gp.m2):] == n).all()
        # grow then re-pad (what DynamicSPC does after ensure_capacity)
        gg = pad_graph_for(G.ensure_capacity(gp, gp.cap_e + 1), shards)
        assert gg.cap_e % shards == 0
        assert sorted(G.to_ref(gg).edge_list()) == \
            sorted(G.to_ref(g).edge_list())


SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import graph as G
    from repro.core.construct import build_index
    from repro.core.distributed import make_distributed_updater
    from repro.core.dynamic import DynamicSPC
    from repro.core.labels import to_ref
    from repro.data import random_graph_edges

    assert len(jax.devices()) == 4, jax.devices()
    mesh = Mesh(np.asarray(jax.devices()), ("model",))
    upd = make_distributed_updater(mesh, "model")

    n = 24
    edges = random_graph_edges(n, 50, seed=15, power_law=True)
    g = upd.pad(G.from_edges(n, edges))
    assert g.cap_e % 4 == 0
    seq = build_index(g, n + 2)
    want = to_ref(seq).labels

    # sharded batched build == replicated sequential, per hub_batch
    for hb in (1, 4, 32):
        bat = upd.build_index_batched(g, n + 2, hub_batch=hb)
        assert int(bat.overflow) == 0
        assert to_ref(bat).labels == want, hb

    # overflow-retry re-pads under the mesh and still matches
    bat = upd.build_index_batched(g, 2, hub_batch=8)
    assert int(bat.overflow) == 0 and bat.l_cap > 2
    assert to_ref(bat).labels == want

    # end to end: DynamicSPC(mesh=, construct_batch=) == replicated
    rep = DynamicSPC(n, edges, l_cap=n + 2)
    sh = DynamicSPC(n, edges, l_cap=n + 2, mesh=mesh, construct_batch=8)
    assert to_ref(sh.index).labels == to_ref(rep.index).labels
    have = {tuple(sorted(e)) for e in edges}
    u, v = next((u, v) for u in range(n) for v in range(u + 1, n)
                if (u, v) not in have)
    ops = [("+", u, v), ("-", edges[0][0], edges[0][1])]
    rep.apply_events(ops, batch_size=4)
    sh.apply_events(ops, batch_size=4)
    assert to_ref(sh.index).labels == to_ref(rep.index).labels
    print("CONSTRUCT_BATCHED_DIST_OK")
    """
)


@pytest.mark.slow
def test_sharded_batched_build_matches_multi_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        timeout=600,
    )
    assert "CONSTRUCT_BATCHED_DIST_OK" in proc.stdout, proc.stderr[-3000:]
