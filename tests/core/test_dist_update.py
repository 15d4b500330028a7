"""Differential tests for the edge-sharded update engines.

``make_distributed_updater`` must preserve the replicated engines'
results bit-for-bit: the algorithms are the same single-source bodies,
only the relaxation primitive is swapped for the shard_map edge-sharded
one.  The multi-device checks need >1 XLA host device, which must be
configured before jax initializes, so they run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the CI
"distributed" step opts into them with ``-m slow``); a single-device
mesh variant runs in-process so tier-1 always covers the sharded code
path.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import graph as G
    from repro.core import refimpl as R
    from repro.core.distributed import pad_graph_for
    from repro.core.dynamic import DynamicSPC
    from repro.core.hybrid import OP_DELETE, OP_INSERT, hyb_spc_batch
    from repro.core.labels import to_ref
    from repro.core.query import batched_query
    from repro.data import graph_stream, random_graph_edges

    assert len(jax.devices()) == 4, jax.devices()
    mesh = Mesh(np.asarray(jax.devices()), ("model",))

    n = 16
    # pendant edge (2, n-1): deg(n-1) == 1, for the isolated fast path
    edges = random_graph_edges(n - 1, 26, seed=0) + [(2, n - 1)]

    rep = DynamicSPC(n, edges, l_cap=n + 2)
    sh = DynamicSPC(n, edges, l_cap=n + 2, mesh=mesh)
    assert sh.graph.cap_e % 4 == 0
    assert to_ref(sh.index).labels == to_ref(rep.index).labels  # build

    rg = R.RefGraph(n, edges)

    def check(tag):
        assert to_ref(sh.index).labels == to_ref(rep.index).labels, tag
        assert sorted(G.to_ref(sh.graph).edge_list()) == \\
            sorted(rg.edge_list()), tag
        pairs = [(s, t) for s in range(n) for t in range(n)]
        d, c = batched_query(sh.index,
                             jnp.asarray([p[0] for p in pairs]),
                             jnp.asarray([p[1] for p in pairs]))
        truth = {s: R.bfs_spc(rg, s) for s in range(n)}
        for i, (s, t) in enumerate(pairs):
            dist, cnt = truth[s]
            if int(cnt[t]) == 0:
                assert int(c[i]) == 0 and int(d[i]) >= (1 << 28), (tag, s, t)
            else:
                assert (int(d[i]), int(c[i])) == \\
                    (int(dist[t]), int(cnt[t])), (tag, s, t)

    # 1. inserts (sharded inc_spc)
    def absent_edges(k):
        got, have = [], set(rg.edge_list())
        for a in range(n - 1):           # avoid the pendant vertex n-1
            for b in range(a + 1, n - 1):
                if (a, b) not in have and len(got) < k:
                    got.append((a, b))
                    have.add((a, b))
        return got

    for a, b in absent_edges(2):
        rep.insert_edge(a, b)
        sh.insert_edge(a, b)
        rg.add_edge(a, b)
    check("insert")

    # 2. delete, full SRRSearch path (sharded dec_spc_step)
    a, b = edges[0]
    rep.delete_edge(a, b)
    sh.delete_edge(a, b)
    rg.remove_edge(a, b)
    check("delete")

    # 3. isolated-vertex fast path (host-side, Section 3.2.3)
    rep.delete_edge(2, n - 1)
    sh.delete_edge(2, n - 1)
    rg.remove_edge(2, n - 1)
    assert sh.stats.isolated_fast_path == 1
    check("isolated")

    # 4. mixed stream through the batched engine (sharded hyb_spc_batch)
    events = graph_stream(sorted(rg.edge_list()), n, 5, 3, seed=2)
    rep.apply_events(events, batch_size=4)
    sh.apply_events(events, batch_size=4)
    for op, a, b in events:
        rg.add_edge(a, b) if op == "+" else rg.remove_edge(a, b)
    assert sh.stats.batches == rep.stats.batches >= 2
    check("hybrid-stream")

    # 5. engine-level differential on identical inputs (incl. padding row)
    present = sorted(rg.edge_list())
    absent = next((a, b) for a in range(n) for b in range(a + 1, n)
                  if (a, b) not in set(present))
    ev = jnp.asarray(np.asarray(
        [[OP_INSERT, absent[0], absent[1]], [0, 0, 0],
         [OP_DELETE, present[0][0], present[0][1]]], np.int32))
    g0 = pad_graph_for(G.ensure_capacity(rep.graph, 4), 4)
    (g_r, _), i_r = hyb_spc_batch(g0, rep.index, ev)
    (g_s, _), i_s = sh._updater.hyb_spc_batch(g0, rep.index, ev)
    assert int(i_s.overflow) == int(i_r.overflow) == 0
    assert to_ref(i_s).labels == to_ref(i_r).labels
    np.testing.assert_array_equal(np.asarray(g_s.src), np.asarray(g_r.src))
    np.testing.assert_array_equal(np.asarray(g_s.dst), np.asarray(g_r.dst))
    print("DIST_UPDATE_OK")
    """
)


@pytest.mark.slow
def test_sharded_updaters_match_replicated_multi_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        timeout=600,
    )
    assert "DIST_UPDATE_OK" in proc.stdout, proc.stderr[-3000:]


def test_mesh_mode_single_device_differential():
    """Tier-1 coverage of the sharded update path (1-device mesh): the
    DynamicSPC ``mesh=`` mode must be bit-identical to the replicated
    driver across build, per-op updates and batched event replay."""
    import jax
    from jax.sharding import Mesh

    from repro.core.dynamic import DynamicSPC
    from repro.core.labels import to_ref
    from repro.data import graph_stream, random_graph_edges

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("model",))
    n = 10
    edges = random_graph_edges(n, 16, seed=7)
    rep = DynamicSPC(n, edges, l_cap=n + 2)
    sh = DynamicSPC(n, edges, l_cap=n + 2, mesh=mesh)
    assert to_ref(sh.index).labels == to_ref(rep.index).labels
    events = graph_stream(edges, n, 3, 2, seed=8)
    rep.apply_events(events, batch_size=4)
    sh.apply_events(events, batch_size=4)
    assert sh.stats.batches == rep.stats.batches
    assert to_ref(sh.index).labels == to_ref(rep.index).labels
    d_r, c_r = rep.query_batch(list(range(n)), [0] * n)
    d_s, c_s = sh.query_batch(list(range(n)), [0] * n)
    np.testing.assert_array_equal(np.asarray(d_s), np.asarray(d_r))
    np.testing.assert_array_equal(np.asarray(c_s), np.asarray(c_r))
