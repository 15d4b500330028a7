"""All-insert chunks through the update engine: exact agreement with
one-at-a-time IncSPC, padding rows skipped.  Plus coverage for the
driver's vertex-level events and the isolated-vertex fast path, checked
against freshly rebuilt indexes."""

import jax.numpy as jnp
import numpy as np

from repro.core import graph as G
from repro.core.dynamic import DynamicSPC
from repro.core.hybrid import OP_INSERT, hyb_spc_batch
from repro.core.labels import to_ref
from repro.core.query import batched_query, pair_query
from repro.data import random_graph_edges


def fresh_edges(n, present, k, rng):
    out = []
    while len(out) < k:
        a, b = rng.integers(0, n, 2)
        key = (min(int(a), int(b)), max(int(a), int(b)))
        if a != b and key not in present:
            present.add(key)
            out.append(key)
    return out


def test_batch_equals_sequential(per_event):
    """An all-insert apply_events chunk == one inc_spc per edge."""
    n = 120
    edges = random_graph_edges(n, 300, seed=0)
    svc = DynamicSPC(n, edges, l_cap=32)
    rng = np.random.default_rng(3)
    present = set(edges)
    batch = [("+",) + e for e in fresh_edges(n, present, 6, rng)]
    _, want = per_event(svc.graph, svc.index, batch)
    svc.apply_events(batch)
    assert svc.stats.batches == 1 and svc.stats.inserts == len(batch)
    assert to_ref(svc.index).labels == to_ref(want).labels
    for s in range(0, n, 17):
        for t in range(0, n, 13):
            assert svc.query(s, t) == tuple(
                int(x) for x in pair_query(want, s, t)), (s, t)


def test_batch_padding_rows_noop(per_event):
    """Self-loop rows of any op inside an all-insert hyb_spc_batch chunk
    are skipped: the chunk leaves what the real inserts alone leave."""
    n = 40
    edges = random_graph_edges(n, 100, seed=1)
    svc = DynamicSPC(n, edges, l_cap=24)
    rng = np.random.default_rng(4)
    present = set(edges)
    real = fresh_edges(n, present, 3, rng)
    padded = jnp.asarray(np.asarray(
        [(OP_INSERT, a, b) for a, b in real]
        + [(OP_INSERT, 7, 7), (0, 0, 0)], np.int32))  # a == b pads
    g = G.ensure_capacity(svc.graph, 2 * len(real) + 4)
    (_, _), idx2 = hyb_spc_batch(g, svc.index, padded)
    assert int(idx2.overflow) == 0
    _, ref = per_event(svc.graph, svc.index, [("+", a, b) for a, b in real])
    np.testing.assert_array_equal(np.asarray(idx2.hub[: n]),
                                  np.asarray(ref.hub[: n]))
    np.testing.assert_array_equal(np.asarray(idx2.cnt[: n]),
                                  np.asarray(ref.cnt[: n]))


def _assert_same_answers(svc_a: DynamicSPC, svc_b: DynamicSPC):
    """All-pairs (dist, count) agreement between two services.

    Maintained indexes may keep redundant-but-correct labels that a
    fresh build prunes, so rebuild comparisons go through the query
    path (ESPC), not raw label equality.
    """
    n = svc_a.n
    assert n == svc_b.n
    pairs = [(s, t) for s in range(n) for t in range(n)]
    ss = jnp.asarray([p[0] for p in pairs])
    tt = jnp.asarray([p[1] for p in pairs])
    d_a, c_a = batched_query(svc_a.index, ss, tt)
    d_b, c_b = batched_query(svc_b.index, ss, tt)
    np.testing.assert_array_equal(np.asarray(c_a), np.asarray(c_b))
    reach = np.asarray(c_a) > 0
    np.testing.assert_array_equal(np.asarray(d_a)[reach],
                                  np.asarray(d_b)[reach])


def test_isolated_fast_path_matches_rebuild():
    """delete_edge on a degree-1 endpoint takes the Section 3.2.3 row
    reset and leaves an index label-identical to reconstruction."""
    n = 32
    base = random_graph_edges(n - 1, 60, seed=5)  # vertex n-1 untouched
    edges = base + [(4, n - 1)]                   # pendant edge
    svc = DynamicSPC(n, edges, l_cap=32)
    svc.delete_edge(4, n - 1)
    assert svc.stats.isolated_fast_path == 1
    rebuilt = DynamicSPC(n, base, l_cap=32)
    # a pendant vertex is never interior to a shortest path and is the
    # lowest-ranked hub, so even exact label equality must hold here
    assert to_ref(svc.index).labels == to_ref(rebuilt.index).labels
    assert svc.query(n - 1, n - 1) == (0, 1)
    assert svc.query(4, n - 1)[1] == 0  # now disconnected


def test_vertex_roundtrip_matches_rebuild():
    """insert_vertex + edges, then delete_vertex: answers match freshly
    rebuilt indexes at every step."""
    n = 24
    edges = random_graph_edges(n, 50, seed=7)
    svc = DynamicSPC(n, edges, l_cap=32)
    v = svc.insert_vertex()
    assert v == n and svc.n == n + 1
    assert svc.query(v, v) == (0, 1)
    svc.insert_edge(v, 3)
    svc.insert_edge(v, 11)
    rebuilt = DynamicSPC(n + 1, edges + [(3, v), (11, v)], l_cap=32)
    _assert_same_answers(svc, rebuilt)
    svc.delete_vertex(v)  # routes through the batched engine
    assert svc.stats.batches >= 1
    rebuilt2 = DynamicSPC(n + 1, edges, l_cap=32)
    _assert_same_answers(svc, rebuilt2)
    assert svc.query(v, v) == (0, 1)
    assert svc.query(v, 3)[1] == 0  # isolated again
