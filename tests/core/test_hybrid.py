"""Differential tests for the hybrid batched update engine.

The engine's contract (see ``repro.core.hybrid``) is that replaying a
tagged event stream inside one ``lax.scan`` is state-for-state identical
to a one-event-at-a-time replay, so ESPC holds after EVERY prefix of
the stream -- we check all three implementations against each other:

  hyb_spc_batch  (one jitted dispatch, prefix by prefix)
  per-event      (the ``per_event`` fixture: one inc_spc / dec_spc
                  dispatch per event, at a label capacity that holds)
  refimpl oracle (online ``bfs_spc`` counting on the reference graph)
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as G
from repro.core import refimpl as R
from repro.core.dynamic import DynamicSPC
from repro.core.hybrid import OP_DELETE, OP_INSERT, hyb_spc_batch
from repro.core.labels import to_ref
from repro.core.query import batched_query
from repro.data import graph_stream, random_graph_edges

CODE = {"+": OP_INSERT, "-": OP_DELETE}


def _events_array(events, pad_to=None):
    arr = np.zeros((pad_to or len(events), 3), dtype=np.int32)
    for i, (op, a, b) in enumerate(events):
        arr[i] = (CODE[op], a, b)
    return arr


def _assert_espc(idx, rg):
    """Index answers == BFS counting on every pair of the ref graph."""
    n = rg.n
    pairs = [(s, t) for s in range(n) for t in range(n)]
    d, c = batched_query(idx, jnp.asarray([p[0] for p in pairs]),
                         jnp.asarray([p[1] for p in pairs]))
    truth = {s: R.bfs_spc(rg, s) for s in range(n)}
    for i, (s, t) in enumerate(pairs):
        dist, cnt = truth[s]
        if int(cnt[t]) == 0:  # disconnected: INF sentinels differ
            assert int(c[i]) == 0 and int(d[i]) >= (1 << 28), (s, t)
        else:
            assert (int(d[i]), int(c[i])) == (int(dist[t]), int(cnt[t])), (s, t)


def test_prefix_differential_vs_per_event_and_oracle(per_event):
    """ESPC + per-event agreement after every prefix of a mixed stream."""
    n = 12
    edges = random_graph_edges(n, 20, seed=0)
    events = graph_stream(edges, n, 6, 4, seed=1)
    B = len(events)
    cap_e = 4 * (len(edges) + B)
    svc0 = DynamicSPC(n, edges, l_cap=n + 2, cap_e=cap_e)
    g0 = G.ensure_capacity(svc0.graph, 2 * B)
    idx0 = svc0.index
    g_seq, idx_seq = svc0.graph, svc0.index
    rg = R.RefGraph(n, edges)
    arr = _events_array(events)
    for k in range(B + 1):
        ev = arr.copy()
        ev[k:] = 0  # rows >= k become (0, 0, 0) self-loop padding
        (g2, _), idx2 = hyb_spc_batch(g0, idx0, jnp.asarray(ev))
        assert int(idx2.overflow) == 0
        assert to_ref(idx2).labels == to_ref(idx_seq).labels, k
        assert sorted(G.to_ref(g2).edge_list()) == sorted(rg.edge_list()), k
        _assert_espc(idx2, rg)
        if k < B:
            op, a, b = events[k]
            g_seq, idx_seq = per_event(g_seq, idx_seq, [(op, a, b)])
            if op == "+":
                rg.add_edge(a, b)
            else:
                rg.remove_edge(a, b)


def test_padding_rows_are_noops():
    n = 20
    edges = random_graph_edges(n, 45, seed=2)
    events = graph_stream(edges, n, 4, 2, seed=3)
    svc = DynamicSPC(n, edges, l_cap=n + 2)
    g0 = G.ensure_capacity(svc.graph, 2 * len(events))
    plain = _events_array(events)
    padded = np.concatenate([
        np.asarray([[0, 0, 0], [OP_INSERT, 5, 5]], np.int32),
        plain[:3],
        np.asarray([[OP_DELETE, 7, 7], [9, 1, 1]], np.int32),  # 9: bad op
        plain[3:],
        np.zeros((2, 3), np.int32),
    ])
    (g_a, _), idx_a = hyb_spc_batch(g0, svc.index, jnp.asarray(plain))
    (g_b, _), idx_b = hyb_spc_batch(g0, svc.index, jnp.asarray(padded))
    assert int(idx_b.overflow) == int(idx_a.overflow) == 0
    assert to_ref(idx_a).labels == to_ref(idx_b).labels
    np.testing.assert_array_equal(np.asarray(g_a.src), np.asarray(g_b.src))
    np.testing.assert_array_equal(np.asarray(g_a.dst), np.asarray(g_b.dst))


def test_overflow_retry_tiny_lcap(per_event):
    """Star graph fits exactly at l_cap=2; densifying inserts must
    overflow, trigger the snapshot-replay retry, and still agree with
    the per-event replay (at a capacity that holds) and the oracle."""
    n = 8
    star = [(0, v) for v in range(1, n)]
    events = [("+", 1, 2), ("+", 2, 3), ("-", 0, 4), ("+", 4, 5)]
    seq = DynamicSPC(n, star, l_cap=n + 2)
    bat = DynamicSPC(n, star, l_cap=2)
    assert bat.index.l_cap == 2
    _, want = per_event(seq.graph, seq.index, events)
    bat.apply_events(events, batch_size=4)
    assert bat.stats.label_regrows >= 1
    assert bat.stats.batches == 1
    assert to_ref(bat.index).labels == to_ref(want).labels
    rg = R.RefGraph(n, star)
    for op, a, b in events:
        rg.add_edge(a, b) if op == "+" else rg.remove_edge(a, b)
    _assert_espc(bat.index, rg)


def test_all_delete_batch_matches_sequential(per_event):
    """hyb_spc_batch on an all-delete stream (incl. the traced isolated
    fast path) == one dec_spc dispatch per edge."""
    n = 26
    base = random_graph_edges(n - 1, 50, seed=4)
    edges = base + [(3, n - 1)]  # pendant: deg(n-1) == 1
    svc = DynamicSPC(n, edges, l_cap=32)
    doomed = [("-",) + e for e in (edges[1], edges[7], (3, n - 1),
                                   edges[15])]
    g_seq, idx_seq = per_event(svc.graph, svc.index, doomed)
    arr = _events_array(doomed + [("-", 6, 6)])  # trailing padding row
    (g2, work), idx2 = hyb_spc_batch(svc.graph, svc.index,
                                     jnp.asarray(arr))
    assert int(idx2.overflow) == 0
    assert int(work.isolated_fast_path) == 1
    assert to_ref(idx2).labels == to_ref(idx_seq).labels
    assert sorted(G.to_ref(g2).edge_list()) == \
        sorted(G.to_ref(g_seq).edge_list())


def test_64_event_stream_batched_equals_per_event(per_event):
    """Acceptance: a >= 64-event mixed stream through hyb_spc_batch
    yields an index identical to a per-event replay, with fewer
    jitted dispatches than events."""
    n, m = 48, 110
    edges = random_graph_edges(n, m, seed=5)
    events = graph_stream(edges, n, 48, 16, seed=6)
    assert len(events) >= 64
    seq = DynamicSPC(n, edges, l_cap=n + 2)
    g_seq, idx_seq = per_event(seq.graph, seq.index, events)
    bat = DynamicSPC(n, edges, l_cap=32)
    bat.apply_events(events, batch_size=16)
    assert bat.stats.batches < len(events)  # batching actually engaged
    assert bat.stats.batched_events == len(events)
    assert bat.stats.events_per_batch == pytest.approx(16.0)
    ref_seq, ref_bat = to_ref(idx_seq), to_ref(bat.index)
    assert ref_bat.labels == ref_seq.labels  # hub/dist/cnt/size identical
    assert sorted(G.to_ref(bat.graph).edge_list()) == \
        sorted(G.to_ref(g_seq).edge_list())


def test_apply_events_validates_stream():
    n = 10
    edges = [(0, 1), (1, 2), (2, 3)]
    svc = DynamicSPC(n, edges, l_cap=8)
    with pytest.raises(ValueError, match="already present"):
        svc.apply_events([("+", 0, 1)])
    with pytest.raises(ValueError, match="not present"):
        svc.apply_events([("-", 0, 5)])
    with pytest.raises(ValueError, match="self loop"):
        svc.apply_events([("+", 4, 4)])
    with pytest.raises(ValueError, match="unknown event"):
        svc.apply_events([("x", 0, 5)])
    # validation is transactional: nothing above was applied
    assert svc.stats.batches == 0 and svc.stats.inserts == 0
    # a stream that is only valid *in order* (delete then re-insert) passes
    svc.apply_events([("-", 0, 1), ("+", 0, 1), ("+", 0, 4), ("-", 0, 4)],
                     batch_size=4)
    assert svc.stats.batches == 1


def test_apply_events_rejects_bad_op_tags_naming_row():
    """The batched engine maps unknown op tags to its padding branch
    inside the trace (it cannot raise mid-scan), so a corrupted stream
    would silently drop updates; the driver must reject them host-side,
    naming the first bad row -- at every chunk size."""
    n = 8
    svc = DynamicSPC(n, [(0, 1), (1, 2)], l_cap=8)
    bad = [("+", 0, 3), (9, 1, 4), ("-", 0, 1)]  # row 1: engine pad branch
    for bs in (4, 1):
        with pytest.raises(ValueError, match=r"row 1"):
            svc.apply_events(bad, batch_size=bs)
        # transactional even in one-event chunks: op tags are resolved
        # before any event is applied
        assert svc.stats.inserts == 0 and svc.stats.deletions == 0
    with pytest.raises(ValueError, match=r"row 0"):
        svc.apply_events([(None, 0, 3)])
    # bool/float tags must not coerce through int equality (True == 1)
    with pytest.raises(ValueError, match=r"row 0"):
        svc.apply_events([(True, 0, 3)])
    with pytest.raises(ValueError, match=r"row 0"):
        svc.apply_events([(2.0, 0, 1)])
    with pytest.raises(ValueError, match=r"row 2"):
        svc.apply_events([("+", 0, 3), ("-", 1, 2), ("*", 2, 5)])
    with pytest.raises(ValueError, match=r"row 1.*endpoint"):
        svc.apply_events([("+", 0, 3), ("+", "x", 4)])
    assert svc._edge_set() == {(0, 1), (1, 2)}  # nothing applied


def test_apply_events_accepts_engine_op_codes():
    """OP_INSERT/OP_DELETE integer tags (the engine encoding) are
    accepted and equivalent to the '+'/'-' symbols."""
    n = 8
    edges = [(0, 1), (1, 2), (2, 3)]
    sym = DynamicSPC(n, edges, l_cap=8)
    num = DynamicSPC(n, edges, l_cap=8)
    sym.apply_events([("+", 0, 4), ("-", 1, 2), ("+", 1, 5)], batch_size=4)
    num.apply_events([(OP_INSERT, 0, 4), (OP_DELETE, 1, 2),
                      (int(np.int32(OP_INSERT)), 1, 5)], batch_size=4)
    assert to_ref(num.index).labels == to_ref(sym.index).labels
    assert num._edge_set() == sym._edge_set()


@pytest.mark.parametrize("batch_size", [None, 0, -3, 2.0, True])
def test_apply_events_rejects_bad_batch_size(batch_size):
    """Every chunk runs through hyb_spc_batch: there is no per-event
    fallback for ``batch_size`` to select, so anything but an int >= 1
    is refused, naming the argument, before any event is applied."""
    svc = DynamicSPC(8, [(0, 1), (1, 2)], l_cap=8)
    with pytest.raises(ValueError, match="batch_size"):
        svc.apply_events([("+", 0, 3)], batch_size=batch_size)
    assert svc.version == 0 and svc._edge_set() == {(0, 1), (1, 2)}


@pytest.mark.parametrize("op", ["+", "-"])
def test_single_edge_calls_are_one_event_chunks(op):
    """insert_edge / delete_edge are one-event apply_events calls: each
    publishes exactly one version and counts one engine batch, and
    leaves the index a one-event chunk through hyb_spc_batch leaves."""
    n = 12
    edges = random_graph_edges(n, 20, seed=7)
    svc = DynamicSPC(n, edges, l_cap=n + 2)
    ref = DynamicSPC(n, edges, l_cap=n + 2)
    store = svc.attach_store()
    if op == "+":
        a, b = next((a, b) for a in range(n) for b in range(a + 1, n)
                    if (a, b) not in svc._edge_set())
        svc.insert_edge(a, b)
    else:
        a, b = sorted(svc._edge_set())[0]
        svc.delete_edge(a, b)
    assert store.publishes == 1 and store.version == svc.version == 1
    st = svc.stats.snapshot()
    assert (st.batches, st.batched_events) == (1, 1)
    assert (st.inserts, st.deletions) == ((1, 0) if op == "+" else (0, 1))
    ref.apply_events([(op, a, b)], batch_size=1)
    assert to_ref(svc.index).labels == to_ref(ref.index).labels
    with pytest.raises(ValueError, match="already present" if op == "+"
                       else "not present"):
        (svc.insert_edge if op == "+" else svc.delete_edge)(a, b)
    assert svc.version == 1
