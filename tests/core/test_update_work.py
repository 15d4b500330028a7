"""The hybrid engine's work counters (``bfs.RepairWork``) against a plain
Python count: the same events replayed through the paper-faithful
reference (``refimpl.inc_spc`` / ``dec_spc`` with a ``work`` counter),
which counts one hub repair per Algorithm 3/6 BFS and one relaxation
round per BFS level that expanded a vertex (SRRSearch's included)."""

import collections

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as G
from repro.core import refimpl as R
from repro.core.dynamic import DynamicSPC
from repro.core.hybrid import OP_DELETE, OP_INSERT, hyb_spc_batch
from repro.data import graph_stream, random_graph_edges

CODE = {"+": OP_INSERT, "-": OP_DELETE}
KEYS = ("hub_repairs", "relax_rounds", "isolated_fast_path")


def _stream(n):
    """Edges among vertices 0..n-2 and a mixed stream that also joins
    the isolated vertex n-1 and cuts it off again: that deletion leaves
    its higher-id endpoint of degree 1, the isolated-vertex fast path."""
    edges = random_graph_edges(n - 1, 40, seed=4)
    events = graph_stream(edges, n - 1, 8, 4, seed=5)
    events = events[:6] + [("+", 3, n - 1), ("-", 3, n - 1)] + events[6:]
    return edges, events


def _reference(n, edges, events):
    """Per-event work counts of the reference replay."""
    rg = R.RefGraph(n, edges)
    ridx = R.hp_spc(rg)
    out = []
    for op, a, b in events:
        work = collections.Counter()
        (R.inc_spc if op == "+" else R.dec_spc)(rg, ridx, a, b, work)
        out.append(tuple(work[k] for k in KEYS))
    return out


def test_engine_counts_equal_the_reference_count_per_event():
    n = 24
    edges, events = _stream(n)
    want = _reference(n, edges, events)
    assert any(w[0] for w in want)               # repairs were run
    assert sum(w[2] for w in want) == 1          # the fast path, once
    ops = {op for op, _, _ in events}
    assert ops == {"+", "-"}
    svc = DynamicSPC(n, edges, l_cap=n + 2)
    g = G.ensure_capacity(svc.graph, 2 * len(events))
    idx = svc.index
    got = []
    for op, a, b in events:
        ev = jnp.asarray(np.asarray([[CODE[op], a, b], [0, 0, 0]], np.int32))
        (g, work), idx = hyb_spc_batch(g, idx, ev)
        assert int(idx.overflow) == 0
        got.append(tuple(int(x) for x in work))
    assert got == want


def test_a_chunk_sums_its_events_and_padding_does_no_work():
    n = 24
    edges, events = _stream(n)
    want = np.sum(_reference(n, edges, events), axis=0)
    svc = DynamicSPC(n, edges, l_cap=n + 2)
    g = G.ensure_capacity(svc.graph, 2 * len(events))
    arr = np.zeros((len(events) + 3, 3), np.int32)   # 3 padding rows
    for i, (op, a, b) in enumerate(events):
        arr[i] = (CODE[op], a, b)
    (_, work), idx = hyb_spc_batch(g, svc.index, jnp.asarray(arr))
    assert int(idx.overflow) == 0
    assert [int(x) for x in work] == list(want)


@pytest.mark.parametrize("batch_size", [4, 64])
def test_apply_events_counts_the_batched_path(batch_size):
    """``UpdateStats`` gets the engine's counts, the isolated-vertex fast
    path included (it read 0 on the batched path before)."""
    n = 24
    edges, events = _stream(n)
    want = dict(zip(KEYS, np.sum(_reference(n, edges, events), axis=0)))
    svc = DynamicSPC(n, edges, l_cap=n + 2)
    svc.apply_events(events, batch_size=batch_size)
    st = svc.stats.snapshot()
    assert {k: getattr(st, k) for k in KEYS} == want
    assert st.batched_events == len(events)


def test_the_per_event_path_keeps_its_host_fast_path_count():
    n = 24
    edges, events = _stream(n)
    svc = DynamicSPC(n, edges, l_cap=n + 2)
    svc.apply_events(events[:8], batch_size=None)
    st = svc.stats.snapshot()
    assert st.isolated_fast_path == 1
    assert (st.hub_repairs, st.relax_rounds) == (0, 0)  # not counted there
