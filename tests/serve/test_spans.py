"""The program's host spans (``repro.spans``) and the serving engines'
pairs per evaluation path (``ServeStats.route_pairs``)."""

import glob

import jax
import numpy as np
import pytest

from repro.core import refimpl as R
from repro.core.labels import from_ref
from repro.data import random_graph_edges
from repro.serve import QueryEngine, SPCService
from repro.spans import span


def test_a_span_outside_the_profiler_does_nothing_and_nests():
    with span("spc.outer") as outer:
        with span("spc.outer.inner"):
            x = 1 + 1
    assert x == 2 and outer is not None
    with pytest.raises(ZeroDivisionError):   # exceptions pass through
        with span("spc.outer"):
            1 / 0


def _host_spans(log_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith("spc.")]
    return out


def test_a_reader_batch_and_an_update_record_their_spans(tmp_path):
    """Under the profiler, a served batch is one ``spc.read`` holding its
    host steps, and an applied chunk records validate/apply/publish."""
    with SPCService(30, random_graph_edges(30, 70, seed=11), l_cap=32,
                    update_batch=4) as svc:
        reader = svc.reader()
        np.asarray(reader([0, 1, 2], [3, 4, 5])[1])   # compiled already
        jax.profiler.start_trace(str(tmp_path))
        try:
            np.asarray(reader([0, 1, 2], [3, 4, 5])[1])
            a, b = next((a, b) for a in range(30) for b in range(a + 1, 30)
                        if (a, b) not in svc.spc._edge_set())
            svc.submit([("+", a, b)])
            svc.drain()
        finally:
            jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    names = [n for n, _, _ in spans]
    (read,) = [s for s in spans if s[0] == "spc.read"]
    inner = [s for s in spans if s[0].startswith("spc.read.")]
    assert {n for n, _, _ in inner} == {"spc.read.prep", "spc.read.merge"}
    assert all(read[1] <= s[1] and s[2] <= read[2] for s in inner)
    for name in ("spc.update.validate", "spc.update.apply",
                 "spc.update.publish"):
        assert names.count(name) == 1, name


def _mixed_index():
    """Vertex 1's count is past 2^24, so rows with it are inexact."""
    big = 2 ** 24 + 1
    ref = R.RefSPCIndex(3)
    ref.labels[0] = [(0, 0, 1)]
    ref.labels[1] = [(0, 1, big), (1, 0, 1)]
    ref.labels[2] = [(0, 1, 1), (2, 0, 1)]
    return from_ref(ref, l_cap=4)


@pytest.mark.parametrize("s,t,pairs", [
    ([0, 0, 2], [2, 1, 2], {"pallas": 2, "merge": 1}),   # mixed
    ([0, 2, 2], [2, 2, 0], {"pallas": 3}),               # all exact
    ([0, 1], [1, 0], {"merge": 2}),                      # all inexact
])
def test_route_pairs_count_every_real_pair_once(s, t, pairs):
    eng = QueryEngine()
    eng.query_batch(_mixed_index(), s, t, route="pallas")
    view = eng.stats.snapshot()
    assert dict(view.route_pairs) == pairs
    assert sum(view.route_pairs.values()) == view.queries == len(s)


def test_route_pairs_of_the_merge_and_table_routes_add_up():
    eng = QueryEngine()
    idx = _mixed_index()
    eng.query_batch(idx, [0, 1], [2, 2], route="merge")
    eng.query_batch(idx, [0], [2], route="table")
    eng.query_batch(idx, [0, 0, 2], [2, 1, 2], route="pallas")
    view = eng.stats.snapshot()
    assert dict(view.route_pairs) == {"merge": 3, "table": 1, "pallas": 2}
    assert sum(view.route_pairs.values()) == view.queries == 6
    assert dict(view.routes) == {"merge": 1, "table": 1, "pallas+merge": 1}
