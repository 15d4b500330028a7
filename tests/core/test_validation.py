"""Host-side input validation of the DynamicSPC driver.

Out-of-range vertex ids must raise ``ValueError`` instead of silently
clamping under JAX scatter/gather semantics (which would corrupt the
dump row n) -- and a rejected op must leave the service untouched."""

import numpy as np
import pytest

from repro.core.dynamic import DynamicSPC
from repro.core.labels import to_ref
from repro.data import random_graph_edges


@pytest.fixture(scope="module")
def svc():
    n = 20
    return DynamicSPC(n, random_graph_edges(n, 45, seed=5), l_cap=32)


BAD_EDGES = [(-1, 3), (3, -1), (0, 20), (20, 0), (0, 10 ** 9), (7, 7)]


@pytest.mark.parametrize("a,b", BAD_EDGES)
def test_insert_edge_rejects_bad_ids(svc, a, b):
    before = to_ref(svc.index).labels
    with pytest.raises(ValueError):
        svc.insert_edge(a, b)
    assert to_ref(svc.index).labels == before


@pytest.mark.parametrize("a,b", BAD_EDGES)
def test_delete_edge_rejects_bad_ids(svc, a, b):
    with pytest.raises(ValueError):
        svc.delete_edge(a, b)


@pytest.mark.parametrize("a,b", BAD_EDGES)
def test_apply_events_batched_rejects_bad_ids(svc, a, b):
    """The batched engine cannot raise mid-scan; _validate_events must
    catch bad ids up front, before any chunk dispatches."""
    before = to_ref(svc.index).labels
    with pytest.raises(ValueError):
        svc.apply_events([("+", 0, 19), ("+", a, b)], batch_size=8)
    assert to_ref(svc.index).labels == before


def test_query_rejects_bad_ids(svc):
    for s, t in ((-1, 0), (0, 20), (20, 20)):
        with pytest.raises(ValueError):
            svc.query(s, t)
    with pytest.raises(ValueError):
        svc.query_batch([0, 1], [1, 20])
    with pytest.raises(ValueError):
        svc.query_batch(np.asarray([-3]), np.asarray([0]))


def test_delete_vertex_rejects_bad_ids(svc):
    for v in (-1, 20, 10 ** 9):
        with pytest.raises(ValueError):
            svc.delete_vertex(v)


def test_dump_row_stays_clean_after_rejections(svc):
    """The dump row (row n) is the clamp target; it must stay all-pad."""
    hub = np.asarray(svc.index.hub)
    assert (hub[svc.n] == svc.n).all()
    assert int(svc.index.size[svc.n]) == 0


# -- state-dict schema validation -------------------------------------------
def _state(svc):
    return {k: np.asarray(v) for k, v in svc.state_dict().items()}


def test_from_state_dict_round_trips(svc):
    svc2 = DynamicSPC.from_state_dict(svc.n, _state(svc))
    assert to_ref(svc2.index).labels == to_ref(svc.index).labels
    assert svc2.version == svc.version


def test_from_state_dict_rejects_missing_key(svc):
    state = _state(svc)
    del state["index.cnt"]
    with pytest.raises(ValueError, match="index.cnt"):
        DynamicSPC.from_state_dict(svc.n, state)


@pytest.mark.parametrize("key", ["graph.dst", "index.dist", "index.cnt",
                                 "index.size", "index.hub"])
def test_from_state_dict_rejects_truncated_leaf(svc, key):
    """Regression: a truncated array used to silently build a corrupt
    service (gathers clamp into the dump row); now the offending key is
    named."""
    state = _state(svc)
    state[key] = state[key][:-2]
    with pytest.raises(ValueError, match=key.replace(".", r"\.")):
        DynamicSPC.from_state_dict(svc.n, state)


def test_from_state_dict_rejects_wrong_n(svc):
    with pytest.raises(ValueError, match="index.hub"):
        DynamicSPC.from_state_dict(svc.n + 3, _state(svc))


def test_from_state_dict_rejects_bad_m2_and_dtype(svc):
    state = _state(svc)
    state["graph.m2"] = np.int32(state["graph.src"].shape[0] + 2)
    with pytest.raises(ValueError, match="graph.m2"):
        DynamicSPC.from_state_dict(svc.n, state)
    state = _state(svc)
    state["index.dist"] = state["index.dist"].astype(np.float32)
    with pytest.raises(ValueError, match="index.dist"):
        DynamicSPC.from_state_dict(svc.n, state)
    state = _state(svc)
    state["version"] = np.int64(-4)
    with pytest.raises(ValueError, match="version"):
        DynamicSPC.from_state_dict(svc.n, state)


@pytest.mark.parametrize("schema", ["row_sums", "no_version"])
def test_from_state_dict_accepts_legacy_dict(svc, schema):
    """State dicts of earlier schemas load: one whose index still
    carries its cached row sums (``index.cnt_sum``, dropped on load),
    and one from before the version counter (version 0)."""
    state = _state(svc)
    assert "index.cnt_sum" not in state
    if schema == "row_sums":
        state["index.cnt_sum"] = np.asarray(state["index.cnt"]).sum(axis=1)
    else:
        del state["version"]
    svc2 = DynamicSPC.from_state_dict(svc.n, state)
    assert svc2.version == (svc.version if schema == "row_sums" else 0)
    assert to_ref(svc2.index).labels == to_ref(svc.index).labels
    assert sorted(svc2.state_dict()) == sorted(svc.state_dict())
