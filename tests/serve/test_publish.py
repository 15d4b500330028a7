"""Snapshot publish subsystem: version monotonicity, reader pinning
across concurrent swaps, overflow-retry atomicity, the publish ->
checkpoint durability hook, and the cached ``cnt_sum`` routing bound
(state-dict round trip + differential vs the recomputed per-batch bound
and the ``bfs_spc`` oracle, replicated and ``mesh=`` modes)."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import refimpl as R
from repro.core.dynamic import DynamicSPC
from repro.core.graph import INF
from repro.core.labels import from_ref, recompute_cnt_sum
from repro.core.query import (batched_query_merge, cached_count_bound,
                              count_upper_bound_rows)
from repro.data import graph_stream, random_graph_edges
from repro.kernels.spc_query.ops import exact_query_batch, prep_rows
from repro.serve import QueryEngine, SnapshotStore, load_snapshot
from repro.serve.publish import Snapshot


def _one_insert_one_delete(svc):
    """A valid tiny event chunk for this service's current edge set."""
    present = svc._edge_set()
    absent = next((a, b) for a in range(svc.n) for b in range(a + 1, svc.n)
                  if (a, b) not in present)
    return [("+",) + absent, ("-",) + next(iter(sorted(present)))]


def _arrays(idx):
    return {k: np.asarray(getattr(idx, k)).copy()
            for k in ("hub", "dist", "cnt", "size", "cnt_sum")}


def _assert_index_equal(a, b):
    for k, arr in _arrays(a).items():
        np.testing.assert_array_equal(arr, _arrays(b)[k], err_msg=k)


@pytest.fixture()
def svc():
    n = 30
    svc = DynamicSPC(n, random_graph_edges(n, 70, seed=11), l_cap=32)
    return svc


# -- store mechanics --------------------------------------------------------
def test_version_monotonicity(svc):
    store = SnapshotStore(svc.index, version=5)
    assert store.version == 5
    assert store.publish(svc.index) == 6          # default: bump
    assert store.publish(svc.index, version=9) == 9
    for bad in (9, 8, 0, -1):
        with pytest.raises(ValueError, match="monotonically"):
            store.publish(svc.index, version=bad)
    assert store.version == 9                      # failed publishes: no swap
    assert store.publishes == 2


def test_empty_store_raises_until_first_publish(svc):
    store = SnapshotStore()
    assert store.version is None
    with pytest.raises(RuntimeError):
        store.current()
    assert store.publish(svc.index) == 0           # first version is 0
    assert store.current().index is not None


def test_reader_pinned_while_next_version_is_written(svc):
    """The acceptance property: a batch pinned on version k is unaffected
    by a concurrent k+1 staging + swap, bit-for-bit."""
    store = svc.attach_store()
    eng = QueryEngine()
    rng = np.random.default_rng(0)
    s = rng.integers(0, svc.n, 33)
    t = rng.integers(0, svc.n, 33)
    pinned = store.current()                       # reader enters its batch
    want = _arrays(pinned.index)
    d_before, c_before = eng.query_batch(pinned.index, s, t)
    # updater writes k+1 and swaps it in mid-"batch"
    svc.apply_events(graph_stream(sorted(svc._edge_set()), svc.n, 6, 3,
                                  seed=1), batch_size=4)
    assert store.version > pinned.version
    for k, arr in _arrays(pinned.index).items():   # pinned pytree untouched
        np.testing.assert_array_equal(arr, want[k], err_msg=k)
    d_after, c_after = eng.query_batch(pinned.index, s, t)
    np.testing.assert_array_equal(np.asarray(d_after), np.asarray(d_before))
    np.testing.assert_array_equal(np.asarray(c_after), np.asarray(c_before))
    # and the front moved on to the updater's committed state
    _assert_index_equal(store.current().index, svc.index)


def test_swap_atomicity_under_overflow_retry():
    """A chunk that overflows and replays must publish exactly once --
    after the retry commits -- and never expose the overflowed
    intermediate index to readers."""
    n = 8
    star = [(0, v) for v in range(1, n)]           # fits exactly at l_cap=2
    svc = DynamicSPC(n, star, l_cap=2)
    seq = DynamicSPC(n, star, l_cap=2)
    store = svc.attach_store()
    pinned = store.current()
    before = _arrays(pinned.index)
    events = [("+", 1, 2), ("+", 2, 3), ("-", 0, 4), ("+", 4, 5)]
    svc.apply_events(events, batch_size=4)         # one chunk, must regrow
    assert svc.stats.label_regrows >= 1
    assert store.publishes == 1                    # retry != extra publish
    assert store.version == pinned.version + 1
    for k, arr in _arrays(pinned.index).items():
        np.testing.assert_array_equal(arr, before[k], err_msg=k)
    front = store.current().index
    assert int(front.overflow) == 0
    seq.apply_events(events, batch_size=None)      # per-event trajectory
    from repro.core.labels import to_ref
    assert to_ref(front).labels == to_ref(seq.index).labels


def test_serve_from_bit_identical_across_publish(svc):
    """serve_from(store) == direct query_batch on the same version,
    before, during (pinned snapshot) and after a publish."""
    store = svc.attach_store()
    eng = QueryEngine()
    direct = QueryEngine()
    serve = eng.serve_from(store)
    rng = np.random.default_rng(2)
    s = rng.integers(0, svc.n, 50)
    t = rng.integers(0, svc.n, 50)

    def check(idx):
        d, c = serve(s, t)
        d0, c0 = direct.query_batch(idx, s, t)
        np.testing.assert_array_equal(np.asarray(d), np.asarray(d0))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(c0))

    check(svc.index)                               # before
    pinned = store.current()
    svc.apply_events(_one_insert_one_delete(svc), batch_size=8)
    check(svc.index)                               # after: new front
    # "during": a replica still holding version k answers from k
    stale = QueryEngine()
    d, c = stale.serve_from(SnapshotStore(pinned.index,
                                          version=pinned.version))(s, t)
    d0, c0 = direct.query_batch(pinned.index, s, t)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d0))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c0))
    assert eng.stats.versions == {0: 50, 1: 50}


def test_concurrent_updater_and_reader_threads(svc):
    """One publisher thread streaming chunks, one reader thread serving
    continuously: every batch must answer from a committed version (no
    torn reads) and versions must be non-decreasing."""
    store = svc.attach_store()
    eng = QueryEngine()
    serve = eng.serve_from(store)
    expected = {0: _arrays(svc.index)["cnt_sum"]}
    events = graph_stream(sorted(svc._edge_set()), svc.n, 10, 5, seed=3)
    errors = []

    def updater():
        try:
            for lo in range(0, len(events), 3):
                svc.apply_events(events[lo:lo + 3], batch_size=3)
                expected[svc.version] = _arrays(svc.index)["cnt_sum"]
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    th = threading.Thread(target=updater)
    th.start()
    seen = []
    while th.is_alive():
        snap = store.current()
        seen.append(snap.version)
        # a torn snapshot would break the cnt_sum invariant; the
        # expected map only has versions the updater already recorded
        # (publish happens inside apply_events, records after it)
        np.testing.assert_array_equal(
            np.asarray(snap.index.cnt_sum),
            np.asarray(recompute_cnt_sum(snap.index.cnt)),
            err_msg=f"torn read at version {snap.version}")
        if snap.version in expected:
            np.testing.assert_array_equal(
                np.asarray(snap.index.cnt_sum), expected[snap.version],
                err_msg=f"wrong state at version {snap.version}")
        d, c = serve([0, 1], [2, 3])
        assert d.shape == (2,)
    th.join()
    assert not errors, errors
    assert seen == sorted(seen)
    assert store.version == svc.version == -(-len(events) // 3)


# -- durability hook --------------------------------------------------------
@pytest.mark.parametrize("async_ckpt", [False, True])
def test_publish_checkpoint_hook_round_trip(svc, tmp_path, async_ckpt):
    from repro.train import checkpoint as C

    store = svc.attach_store(checkpoint_dir=str(tmp_path),
                             async_checkpoint=async_ckpt)
    svc.apply_events(_one_insert_one_delete(svc), batch_size=8)
    store.wait()
    assert C.latest_step(str(tmp_path)) == store.version == 1
    snap = load_snapshot(str(tmp_path))
    assert snap.version == 1
    _assert_index_equal(snap.index, svc.index)
    # a crashed-writer .tmp dir must not shadow the committed version
    older = load_snapshot(str(tmp_path), step=0)
    assert older.version == 0


def test_loaded_snapshot_serves_identically(svc, tmp_path):
    store = svc.attach_store(checkpoint_dir=str(tmp_path))
    svc.apply_events(_one_insert_one_delete(svc), batch_size=8)
    snap = load_snapshot(str(tmp_path))
    eng = QueryEngine()
    rng = np.random.default_rng(4)
    s = rng.integers(0, svc.n, 20)
    t = rng.integers(0, svc.n, 20)
    d, c = eng.serve_from(SnapshotStore(snap.index,
                                        version=snap.version))(s, t)
    d0, c0 = eng.query_batch(svc.index, s, t)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d0))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c0))


# -- cached cnt-sum bound ---------------------------------------------------
def _big_count_index():
    big = 2 ** 24 + 1
    ref = R.RefSPCIndex(3)
    ref.labels[0] = [(0, 0, 1)]
    ref.labels[1] = [(0, 1, big), (1, 0, 1)]
    ref.labels[2] = [(0, 1, 1), (2, 0, 1)]
    return from_ref(ref, l_cap=4)


def _assert_bound_consistent(idx, s, t):
    """The cached bound equals the recomputed per-batch bound and bounds
    every true count, and exact_query_batch patches a batch iff one of
    its true counts reaches 2^23 (the kernel reads such a count as 2^23
    or more, and any smaller one exactly)."""
    s = jnp.asarray(np.asarray(s, np.int32))
    t = jnp.asarray(np.asarray(t, np.int32))
    rows = prep_rows(idx, s, t)
    recomputed = np.asarray(count_upper_bound_rows(rows[2], rows[5]))
    cached = np.asarray(cached_count_bound(idx, s, t))
    np.testing.assert_array_equal(cached, recomputed)
    d, c, route = exact_query_batch(idx, s, t)
    _, c_merge = batched_query_merge(idx, s, t)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c_merge))
    assert (recomputed >= np.asarray(c_merge)).all()
    want = ("pallas+merge" if (np.asarray(c_merge) >= 2 ** 23).any()
            else "pallas")
    assert route == want


def test_cached_bound_matches_recomputed_on_engine_cases(svc):
    rng = np.random.default_rng(5)
    _assert_bound_consistent(svc.index, rng.integers(0, svc.n, 64),
                             rng.integers(0, svc.n, 64))
    svc.apply_events(graph_stream(sorted(svc._edge_set()), svc.n, 6, 3,
                                  seed=6), batch_size=4)
    _assert_bound_consistent(svc.index, rng.integers(0, svc.n, 64),
                             rng.integers(0, svc.n, 64))
    idx = _big_count_index()
    _assert_bound_consistent(idx, [0, 0, 2], [2, 1, 2])   # mixed split
    _assert_bound_consistent(idx, [0], [1])               # all-inexact
    _assert_bound_consistent(idx, [2], [2])               # all-exact


def test_cached_bound_survives_state_dict_round_trip(svc):
    svc.apply_events(_one_insert_one_delete(svc), batch_size=1)
    state = {k: np.asarray(v) for k, v in svc.state_dict().items()}
    svc2 = DynamicSPC.from_state_dict(svc.n, state)
    assert svc2.version == svc.version == 2
    np.testing.assert_array_equal(np.asarray(svc2.index.cnt_sum),
                                  np.asarray(svc.index.cnt_sum))
    np.testing.assert_array_equal(
        np.asarray(svc2.index.cnt_sum),
        np.asarray(recompute_cnt_sum(svc2.index.cnt)))


def _oracle_tables(svc):
    g = R.RefGraph(svc.n, sorted(svc._edge_set()))
    return {s: R.bfs_spc(g, s) for s in range(svc.n)}


@pytest.mark.parametrize("use_mesh", [False, True])
def test_cached_bound_differential_vs_bfs(use_mesh):
    """cnt_sum stays exact under every engine (replicated and sharded):
    after a mixed stream it equals the row sums AND the row sums agree
    with BFS ground truth through the serving path."""
    n = 24
    edges = random_graph_edges(n, 55, seed=7)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("model",)) if use_mesh \
        else None
    svc = DynamicSPC(n, edges, l_cap=32, mesh=mesh)
    svc.apply_events(graph_stream(edges, n, 8, 4, seed=8), batch_size=4)
    np.testing.assert_array_equal(
        np.asarray(svc.index.cnt_sum),
        np.asarray(recompute_cnt_sum(svc.index.cnt)))
    truth = _oracle_tables(svc)
    eng = QueryEngine()
    serve = eng.serve_from(svc.attach_store())
    rng = np.random.default_rng(9)
    s = [int(x) for x in rng.integers(0, n, 40)]
    t = [int(x) for x in rng.integers(0, n, 40)]
    d, c = serve(s, t)
    for k, (sk, tk) in enumerate(zip(s, t)):
        dist, cnt = truth[sk]
        if dist[tk] >= int(INF):
            assert int(c[k]) == 0 and int(d[k]) >= int(INF)
        else:
            assert (int(d[k]), int(c[k])) == (int(dist[tk]), int(cnt[tk]))


def test_mesh_store_replicates_and_serves(svc):
    """A mesh-placed store stages snapshots replicated over the serving
    mesh; serve_from(mesh=) answers identically to the routed path."""
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    store = svc.attach_store(mesh=mesh)
    eng = QueryEngine()
    serve = eng.serve_from(store, mesh=mesh)
    svc.apply_events(_one_insert_one_delete(svc), batch_size=4)
    rng = np.random.default_rng(10)
    s = rng.integers(0, svc.n, 13)
    t = rng.integers(0, svc.n, 13)
    d, c = serve(s, t)
    d0, c0 = QueryEngine().query_batch(svc.index, s, t, route="merge")
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d0))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c0))
    assert eng.stats.routes == {"sharded[data]:merge": 1}
    assert eng.stats.versions == {1: 13}


def test_attach_store_rejects_store_ahead_of_service(svc):
    """An out-of-date service must fail at attach time, not with a
    monotonicity error on its first update after attach."""
    store = SnapshotStore(svc.index, version=7)
    with pytest.raises(ValueError, match="ahead"):
        svc.attach_store(store)
    assert svc._store is None


def test_from_checkpoint_restores_new_and_legacy_layouts(svc, tmp_path):
    """On-disk round trip through the manifest-driven template, for the
    9-leaf schema AND a pre-cached-bound 7-leaf checkpoint (which
    ``checkpoint.restore(dir, svc.state_dict())`` would reject on leaf
    count before the legacy handling could run)."""
    from repro.train import checkpoint as C

    svc.apply_events(_one_insert_one_delete(svc), batch_size=8)
    new_dir, old_dir = str(tmp_path / "new"), str(tmp_path / "old")
    C.save(new_dir, svc.version, svc.state_dict())
    legacy = {k: v for k, v in svc.state_dict().items()
              if k not in ("index.cnt_sum", "version")}
    C.save(old_dir, 0, legacy)
    svc2 = DynamicSPC.from_checkpoint(new_dir, svc.n)
    assert svc2.version == svc.version
    _assert_index_equal(svc2.index, svc.index)
    svc3 = DynamicSPC.from_checkpoint(old_dir, svc.n)
    assert svc3.version == 0
    _assert_index_equal(svc3.index, svc.index)  # cnt_sum rebuilt
    with pytest.raises(ValueError, match="leaves"):
        C.save(str(tmp_path / "bad"), 0, {"x": np.zeros(3)})
        DynamicSPC.from_checkpoint(str(tmp_path / "bad"), svc.n)


def test_snapshot_is_immutable_dataclass(svc):
    snap = Snapshot(3, svc.index)
    with pytest.raises(Exception):
        snap.version = 4
