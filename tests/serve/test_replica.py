"""Puller-fed replicas: ``ReplicaGroup`` mechanics (follow, verify,
skip-behind, survive publisher failures) and the ``role="replica"``
``SPCService`` -- read path unchanged (oracle differential, consistency
levels, FrontDoor), write path a typed refusal."""

import os
import threading
import time

import numpy as np
import pytest

from repro.core import refimpl as R
from repro.core.dynamic import DynamicSPC
from repro.core.graph import INF
from repro.data import graph_stream, random_graph_edges
from repro.serve import (ReplicaGroup, ReplicaReadOnlyError, SnapshotStore,
                         SPCService)
from repro.serve.transport import (DirTransport, LocalTransport,
                                   PublisherBehindError, Snapshot)
from repro.train import checkpoint as C

N, M, SEED = 16, 36, 13


def _arrays(idx):
    return {k: np.asarray(getattr(idx, k)).copy()
            for k in ("hub", "dist", "cnt", "size")}


def _assert_index_equal(a, b):
    for k, arr in _arrays(a).items():
        np.testing.assert_array_equal(arr, _arrays(b)[k], err_msg=k)


@pytest.fixture()
def spc():
    return DynamicSPC(N, random_graph_edges(N, M, seed=SEED), l_cap=32)


def _oracle(n, edge_set):
    g = R.RefGraph(n, sorted(edge_set))
    return {s: R.bfs_spc(g, s) for s in range(n)}


def _assert_matches_oracle(truth, s, t, d, c):
    for k, (sk, tk) in enumerate(zip(s, t)):
        dist, cnt = truth[sk]
        if dist[tk] >= int(INF):
            assert int(c[k]) == 0 and int(d[k]) >= int(INF), (sk, tk)
        else:
            assert (int(d[k]), int(c[k])) == (int(dist[tk]), int(cnt[tk]))


# -- ReplicaGroup mechanics -------------------------------------------------
def test_group_follows_publishes(spc):
    tr = LocalTransport()
    store = spc.attach_store(transport=tr)
    with ReplicaGroup(tr, poll_interval_s=0.01) as group:
        assert group.version == 0  # start() blocked for the first pull
        events = graph_stream(sorted(spc._edge_set()), spc.n, 6, 3,
                              seed=SEED + 1)
        for lo in range(0, len(events), 3):
            spc.apply_events(events[lo:lo + 3], batch_size=3)
        group.wait_for_version(store.version, timeout=30.0)
        _assert_index_equal(group.store.current().index, spc.index)
        st = group.stats()
        assert st["version"] == store.version and st["errors"] == 0
        assert st["pulls"] >= 1 and st["sources"] == 1


def test_group_start_times_out_without_publisher(tmp_path):
    group = ReplicaGroup(DirTransport(str(tmp_path)),
                         poll_interval_s=0.01)
    with pytest.raises(TimeoutError, match="updater up"):
        group.start(timeout=0.2)
    group.close()


def test_group_survives_fetch_failures_and_recovers(spc, tmp_path):
    """A pull that keeps failing (payload gone in a way retries cannot
    fix) is recorded and retried -- the last good version keeps serving
    -- and the group catches up once the medium heals."""
    tr = DirTransport(str(tmp_path))
    store = spc.attach_store(transport=tr)
    with ReplicaGroup(DirTransport(str(tmp_path)),
                      poll_interval_s=0.01) as group:
        assert group.version == 0
        # publish v1, then break its payload AND regress nothing else:
        # the puller sees a newer committed version it cannot fetch
        spc.apply_events([("+",) + _absent_edge(spc)], batch_size=1)
        payload = tmp_path / "step_000000001" / "arrays.npz"
        good = payload.read_bytes()
        payload.write_bytes(good[: len(good) // 2])
        deadline = time.monotonic() + 30.0
        while group.stats()["errors"] == 0:
            assert time.monotonic() < deadline, "no failed pull recorded"
            time.sleep(0.01)
        assert group.version == 0                    # still serving v0
        assert "step 1" in group.stats()["last_error"] or \
            "000000001" in group.stats()["last_error"]
        payload.write_bytes(good)                    # medium heals
        group.wait_for_version(1, timeout=30.0)
        _assert_index_equal(group.store.current().index, spc.index)
    assert store.version == 1


def _absent_edge(spc):
    present = spc._edge_set()
    return next((a, b) for a in range(spc.n) for b in range(a + 1, spc.n)
                if (a, b) not in present)


def test_group_skips_remote_behind(spc, tmp_path):
    """A remote pointer BEHIND the replica (a restarted updater that
    lost state) is never applied: the replica keeps serving its newer
    version and counts the sighting."""
    tr = DirTransport(str(tmp_path))
    store = spc.attach_store(transport=tr)
    spc.apply_events([("+",) + _absent_edge(spc)], batch_size=1)
    assert store.version == 1
    with ReplicaGroup(DirTransport(str(tmp_path)),
                      poll_interval_s=0.01) as group:
        group.wait_for_version(1, timeout=30.0)
        served = _arrays(group.store.current().index)
        # regress the pointer by hand -- the publish protocol itself
        # refuses to (PublisherBehindError), which is exactly why the
        # puller must treat an out-of-protocol regression as hostile
        with open(tmp_path / "LATEST", "w") as f:
            f.write("0")
        deadline = time.monotonic() + 30.0
        while group.stats()["skipped_behind"] == 0:
            assert time.monotonic() < deadline, "regression never seen"
            time.sleep(0.01)
        assert group.version == 1                    # never rolled back
        for k, arr in _arrays(group.store.current().index).items():
            np.testing.assert_array_equal(arr, served[k], err_msg=k)


def test_group_rejects_different_graph(spc):
    """A snapshot whose vertex count disagrees with what the replica
    already serves is a configuration error, not a version bump."""
    tr = LocalTransport()
    spc.attach_store(transport=tr)
    other = DynamicSPC(8, [(0, 1), (1, 2)], l_cap=8)
    with ReplicaGroup(tr, poll_interval_s=0.01) as group:
        assert group.version == 0
        tr.publish(Snapshot(1, other.index))  # foreign index, n=8 != 16
        deadline = time.monotonic() + 30.0
        while group.stats()["errors"] == 0:
            assert time.monotonic() < deadline, "mismatch never recorded"
            time.sleep(0.01)
        assert group.version == 0
        assert "different graph" in group.stats()["last_error"]


def test_restarted_publisher_reattach_and_behind(spc, tmp_path):
    """The two restart outcomes, end to end over one directory: a
    correctly-restored publisher re-attaches as a no-op and continues
    the version stream (pullers follow); one that rebuilt from scratch
    gets the typed PublisherBehindError at attach time."""
    from repro.serve.transport import load_snapshot

    store = spc.attach_store(transport=DirTransport(str(tmp_path)))
    spc.apply_events([("+",) + _absent_edge(spc)], batch_size=1)
    assert store.version == 1
    with ReplicaGroup(DirTransport(str(tmp_path)),
                      poll_interval_s=0.01) as group:
        group.wait_for_version(1, timeout=30.0)
        # -- updater "crashes"; a fresh one restores from the medium --
        snap = load_snapshot(str(tmp_path))
        store2 = SnapshotStore(snap.index, version=snap.version,
                               transport=DirTransport(str(tmp_path)))
        assert store2.version == 1  # idempotent re-attach, no error
        store2.publish(snap.index, version=2)  # stream continues
        group.wait_for_version(2, timeout=30.0)
        assert group.version == 2
        # -- and one that lost state must fail fast on the PUBLISHER --
        stale = DynamicSPC(N, random_graph_edges(N, M, seed=SEED),
                           l_cap=32)
        with pytest.raises(PublisherBehindError, match="restore"):
            stale.attach_store(transport=DirTransport(str(tmp_path)))


# -- role="replica" service -------------------------------------------------
def test_replica_service_oracle_differential(tmp_path):
    """The acceptance property: a replica service fed only through the
    directory answers every query exactly like BFS on the updater's
    current graph, across a mutation stream."""
    edges = random_graph_edges(N, M, seed=SEED)
    updater = SPCService(N, edges, l_cap=32, transport="dir",
                         publish_dir=str(tmp_path))
    replica = SPCService(role="replica", transport="dir",
                         publish_dir=str(tmp_path), poll_interval_s=0.01)
    rng = np.random.default_rng(3)
    with updater, replica:
        events = graph_stream(sorted(updater.spc._edge_set()), N, 8, 4,
                              seed=SEED + 2)
        for lo in range(0, len(events), 4):
            updater.submit(events[lo:lo + 4])
            updater.drain()
            replica.drain()  # catch up to the committed LATEST
            assert replica.version == updater.version
            truth = _oracle(N, updater.spc._edge_set())
            s = [int(x) for x in rng.integers(0, N, 24)]
            t = [int(x) for x in rng.integers(0, N, 24)]
            d, c = replica.query_batch(s, t)
            _assert_matches_oracle(truth, s, t, d, c)
        stats = replica.stats()
        assert stats["role"] == "replica"
        assert stats["update"] is None
        assert stats["replica"]["errors"] == 0
        assert updater.stats()["role"] == "updater"


def test_replica_service_is_read_only(tmp_path):
    updater = SPCService(N, random_graph_edges(N, M, seed=SEED),
                         l_cap=32, transport="dir",
                         publish_dir=str(tmp_path))
    with updater:
        updater.drain()
    replica = SPCService(role="replica", transport="dir",
                         publish_dir=str(tmp_path), poll_interval_s=0.01)
    with replica:
        with pytest.raises(ReplicaReadOnlyError, match="updater host"):
            replica.submit([("+", 0, 1)])
        with pytest.raises(ReplicaReadOnlyError):
            replica.spc
        with pytest.raises(ReplicaReadOnlyError):
            replica.state_dict()
        assert replica.replica_group is not None
        # a replica-local session never waits: its tickets are NO_TICKET
        sess = replica.session()
        assert sess.last_ticket == 0
        serve = replica.reader("read_your_writes", session=sess)
        d, c = serve([0], [1])
        assert d.shape == (1,)


def test_replica_service_at_version_waits_for_pull(tmp_path):
    updater = SPCService(N, random_graph_edges(N, M, seed=SEED),
                         l_cap=32, transport="dir",
                         publish_dir=str(tmp_path))
    replica = SPCService(role="replica", transport="dir",
                         publish_dir=str(tmp_path), poll_interval_s=0.01)
    with updater, replica:
        serve = replica.reader(at_version=1, timeout=30.0)
        done = []

        def reader_thread():
            d, c = serve([0, 1], [2, 3])
            done.append(serve.last_version)

        th = threading.Thread(target=reader_thread)
        th.start()
        time.sleep(0.1)
        assert not done  # parked: version 1 not published yet
        updater.submit([("+",) + _absent_edge(updater.spc)])
        updater.drain()
        th.join(timeout=30.0)
        assert done and done[0] >= 1


def test_replica_service_frontdoor(tmp_path):
    updater = SPCService(N, random_graph_edges(N, M, seed=SEED),
                         l_cap=32, transport="dir",
                         publish_dir=str(tmp_path))
    with updater:
        updater.drain()
        truth = _oracle(N, updater.spc._edge_set())
    replica = SPCService(role="replica", transport="dir",
                         publish_dir=str(tmp_path), poll_interval_s=0.01)
    with replica:
        door = replica.frontdoor(max_batch=8, dispatchers=1)
        with door:
            sess = door.session()
            for (s, t) in [(0, 5), (3, 3), (1, 14)]:
                d, c = sess.query(s, t)
                _assert_matches_oracle(truth, [s], [t], [d], [c])


def test_replica_service_rejects_updater_args(tmp_path):
    with pytest.raises(ValueError, match="owns no updater"):
        SPCService(N, [(0, 1)], role="replica",
                   publish_dir=str(tmp_path))
    with pytest.raises(ValueError, match="publication medium"):
        SPCService(role="replica")
    with pytest.raises(ValueError, match="checkpoint_dir"):
        SPCService(role="replica", publish_dir=str(tmp_path),
                   checkpoint_dir=str(tmp_path))
    with pytest.raises(ValueError, match="unknown role"):
        SPCService(N, [(0, 1)], role="observer")
    with pytest.raises(ValueError, match="one or the other"):
        SPCService(N, [(0, 1)], publish_dir=str(tmp_path),
                   checkpoint_dir=str(tmp_path))
