"""Snapshot transports: the pluggable publication medium.  Publisher-
side monotonicity (typed ``PublisherBehindError`` on a restarted
updater, idempotent re-publish of the committed version), DirTransport
round trips over the committed checkpoint protocol, gc-race retries,
payload <-> manifest verification, the socket doorbell, and the
``make_transport`` config coercions."""

import os
import threading
import time

import numpy as np
import pytest

from repro.core.dynamic import DynamicSPC
from repro.data import graph_stream, random_graph_edges
from repro.serve.transport import (FETCH_RETRIES, NOTIFY_FILE, TRANSPORTS,
                                   DirTransport, LocalTransport,
                                   PublisherBehindError, Snapshot,
                                   SnapshotTransport, SocketTransport,
                                   TransportError, load_snapshot,
                                   make_transport, snapshot_tree)
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


def _versions(spc, count):
    """``count`` distinct (version, index) states from a mutation
    stream: snapshots[k] is the index after k committed chunks."""
    snaps = [Snapshot(0, spc.index)]
    events = graph_stream(sorted(spc._edge_set()), spc.n,
                          2 * count, count, seed=SEED + 1)
    for k in range(1, count):
        spc.apply_events(events[3 * (k - 1):3 * k], batch_size=3)
        snaps.append(Snapshot(k, spc.index))
    return snaps


# -- LocalTransport ---------------------------------------------------------
def test_local_transport_round_trip(spc):
    tr = LocalTransport()
    assert tr.poll() is None
    with pytest.raises(FileNotFoundError):
        tr.fetch()
    snaps = _versions(spc, 3)
    for snap in snaps:
        tr.publish(snap)
        assert tr.poll() == snap.version
    got = tr.fetch()
    assert got.version == 2
    _assert_index_equal(got.index, snaps[-1].index)
    # an explicitly requested older version is gone on this medium
    with pytest.raises(C.SnapshotGoneError):
        tr.fetch(0)


def test_local_transport_behind_and_idempotent(spc):
    tr = LocalTransport()
    snaps = _versions(spc, 3)
    tr.publish(snaps[2])
    with pytest.raises(PublisherBehindError) as ei:
        tr.publish(snaps[1])
    assert (ei.value.version, ei.value.committed) == (1, 2)
    assert isinstance(ei.value, TransportError)
    tr.publish(snaps[2])  # re-publish of the committed version: no-op
    assert tr.poll() == 2


def test_local_transport_notify_wakes_waiter(spc):
    tr = LocalTransport()
    tr.publish(Snapshot(0, spc.index))
    woke = []

    def waiter():
        woke.append(tr.wait_notify(5.0))

    th = threading.Thread(target=waiter)
    th.start()
    time.sleep(0.05)
    tr.publish(Snapshot(1, spc.index))
    th.join(timeout=5.0)
    assert woke == [True]
    assert tr.wait_notify(0.01) is False  # nothing new: times out


# -- DirTransport -----------------------------------------------------------
def test_dir_transport_round_trip(spc, tmp_path):
    tr = DirTransport(str(tmp_path))
    assert tr.poll() is None
    snaps = _versions(spc, 3)
    for snap in snaps:
        tr.publish(snap)
    assert tr.poll() == 2
    got = tr.fetch()
    assert got.version == 2
    _assert_index_equal(got.index, snaps[-1].index)
    older = tr.fetch(1)  # inside the keep=3 retention window
    _assert_index_equal(older.index, snaps[1].index)


def test_dir_transport_retention_pins_latest(spc, tmp_path):
    tr = DirTransport(str(tmp_path), keep=1)
    for snap in _versions(spc, 4):
        tr.publish(snap)
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path)
                   if d.startswith("step_"))
    assert steps == [3]  # keep=1 retains exactly the LATEST-pinned step
    assert tr.fetch().version == 3


def test_dir_transport_publisher_behind(spc, tmp_path):
    snaps = _versions(spc, 3)
    DirTransport(str(tmp_path)).publish(snaps[2])
    # a restarted updater that rebuilt from scratch comes back behind
    # the committed stream: typed error, nothing committed
    fresh = DirTransport(str(tmp_path))
    with pytest.raises(PublisherBehindError, match="restore from the"):
        fresh.publish(snaps[1])
    assert C.latest_step(str(tmp_path)) == 2
    # a correctly-restored updater re-publishing the committed version
    # is an idempotent no-op (same pointer, payload untouched)
    before = os.path.getmtime(tmp_path / "step_000000002" / "arrays.npz")
    fresh.publish(snaps[2])
    assert C.latest_step(str(tmp_path)) == 2
    assert os.path.getmtime(
        tmp_path / "step_000000002" / "arrays.npz") == before


def test_dir_transport_async_save(spc, tmp_path):
    tr = DirTransport(str(tmp_path), async_save=True)
    snaps = _versions(spc, 2)
    for snap in snaps:
        tr.publish(snap)
    tr.wait()
    _assert_index_equal(tr.fetch().index, snaps[-1].index)
    tr.close()


# -- load_snapshot: gc races + verification ---------------------------------
def test_load_snapshot_retries_against_new_latest(spc, tmp_path,
                                                  monkeypatch):
    tr = DirTransport(str(tmp_path))
    snaps = _versions(spc, 2)
    for snap in snaps:
        tr.publish(snap)
    real = C.manifest
    calls = []

    def racing_manifest(path, step=None):
        calls.append(step)
        if len(calls) == 1:  # the step vanished under the first read
            raise C.SnapshotGoneError(path, 0, "gc race (test)")
        return real(path, step)

    monkeypatch.setattr(C, "manifest", racing_manifest)
    got = load_snapshot(str(tmp_path))
    assert got.version == 1 and len(calls) == 2


def test_load_snapshot_explicit_step_never_substituted(spc, tmp_path,
                                                       monkeypatch):
    tr = DirTransport(str(tmp_path))
    for snap in _versions(spc, 2):
        tr.publish(snap)
    calls = []
    real = C.manifest

    def counting_manifest(path, step=None):
        calls.append(step)
        return real(path, step)

    monkeypatch.setattr(C, "manifest", counting_manifest)
    with pytest.raises(C.SnapshotGoneError) as ei:
        load_snapshot(str(tmp_path), step=7)
    assert ei.value.step == 7
    assert len(calls) == 1  # no retry: an explicit step is the contract
    assert 1 <= FETCH_RETRIES


def test_load_snapshot_rejects_foreign_checkpoint(tmp_path):
    C.save(str(tmp_path), 0, {"weights": np.zeros(4)})
    with pytest.raises(ValueError, match="not a snapshot checkpoint"):
        load_snapshot(str(tmp_path))


def test_load_snapshot_rejects_version_step_mismatch(spc, tmp_path):
    """A dir assembled outside the publish protocol (payload version 0
    committed as step 5) must fail verification, not serve as v5."""
    tree = snapshot_tree(Snapshot(0, spc.index))
    C.save(str(tmp_path), 5, tree,
           {"n": spc.n, "l_cap": spc.index.l_cap, "version": 5})
    with pytest.raises(C.CheckpointCorruptError, match="does not match"):
        load_snapshot(str(tmp_path))


def test_load_snapshot_rejects_row_count_mismatch(spc, tmp_path):
    """A payload whose ``index.size`` rows disagree with the manifest's
    n (a foreign graph's index under this one's metadata) must fail
    verification before any reader could pin it."""
    tree = snapshot_tree(Snapshot(3, spc.index))
    C.save(str(tmp_path), 3, tree,
           {"n": spc.n + 1, "l_cap": spc.index.l_cap, "version": 3})
    with pytest.raises(C.CheckpointCorruptError, match="rows for manifest"):
        load_snapshot(str(tmp_path))


# -- SocketTransport --------------------------------------------------------
def test_socket_transport_notify_and_payload(spc, tmp_path):
    pub = SocketTransport(str(tmp_path))
    sub = SocketTransport(str(tmp_path))
    snaps = _versions(spc, 2)
    try:
        pub.publish(snaps[0])
        assert os.path.exists(tmp_path / NOTIFY_FILE)
        assert sub.poll() == 0

        stop = threading.Event()

        def republisher():
            # re-broadcasts of the committed version are payload no-ops
            # but still ring the doorbell, so the subscriber cannot
            # miss the edge no matter when its connection lands
            while not stop.is_set():
                pub.publish(snaps[1])
                time.sleep(0.02)

        th = threading.Thread(target=republisher, daemon=True)
        th.start()
        try:
            deadline = time.monotonic() + 10.0
            notified = False
            while not notified and time.monotonic() < deadline:
                notified = sub.wait_notify(0.5)
            assert notified, "doorbell never rang"
        finally:
            stop.set()
            th.join(timeout=5.0)
        assert sub.poll() == 1
        _assert_index_equal(sub.fetch().index, snaps[1].index)
    finally:
        sub.close()
        pub.close()


def test_socket_transport_degrades_to_polling(tmp_path):
    sub = SocketTransport(str(tmp_path))  # no publisher, no NOTIFY file
    try:
        t0 = time.monotonic()
        assert sub.wait_notify(0.05) is False
        assert time.monotonic() - t0 >= 0.04  # slept the poll interval
        assert sub.poll() is None
    finally:
        sub.close()


# -- make_transport ---------------------------------------------------------
def test_make_transport_coercions(tmp_path):
    assert isinstance(make_transport(None), LocalTransport)
    assert isinstance(make_transport("local"), LocalTransport)
    tr = make_transport("dir", publish_dir=str(tmp_path), keep=5)
    assert isinstance(tr, DirTransport) and tr._keep == 5
    sock = make_transport("socket", publish_dir=str(tmp_path))
    assert isinstance(sock, SocketTransport)
    sock.close()
    passthrough = LocalTransport()
    assert make_transport(passthrough) is passthrough
    with pytest.raises(ValueError, match="publish_dir"):
        make_transport("dir")
    with pytest.raises(ValueError, match="unknown transport"):
        make_transport("carrier-pigeon")
    for name in TRANSPORTS:
        assert isinstance(name, str)


def test_transports_satisfy_protocol(tmp_path):
    for tr in (LocalTransport(), DirTransport(str(tmp_path))):
        assert isinstance(tr, SnapshotTransport)
