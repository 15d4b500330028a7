"""Snapshot transports: the pluggable publication medium of the fleet.

PR 4's ``SnapshotStore`` fused two jobs: versioning snapshots (the
double-buffered swap readers pin against) and *moving* them -- the
optional publish -> checkpoint durability hook.  That coupling capped
the system at one process: the swap is an in-memory pointer write, so
an updater crash took every reader down with it, and reads could not
scale past the updater's host.  This module splits the second job out
behind one small protocol so the store stays a pure in-process
double buffer and the *medium* becomes a deployment choice:

===============  ==========================================  ==========
transport        medium                                      scope
===============  ==========================================  ==========
LocalTransport   in-process reference + notify condition     1 process
DirTransport     committed ``step_*`` dirs + ``LATEST``      N processes
                 pointer (``repro.train.checkpoint``'s        / hosts on
                 tmp + ``os.replace`` protocol)               a shared
                                                              filesystem
SocketTransport  DirTransport payload + a thin TCP notify    N hosts,
                 channel (publisher broadcasts version        low-latency
                 bumps; pullers block on the socket            refresh
                 instead of sleeping out a poll interval)
===============  ==========================================  ==========

This is saxml's primary-host pattern
(``ServableModelState.is_primary_host``): exactly one host *publishes*
each version, replica groups pull, verify, and swap locally
(``repro.serve.replica.ReplicaGroup``).  Version monotonicity is the
whole safety argument, and it is enforced at BOTH ends:

* **Publisher side.**  A restarted updater that lost state (rebuilt
  behind the fleet's committed ``LATEST``) must not roll replicas back
  -- :meth:`DirTransport.publish` raises the typed
  :class:`PublisherBehindError` when asked to commit a version at or
  below a DIFFERENT committed one, so the operator restores from the
  published snapshot instead of silently regressing the fleet.
  Re-publishing exactly the committed payload version (the
  correctly-restored updater's attach) is an idempotent no-op.
* **Puller side.**  ``ReplicaGroup`` only stages versions strictly
  above its local one; a remote pointer *behind* the replica (the same
  restart race, seen from the other end) is skipped and counted, never
  applied -- the replica keeps serving its newer version.

Cross-process readers race the publisher's retention gc; the checkpoint
layer turns a vanished ``step_*`` dir into a typed
``SnapshotGoneError`` and :func:`load_snapshot` retries against the new
``LATEST`` a bounded number of times before giving up.
"""

from __future__ import annotations

import dataclasses
import os
import socket
import threading
import time
from typing import Optional, Protocol, runtime_checkable

import jax.numpy as jnp
import numpy as np

from repro.analysis.shadow import make_condition
from repro.core.labels import SPCIndex
from repro.train import checkpoint as C

#: Bounded attempts of a fetch that keeps losing the gc race (each
#: retry re-reads ``LATEST``; the publisher commits strictly forward,
#: so two consecutive losses already mean gc is outrunning the reader).
FETCH_RETRIES = 4

#: Name of the notify-endpoint file ``SocketTransport`` publishers drop
#: next to ``LATEST`` so pullers need no out-of-band address exchange.
NOTIFY_FILE = "NOTIFY"


class TransportError(RuntimeError):
    """Base class of typed transport failures."""


class PublisherBehindError(TransportError):
    """A (restarted) publisher asked to commit a version at or below a
    different already-committed one -- accepting it would roll every
    puller-fed replica back.  Restore the updater from the published
    snapshot (``load_snapshot``) instead."""

    def __init__(self, version: int, committed: int, where: str) -> None:
        self.version = version
        self.committed = committed
        super().__init__(
            f"publisher is behind the committed publication stream at "
            f"{where}: asked to publish version {version} but version "
            f"{committed} is already committed; a restarted updater "
            f"must restore from the published snapshot, not re-publish "
            f"history")


@dataclasses.dataclass(frozen=True)
class Snapshot:
    """One immutable published (version, index) pair.

    Holding a ``Snapshot`` IS the pin: the store never mutates published
    objects, so a batch evaluated against ``snap.index`` is unaffected
    by any number of concurrent publishes.
    """

    version: int
    index: SPCIndex


def snapshot_tree(snap: Snapshot) -> dict:
    """Flat host-array dict of a snapshot (the checkpoint payload).

    Dict pytrees flatten in sorted-key order, which is what lets
    :func:`load_snapshot` rebuild a ``tree_like`` from the manifest's
    positional shapes/dtypes.
    """
    idx = snap.index
    return {
        "index.hub": np.asarray(idx.hub),
        "index.dist": np.asarray(idx.dist),
        "index.cnt": np.asarray(idx.cnt),
        "index.size": np.asarray(idx.size),
        "version": np.int64(snap.version),
    }


_SNAPSHOT_KEYS = sorted(("index.hub", "index.dist", "index.cnt",
                         "index.size", "version"))


def _load_snapshot_once(path: str, step: int | None) -> Snapshot:
    man = C.manifest(path, step)
    if len(man["shapes"]) != len(_SNAPSHOT_KEYS):
        raise ValueError(
            f"checkpoint at {path} has {len(man['shapes'])} leaves, "
            f"want {len(_SNAPSHOT_KEYS)} (not a snapshot checkpoint?)")
    tree_like = {
        k: np.empty(shape, dtype=np.dtype(dt))
        for k, shape, dt in zip(_SNAPSHOT_KEYS, man["shapes"],
                                man["dtypes"])
    }
    tree, got_step, meta = C.restore(path, tree_like, step=man["step"])
    n = int(meta["n"])
    version = int(tree["version"])
    # manifest <-> payload verification BEFORE the snapshot is staged
    # anywhere a reader could pin it: a mismatch means the dir was
    # assembled by something other than the atomic publish protocol
    if version != got_step or int(meta.get("version", version)) != version:
        raise C.CheckpointCorruptError(
            path, got_step,
            f"payload version {version} does not match committed step "
            f"{got_step} / manifest version {meta.get('version')}")
    rows = np.asarray(tree["index.size"]).shape[0]
    if rows != n + 1:
        raise C.CheckpointCorruptError(
            path, got_step, f"size has {rows} rows for manifest n={n}")
    idx = SPCIndex(
        hub=jnp.asarray(tree["index.hub"]),
        dist=jnp.asarray(tree["index.dist"]),
        cnt=jnp.asarray(tree["index.cnt"]),
        size=jnp.asarray(tree["index.size"]),
        overflow=jnp.int32(0), n=n)
    return Snapshot(version=version, index=idx)


def load_snapshot(path: str, step: int | None = None,
                  retries: int = FETCH_RETRIES) -> Snapshot:
    """Restore a published snapshot from a publication directory
    (default: the latest committed version).

    Shapes come from the committed manifest
    (``repro.train.checkpoint.manifest``), so no ``tree_like`` template
    is needed; the version counter is restored from the payload itself
    and cross-checked against the committed step.

    A reader racing the publisher's retention gc can lose its step dir
    between the ``LATEST`` read and the payload read; each such loss
    retries against the *new* ``LATEST`` (``retries`` bounded).  An
    explicitly requested ``step=`` is never silently substituted: its
    loss raises ``SnapshotGoneError`` naming the step immediately.
    """
    attempts = max(1, int(retries))
    for attempt in range(attempts):
        try:
            return _load_snapshot_once(path, step)
        except C.SnapshotGoneError:
            if step is not None or attempt == attempts - 1:
                raise
            # LATEST moved on while we were reading; take the new one
    raise AssertionError("unreachable")  # pragma: no cover


@runtime_checkable
class SnapshotTransport(Protocol):
    """The publication medium between ONE publisher and N pullers.

    Publisher side (exactly one process calls these):

    ``publish(snapshot)``
        Commit the snapshot to the medium (atomically: pullers see
        either the previous version or this one, never a torn payload)
        and notify subscribers.  Must raise
        :class:`PublisherBehindError` when ``snapshot.version`` is at
        or below a different already-committed version, and be an
        idempotent no-op when it *equals* the committed payload.
    ``wait()``
        Settle any in-flight asynchronous commit (re-raising its
        failure); called on drain/close.

    Puller side (any number of processes):

    ``poll() -> int | None``
        The committed version (None while nothing is committed).
        Cheap: called once per poll interval per puller.
    ``fetch(version=None) -> Snapshot``
        Materialize the committed snapshot (default: latest).  Verifies
        version/manifest consistency before returning; typed errors on
        gone (``SnapshotGoneError``) / corrupt payloads.
    ``wait_notify(timeout) -> bool``
        Block up to ``timeout`` seconds for a publish notification;
        True if one (probably) arrived.  Polling transports just sleep.

    ``close()`` releases sockets/threads on either side.
    """

    def publish(self, snapshot: Snapshot) -> None: ...

    def wait(self) -> None: ...

    def poll(self) -> int | None: ...

    def fetch(self, version: int | None = None) -> Snapshot: ...

    def wait_notify(self, timeout: float) -> bool: ...

    def close(self) -> None: ...


class LocalTransport:
    """Today's in-process behavior as a transport (the default).

    The medium is one reference slot guarded by a condition: publish
    stores the snapshot and notifies, pullers in the same process wake
    immediately.  Useful on its own for single-process replica groups
    (tests, benchmarks) and as the null object the refactored
    ``SnapshotStore`` plugs in when no cross-process medium is wanted.
    """

    def __init__(self) -> None:
        self._cond = make_condition("transport.cond")
        self._snap: Optional[Snapshot] = None

    def publish(self, snapshot: Snapshot) -> None:
        with self._cond:
            committed = self._snap
            if committed is not None and \
                    snapshot.version < committed.version:
                raise PublisherBehindError(
                    snapshot.version, committed.version, "LocalTransport")
            if committed is not None and \
                    snapshot.version == committed.version:
                return  # idempotent re-publish of the committed version
            self._snap = snapshot
            self._cond.notify_all()

    def wait(self) -> None:  # synchronous medium: nothing in flight
        return

    def poll(self) -> int | None:
        with self._cond:
            return None if self._snap is None else self._snap.version

    def fetch(self, version: int | None = None) -> Snapshot:
        with self._cond:
            snap = self._snap
        if snap is None:
            raise FileNotFoundError(
                "LocalTransport holds no published snapshot")
        if version is not None and snap.version != version:
            raise C.SnapshotGoneError(
                "<local>", version,
                f"committed version is {snap.version}")
        return snap

    def wait_notify(self, timeout: float) -> bool:
        with self._cond:
            start = self._snap.version if self._snap is not None else None
            self._cond.wait(timeout)
            now = self._snap.version if self._snap is not None else None
        return now != start

    def close(self) -> None:
        return


class DirTransport:
    """Committed ``step_*`` dirs + ``LATEST`` pointer: the cross-process
    medium, over ``repro.train.checkpoint``'s tmp + ``os.replace``
    protocol.  Any number of puller processes on the same (shared)
    filesystem follow one publisher.

    ``keep=`` bounds the publisher's retention window; gc never deletes
    the step ``LATEST`` names, and pullers that lose the race on older
    steps retry against the new pointer (:func:`load_snapshot`).
    ``async_save=True`` moves serialization off the publish path onto
    the checkpoint layer's saver thread (failures re-raised on the next
    publish/wait).
    """

    def __init__(self, path: str, *, keep: int = 3,
                 async_save: bool = False) -> None:
        if not path:
            raise ValueError("DirTransport needs a publication directory")
        self.path = str(path)
        self._keep = int(keep)
        self._saver = C.AsyncSaver() if async_save else None

    # -- publisher side -----------------------------------------------------
    def publish(self, snapshot: Snapshot) -> None:
        committed = C.latest_step(self.path)
        if committed is not None:
            if snapshot.version < committed:
                raise PublisherBehindError(
                    snapshot.version, committed, self.path)
            if snapshot.version == committed:
                return  # correctly-restored updater re-attaching: no-op
        tree = snapshot_tree(snapshot)
        meta = {"n": snapshot.index.n, "l_cap": snapshot.index.l_cap,
                "version": snapshot.version}
        if self._saver is not None:
            self._saver.save(self.path, snapshot.version, tree, meta)
        else:
            C.save(self.path, snapshot.version, tree, meta)
        # only committed step_* dirs are touched; an in-flight async
        # write lives in a .tmp dir and is invisible to gc, and the
        # LATEST-pinned step survives regardless of the keep window
        C.gc_old(self.path, keep=self._keep)

    def wait(self) -> None:
        if self._saver is not None:
            self._saver.wait()

    # -- puller side --------------------------------------------------------
    def poll(self) -> int | None:
        return C.latest_step(self.path)

    def fetch(self, version: int | None = None) -> Snapshot:
        return load_snapshot(self.path, step=version)

    def wait_notify(self, timeout: float) -> bool:
        time.sleep(max(0.0, timeout))  # pure polling medium
        return False

    def close(self) -> None:
        self.wait()


class SocketTransport:
    """``DirTransport`` payload + a thin TCP notify channel.

    The publisher binds an ephemeral TCP port, drops its address in
    ``<dir>/NOTIFY`` (no out-of-band exchange), and broadcasts one
    ``<version>\\n`` line per publish; pullers connect lazily and block
    on the socket in :meth:`wait_notify` instead of sleeping out a poll
    interval -- refresh latency becomes network latency instead of
    ``poll_interval_s``.  The socket is ONLY a doorbell: versions and
    payloads are still read from the committed directory, so a dropped
    connection degrades to polling, never to wrong data.
    """

    def __init__(self, path: str, *, keep: int = 3,
                 async_save: bool = False, host: str = "127.0.0.1") -> None:
        self._dir = DirTransport(path, keep=keep, async_save=async_save)
        self.path = self._dir.path
        self._host = host
        self._cond = make_condition("transport.cond")
        self._server: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._clients: list = []
        self._conn: Optional[socket.socket] = None
        self._closed = False

    # -- publisher side -----------------------------------------------------
    def _ensure_server(self) -> None:
        with self._cond:
            if self._server is not None or self._closed:
                return
            srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            srv.bind((self._host, 0))
            srv.listen(16)
            self._server = srv
            self._accept_thread = threading.Thread(
                target=self._accept_loop, name="snapshot-notify-accept",
                daemon=True)
            self._accept_thread.start()
        host, port = srv.getsockname()
        os.makedirs(self.path, exist_ok=True)
        tmp = os.path.join(self.path, NOTIFY_FILE + ".tmp")
        with open(tmp, "w") as f:
            f.write(f"{host}:{port}")
        os.replace(tmp, os.path.join(self.path, NOTIFY_FILE))

    def _accept_loop(self) -> None:
        # set under the cond before this thread starts; never reassigned
        # while it runs (close() swaps it out, which aborts accept())
        srv = self._server  # analysis: ignore[unlocked-attr]
        while True:
            try:
                conn, _ = srv.accept()
            except OSError:
                return  # server closed
            with self._cond:
                if self._closed:
                    conn.close()
                    return
                self._clients.append(conn)

    def publish(self, snapshot: Snapshot) -> None:
        self._ensure_server()
        self._dir.publish(snapshot)
        line = f"{snapshot.version}\n".encode()
        with self._cond:
            clients = list(self._clients)
        dead = []
        for conn in clients:
            try:
                conn.sendall(line)
            except OSError:
                dead.append(conn)
        if dead:
            with self._cond:
                for conn in dead:
                    if conn in self._clients:
                        self._clients.remove(conn)
            for conn in dead:
                conn.close()

    def wait(self) -> None:
        self._dir.wait()

    # -- puller side --------------------------------------------------------
    def _connect(self) -> Optional[socket.socket]:
        with self._cond:
            if self._conn is not None or self._closed:
                return self._conn
        ep = os.path.join(self.path, NOTIFY_FILE)
        try:
            with open(ep) as f:
                host, port = f.read().strip().rsplit(":", 1)
            conn = socket.create_connection((host, int(port)), timeout=1.0)
        except (OSError, ValueError):
            return None  # no publisher up yet: degrade to polling
        with self._cond:
            if self._closed:
                conn.close()
                return None
            self._conn = conn
        return conn

    def poll(self) -> int | None:
        return self._dir.poll()

    def fetch(self, version: int | None = None) -> Snapshot:
        return self._dir.fetch(version)

    def wait_notify(self, timeout: float) -> bool:
        conn = self._connect()
        if conn is None:
            time.sleep(max(0.0, timeout))
            return False
        conn.settimeout(max(0.01, timeout))
        try:
            data = conn.recv(64)
        except socket.timeout:
            return False
        except OSError:
            data = b""
        if not data:  # publisher went away: reconnect on the next wait
            with self._cond:
                if self._conn is conn:
                    self._conn = None
            conn.close()
            # the restarted publisher commits to the same directory, so
            # the poll fallback still observes it
            return False
        return True

    def close(self) -> None:
        with self._cond:
            self._closed = True
            server, self._server = self._server, None
            conn, self._conn = self._conn, None
            clients, self._clients = list(self._clients), []
        for sock in [server, conn, *clients]:
            if sock is not None:
                try:
                    sock.close()
                except OSError:  # pragma: no cover - teardown best-effort
                    pass
        self._dir.close()


#: Transport spec names accepted by :func:`make_transport` (and the
#: ``transport=`` config knob).
TRANSPORTS = ("local", "dir", "socket")


def make_transport(spec, *, publish_dir: str | None = None,
                   keep: int = 3, async_save: bool = False):
    """Build a transport from a config spec: an instance passes
    through; ``"local"`` / ``"dir"`` / ``"socket"`` construct one
    (the latter two need ``publish_dir=``)."""
    if spec is None:
        spec = "local"
    if not isinstance(spec, str):
        return spec  # an already-built transport object
    if spec not in TRANSPORTS:
        raise ValueError(
            f"unknown transport {spec!r}; want one of {TRANSPORTS} "
            f"(or a SnapshotTransport instance)")
    if spec == "local":
        return LocalTransport()
    if publish_dir is None:
        raise ValueError(
            f"transport {spec!r} publishes through a directory; pass "
            f"publish_dir=")
    if spec == "dir":
        return DirTransport(publish_dir, keep=keep, async_save=async_save)
    return SocketTransport(publish_dir, keep=keep, async_save=async_save)
