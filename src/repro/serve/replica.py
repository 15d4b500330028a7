"""Puller-fed serving replicas: the remote end of the publication pipe.

One updater host publishes versioned snapshots through a
``repro.serve.transport.SnapshotTransport``; a :class:`ReplicaGroup` on
each serving host runs one puller thread per source transport that

1. **polls / subscribes** -- ``wait_notify`` blocks on the medium's
   doorbell (condition, socket) or sleeps out ``poll_interval_s`` on
   pure-polling media;
2. **verifies before staging** -- the fetch path cross-checks the
   committed manifest against the payload (leaf count, version == step,
   ``size`` row count) and the group rejects a snapshot whose vertex
   count disagrees with what it already serves, so a torn or foreign
   payload never reaches a reader;
3. **swaps locally** -- the verified snapshot is published into the
   group's own ``SnapshotStore``, giving local readers the exact PR 4
   pin-per-batch contract with zero new read-path machinery;
4. **keeps serving through updater crashes** -- every puller failure
   (medium unreachable, snapshot gc'd faster than it could be read,
   corrupt payload) is *recorded* and retried, never propagated to
   readers: the last good version keeps answering, which is the whole
   fleet story (saxml's primary-host pattern: replicas outlive the
   publisher);
5. **re-attaches to a restarted updater** -- version monotonicity makes
   the handoff safe: a correctly-restored updater resumes the version
   stream and pullers simply continue, while a restarted updater that
   came back *behind* the fleet is skipped and counted
   (``skipped_behind``) on this end -- the typed
   ``PublisherBehindError`` belongs on the *publisher*, where the
   operator can act on it.

Thread contract: puller threads touch only their own bookkeeping under
``replica.lock`` and never hold it across a fetch or a local publish
(staging asserts no locks held across the JAX dispatch); readers go
through ``group.store`` exactly as they would on the updater host.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

from repro.analysis.shadow import make_lock
from repro.serve.publish import SnapshotStore
from repro.serve.transport import SnapshotTransport

_log = logging.getLogger(__name__)


class ReplicaGroup:
    """A local ``SnapshotStore`` continuously fed by puller threads.

    ``transports`` are the remote publication media to follow (one
    puller thread each; the store's monotone version makes multiple
    sources safe -- whichever pulls a newer version first wins, the
    rest skip).  ``poll_interval_s`` bounds staleness on pure-polling
    media and is the doorbell wait on subscribing ones; ``mesh=``
    stages every pulled snapshot replicated over the serving mesh.

    Lifecycle: :meth:`start` blocks (bounded) until the first snapshot
    is pulled -- a started group is serving-ready -- then keeps pulling
    in the background until :meth:`close`.
    """

    def __init__(self, *transports: SnapshotTransport,
                 poll_interval_s: float = 0.05, mesh=None) -> None:
        if not transports:
            raise ValueError("ReplicaGroup needs at least one transport")
        if poll_interval_s <= 0:
            raise ValueError(
                f"poll_interval_s must be > 0, got {poll_interval_s!r}")
        self._transports = tuple(transports)
        self.poll_interval_s = float(poll_interval_s)
        self._store = SnapshotStore(mesh=mesh)
        self._lock = make_lock("replica.lock")
        self._stop = threading.Event()
        self._threads: list = []
        self._started = False
        self._closed = False
        # -- bookkeeping (under replica.lock) ---------------------------
        self._pulls = 0            # snapshots staged + swapped locally
        self._skipped_behind = 0   # remote versions <= local (restart race)
        self._errors = 0           # failed pull attempts (retried)
        self._last_error: Optional[BaseException] = None

    # -- reader side ---------------------------------------------------------
    @property
    def store(self) -> SnapshotStore:
        """The local store readers pin batches against (the PR 4
        contract, unchanged on a replica host)."""
        return self._store

    @property
    def version(self) -> int | None:
        """Version currently served locally (None before the first
        pull)."""
        return self._store.version

    def stats(self) -> dict:
        """Frozen view of the puller bookkeeping."""
        with self._lock:
            return {
                "version": self._store.version,
                "pulls": self._pulls,
                "skipped_behind": self._skipped_behind,
                "errors": self._errors,
                "last_error": (None if self._last_error is None
                               else repr(self._last_error)),
                "sources": len(self._transports),
            }

    # -- puller side ---------------------------------------------------------
    def _record(self, *, pulls: int = 0, skipped: int = 0,
                error: BaseException | None = None) -> None:
        with self._lock:
            self._pulls += pulls
            self._skipped_behind += skipped
            if error is not None:
                self._errors += 1
                self._last_error = error

    def _pull_once(self, transport: SnapshotTransport) -> bool:
        """One poll -> verify -> stage -> swap attempt; True if a new
        version went live locally."""
        remote = transport.poll()
        local = self._store.version
        if remote is None:
            return False
        if local is not None and remote <= local:
            if remote < local:
                # the remote pointer is BEHIND this replica: a restarted
                # updater that lost state.  Never applied -- the typed
                # PublisherBehindError fires on the publisher; here we
                # keep serving our newer version and count the sighting.
                self._record(skipped=1)
            return False
        snap = transport.fetch(remote)  # verifies manifest <-> payload
        current = None if local is None else self._store.current()
        if current is not None and snap.index.n != current.index.n:
            raise ValueError(
                f"pulled snapshot v{snap.version} has n={snap.index.n} "
                f"but this replica serves n={current.index.n}; refusing "
                f"to stage a different graph's index")
        try:
            # local swap: readers refresh on their next batch pin
            self._store.publish(snap.index, version=snap.version)
        except ValueError:
            # another puller of this group won the race to an equal or
            # newer version while we fetched; their snapshot serves
            self._record(skipped=1)
            return False
        self._record(pulls=1)
        return True

    def _run(self, transport: SnapshotTransport) -> None:
        while not self._stop.is_set():
            try:
                advanced = self._pull_once(transport)
            except BaseException as e:
                # a failed pull NEVER stops serving: record, back off,
                # retry -- the last good version keeps answering
                self._record(error=e)
                _log.warning("replica pull failed (still serving v%s): %r",
                             self._store.version, e)
                advanced = False
            if not advanced and not self._stop.is_set():
                transport.wait_notify(self.poll_interval_s)

    # -- lifecycle -----------------------------------------------------------
    def start(self, timeout: float | None = 60.0) -> "ReplicaGroup":
        """Pull the first snapshot (blocking, bounded by ``timeout``;
        ``None`` waits forever) and launch the puller threads.  A
        started group is serving-ready: ``store.current()`` answers.
        Idempotent."""
        if self._closed:
            raise RuntimeError("replica group is closed")
        if self._started:
            return self
        deadline = (None if timeout is None
                    else time.monotonic() + float(timeout))
        while self._store.version is None:
            for transport in self._transports:
                try:
                    if self._pull_once(transport):
                        break
                except BaseException as e:
                    self._record(error=e)
            if self._store.version is not None:
                break
            if deadline is not None and time.monotonic() >= deadline:
                raise TimeoutError(
                    f"no published snapshot appeared on any of "
                    f"{len(self._transports)} transport(s) within "
                    f"{timeout:.1f}s; is the updater up and publishing?")
            self._transports[0].wait_notify(
                min(self.poll_interval_s, 0.05))
        self._threads = [
            threading.Thread(target=self._run, args=(transport,),
                             name=f"snapshot-puller-{i}", daemon=True)
            for i, transport in enumerate(self._transports)]
        for th in self._threads:
            th.start()
        self._started = True
        return self

    def __enter__(self) -> "ReplicaGroup":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    def wait_for_version(self, version: int,
                         timeout: float | None = 60.0) -> None:
        """Block until the locally served version reaches ``version``
        (the replica-side ``at_version`` wait)."""
        deadline = (None if timeout is None
                    else time.monotonic() + float(timeout))
        while True:
            local = self._store.version
            if local is not None and local >= version:
                return
            if deadline is not None and time.monotonic() >= deadline:
                raise TimeoutError(
                    f"replica still at version {local} after "
                    f"{timeout:.1f}s waiting for version {version}")
            time.sleep(min(self.poll_interval_s, 0.02))

    def catch_up(self, timeout: float | None = 60.0) -> None:
        """Block until the locally served version covers every source's
        *currently* committed version (the replica-side ``drain``):
        useful before measuring staleness or tearing down a test
        topology.  Sources that are unreachable right now are skipped --
        there is nothing committed to catch up to."""
        target = None
        for transport in self._transports:
            try:
                remote = transport.poll()
            except OSError as e:  # pragma: no cover - medium unreachable
                self._record(error=e)
                continue
            if remote is not None:
                target = remote if target is None else max(target, remote)
        if target is not None:
            self.wait_for_version(target, timeout)

    def close(self) -> None:
        """Stop the pullers and release the transports.  The local
        store keeps serving whatever it last swapped in (drain-friendly:
        readers need no coordination with a closing group)."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        for th in self._threads:
            th.join(timeout=5.0)
            if th.is_alive():  # pragma: no cover - hung medium
                _log.warning("puller thread %s did not stop", th.name)
        for transport in self._transports:
            transport.close()
