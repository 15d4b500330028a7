"""Versioned snapshot publish: the update -> serve coordination layer.

DSPC's premise is that the maintained SPC-Index keeps *serving* cheap
while updates run continuously -- but that only holds if the updater and
the serving replicas agree on WHICH index a batch is answered from.
Handing the index around as a bare pytree attribute (what the driver did
before this module) has no publish step: a reader that gathers its label
rows while the updater commits chunk k+1 can mix rows from two logical
indexes.  This module closes that gap with a double-buffered,
version-counted snapshot store between the updater and the replicas:

* **Double buffer.**  Functional pytrees make the two buffers implicit:
  the updater *stages* snapshot k+1 -- builds a brand-new index pytree
  and (on a mesh) lays it out replicated across the serving devices via
  ``repro.core.distributed.replicate_index`` -- while every reader keeps
  its pinned reference to snapshot k.  Staging happens OUTSIDE the
  store's lock: writing the back buffer never blocks readers.

* **Atomic swap.**  :meth:`SnapshotStore.publish` swaps the front
  pointer under a lock -- one reference assignment -- and bumps a
  monotonically increasing version counter.  A reader that called
  :meth:`SnapshotStore.current` a microsecond earlier is untouched: its
  batch finishes on the pinned ``Snapshot`` bit-for-bit as if no swap
  had happened.  Version regressions (a stale updater republishing an
  old state) raise instead of silently rolling replicas back.

* **The whole index travels with the version.**  Every field of the
  published index, the per-vertex cached ``cnt_sum`` included
  (``repro.core.labels``), rides inside the snapshot, so every replica
  pinned on version k answers from k alone, consistent mid-refresh.

* **The medium is pluggable.**  This store only *versions* snapshots;
  *moving* them between processes/hosts is a
  ``repro.serve.transport.SnapshotTransport`` -- ``LocalTransport``
  (in-process, the default), ``DirTransport`` (committed ``step_*``
  dirs + ``LATEST``, which also makes every published version durable
  through the atomic checkpoint protocol), or the socket-notify
  transport.  Every committed swap is forwarded to the transport;
  remote ``ReplicaGroup`` pullers (``repro.serve.replica``) follow the
  medium, verify, and swap into their own local store.  The legacy
  ``checkpoint_dir=`` / ``async_checkpoint=`` kwargs are a shim that
  builds the equivalent ``DirTransport``.

Producer side: ``DynamicSPC.attach_store()`` publishes after every
committed mutation / event chunk -- and only committed ones, so an
overflow-retry mid-chunk never exposes its intermediate index.  Consumer
side: ``QueryEngine.serve_from(store)`` pins ``store.current()`` per
batch (single- or multi-device).  Both ends are normally owned by the
``repro.serve.SPCService`` façade, which layers the explicit
consistency contract (read-your-writes / at_version) on top of this
store's version counter; wire them by hand only when composing a
custom topology.  Cf. PSPC's replicated hub-label
serving workers (arXiv:2212.00977) and Farhan et al.'s argument that the
label structure should carry the metadata queries need (arXiv:2102.08529).
"""

from __future__ import annotations

from typing import Optional

from repro.analysis.shadow import assert_no_locks_held, make_lock
from repro.core.labels import SPCIndex
# Snapshot/load_snapshot moved to repro.serve.transport with the
# publication-medium split; re-exported here for compatibility.
from repro.serve.transport import (DirTransport, LocalTransport,  # noqa: F401
                                   Snapshot, SnapshotTransport,
                                   load_snapshot, snapshot_tree)

_snapshot_tree = snapshot_tree  # legacy private alias


class SnapshotStore:
    """Double-buffered, versioned SPCIndex snapshots (see module doc).

    Thread contract: one publisher (the updater), any number of readers.
    Readers go through :meth:`current` (or ``QueryEngine.serve_from``)
    and hold the returned ``Snapshot`` for the duration of a batch;
    :meth:`publish` stages outside the lock and swaps inside it.

    ``mesh=`` places every staged snapshot replicated over the mesh
    before the swap (serving-replica layout).  ``transport=`` plugs the
    publication medium every committed swap is forwarded through
    (default ``LocalTransport``: in-process only); the legacy
    ``checkpoint_dir=`` / ``async_checkpoint=`` / ``keep=`` kwargs
    build the equivalent ``DirTransport``.
    """

    def __init__(self, index: SPCIndex | None = None, *, version: int = 0,
                 mesh=None, transport: SnapshotTransport | None = None,
                 checkpoint_dir: str | None = None,
                 async_checkpoint: bool = False, keep: int = 3) -> None:
        if transport is not None and checkpoint_dir is not None:
            raise ValueError(
                "pass transport= OR the legacy checkpoint_dir= shim, "
                "not both")
        if transport is None:
            transport = (DirTransport(checkpoint_dir, keep=keep,
                                      async_save=async_checkpoint)
                         if checkpoint_dir is not None else LocalTransport())
        self._lock = make_lock("store.lock")
        self._mesh = mesh
        self._transport = transport
        self._front: Optional[Snapshot] = None
        self.publishes = 0  # swap count (excludes the seed snapshot)
        if index is not None:
            self._front = Snapshot(int(version), self._stage(index))
            self._transport.publish(self._front)

    # -- reader side --------------------------------------------------------
    @property
    def version(self) -> int | None:
        """Version of the front snapshot (None while empty)."""
        snap = self._front  # analysis: ignore[unlocked-attr]
        return None if snap is None else snap.version

    @property
    def transport(self) -> SnapshotTransport:
        """The publication medium committed swaps are forwarded to."""
        return self._transport

    def current(self) -> Snapshot:
        """Pin the front snapshot: the returned object is immutable and
        survives any concurrent publish unchanged."""
        # single reference read: atomic under the GIL (lock-free pin)
        snap = self._front  # analysis: ignore[unlocked-attr]
        if snap is None:
            raise RuntimeError("SnapshotStore holds no published snapshot")
        return snap

    # -- publisher side -----------------------------------------------------
    def _stage(self, index: SPCIndex) -> SPCIndex:
        """Write the back buffer: place the new snapshot where replicas
        will read it.  Runs outside the lock -- readers stay on the
        front snapshot for however long this takes."""
        assert_no_locks_held("SnapshotStore._stage")
        if self._mesh is not None:
            from repro.core.distributed import replicate_index
            index = replicate_index(self._mesh, index)
        return index

    def publish(self, index: SPCIndex, *, version: int | None = None) -> int:
        """Stage ``index`` as the next snapshot and atomically swap it
        in at ``version`` (default: front version + 1), then forward
        the committed snapshot through the transport.  Returns the
        published version; raises ``ValueError`` on a non-increasing
        one (stale publisher) before anything is swapped or forwarded,
        and ``transport.PublisherBehindError`` when the *medium* is
        ahead (a restarted updater trying to re-publish history)."""
        staged = self._stage(index)
        with self._lock:
            prev = -1 if self._front is None else self._front.version
            v = prev + 1 if version is None else int(version)
            if v <= prev:
                raise ValueError(
                    f"snapshot version must increase monotonically: "
                    f"got {v}, front is {prev}")
            snap = Snapshot(v, staged)
            self._front = snap
            self.publishes += 1
        # outside the lock: the medium may serialize/do IO, and readers
        # pinning the new front must never wait on it
        self._transport.publish(snap)
        return v

    def wait(self) -> None:
        """Settle an in-flight async transport commit (re-raising its
        failure; no-op for synchronous media)."""
        self._transport.wait()

    def close(self) -> None:
        """Settle and release the transport (sockets, saver threads)."""
        self._transport.close()
