"""DynamicSPC: the host-side driver that makes DSPC a *service*.

Responsibilities beyond the jitted algorithm steps:

* capacity management -- grows the edge arrays and the label matrices
  (overflow-retry: every jitted update reports lost writes through the
  index's ``overflow`` counter; the driver re-pads the *pre-op* snapshot
  and replays the op, which is sound because all ops are functional);
* the isolated-vertex fast path of Section 3.2.3;
* vertex insertion/deletion (reduction to edge events, Section 3);
* update batching (streams of mixed events, the Section 4.4 scenario,
  chunked through the hybrid engine ``repro.core.hybrid`` so a whole
  chunk costs one jitted dispatch);
* stream validation (op tags, vertex bounds, presence/absence -- the
  batched engine treats unknown tags as padding inside the trace, so
  corrupted streams MUST be rejected host-side before dispatch);
* distributed updates: ``mesh=`` swaps every build/update engine for
  the edge-sharded variants of ``repro.core.distributed
  .make_distributed_updater`` (same algorithms, relaxation sharded over
  the mesh's edge axis) while this driver's capacity pre-provision and
  overflow-retry machinery runs unchanged, re-padding the edge arrays
  to the shard count after every capacity change;
* checkpointable state (arrays only -- see ``repro.train.checkpoint``),
  including a monotone update version counter;
* snapshot publishing: ``attach_store()`` wires a
  ``repro.serve.publish.SnapshotStore`` so every *committed* update (one
  per mutation / event chunk, after overflow-retry settles) publishes a
  versioned snapshot for serving replicas to pin -- the double-buffered
  update -> replica refresh protocol.

This mirrors what the C++ artifact's main loop does, lifted into a
recoverable, shardable form.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Iterable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.shadow import make_lock
from repro.core import graph as G
from repro.core import labels as L
from repro.core.construct import (build_index, build_index_batched,
                                  provision_l_cap)
from repro.core.decremental import dec_spc
from repro.core.graph import Graph
from repro.core.incremental import inc_spc
from repro.core.labels import SPCIndex
from repro.core.order import (identity_ordering, ordering_from_state,
                              vertex_ordering)
from repro.spans import span


#: Default chunk size for batched event replay.  Chunks are padded to
#: this length so ``hyb_spc_batch`` compiles once per (cap_e, l_cap)
#: shape regardless of how many events each call carries.
DEFAULT_BATCH = 64


@dataclasses.dataclass(frozen=True)
class UpdateStatsView:
    """Point-in-time frozen copy of an ``UpdateStats`` (``snapshot``)."""

    inserts: int
    deletions: int
    isolated_fast_path: int
    label_regrows: int
    edge_regrows: int
    batches: int
    batched_events: int
    hub_repairs: int
    relax_rounds: int
    build_relax_rounds: int

    @property
    def events_per_batch(self) -> float:
        return self.batched_events / self.batches if self.batches else 0.0


@dataclasses.dataclass
class UpdateStats:
    inserts: int = 0
    deletions: int = 0
    isolated_fast_path: int = 0  # deletions that took the fast path,
    # on the host path and inside the hybrid engine's trace
    label_regrows: int = 0
    edge_regrows: int = 0
    batches: int = 0          # jitted hybrid-engine dispatches
    batched_events: int = 0   # events carried by those dispatches
    #: work the hybrid engine counted inside its dispatches, retried ones
    #: included (``bfs.RepairWork``): per-hub repair BFSs run, and
    #: relaxation rounds (one full-edge ``segment_sum`` each) of those
    #: and of SRRSearch
    hub_repairs: int = 0
    relax_rounds: int = 0
    #: relaxation rounds of the batched build (``MultiBFSResult.levels``
    #: summed over its hub rounds, retried ones included)
    build_relax_rounds: int = 0

    def __post_init__(self):
        # one updater thread writes, but serving/monitoring threads read
        # while it counts (the service façade's stats endpoint); all
        # increments and snapshots go through this lock
        self._lock = make_lock("update_stats.lock")

    def bump(self, **deltas: int) -> None:
        """Lock-guarded counter increments (the only write path)."""
        with self._lock:
            for key, d in deltas.items():
                setattr(self, key, getattr(self, key) + d)

    def snapshot(self) -> UpdateStatsView:
        """Lock-guarded frozen copy -- what cross-thread readers use
        instead of touching the live counters mid-increment."""
        with self._lock:
            return UpdateStatsView(**{
                f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)})

    @property
    def events_per_batch(self) -> float:
        """Average events amortized per jitted dispatch (batching win)."""
        return self.batched_events / self.batches if self.batches else 0.0


class DynamicSPC:
    """Maintains (graph, SPC-Index) under a stream of topology events.

    With ``mesh=`` the service runs its build and every update through
    the edge-sharded engines (``repro.core.distributed``): the edge list
    is partitioned over ``edge_axis``, labels stay replicated, and the
    public contract (queries, events, overflow-retry, checkpointing) is
    unchanged -- differential tests hold the two modes bit-identical.
    """

    def __init__(self, n: int, edges: Sequence[Tuple[int, int]] = (),
                 l_cap: int | None = 32, cap_e: int | None = None, *,
                 mesh=None, edge_axis: str = "model",
                 construct_batch: int | None = None,
                 vertex_order: str = "id") -> None:
        """``construct_batch`` >= 2 builds the index through the batched
        PSPC-style constructor (``construct.build_index_batched``; same
        index, fewer dispatches); ``vertex_order="degree"`` relabels the
        vertex ids into degree-rank space at this driver's id boundary
        (every public entry point translates; the engines keep their
        rank == id invariant).  ``l_cap=None`` pre-provisions the label
        capacity from the graph's degree statistics."""
        self.stats = UpdateStats()
        self._engine = None
        self._updater = None
        self._store = None
        self.version = 0  # bumped per committed update; state_dict carries it
        self._construct_batch = construct_batch
        self.order = vertex_ordering(n, edges, vertex_order)
        if mesh is not None:
            from repro.core.distributed import make_distributed_updater
            self._updater = make_distributed_updater(mesh, edge_axis)
        self.graph = self._pad_for_mesh(
            G.from_edges(n, self.order.edges_to_internal(edges), cap_e))
        self.index = self._build(l_cap)

    def _pad_for_mesh(self, g: Graph) -> Graph:
        """Keep cap_e divisible over the edge axis (no-op off-mesh)."""
        return self._updater.pad(g) if self._updater is not None else g

    # -- construction with overflow-retry ---------------------------------
    def _build(self, l_cap: int | None) -> SPCIndex:
        if self._construct_batch is not None and self._construct_batch >= 2:
            # batched constructor: overflow-retry happens inside, per
            # hub round from the pre-round snapshot (committed labels
            # survive); the stats hook keeps regrow accounting at parity
            # with the sequential path below
            build_b = (self._updater.build_index_batched
                       if self._updater is not None else build_index_batched)
            return build_b(
                self.graph, l_cap, hub_batch=self._construct_batch,
                on_regrow=lambda _cap: self.stats.bump(label_regrows=1),
                on_round=lambda r: self.stats.bump(build_relax_rounds=r))
        if l_cap is None:
            l_cap = provision_l_cap(self.graph)
        build = (self._updater.build_index if self._updater is not None
                 else build_index)
        while True:
            idx = build(self.graph, l_cap)
            if int(idx.overflow) == 0:
                return idx
            l_cap *= 2
            self.stats.bump(label_regrows=1)

    def rebuild(self) -> None:
        """Reconstruction baseline (what the paper's HP-SPC rerun does)."""
        self.index = self._build(self.index.l_cap)
        self._commit()

    @property
    def n(self) -> int:
        return self.graph.n

    # -- queries -----------------------------------------------------------
    @property
    def engine(self):
        """The serving engine (``repro.serve.QueryEngine``); every query
        entry point of this driver routes through it."""
        if self._engine is None:
            from repro.serve import QueryEngine
            self._engine = QueryEngine()
        return self._engine

    # -- snapshot publishing -------------------------------------------------
    def attach_store(self, store=None, **store_kwargs):
        """Attach (or create) a ``repro.serve.SnapshotStore``: every
        committed update from here on publishes the new index snapshot
        at its bumped version, so serving replicas reading through the
        store refresh via the double-buffered swap instead of sharing
        this driver's mutable ``.index`` attribute.

        Only *committed* states publish -- a chunk that overflows and
        replays never exposes its intermediate index, readers stay
        pinned on version k until k+1's retry succeeds.

        Legacy wiring: ``repro.serve.SPCService`` owns this driver, the
        store and the serving replicas behind one lifecycle (async
        ingest queue, explicit read consistency); prefer the façade over
        hand-rolling attach_store + updater threads.
        """
        if store is None:
            from repro.serve.publish import SnapshotStore
            store = SnapshotStore(self.index, version=self.version,
                                  **store_kwargs)
        elif store.version is not None and store.version > self.version:
            # fail here, not with a confusing monotonicity error on the
            # first update after attach
            raise ValueError(
                f"store is at version {store.version}, ahead of this "
                f"service (version {self.version}); restore a newer "
                f"state or attach a fresh store")
        elif store.version is None or store.version < self.version:
            store.publish(self.index, version=self.version)
        self._store = store
        return store

    def _commit(self) -> None:
        """Bump the version and publish the committed snapshot (if a
        store is attached).  Called exactly once per successful public
        mutation / event chunk, after overflow-retry has settled."""
        with span("spc.update.publish"):
            self.version += 1
            if self._store is not None:
                self._store.publish(self.index, version=self.version)

    def query(self, s: int, t: int) -> Tuple[int, int]:
        # bounds validation happens inside the engine (host-side);
        # to_internal is the identity (and validation-free) for the
        # default vertex_order="id"
        return self.engine.query_pair(
            self.index, self.order.to_internal(s), self.order.to_internal(t))

    def query_batch(self, s, t, route: str | None = None):
        # bounds validation happens inside the engine (host-side)
        return self.engine.query_batch(
            self.index, self.order.to_internal(s), self.order.to_internal(t),
            route=route)

    # -- updates -----------------------------------------------------------
    def _check_vertex(self, v: int, *, what: str = "vertex") -> None:
        """Host-side bounds check: out-of-range ids would silently clamp
        under JAX scatter/gather semantics and corrupt the dump row."""
        v = int(v)
        if not 0 <= v < self.n:
            raise ValueError(f"{what} id {v} out of range [0, {self.n})")

    def _check_edge_ids(self, a: int, b: int) -> None:
        self._check_vertex(a, what="endpoint")
        self._check_vertex(b, what="endpoint")
        if int(a) == int(b):
            raise ValueError(f"self loop ({a},{b}) not allowed")

    def insert_edge(self, a: int, b: int) -> None:
        self._check_edge_ids(a, b)
        a, b = self.order.to_internal(a), self.order.to_internal(b)
        if bool(G.has_edge(self.graph, a, b)):
            raise ValueError(f"edge ({a},{b}) already present")
        self.graph = self._pad_for_mesh(G.ensure_capacity(self.graph, 2))
        inc = (self._updater.inc_spc if self._updater is not None
               else inc_spc)
        while True:
            g2, idx2 = inc(self.graph, self.index, a, b)
            if int(idx2.overflow) == 0:
                self.graph, self.index = g2, idx2
                break
            self.index = L.repad(self.index, self.index.l_cap * 2)
            self.stats.bump(label_regrows=1)
        self.stats.bump(inserts=1)
        self._commit()

    def delete_edge(self, a: int, b: int) -> None:
        self._check_edge_ids(a, b)
        a, b = self.order.to_internal(a), self.order.to_internal(b)
        if not bool(G.has_edge(self.graph, a, b)):
            raise ValueError(f"edge ({a},{b}) not present")
        lo, hi = (a, b) if a < b else (b, a)
        deg = G.degrees(self.graph)
        if int(deg[hi]) == 1:
            # Section 3.2.3: the lower-ranked endpoint becomes isolated and
            # is never a hub elsewhere -- reset its row to the self label.
            self.graph = G.delete_edge(self.graph, a, b)
            self.index = L.reset_isolated_row(self.index, hi)
            self.stats.bump(isolated_fast_path=1)
        else:
            # the isolated case was excluded host-side above, so both
            # modes jit the same plain dec_spc body (shared compile cache)
            dec = (self._updater.dec_spc if self._updater is not None
                   else dec_spc)
            while True:
                g2, idx2 = dec(self.graph, self.index, a, b)
                if int(idx2.overflow) == 0:
                    self.graph, self.index = g2, idx2
                    break
                self.index = L.repad(self.index, self.index.l_cap * 2)
                self.stats.bump(label_regrows=1)
        self.stats.bump(deletions=1)
        self._commit()

    def insert_edges(self, edges) -> None:
        """Batched insertion: one jitted call for the whole batch
        (beyond-paper; see ``incremental.inc_spc_batch``)."""
        from repro.core.incremental import inc_spc_batch
        edges = [(a, b) for a, b in edges]
        for a, b in edges:
            self._check_edge_ids(a, b)
        edges = self.order.edges_to_internal(edges)
        for a, b in edges:
            if bool(G.has_edge(self.graph, a, b)):
                raise ValueError(f"edge ({a},{b}) already present")
        self.graph = self._pad_for_mesh(
            G.ensure_capacity(self.graph, 2 * len(edges)))
        batch = (self._updater.inc_spc_batch if self._updater is not None
                 else inc_spc_batch)
        arr = jnp.asarray(np.asarray(edges, dtype=np.int32))
        while True:
            g2, idx2 = batch(self.graph, self.index, arr)
            if int(idx2.overflow) == 0:
                self.graph, self.index = g2, idx2
                break
            self.index = L.repad(self.index, self.index.l_cap * 2)
            self.stats.bump(label_regrows=1)
        self.stats.bump(inserts=len(edges))
        self._commit()

    def insert_vertex(self) -> int:
        """Append an isolated vertex (lowest rank). Recompiles (n changes)."""
        self.graph = G.add_vertices(self.graph, 1)
        self.index = L.add_vertices(self.index, 1)
        self.order = self.order.grow(1)  # fresh id maps to itself
        self._commit()
        return self.n - 1

    def delete_vertex(self, v: int,
                      batch_size: int | None = DEFAULT_BATCH) -> None:
        """Reduce to edge deletions (Section 3) and replay them through
        the batched engine -- one jitted dispatch per chunk instead of
        one per incident edge."""
        self._check_vertex(v)
        vi = self.order.to_internal(v)
        src = np.asarray(self.graph.src)
        dst = np.asarray(self.graph.dst)
        # live directed slots out of v give the neighbor set in one
        # vectorized pass (tombstones/pads hold src = n, never v);
        # np.unique also delivers the sorted order the old scan produced
        nbrs = np.unique(dst[(src == vi) & (dst != self.n)])
        if not nbrs.size:
            return
        # apply_events translates at ITS boundary, so hand it external
        # ids (identity order: u == to_external(u), zero change)
        self.apply_events(
            [("-", v, int(self.order.to_external(u))) for u in nbrs],
            batch_size=batch_size)

    # -- batched event replay (the hybrid engine) ---------------------------
    def _edge_set(self) -> set:
        src = np.asarray(self.graph.src)
        dst = np.asarray(self.graph.dst)
        live = (src != self.n) & (src < dst)
        return {(int(a), int(b)) for a, b in zip(src[live], dst[live])}

    def _normalize_events(self, events) -> list:
        """Host-side op-tag validation (first line of defense).

        The batched engine maps any unknown tag to its padding branch
        inside the trace -- it *cannot* raise mid-scan -- so a corrupted
        stream would silently drop updates.  Tags are therefore resolved
        here: ``'+'``/``'-'`` (the public symbols) and the engine codes
        ``OP_INSERT``/``OP_DELETE`` are accepted; anything else raises a
        ``ValueError`` naming the first bad row.
        """
        from repro.core.hybrid import OP_DELETE, OP_INSERT
        out = []
        for i, ev in enumerate(events):
            try:
                op, a, b = ev
            except (TypeError, ValueError):
                raise ValueError(
                    f"event row {i}: want an (op, a, b) triple, got {ev!r}"
                ) from None
            if isinstance(op, (int, np.integer)) and \
                    not isinstance(op, bool):
                if op == OP_INSERT:
                    op = "+"
                elif op == OP_DELETE:
                    op = "-"
            if op not in ("+", "-"):
                raise ValueError(
                    f"unknown event op {op!r} at row {i}: want '+'/'-' or "
                    f"OP_INSERT/OP_DELETE (the batched engine would "
                    f"silently treat this row as padding)")
            try:
                out.append((op, int(a), int(b)))
            except (TypeError, ValueError):
                raise ValueError(
                    f"event row {i}: non-integer endpoint in "
                    f"({a!r}, {b!r})") from None
        return out

    def _validate_events(self, events) -> None:
        """Host-side simulation of the stream against the current edge
        set: the batched engine has no way to raise mid-scan, so the
        per-event error semantics are enforced up front."""
        present = self._edge_set()
        for i, (op, a, b) in enumerate(events):
            try:
                self._check_edge_ids(a, b)
            except ValueError as e:
                raise ValueError(f"event row {i}: {e}") from None
            key = (a, b) if a < b else (b, a)
            if op == "+":
                if key in present:
                    raise ValueError(
                        f"event row {i}: edge {key} already present")
                present.add(key)
            else:
                if key not in present:
                    raise ValueError(f"event row {i}: edge {key} not present")
                present.discard(key)

    def apply_events(self, events: Iterable[Tuple[str, int, int]],
                     batch_size: int | None = DEFAULT_BATCH) -> None:
        """Apply a stream of ('+'|'-', a, b) events (Section 4.4).

        By default the stream is chunked and each chunk replays inside
        ONE jitted dispatch (``hybrid.hyb_spc_batch``), padded with
        self-loop rows to a fixed shape.  Each chunk gets a single
        edge-capacity pre-provision and the usual overflow-retry: on
        label overflow anywhere in the chunk the *pre-chunk* snapshot is
        re-padded at doubled capacity and the whole chunk replays (sound
        because every op is functional).  ``batch_size=None`` (or <= 1)
        falls back to one jitted dispatch per event -- kept as the
        differential-testing and benchmark baseline.
        """
        batched = batch_size is not None and batch_size > 1
        with span("spc.update.validate"):
            events = self._normalize_events(events)
            if batched:
                # the per-event fallback below translates inside
                # insert_edge / delete_edge; the chunked path translates
                # here, once, before the stream is simulated against the
                # (internal-id) edge set
                events = [(op, self.order.to_internal(a),
                           self.order.to_internal(b))
                          for op, a, b in events]
                self._validate_events(events)
        if not batched:
            for op, a, b in events:
                if op == "+":
                    self.insert_edge(a, b)
                else:
                    self.delete_edge(a, b)
            return

        from repro.core.hybrid import OP_DELETE, OP_INSERT, hyb_spc_batch
        hyb = (self._updater.hyb_spc_batch if self._updater is not None
               else hyb_spc_batch)
        code = {"+": OP_INSERT, "-": OP_DELETE}
        for lo in range(0, len(events), batch_size):
            chunk = events[lo:lo + batch_size]
            with span("spc.update.apply"):
                arr = np.zeros((batch_size, 3), dtype=np.int32)  # pads
                for i, (op, a, b) in enumerate(chunk):
                    arr[i] = (code[op], a, b)
                n_ins = sum(1 for op, _, _ in chunk if op == "+")
                cap_before = self.graph.cap_e
                self.graph = self._pad_for_mesh(
                    G.ensure_capacity(self.graph, 2 * n_ins))
                if self.graph.cap_e != cap_before:
                    self.stats.bump(edge_regrows=1)
                g0, idx0 = self.graph, self.index  # pre-chunk snapshot
                ev = jnp.asarray(arr)
                repairs = rounds = 0
                while True:
                    (g2, work), idx2 = hyb(self.graph, self.index, ev)
                    # the work counts come in the overflow check's fetch
                    overflow, work = jax.device_get((idx2.overflow, work))
                    repairs += int(work.hub_repairs)
                    rounds += int(work.relax_rounds)
                    if int(overflow) == 0:
                        self.graph, self.index = g2, idx2
                        break
                    self.graph = g0
                    self.index = L.repad(idx0, self.index.l_cap * 2)
                    self.stats.bump(label_regrows=1)
            self.stats.bump(batches=1, batched_events=len(chunk),
                            inserts=n_ins,
                            deletions=len(chunk) - n_ins,
                            hub_repairs=repairs, relax_rounds=rounds,
                            isolated_fast_path=int(work.isolated_fast_path))
            # one publish per committed chunk: replicas reading through
            # an attached store refresh at chunk granularity, never
            # seeing a mid-retry intermediate
            self._commit()

    # -- introspection -------------------------------------------------------
    def index_entries(self) -> int:
        return int(self.index.total_entries())

    def index_bytes(self) -> int:
        """Paper's packed accounting: 8 bytes per label entry."""
        return 8 * self.index_entries()

    def state_dict(self) -> dict:
        state = {
            "graph.src": self.graph.src, "graph.dst": self.graph.dst,
            "graph.m2": self.graph.m2,
            "index.hub": self.index.hub, "index.dist": self.index.dist,
            "index.cnt": self.index.cnt, "index.size": self.index.size,
            "index.cnt_sum": self.index.cnt_sum,
            "version": jnp.int64(self.version),
        }
        if not self.order.identity:
            # the external->rank permutation travels with the state; the
            # default "id" order keeps the seed's 9-leaf schema verbatim
            state["order.vertex_of"] = jnp.asarray(self.order.vertex_of,
                                                   jnp.int32)
        return state

    @staticmethod
    def _validate_state(n: int, state: dict) -> dict:
        """Host-side schema check of a state dict before any array lands
        on device.  A truncated or shape-mismatched leaf would otherwise
        build a service whose gathers/scatters silently clamp into the
        dump row (the same defect class as unvalidated vertex ids) --
        every violation raises ``ValueError`` naming the offending key.
        Returns the leaves as host numpy arrays.
        """
        required = ("graph.src", "graph.dst", "graph.m2",
                    "index.hub", "index.dist", "index.cnt", "index.size")
        host = {}
        for key in required:
            if key not in state:
                raise ValueError(f"state dict missing key {key!r}")
        for key in state:
            arr = np.asarray(state[key])
            if not np.issubdtype(arr.dtype, np.integer):
                raise ValueError(
                    f"state[{key!r}] has non-integer dtype {arr.dtype}")
            host[key] = arr

        def want(key, shape):
            if host[key].shape != shape:
                raise ValueError(
                    f"state[{key!r}] has shape {host[key].shape}, "
                    f"want {shape} (n={n})")

        cap_e = host["graph.src"].shape
        if len(cap_e) != 1:
            raise ValueError(
                f"state['graph.src'] must be 1-D, got shape {cap_e}")
        want("graph.dst", cap_e)
        want("graph.m2", ())
        m2 = int(host["graph.m2"])
        if not 0 <= m2 <= cap_e[0]:
            raise ValueError(
                f"state['graph.m2'] = {m2} outside [0, cap_e={cap_e[0]}]")
        hub = host["index.hub"].shape
        if len(hub) != 2 or hub[0] != n + 1:
            raise ValueError(
                f"state['index.hub'] has shape {hub}, want (n + 1 = "
                f"{n + 1}, l_cap)")
        want("index.dist", hub)
        want("index.cnt", hub)
        want("index.size", (n + 1,))
        if "index.cnt_sum" in host:
            want("index.cnt_sum", (n + 1,))
        if "order.vertex_of" in host:
            want("order.vertex_of", (n,))
        if "version" in host:
            want("version", ())
            if int(host["version"]) < 0:
                raise ValueError(
                    f"state['version'] = {int(host['version'])} < 0")
        return host

    @classmethod
    def from_state_dict(cls, n: int, state: dict, *,
                        mesh=None, edge_axis: str = "model",
                        construct_batch: int | None = None) -> "DynamicSPC":
        host = cls._validate_state(n, state)
        obj = cls.__new__(cls)
        obj.stats = UpdateStats()
        obj._engine = None
        obj._updater = None
        obj._store = None
        obj.version = int(host.get("version", 0))
        obj._construct_batch = construct_batch
        obj.order = (ordering_from_state(host["order.vertex_of"])
                     if "order.vertex_of" in host else identity_ordering(n))
        if mesh is not None:
            from repro.core.distributed import make_distributed_updater
            obj._updater = make_distributed_updater(mesh, edge_axis)
        obj.graph = obj._pad_for_mesh(
            Graph(src=jnp.asarray(host["graph.src"], jnp.int32),
                  dst=jnp.asarray(host["graph.dst"], jnp.int32),
                  m2=jnp.asarray(host["graph.m2"], jnp.int32), n=n))
        cnt = jnp.asarray(host["index.cnt"], jnp.int64)
        # pre-cached-bound state dicts lack the field: rebuild the cache
        cnt_sum = (jnp.asarray(host["index.cnt_sum"], jnp.int64)
                   if "index.cnt_sum" in host else L.recompute_cnt_sum(cnt))
        obj.index = SPCIndex(
            hub=jnp.asarray(host["index.hub"], jnp.int32),
            dist=jnp.asarray(host["index.dist"], jnp.int32),
            cnt=cnt, size=jnp.asarray(host["index.size"], jnp.int32),
            cnt_sum=cnt_sum, overflow=jnp.int32(0), n=n)
        return obj

    @classmethod
    def from_checkpoint(cls, path: str, n: int, step: int | None = None, *,
                        mesh=None, edge_axis: str = "model") -> "DynamicSPC":
        """Restore from an on-disk ``repro.train.checkpoint`` directory.

        Builds the restore template from the *committed manifest* rather
        than from a live ``state_dict()``, so checkpoints written before
        the cached-bound/version schema (7 leaves instead of 9) restore
        too -- ``checkpoint.restore(dir, svc.state_dict())`` would
        reject them on leaf count before :meth:`from_state_dict`'s
        legacy handling could run.
        """
        from repro.train import checkpoint as C
        man = C.manifest(path, step)
        ordered = sorted(("graph.src", "graph.dst", "graph.m2", "index.hub",
                          "index.dist", "index.cnt", "index.size",
                          "index.cnt_sum", "order.vertex_of", "version"))
        new = sorted(k for k in ordered if k != "order.vertex_of")
        legacy = sorted(k for k in new
                        if k not in ("index.cnt_sum", "version"))
        for keys in (ordered, new, legacy):
            if len(keys) == len(man["shapes"]):
                break
        else:
            raise ValueError(
                f"checkpoint at {path} has {len(man['shapes'])} leaves; "
                f"not a DynamicSPC state dict")
        tree_like = {
            k: np.empty(shape, dtype=np.dtype(dt))
            for k, shape, dt in zip(keys, man["shapes"], man["dtypes"])
        }
        state, _, _ = C.restore(path, tree_like, step=man["step"])
        return cls.from_state_dict(n, state, mesh=mesh, edge_axis=edge_axis)
