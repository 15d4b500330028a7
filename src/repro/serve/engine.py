"""Unified SPC query-serving engine (the DSPC read hot path).

The whole point of maintaining the SPC-Index under updates (DSPC §4)
is that serving stays O(L) hub-label work per query; this module makes
that the *engineered* path instead of three diverging ones:

1. **Gather once.**  Each batch gathers the six label-row operands
   ([B, L] per side) a single time; the routing decision and every
   evaluation route consume the same rows.
2. **Bucket-pad.**  Batches are padded to a small static set of bucket
   sizes (``DEFAULT_BUCKETS``) with dump-row pairs ``(n, n)`` -- which
   evaluate to the disconnected sentinel and are sliced off -- so the
   jit compile cache holds one executable per (bucket, l_cap) instead
   of one per observed batch size.
3. **Route.**  Per batch, by backend and exactness:

   ========  ==========================================  ===========
   route     when                                        counts
   ========  ==========================================  ===========
   merge     default (CPU, or any row's bound >= 2^24)   int64 exact
   pallas    TPU/kernel backend AND every per-row count  fp32, exact
             bound ``sum(cnt_s) * sum(cnt_t)`` < 2^24    by the bound
   table     explicit only (eager-parity debugging; the  int64 exact
             O(L^2) arithmetic of the kernel, in jnp)
   ========  ==========================================  ===========

   The exactness bound is enforced per row: on a mixed batch the
   kernel answers every row and the rows over the bound are re-answered
   in int64 and patched in, recorded as ``pallas+merge``; a batch
   with no provably-exact row degrades whole to the merge path,
   recorded as ``pallas->merge`` -- the silent-overflow bug this engine
   exists to close.  ``interpret`` defaults from the backend at dispatch
   time (compiled only on TPU), so an explicit ``route="pallas"`` works
   on CPU/GPU hosts too.
4. **Shard.**  ``QueryEngine.sharded`` wraps
   ``repro.core.distributed.make_sharded_query`` (index replicated,
   batch split over mesh axes) with the same pad-and-slice handling so
   multi-device replicas serve arbitrary batch sizes.

5. **Refresh.**  ``QueryEngine.serve_from(store)`` serves from a
   ``repro.serve.publish.SnapshotStore``: each batch pins one published
   (version, index) snapshot, the updater swaps new versions in
   underneath without ever touching an in-flight batch, and the 2^24
   routing bound is read off the snapshot's cached per-vertex
   ``cnt_sum`` field -- O(1) per row, consistent across replicas
   mid-refresh.
"""

from __future__ import annotations

import dataclasses
import threading
import types
from typing import Dict, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.shadow import assert_no_locks_held, make_lock
from repro.core import query as Q
from repro.core.labels import SPCIndex
from repro.kernels.spc_query.ops import exact_query_split
from repro.serve.routing import RoutePolicy
from repro.spans import span

#: Static batch shapes the jit cache may hold.  Batches larger than the
#: last bucket are padded to the next multiple of it.
DEFAULT_BUCKETS = (8, 64, 256, 1024)


def bucket_size(b: int, buckets=DEFAULT_BUCKETS) -> int:
    """Smallest bucket >= b (multiples of the largest bucket beyond)."""
    for cap in buckets:
        if b <= cap:
            return cap
    top = buckets[-1]
    return -(-b // top) * top


def coalesce_pairs(parts):
    """Assemble heterogeneous per-request ``(s, t)`` pair lists into one
    flat batch (the front door's coalescing step).

    ``parts`` is a sequence of ``(s_i, t_i)`` array-likes of arbitrary
    (possibly different) lengths.  Returns ``(s, t, offsets)`` where
    ``s``/``t`` are the concatenated 1-D id arrays and
    ``offsets[i]:offsets[i + 1]`` spans part ``i`` -- the mapping
    :func:`split_rows` uses to scatter a batch's answers back per
    request.  Ids keep their natural dtype: the engine's host-side
    bounds check must see un-wrapped values, so no int32 cast here.
    """
    ss, ts, offsets = [], [], [0]
    for k, (s, t) in enumerate(parts):
        s = np.asarray(s).reshape(-1)
        t = np.asarray(t).reshape(-1)
        if s.shape != t.shape:
            raise ValueError(
                f"part {k}: s/t shape mismatch: {s.shape} vs {t.shape}")
        ss.append(s)
        ts.append(t)
        offsets.append(offsets[-1] + s.shape[0])
    if not ss:
        return (np.empty(0, np.int32), np.empty(0, np.int32),
                np.zeros(1, np.int64))
    return (np.concatenate(ss), np.concatenate(ts),
            np.asarray(offsets, np.int64))


def split_rows(d, c, offsets):
    """Scatter a coalesced batch's answers back per request: the inverse
    of :func:`coalesce_pairs`.  Materializes the device arrays once and
    returns a list of ``(dist_i, cnt_i)`` numpy views, one per part."""
    d = np.asarray(d)
    c = np.asarray(c)
    if d.shape[0] != int(offsets[-1]) or c.shape[0] != int(offsets[-1]):
        raise ValueError(
            f"answers of {d.shape[0]}/{c.shape[0]} rows do not cover the "
            f"coalesced batch of {int(offsets[-1])} pairs")
    return [(d[int(offsets[i]):int(offsets[i + 1])],
             c[int(offsets[i]):int(offsets[i + 1])])
            for i in range(len(offsets) - 1)]


#: The merge route IS the one fused jitted merge entry point of
#: ``core.query`` (gather + sorted-merge in a single dispatch).
_serve_merge = Q.batched_query_jit

#: B = 0 answers, materialized once host-side so empty batches return
#: without touching any jit cache (see ``QueryEngine.query_batch``).
_EMPTY_DIST = jnp.asarray(np.empty(0, np.int32))
_EMPTY_CNT = jnp.asarray(np.empty(0, np.int64))


@jax.jit
def _serve_table(idx: SPCIndex, s, t):
    rows = Q.gather_rows(idx, s) + Q.gather_rows(idx, t)
    return Q.table_rows(*rows, jnp.int32(idx.n + 1))


@dataclasses.dataclass(frozen=True)
class ServeStatsView:
    """Point-in-time frozen copy of a ``ServeStats`` (see ``snapshot``).

    The dict fields are read-only mapping proxies over fresh copies, so
    a view taken mid-traffic can be iterated, serialized or compared
    while replica threads keep counting on the live object.
    """

    queries: int
    batches: int
    routes: Mapping[str, int]
    versions: Mapping[int, int]
    route_pairs: Mapping[str, int]


@dataclasses.dataclass
class ServeStats:
    queries: int = 0          # real (un-padded) queries answered
    batches: int = 0          # engine dispatches
    routes: Dict[str, int] = dataclasses.field(default_factory=dict)
    #: queries answered per pinned snapshot version (``serve_from`` only)
    versions: Dict[int, int] = dataclasses.field(default_factory=dict)
    #: real pairs per evaluation path that answered them (``pallas``,
    #: ``merge``, ``table``): a ``pallas+merge`` batch adds its exact
    #: rows to ``pallas`` and its inexact rows to ``merge``
    route_pairs: Dict[str, int] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        # one engine may front many replica threads (the publish
        # module's reader contract); counters must not lose increments
        # to interleaved read-modify-writes
        self._lock = make_lock("serve_stats.lock")

    def count(self, route: str, queries: int,
              paths: Mapping[str, int] | None = None) -> None:
        """One batch of ``queries`` real pairs on ``route``; ``paths``
        splits them over the evaluation paths that answered them (by
        default all on the path the route names)."""
        with self._lock:
            self.queries += queries
            self.batches += 1
            self.routes[route] = self.routes.get(route, 0) + 1
            for path, pairs in (paths or {route: queries}).items():
                if pairs:
                    self.route_pairs[path] = (
                        self.route_pairs.get(path, 0) + pairs)

    def count_version(self, version: int, queries: int) -> None:
        with self._lock:
            self.versions[version] = self.versions.get(version, 0) + queries

    def snapshot(self) -> ServeStatsView:
        """Lock-guarded frozen copy.  Reading the live ``routes`` /
        ``versions`` dicts while replica threads count is a data race
        (dict iteration raises ``RuntimeError`` on concurrent insert);
        every cross-thread stats read goes through here."""
        with self._lock:
            return ServeStatsView(
                queries=self.queries, batches=self.batches,
                routes=types.MappingProxyType(dict(self.routes)),
                versions=types.MappingProxyType(dict(self.versions)),
                route_pairs=types.MappingProxyType(dict(self.route_pairs)))


class QueryEngine:
    """Routed, bucket-padded serving front end over one SPCIndex pytree.

    Stateless with respect to the index (pass it per call -- updates
    produce new functional snapshots), stateful only in routing config
    and counters, so one engine can front many replicas.
    """

    ROUTES = ("auto", "merge", "table", "pallas")

    def __init__(self, *, route: str | RoutePolicy = "auto",
                 buckets=DEFAULT_BUCKETS,
                 block_b: int = 128, interpret: bool | None = None) -> None:
        if isinstance(route, RoutePolicy):
            # a policy carries the kernel knobs; explicit kwargs would
            # silently fight it, so the policy wins wholesale.  A
            # sharded policy builds the merge core engine -- the
            # multi-device binding happens through .sharded(mesh)
            # (SPCService.reader does exactly that).
            block_b = route.block_b
            interpret = route.interpret
            route = route.engine_route
        if route not in self.ROUTES:
            raise ValueError(f"unknown route {route!r}; want one of "
                             f"{self.ROUTES}")
        self.route = route
        self.buckets = tuple(buckets)
        self.block_b = block_b
        self.interpret = interpret
        self.stats = ServeStats()

    # -- routing -----------------------------------------------------------
    def _kernel_backend(self) -> bool:
        return jax.default_backend() == "tpu"

    @staticmethod
    def _validate_ids(n: int, s: np.ndarray, t: np.ndarray) -> None:
        """Host-side bounds check: jnp gathers wrap negative ids and
        clamp ids > n, silently answering for the *wrong* vertex."""
        for arr in (s, t):
            if arr.size and (arr.min() < 0 or arr.max() >= n):
                bad = arr[(arr < 0) | (arr >= n)][0]
                raise ValueError(
                    f"vertex id {int(bad)} out of range [0, {n})")

    # -- serving -----------------------------------------------------------
    def query_batch(self, idx: SPCIndex, s, t,
                    route: str | None = None) -> Tuple[jax.Array, jax.Array]:
        """Answer B (s, t) pairs: (dist int32[B], count int64[B])."""
        s = np.asarray(s).reshape(-1)  # validate on the natural dtype --
        t = np.asarray(t).reshape(-1)  # an int32 cast could wrap huge ids
        if s.shape != t.shape:
            raise ValueError(f"s/t shape mismatch: {s.shape} vs {t.shape}")
        if isinstance(route, RoutePolicy):
            # a per-call policy must actually bind, not silently
            # degrade: sharded needs the multi-device path, and kernel
            # knobs live on the engine, so a mismatch is an error
            if route.needs_mesh:
                raise ValueError(
                    "sharded RoutePolicy cannot be evaluated on the "
                    "single-device query path; bind it through "
                    "QueryEngine.sharded(mesh) or SPCService.reader")
            if route.kind in ("auto", "pallas") and \
                    (route.block_b, route.interpret) != (self.block_b,
                                                         self.interpret):
                raise ValueError(
                    f"policy kernel knobs (block_b={route.block_b}, "
                    f"interpret={route.interpret}) differ from this "
                    f"engine's ({self.block_b}, {self.interpret}); "
                    f"construct a QueryEngine(route=<policy>) instead")
            route = route.engine_route
        route = route or self.route
        if route not in self.ROUTES:
            raise ValueError(f"unknown route {route!r}; want one of "
                             f"{self.ROUTES}")
        with span("spc.read.prep"):
            self._validate_ids(idx.n, s, t)
            assert_no_locks_held("QueryEngine.query_batch")
            b = s.shape[0]
            if b == 0:
                # empty batch: answer host-side -- padding B=0 up to the
                # smallest bucket would dispatch 8 dump rows and record
                # a phantom batch of 0 queries in the stats
                return _EMPTY_DIST, _EMPTY_CNT
            s = s.astype(np.int32)
            t = t.astype(np.int32)
            pad = bucket_size(b, self.buckets) - b
            if pad:  # dump-row pairs: evaluate to (INF, 0), sliced off
                s = np.pad(s, (0, pad), constant_values=idx.n)
                t = np.pad(t, (0, pad), constant_values=idx.n)
        want_pallas = route == "pallas" or (route == "auto"
                                            and self._kernel_backend())
        if route == "table":
            chosen = "table"
            with span("spc.read.merge"):  # the merge route's span
                d, c = _serve_table(idx, s, t)
            paths = {"table": b}
        elif not want_pallas:
            chosen = "merge"
            with span("spc.read.merge"):
                d, c = _serve_merge(idx, s, t)
            paths = {"merge": b}
        else:
            # The shared exactness-routed kernel call: gathers once,
            # syncs the per-row bound vector, and re-answers in int64
            # only the rows that could exceed 2^24 on the fp32 path
            # ("pallas" / "pallas+merge" / "pallas->merge").
            d, c, chosen, merged = exact_query_split(
                idx, s, t, block_b=self.block_b, interpret=self.interpret,
                real_rows=b)
            paths = {"pallas": b - merged, "merge": merged}
        self.stats.count(chosen, b, paths)
        return d[:b], c[:b]

    def query_pair(self, idx: SPCIndex, s: int, t: int) -> Tuple[int, int]:
        """Single (s, t) query through the same bucketed batch path (pads
        to the smallest bucket; no per-call L x L table, no recompiles)."""
        d, c = self.query_batch(idx, [s], [t])
        return int(d[0]), int(c[0])

    # -- multi-device serving ----------------------------------------------
    def sharded(self, mesh, batch_axes: Tuple[str, ...] = ("data",)):
        """Serving closure over replicated-index / batch-sharded replicas.

        Returns ``serve(idx, s, t) -> (dist[B], cnt[B])``; batches are
        padded with dump-row pairs to a bucket that divides evenly over
        the mesh axes, so callers keep arbitrary batch sizes.
        """
        from repro.core.distributed import make_sharded_query

        fn = make_sharded_query(mesh, batch_axes)
        shards = 1
        for ax in batch_axes:
            shards *= mesh.shape[ax]
        axes = "x".join(batch_axes)

        def serve(idx: SPCIndex, s, t, route: str | None = None):
            s = np.asarray(s).reshape(-1)
            t = np.asarray(t).reshape(-1)
            if s.shape != t.shape:
                raise ValueError(
                    f"s/t shape mismatch: {s.shape} vs {t.shape}")
            # same route contract as query_batch: unknown names raise,
            # and a configured route the sharded path cannot honor is an
            # error instead of being silently ignored
            route_ = (route.engine_route if isinstance(route, RoutePolicy)
                      else route) or self.route
            if route_ not in self.ROUTES:
                raise ValueError(f"unknown route {route_!r}; want one of "
                                 f"{self.ROUTES}")
            if route_ not in ("auto", "merge"):
                raise ValueError(
                    f"route {route_!r} is not available on the sharded "
                    f"serving path (only the sorted-merge core is "
                    f"sharded); use route='auto' or 'merge'")
            self._validate_ids(idx.n, s, t)
            assert_no_locks_held("QueryEngine.sharded.serve")
            b = s.shape[0]
            if b == 0:  # see query_batch: no dispatch, no phantom batch
                return _EMPTY_DIST, _EMPTY_CNT
            s = s.astype(np.int32)
            t = t.astype(np.int32)
            bp = bucket_size(b, self.buckets)
            bp = -(-bp // shards) * shards  # divisible over the mesh axes
            if bp != b:
                s = np.pad(s, (0, bp - b), constant_values=idx.n)
                t = np.pad(t, (0, bp - b), constant_values=idx.n)
            d, c = fn(idx, jnp.asarray(s), jnp.asarray(t))
            # route recorded like the single-device paths record theirs,
            # so mixed single-/multi-device stats stay comparable
            self.stats.count(f"sharded[{axes}]:merge", b, {"merge": b})
            return d[:b], c[:b]

        return serve

    # -- replica serving over a snapshot store ------------------------------
    def serve_from(self, store, *, mesh=None,
                   batch_axes: Tuple[str, ...] = ("data",)):
        """Serving-replica closure over a ``SnapshotStore``
        (``repro.serve.publish``): each batch pins ``store.current()``
        for its whole duration, so a concurrent publish of version k+1
        never touches a batch answering from version k.

        Legacy wiring: prefer ``repro.serve.SPCService.reader`` -- the
        service façade owns the store, adds explicit consistency levels
        (pinned / read-your-writes / at_version) and surfaces updater
        failures; this method stays for callers managing their own
        store.

        Returns ``serve(s, t, route=None) -> (dist[B], cnt[B])``.  With
        ``mesh=`` the batch is answered through :meth:`sharded` replicas
        instead of the single-device routed path.  Consecutive versions
        reuse the engine's jit compile caches -- executables key on
        (bucket, l_cap) shapes, not on the snapshot -- so a publish only
        recompiles when an overflow-retry grew ``l_cap``.  Per-version
        query counts land in ``stats.versions``.
        """
        inner = self.sharded(mesh, batch_axes) if mesh is not None else None

        def serve(s, t, route: str | None = None):
            snap = store.current()  # pinned for the whole batch
            if inner is not None:
                d, c = inner(snap.index, s, t, route=route)
            else:
                d, c = self.query_batch(snap.index, s, t, route=route)
            b = int(d.shape[0])
            if b:
                self.stats.count_version(snap.version, b)
            return d, c

        return serve
