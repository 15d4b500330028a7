"""Betweenness centrality from SPC counts (pair-dependency accumulation).

The paper's own motivating application: once ``SPC(s, t)`` is O(L) off
the maintained index, Brandes' pair dependency

    delta(s, t | v) = sigma_sv * sigma_vt / sigma_st
                      when  d(s, v) + d(v, t) == d(s, t),  v not in {s, t}

is three label-row merges, and betweenness is its accumulation

    BC(v) = sum over ordered pairs (s, t), s != t, of delta(s, t | v)

(ordered pairs: on undirected graphs every unordered pair contributes
twice -- Brandes' convention; halve externally if desired).  The fully
dynamic route follows Pontecorvi & Ramachandran: maintain BC over a
fixed pair workload and re-score only what an update actually touched.
Here the touched set falls straight out of DSPC -- an update batch only
rewrites the label rows of *affected* vertices, so diffing two published
snapshots (:func:`changed_rows`) recovers exactly the affected set, and
:class:`TopKBetweenness` re-scores

* changed candidate vertices against the whole pair workload, and
* all candidates against the changed pairs only (new minus old
  contribution, using the previous pinned snapshot),

leaving every (unchanged vertex, unchanged pair) cell untouched.

Everything dispatches through one jitted kernel over gathered label
rows, padded on the serving engine's bucket ladder (pairs) and a vertex
tile ladder (candidates) so the compile cache stays small.  Pad pairs
are dump-row pairs ``(n, n)`` -- they evaluate disconnected and
contribute zero; pad vertices are the dump row ``n`` and are masked.

Dependencies are accumulated in float64: sigma products are exact to
2^53, far beyond the fp32 kernel's exact range (2^24), and the
Brandes ratio is fractional anyway.

:func:`betweenness_numpy` is the Brandes-style oracle (pure
numpy + ``refimpl.bfs_spc``) the jitted path is differential-tested
against in ``tests/analytics``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.shadow import make_lock
from repro.core import query as Q
from repro.core import refimpl
from repro.core.graph import INF
from repro.core.labels import SPCIndex
from repro.serve.engine import DEFAULT_BUCKETS, bucket_size

#: Vertex-tile ladder for the candidate axis.  Smaller head than the
#: pair buckets: the incremental path re-scores few changed vertices and
#: must not pad ~10 candidates to a full 256-wide dispatch.
DEFAULT_V_TILES = (16, 64, 256)


# --------------------------------------------------------------------------
# Jitted pair-dependency kernel.
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=())
def _dependency_block(idx: SPCIndex, s: jax.Array, t: jax.Array,
                      vs: jax.Array) -> jax.Array:
    """sum_b delta(s_b, t_b | v) for every v in ``vs`` -> float64 [V].

    ``s``/``t`` int32 [B] (pad with the dump row ``n``: disconnected,
    zero contribution); ``vs`` int32 [V] (pad with ``n``: masked).
    """
    hs, ds, cs = Q.gather_rows(idx, s)            # [B, L]
    ht, dt, ct = Q.gather_rows(idx, t)
    d_st, c_st = Q.merge_rows(hs, ds, cs, ht, dt, ct)   # [B]
    inv_st = jnp.where(c_st > 0, 1.0 / c_st.astype(jnp.float64), 0.0)

    def per_v(v):
        hv, dv, cv = idx.hub[v], idx.dist[v], idx.cnt[v]
        d_sv, c_sv = jax.vmap(
            Q._intersect_merge,
            in_axes=(0, 0, 0, None, None, None))(hs, ds, cs, hv, dv, cv)
        d_vt, c_vt = jax.vmap(
            Q._intersect_merge,
            in_axes=(None, None, None, 0, 0, 0))(hv, dv, cv, ht, dt, ct)
        # INF + INF stays int32-safe (INF = int32max // 4) and can never
        # equal a finite d_st, so no explicit d_sv/d_vt masks are needed.
        on = ((d_st < INF)
              & (d_sv + d_vt == d_st)
              & (v != s) & (v != t) & (v < idx.n))
        num = c_sv.astype(jnp.float64) * c_vt.astype(jnp.float64)
        return jnp.sum(jnp.where(on, num * inv_st, 0.0))

    return jax.vmap(per_v)(vs)


def _pad_to(arr: np.ndarray, size: int, fill: int) -> np.ndarray:
    out = np.full(size, fill, dtype=np.int32)
    out[:arr.shape[0]] = arr
    return out


def dependency_scores(idx: SPCIndex,
                      pairs_s: np.ndarray, pairs_t: np.ndarray,
                      vertices: np.ndarray, *,
                      buckets: Sequence[int] = DEFAULT_BUCKETS,
                      v_tiles: Sequence[int] = DEFAULT_V_TILES) -> np.ndarray:
    """Accumulated pair dependencies: float64 [len(vertices)].

    Host-side tiling: pairs are padded to the engine's bucket ladder
    (dump-row pad pairs), candidates walk ``v_tiles``-sized tiles, the
    final partial tile padded on the same ladder -- one jit executable
    per (bucket, tile, l_cap).
    """
    pairs_s = np.asarray(pairs_s, dtype=np.int32)
    pairs_t = np.asarray(pairs_t, dtype=np.int32)
    vertices = np.asarray(vertices, dtype=np.int32)
    if pairs_s.shape != pairs_t.shape:
        raise ValueError("pairs_s and pairs_t must have equal length")
    n_v = vertices.shape[0]
    out = np.zeros(n_v, dtype=np.float64)
    if pairs_s.size == 0 or n_v == 0:
        return out
    cap = bucket_size(pairs_s.shape[0], buckets)
    s_pad = jnp.asarray(_pad_to(pairs_s, cap, idx.n))
    t_pad = jnp.asarray(_pad_to(pairs_t, cap, idx.n))
    tile = max(v_tiles)
    for lo in range(0, n_v, tile):
        chunk = vertices[lo:lo + tile]
        vcap = bucket_size(chunk.shape[0], v_tiles)
        v_pad = jnp.asarray(_pad_to(chunk, vcap, idx.n))
        dep = _dependency_block(idx, s_pad, t_pad, v_pad)
        out[lo:lo + chunk.shape[0]] = np.asarray(dep)[:chunk.shape[0]]
    return out


def all_pairs(n: int) -> Tuple[np.ndarray, np.ndarray]:
    """Every ordered pair (s, t), s != t -- the exact-BC workload."""
    s, t = np.where(~np.eye(n, dtype=bool))
    return s.astype(np.int32), t.astype(np.int32)


def betweenness(idx: SPCIndex, *,
                pairs: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                vertices: Optional[np.ndarray] = None,
                buckets: Sequence[int] = DEFAULT_BUCKETS,
                v_tiles: Sequence[int] = DEFAULT_V_TILES) -> np.ndarray:
    """Betweenness over a pair workload (default: exact, all ordered
    pairs) for ``vertices`` (default: all) -- float64 [len(vertices)]."""
    if pairs is None:
        pairs = all_pairs(idx.n)
    if vertices is None:
        vertices = np.arange(idx.n, dtype=np.int32)
    return dependency_scores(idx, pairs[0], pairs[1], vertices,
                             buckets=buckets, v_tiles=v_tiles)


# --------------------------------------------------------------------------
# Affected set: diff two published snapshots at the label-row level.
# --------------------------------------------------------------------------
def changed_rows(old: SPCIndex, new: SPCIndex) -> np.ndarray:
    """bool [n]: vertices whose label row differs between snapshots.

    DSPC updates rewrite only affected vertices' rows, so this recovers
    the update stream's affected set from the published artifacts alone
    -- no updater internals needed (replica-compatible).  Rows are
    compared in storage convention (hub-sorted, pad hub = n / dist =
    INF / cnt = 0), so a pure repad (capacity growth) changes nothing.
    """
    if old.n != new.n:
        raise ValueError(
            f"changed_rows requires equal n (got {old.n} vs {new.n}); "
            "vertex insert/delete invalidates the whole score set")
    n = old.n
    l_cap = max(old.l_cap, new.l_cap)

    def padded(idx: SPCIndex):
        hub = np.full((n, l_cap), n, dtype=np.int32)
        dist = np.full((n, l_cap), int(INF), dtype=np.int32)
        cnt = np.zeros((n, l_cap), dtype=np.int64)
        hub[:, :idx.l_cap] = np.asarray(idx.hub)[:n]
        dist[:, :idx.l_cap] = np.asarray(idx.dist)[:n]
        cnt[:, :idx.l_cap] = np.asarray(idx.cnt)[:n]
        return hub, dist, cnt

    ho, do_, co = padded(old)
    hn, dn, cn = padded(new)
    diff = ((ho != hn) | (do_ != dn) | (co != cn)).any(axis=1)
    diff |= (np.asarray(old.size)[:n] != np.asarray(new.size)[:n])
    return diff


class TopKBetweenness:
    """Incrementally maintained top-k betweenness over a fixed pair
    workload, fed by published snapshots.

    ``store`` is anything with ``.current() -> Snapshot`` (a
    ``SnapshotStore`` -- updater- or replica-side).  The constructor
    pins one snapshot and scores every candidate; :meth:`refresh` pins
    the newest snapshot and re-scores only

    * candidates in the affected set (:func:`changed_rows`), against
      the full workload, and
    * all candidates against workload pairs whose endpoint rows
      changed, as ``new - old`` contribution deltas off the previously
      pinned snapshot.

    When the affected fraction exceeds ``full_rescore_frac`` (or n
    changed) it falls back to a full recompute -- incremental work
    would exceed it.  Thread contract: any number of :meth:`top` /
    :meth:`scores` readers, ONE refresher; the score/snapshot swap is
    guarded by ``analytics.lock`` (a leaf: never held across a JAX
    dispatch or another acquisition).
    """

    def __init__(self, store, pairs: Tuple[np.ndarray, np.ndarray], *,
                 vertices: Optional[np.ndarray] = None, k: int = 16,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 v_tiles: Sequence[int] = DEFAULT_V_TILES,
                 full_rescore_frac: float = 0.5) -> None:
        self._store = store
        self._pairs_s = np.asarray(pairs[0], dtype=np.int32)
        self._pairs_t = np.asarray(pairs[1], dtype=np.int32)
        self.k = int(k)
        self._buckets = tuple(buckets)
        self._v_tiles = tuple(v_tiles)
        self._frac = float(full_rescore_frac)
        self._lock = make_lock("analytics.lock")
        snap = store.current()
        self._vertices = (np.arange(snap.index.n, dtype=np.int32)
                          if vertices is None
                          else np.asarray(vertices, dtype=np.int32))
        self.full_recomputes = 0
        self.incremental_refreshes = 0
        self.last_changed = 0
        scores = self._full(snap.index)
        with self._lock:
            self._snap = snap
            self._scores = scores

    # -- internals ----------------------------------------------------------
    def _full(self, idx: SPCIndex) -> np.ndarray:
        self.full_recomputes += 1
        return dependency_scores(idx, self._pairs_s, self._pairs_t,
                                 self._vertices, buckets=self._buckets,
                                 v_tiles=self._v_tiles)

    # -- readers ------------------------------------------------------------
    @property
    def version(self) -> int:
        """Version of the snapshot the current scores answer from.

        Lock-free: a single reference read of the immutable snapshot
        (``scores()`` / ``top()`` are the consistent-pair readers).
        """
        return self._snap.version  # analysis: ignore[unlocked-attr]

    def scores(self) -> np.ndarray:
        """A copy of the maintained score vector (aligned with the
        candidate set passed at construction)."""
        with self._lock:
            return self._scores.copy()

    def top(self, k: Optional[int] = None):
        """[(vertex, score)] sorted by score desc, id asc."""
        k = self.k if k is None else int(k)
        with self._lock:
            scores = self._scores
            verts = self._vertices
        order = np.lexsort((verts, -scores))[:k]
        return [(int(verts[i]), float(scores[i])) for i in order]

    # -- the refresher ------------------------------------------------------
    def refresh(self):
        """Catch the scores up to the newest published snapshot and
        return :meth:`top`.  No-op if the version did not move."""
        snap = self._store.current()
        with self._lock:
            old_snap = self._snap
            scores = self._scores.copy()
        if snap.version == old_snap.version:
            return self.top()
        old_idx, new_idx = old_snap.index, snap.index
        if new_idx.n != old_idx.n:
            scores = self._full(new_idx)
            self.last_changed = new_idx.n
        else:
            changed = changed_rows(old_idx, new_idx)
            self.last_changed = int(changed.sum())
            if self.last_changed > self._frac * new_idx.n:
                scores = self._full(new_idx)
            else:
                self.incremental_refreshes += 1
                v_changed = changed[self._vertices]
                p_changed = (changed[self._pairs_s]
                             | changed[self._pairs_t])
                if p_changed.any():
                    sc, tc = (self._pairs_s[p_changed],
                              self._pairs_t[p_changed])
                    dep_new = dependency_scores(
                        new_idx, sc, tc, self._vertices,
                        buckets=self._buckets, v_tiles=self._v_tiles)
                    dep_old = dependency_scores(
                        old_idx, sc, tc, self._vertices,
                        buckets=self._buckets, v_tiles=self._v_tiles)
                    scores = scores + np.where(v_changed, 0.0,
                                               dep_new - dep_old)
                if v_changed.any():
                    scores[v_changed] = dependency_scores(
                        new_idx, self._pairs_s, self._pairs_t,
                        self._vertices[v_changed],
                        buckets=self._buckets, v_tiles=self._v_tiles)
        with self._lock:
            self._snap = snap
            self._scores = scores
        return self.top()


# --------------------------------------------------------------------------
# Brandes-style numpy oracle (differential-test target).
# --------------------------------------------------------------------------
def betweenness_numpy(n: int, edges, *,
                      pairs: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                      vertices: Optional[np.ndarray] = None) -> np.ndarray:
    """Brute-force pair-dependency accumulation over BFS counts.

    Same definition as :func:`betweenness` (ordered pairs), computed
    from ``refimpl.bfs_spc`` alone -- no label index anywhere, so it is
    a genuine differential oracle for the jitted path.
    """
    g = refimpl.RefGraph(n, edges)
    if pairs is None:
        pairs = all_pairs(n)
    if vertices is None:
        vertices = np.arange(n, dtype=np.int32)
    src = {}
    for u in set(np.concatenate([pairs[0], pairs[1]]).tolist()):
        src[u] = refimpl.bfs_spc(g, int(u))
    vs = np.asarray(vertices, dtype=np.int64)
    bc = np.zeros(vs.shape[0], dtype=np.float64)
    for s, t in zip(pairs[0].tolist(), pairs[1].tolist()):
        dist_s, cnt_s = src[s]
        dist_t, cnt_t = src[t]          # sigma symmetric: undirected
        d_st = dist_s[t]
        if d_st >= refimpl.INF:
            continue
        sigma_st = float(cnt_s[t])
        on = ((dist_s[vs] + dist_t[vs] == d_st)
              & (vs != s) & (vs != t))
        bc += np.where(
            on,
            cnt_s[vs].astype(np.float64) * cnt_t[vs].astype(np.float64)
            / sigma_st,
            0.0)
    return bc
