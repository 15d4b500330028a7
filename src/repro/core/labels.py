"""SPC-Index as fixed-capacity label matrices (a JAX pytree).

Each vertex row holds up to ``L_cap`` labels ``(hub, dist, cnt)`` sorted by
hub id ascending (= rank descending, the paper's storage order).  Padding:
``hub = n`` (sorts after every real hub), ``dist = INF``, ``cnt = 0``.

All mutation helpers are *bulk* and vectorized: they apply one hub's worth
of updates to every row at once under boolean masks.  This is the key
hardware adaptation -- the paper updates labels vertex-by-vertex during the
BFS; we exploit that (a) pruning distances are constant during one hub's
BFS (they only read labels of strictly higher-ranked hubs, or the pre-BFS
value of the row's own ``(h, .)`` entry) and (b) label writes of hub ``h``
only touch ``(h, .)`` entries, to defer all index writes of one BFS into a
single masked pass over the label matrices.

Capacity overflow is recorded in ``overflow`` (a counter); drivers re-pad
with a larger ``L_cap`` and retry (see ``repro.core.dynamic``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.counts import COUNT_MAX, sat_add, sat_sum
from repro.core.graph import INF


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SPCIndex:
    hub: jax.Array   # int32[n + 1, L_cap], pad = n
    dist: jax.Array  # int32[n + 1, L_cap], pad = INF
    cnt: jax.Array   # int64[n + 1, L_cap], pad = 0
    size: jax.Array  # int32[n + 1]
    cnt_sum: jax.Array   # int64[n + 1]: sum of the row's counts (see below)
    overflow: jax.Array  # int32 scalar: #lost label writes (grow & retry)
    n: int = dataclasses.field(metadata=dict(static=True))

    @property
    def l_cap(self) -> int:
        return self.hub.shape[1]

    def total_entries(self) -> jax.Array:
        return jnp.sum(self.size)


#: ``cnt_sum`` invariant -- ``cnt_sum[v] == min(sum(cnt[v]), 2^63 - 1)``
#: at all times (the count contract, ``repro.core.counts``).
#: ``sum(cnt[s]) * sum(cnt[t])`` is the serving engine's per-row fp32
#: exactness bound (``repro.core.query.count_upper_bound_rows``); caching
#: the per-vertex factor on the index turns the per-batch O(B L)
#: reduction into an O(1) lookup per row, and lets the bound travel with
#: a published snapshot so replicas route consistently mid-refresh.  The
#: four bulk mutation helpers below are the ONLY label writers -- every
#: update engine (IncSPC/DecSPC/HybSPC, replicated or edge-sharded) goes
#: through them -- so maintaining the delta here keeps the cache exact
#: everywhere (differential-tested against :func:`recompute_cnt_sum`).
#: Adds saturate; a saturated sum cannot be kept by subtraction, so a
#: row at 2^63 - 1 that loses or replaces a label is summed again from
#: its row (:func:`_resum`), which only ever runs where one is.


def recompute_cnt_sum(cnt: jax.Array) -> jax.Array:
    """The cached field from scratch (validation / legacy state dicts)."""
    return sat_sum(cnt, axis=1)


def _resum(cnt_sum: jax.Array, cnt: jax.Array, stale: jax.Array):
    """``cnt_sum`` with the rows of ``stale`` summed again from ``cnt``;
    the row sums run only when some row is stale."""
    return jax.lax.cond(
        jnp.any(stale),
        lambda: jnp.where(stale, recompute_cnt_sum(cnt), cnt_sum),
        lambda: cnt_sum)


def empty_index(n: int, l_cap: int) -> SPCIndex:
    return SPCIndex(
        hub=jnp.full((n + 1, l_cap), n, dtype=jnp.int32),
        dist=jnp.full((n + 1, l_cap), INF, dtype=jnp.int32),
        cnt=jnp.zeros((n + 1, l_cap), dtype=jnp.int64),
        size=jnp.zeros(n + 1, dtype=jnp.int32),
        cnt_sum=jnp.zeros(n + 1, dtype=jnp.int64),
        overflow=jnp.int32(0),
        n=n,
    )


def repad(idx: SPCIndex, new_cap: int) -> SPCIndex:
    """Host-side: grow label capacity (clears the overflow counter)."""
    if new_cap < idx.l_cap:
        raise ValueError("cannot shrink label capacity")
    pad = new_cap - idx.l_cap
    return SPCIndex(
        hub=jnp.pad(idx.hub, ((0, 0), (0, pad)), constant_values=idx.n),
        dist=jnp.pad(idx.dist, ((0, 0), (0, pad)), constant_values=int(INF)),
        cnt=jnp.pad(idx.cnt, ((0, 0), (0, pad)), constant_values=0),
        size=idx.size,
        cnt_sum=idx.cnt_sum,  # pad entries carry cnt = 0
        overflow=jnp.int32(0),
        n=idx.n,
    )


def add_vertices(idx: SPCIndex, count: int) -> SPCIndex:
    """Host-side: append ``count`` fresh vertices (each gets a self label).

    Mirrors ``graph.add_vertices``: the dump row moves to the end and the
    pad sentinel becomes ``n + count``.
    """
    n_new = idx.n + count
    hub = np.asarray(idx.hub)
    hub = np.where(hub == idx.n, n_new, hub).astype(np.int32)
    l_cap = idx.l_cap
    new_hub = np.full((n_new + 1, l_cap), n_new, dtype=np.int32)
    new_dist = np.full((n_new + 1, l_cap), int(INF), dtype=np.int32)
    new_cnt = np.zeros((n_new + 1, l_cap), dtype=np.int64)
    new_size = np.zeros(n_new + 1, dtype=np.int32)
    new_cnt_sum = np.zeros(n_new + 1, dtype=np.int64)
    new_hub[: idx.n] = hub[: idx.n]
    new_dist[: idx.n] = np.asarray(idx.dist)[: idx.n]
    new_cnt[: idx.n] = np.asarray(idx.cnt)[: idx.n]
    new_size[: idx.n] = np.asarray(idx.size)[: idx.n]
    new_cnt_sum[: idx.n] = np.asarray(idx.cnt_sum)[: idx.n]
    for k in range(count):  # self labels for the new vertices
        v = idx.n + k
        new_hub[v, 0] = v
        new_dist[v, 0] = 0
        new_cnt[v, 0] = 1
        new_size[v] = 1
        new_cnt_sum[v] = 1
    return SPCIndex(
        hub=jnp.asarray(new_hub), dist=jnp.asarray(new_dist),
        cnt=jnp.asarray(new_cnt), size=jnp.asarray(new_size),
        cnt_sum=jnp.asarray(new_cnt_sum),
        overflow=idx.overflow, n=n_new,
    )


# --------------------------------------------------------------------------
# Bulk label mutations for one hub h (vectorized over all rows).
# --------------------------------------------------------------------------
def bulk_append(idx: SPCIndex, h, d_new, c_new, mask) -> SPCIndex:
    """Append label (h, d_new[v], c_new[v]) to every row v with mask[v].

    Only valid during construction where hubs arrive in ascending id order
    (append keeps rows sorted).
    """
    rows = jnp.arange(idx.n + 1)
    col = jnp.minimum(idx.size, idx.l_cap - 1)
    fits = mask & (idx.size < idx.l_cap)
    lost = mask & ~fits
    hub = idx.hub.at[rows, col].set(
        jnp.where(fits, jnp.asarray(h, jnp.int32), idx.hub[rows, col]))
    dist = idx.dist.at[rows, col].set(
        jnp.where(fits, d_new.astype(jnp.int32), idx.dist[rows, col]))
    cnt = idx.cnt.at[rows, col].set(
        jnp.where(fits, c_new.astype(jnp.int64), idx.cnt[rows, col]))
    size = idx.size + fits.astype(jnp.int32)
    cnt_sum = sat_add(idx.cnt_sum, jnp.where(fits, c_new, 0))
    return dataclasses.replace(
        idx, hub=hub, dist=dist, cnt=cnt, size=size, cnt_sum=cnt_sum,
        overflow=idx.overflow + jnp.sum(lost, dtype=jnp.int32))


def bulk_append_batch(idx: SPCIndex, h0, d_new, c_new, mask) -> SPCIndex:
    """Append one whole hub batch's labels in a single masked scatter.

    ``d_new`` / ``c_new`` / ``mask`` carry a leading hub-batch axis
    [B, n + 1]; lane ``b`` holds the BFS result of hub ``h0 + b``.  For
    every row v the labels of kept lanes land at columns
    ``size[v] + rank-within-row`` in ascending lane order -- exactly the
    state B sequential :func:`bulk_append` calls in ascending hub order
    would produce, including the overflow accounting: column offsets
    only grow along the lane axis, so the lanes that fit are precisely
    the first ``l_cap - size[v]`` kept ones, and everything later in
    the row is counted lost (grow & retry, as ever).  ``cnt_sum`` is
    maintained incrementally from the same fit mask.

    Only valid during construction where batches arrive in ascending
    hub-id order (append keeps rows sorted); hub ids ``h0 + b >= n``
    (inactive tail lanes) must arrive fully unmasked.
    """
    b = mask.shape[0]
    rank = jnp.cumsum(mask.astype(jnp.int32), axis=0) - 1   # [B, n+1]
    col = idx.size[None, :] + jnp.where(mask, rank, 0)
    fits = mask & (col < idx.l_cap)
    lost = mask & ~fits
    rows = jnp.broadcast_to(jnp.arange(idx.n + 1)[None, :], mask.shape)
    # non-fitting lanes scatter to column l_cap: out of bounds, dropped
    cols = jnp.where(fits, col, idx.l_cap)
    hubs = jnp.broadcast_to(
        jnp.asarray(h0, jnp.int32) + jnp.arange(b, dtype=jnp.int32)[:, None],
        mask.shape)
    c64 = c_new.astype(jnp.int64)
    hub = idx.hub.at[rows, cols].set(hubs, mode="drop")
    dist = idx.dist.at[rows, cols].set(d_new.astype(jnp.int32), mode="drop")
    cnt = idx.cnt.at[rows, cols].set(c64, mode="drop")
    size = idx.size + jnp.sum(fits, axis=0, dtype=jnp.int32)
    cnt_sum = sat_add(idx.cnt_sum, sat_sum(jnp.where(fits, c64, 0), axis=0))
    return dataclasses.replace(
        idx, hub=hub, dist=dist, cnt=cnt, size=size, cnt_sum=cnt_sum,
        overflow=idx.overflow + jnp.sum(lost, dtype=jnp.int32))


def bulk_upsert(idx: SPCIndex, h, d_new, c_new, mask) -> SPCIndex:
    """Replace-or-sorted-insert label (h, d_new[v], c_new[v]) where mask[v].

    For rows that already contain hub h the entry is overwritten in place;
    otherwise the row is shifted right at the insertion point.
    """
    h = jnp.asarray(h, jnp.int32)
    eq = idx.hub == h                              # [n+1, L]
    has = jnp.any(eq, axis=1)                      # [n+1]
    rows_i = jnp.arange(idx.n + 1)
    old_c = idx.cnt[rows_i, jnp.argmax(eq, axis=1)]  # (h, .) value, if any
    # --- replace path -----------------------------------------------------
    rep = (mask & has)[:, None] & eq
    dist = jnp.where(rep, d_new[:, None].astype(jnp.int32), idx.dist)
    cnt = jnp.where(rep, c_new[:, None].astype(jnp.int64), idx.cnt)
    # --- insert path (shift right at pos) ----------------------------------
    ins = mask & ~has
    fits = ins & (idx.size < idx.l_cap)
    lost = ins & ~fits
    pos = jnp.sum((idx.hub < h).astype(jnp.int32), axis=1)  # sorted position
    cols = jnp.arange(idx.l_cap)[None, :]
    posb = pos[:, None]
    fitsb = fits[:, None]
    shift_src = jnp.maximum(cols - 1, 0)
    take = jnp.take_along_axis
    hub_sh = take(idx.hub, shift_src[0][None, :].repeat(idx.n + 1, 0), axis=1)
    dist_sh = take(dist, shift_src[0][None, :].repeat(idx.n + 1, 0), axis=1)
    cnt_sh = take(cnt, shift_src[0][None, :].repeat(idx.n + 1, 0), axis=1)
    hub = jnp.where(
        fitsb,
        jnp.where(cols < posb, idx.hub,
                  jnp.where(cols == posb, h, hub_sh)),
        idx.hub)
    dist = jnp.where(
        fitsb,
        jnp.where(cols < posb, dist,
                  jnp.where(cols == posb, d_new[:, None].astype(jnp.int32),
                            dist_sh)),
        dist)
    cnt = jnp.where(
        fitsb,
        jnp.where(cols < posb, cnt,
                  jnp.where(cols == posb, c_new[:, None].astype(jnp.int64),
                            cnt_sh)),
        cnt)
    size = idx.size + fits.astype(jnp.int32)
    c64 = c_new.astype(jnp.int64)
    replaced = mask & has
    # replaced in place: the old value comes off an exact sum; freshly
    # inserted: a saturated sum stays saturated
    cnt_sum = sat_add(jnp.where(replaced, idx.cnt_sum - old_c, idx.cnt_sum),
                      jnp.where(replaced | fits, c64, 0))
    cnt_sum = _resum(cnt_sum, cnt, replaced & (idx.cnt_sum == COUNT_MAX))
    return dataclasses.replace(
        idx, hub=hub, dist=dist, cnt=cnt, size=size, cnt_sum=cnt_sum,
        overflow=idx.overflow + jnp.sum(lost, dtype=jnp.int32))


def bulk_remove(idx: SPCIndex, h, mask) -> SPCIndex:
    """Remove label with hub h (shift left) from every row v with mask[v]."""
    h = jnp.asarray(h, jnp.int32)
    eq = idx.hub == h
    has = jnp.any(eq, axis=1)
    act = mask & has
    pos = jnp.argmax(eq, axis=1)                   # position of h (if any)
    cols = jnp.arange(idx.l_cap)[None, :]
    posb = pos[:, None]
    actb = act[:, None]
    nxt = jnp.minimum(cols + 1, idx.l_cap - 1)
    take = jnp.take_along_axis
    idxs = nxt[0][None, :].repeat(idx.n + 1, 0)
    hub_sh = take(idx.hub, idxs, axis=1)
    dist_sh = take(idx.dist, idxs, axis=1)
    cnt_sh = take(idx.cnt, idxs, axis=1)
    last = cols == idx.l_cap - 1
    hub = jnp.where(actb & (cols >= posb),
                    jnp.where(last, jnp.int32(idx.n), hub_sh), idx.hub)
    dist = jnp.where(actb & (cols >= posb),
                     jnp.where(last, INF, dist_sh), idx.dist)
    cnt = jnp.where(actb & (cols >= posb),
                    jnp.where(last, jnp.int64(0), cnt_sh), idx.cnt)
    size = idx.size - act.astype(jnp.int32)
    rows = jnp.arange(idx.n + 1)
    cnt_sum = idx.cnt_sum - jnp.where(act, idx.cnt[rows, pos], 0)
    cnt_sum = _resum(cnt_sum, cnt, act & (idx.cnt_sum == COUNT_MAX))
    return dataclasses.replace(idx, hub=hub, dist=dist, cnt=cnt, size=size,
                               cnt_sum=cnt_sum)


def reset_isolated_row(idx: SPCIndex, v) -> SPCIndex:
    """Collapse row ``v`` to its self label (Section 3.2.3: a vertex
    isolated by deleting its last edge keeps only ``(v, 0, 1)``).

    Traced-compatible; shared by the host driver's fast path and the
    batched engines so both produce bit-identical indexes.
    """
    v = jnp.asarray(v, jnp.int32)
    row_hub = jnp.full(idx.l_cap, idx.n, jnp.int32).at[0].set(v)
    row_dist = jnp.full(idx.l_cap, INF, jnp.int32).at[0].set(0)
    row_cnt = jnp.zeros(idx.l_cap, jnp.int64).at[0].set(1)
    return dataclasses.replace(
        idx,
        hub=idx.hub.at[v].set(row_hub),
        dist=idx.dist.at[v].set(row_dist),
        cnt=idx.cnt.at[v].set(row_cnt),
        size=idx.size.at[v].set(1),
        cnt_sum=idx.cnt_sum.at[v].set(1),
    )


def get_label(idx: SPCIndex, v, h):
    """(found, dist, cnt) of label (h, ., .) in row v (traced)."""
    row_hub = idx.hub[v]
    eq = row_hub == jnp.asarray(h, jnp.int32)
    found = jnp.any(eq)
    pos = jnp.argmax(eq)
    return found, idx.dist[v, pos], idx.cnt[v, pos]


# --------------------------------------------------------------------------
# Conversions (host-side, for tests and benchmarks).
# --------------------------------------------------------------------------
def to_ref(idx: SPCIndex):
    from repro.core.refimpl import RefSPCIndex

    ref = RefSPCIndex(idx.n)
    hub = np.asarray(idx.hub)
    dist = np.asarray(idx.dist)
    cnt = np.asarray(idx.cnt)
    size = np.asarray(idx.size)
    for v in range(idx.n):
        ref.labels[v] = [
            (int(hub[v, j]), int(dist[v, j]), int(cnt[v, j]))
            for j in range(size[v])
        ]
    return ref


def from_ref(ref, l_cap: int | None = None) -> SPCIndex:
    n = len(ref.labels)
    max_len = max((len(r) for r in ref.labels), default=1)
    if l_cap is None:
        l_cap = max(4, max_len)
    if max_len > l_cap:
        raise ValueError(f"l_cap={l_cap} < max label size {max_len}")
    idx = empty_index(n, l_cap)
    hub = np.asarray(idx.hub).copy()
    dist = np.asarray(idx.dist).copy()
    cnt = np.asarray(idx.cnt).copy()
    size = np.asarray(idx.size).copy()
    for v, row in enumerate(ref.labels):
        for j, (h, d, c) in enumerate(row):
            hub[v, j], dist[v, j], cnt[v, j] = h, d, min(c, COUNT_MAX)
        size[v] = len(row)
    return SPCIndex(hub=jnp.asarray(hub), dist=jnp.asarray(dist),
                    cnt=jnp.asarray(cnt), size=jnp.asarray(size),
                    cnt_sum=recompute_cnt_sum(jnp.asarray(cnt)),
                    overflow=jnp.int32(0), n=n)
