"""SPC-Index query evaluation (Algorithm 1 and the PreQuery variant).

Two evaluation strategies, both O(1)-control-flow for XLA:

* ``pair_query`` -- label-row intersection by an L x L comparison table.
  Used for ad-hoc / batched (s, t) queries; this is also what the Pallas
  kernel ``repro.kernels.spc_query`` accelerates on TPU (the comparison
  table maps onto the VPU; blocks of pairs stream through VMEM).

* ``one_to_all`` -- the dense-source trick: scatter L(h) into a dense
  [n+1] (dist, cnt) table, then every row v evaluates its own labels
  against the table in O(L).  Used inside construction/updates where one
  hub is queried against all vertices (turns the per-level O(n L^2) of a
  naive transcription into O(n L) per hub, computed once per BFS).

Row-level cores (``gather_rows`` / ``merge_rows`` / ``table_rows`` /
``count_upper_bound_rows``) operate on *gathered* label rows so callers
that hold B (s, t) pairs gather each side exactly once and reuse the rows
across routing decisions and evaluation -- this is the contract of the
serving engine (``repro.serve``) and the sharded query path
(``repro.core.distributed``).

Counts keep the count contract (``repro.core.counts``): products and
sums over common hubs saturate at 2^63 - 1.  Each evaluation keeps
today's int64 arithmetic where the largest counts it reads prove no
product or sum can reach 2^63 (``counts.products_fit``), and takes the
saturating form under a ``lax.cond`` otherwise.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.counts import products_fit, sat_mul, sat_sum
from repro.core.graph import INF
from repro.core.labels import SPCIndex

_BIG = INF * 2  # > any real distance sum; int32-safe


def _hub_count(hit, a, b, wide: bool, axis=None):
    """Sum of ``a * b`` where ``hit``: int64, or saturating (``wide``)."""
    if wide:
        return sat_sum(jnp.where(hit, sat_mul(a, b), 0), axis=axis)
    return jnp.sum(jnp.where(hit, a * b, 0), axis=axis, dtype=jnp.int64)


def _intersect(hub_s, dist_s, cnt_s, hub_t, dist_t, cnt_t, limit,
               wide: bool = True):
    """Shared pair-intersection core; ``limit`` masks hubs >= limit
    (PreQuery); pass limit = n+1 for the full query.  ``wide=False``
    drops the saturation where the caller has proved it cannot bite."""
    eq = (hub_s[:, None] == hub_t[None, :]) & (hub_s[:, None] < limit)
    dsum = dist_s[:, None] + dist_t[None, :]
    dsum = jnp.where(eq, dsum, _BIG)
    d = jnp.min(dsum)
    c = _hub_count(dsum == d, cnt_s[:, None], cnt_t[None, :], wide)
    disconnected = d >= INF
    return (jnp.where(disconnected, INF, d).astype(jnp.int32),
            jnp.where(disconnected, 0, c))


def _intersect_merge(hub_s, dist_s, cnt_s, hub_t, dist_t, cnt_t,
                     wide: bool = True):
    """Sorted-merge intersection via searchsorted: O(L log L) ops and
    O(L) intermediates (vs the L x L table's O(L^2)).  Rows are sorted
    by hub id with pad = n (sorts last), so a binary probe of L(t) per
    label of L(s) finds every common hub.  SPerf cell-C it-1: cuts the
    dominant memory term ~20x on the query_batch cell."""
    l_cap = hub_t.shape[0]
    pos = jnp.searchsorted(hub_t, hub_s)
    pos_c = jnp.minimum(pos, l_cap - 1).astype(jnp.int32)
    match = hub_t[pos_c] == hub_s
    dsum = jnp.where(match, dist_s + dist_t[pos_c], _BIG)
    d = jnp.min(dsum)
    c = _hub_count(dsum == d, cnt_s, cnt_t[pos_c], wide)
    disconnected = d >= INF
    return (jnp.where(disconnected, INF, d).astype(jnp.int32),
            jnp.where(disconnected, 0, c))


def _keeps_contract(core, in_axes=None, name: str | None = None):
    """``core(hub_s, dist_s, cnt_s, hub_t, dist_t, cnt_t, *rest, wide)``
    (vmapped over rows with ``in_axes`` if given) as one evaluation that
    keeps the count contract: today's int64 arithmetic where the largest
    counts of its rows prove no product or sum of at most l_cap terms
    reaches 2^63 (``counts.products_fit``), the saturating form
    otherwise.  The test is one scalar over the whole batch, so a
    batched evaluation runs one of the two, never both.  ``name`` names
    the evaluation (and so its jitted executable)."""
    plain = partial(core, wide=False)
    wide = partial(core, wide=True)
    if in_axes is not None:
        plain = jax.vmap(plain, in_axes=in_axes)
        wide = jax.vmap(wide, in_axes=in_axes)

    def evaluate(hub_s, dist_s, cnt_s, hub_t, dist_t, cnt_t, *rest):
        fit = products_fit(jnp.max(cnt_s, initial=0),
                           jnp.max(cnt_t, initial=0), cnt_s.shape[-1])
        return jax.lax.cond(fit, plain, wide, hub_s, dist_s, cnt_s,
                            hub_t, dist_t, cnt_t, *rest)

    evaluate.__name__ = evaluate.__qualname__ = name or core.__name__
    return evaluate


_pair = _keeps_contract(_intersect)
_pair_merge = _keeps_contract(_intersect_merge)


def pair_query(idx: SPCIndex, s, t):
    """Algorithm 1: (dist, count) between s and t. Returns (INF, 0) if
    disconnected."""
    return _pair(
        idx.hub[s], idx.dist[s], idx.cnt[s],
        idx.hub[t], idx.dist[t], idx.cnt[t],
        jnp.int32(idx.n + 1))


def pair_query_merge(idx: SPCIndex, s, t):
    """Algorithm 1 by sorted merge (memory-optimal serving path)."""
    return _pair_merge(
        idx.hub[s], idx.dist[s], idx.cnt[s],
        idx.hub[t], idx.dist[t], idx.cnt[t])


def batched_query_merge(idx: SPCIndex, s, t):
    """:func:`pair_query_merge` over pair batches: one count-contract test
    for the whole batch (:func:`merge_rows`)."""
    return merge_rows(*gather_rows(idx, s), *gather_rows(idx, t))


# --------------------------------------------------------------------------
# Row-level cores: evaluate *gathered* label rows ([B, L] per operand).
# --------------------------------------------------------------------------
def gather_rows(idx: SPCIndex, v):
    """Label rows of vertices ``v``: (hub, dist, cnt), each [B, L_cap].

    Rows stay sorted by hub id (storage order) with pad ``hub = n``, so
    they feed ``merge_rows`` directly.
    """
    return idx.hub[v], idx.dist[v], idx.cnt[v]


#: Batched sorted-merge intersection over gathered rows (six [B, L]
#: operands -> (dist int32[B], cnt int64[B])).  The serving default.
#: Tolerates a t side whose pad sentinel was re-padded to n + 1 for the
#: Pallas kernel (real hub ids are < n, and n + 1 still sorts last).
merge_rows = _keeps_contract(_intersect_merge, in_axes=0, name="merge_rows")

#: One-dispatch variant for callers that already hold gathered rows.
merge_rows_jit = jax.jit(merge_rows)

#: Batched L x L comparison-table intersection over gathered rows; the
#: trailing ``limit`` is shared (pass n + 1 for the full query).  Same
#: arithmetic as the Pallas kernel but int64 under the count contract.
table_rows = _keeps_contract(_intersect, in_axes=(0, 0, 0, 0, 0, 0, None),
                             name="table_rows")


def count_upper_bound_rows(cnt_s, cnt_t):
    """Sound per-row upper bound on the pair count, [B] float64.

    ``SpcQuery(s, t).cnt = sum over common hubs of cnt_s * cnt_t`` and
    every term is non-negative, so ``sum(cnt_s) * sum(cnt_t)`` bounds the
    count AND every partial sum/product the fp32 kernel forms.  Rows whose
    bound stays below 2^24 are therefore provably exact on the fp32 path
    (pad entries carry cnt = 0 and do not inflate the bound).  float64 so
    the bound itself cannot overflow (exact to 2^53); each row's sum
    saturates at 2^63 - 1 rather than wrap.
    """
    tot_s = sat_sum(cnt_s, axis=1).astype(jnp.float64)
    tot_t = sat_sum(cnt_t, axis=1).astype(jnp.float64)
    return tot_s * tot_t


def cached_count_bound(idx: SPCIndex, s, t):
    """The same per-row bound as :func:`count_upper_bound_rows`, but from
    the index's cached per-vertex ``cnt_sum`` field: two O(1) lookups per
    row instead of an O(L) reduction per side.  The cache is maintained
    incrementally by every update engine (see ``repro.core.labels``), so
    a bound read off a published snapshot equals the bound recomputed
    from that snapshot's rows -- routing stays consistent across serving
    replicas mid-refresh.
    """
    return (idx.cnt_sum[s].astype(jnp.float64)
            * idx.cnt_sum[t].astype(jnp.float64))


def pre_pair_query(idx: SPCIndex, s, t):
    """PreQuery(s, t): only hubs ranked strictly higher than s."""
    return _pair(
        idx.hub[s], idx.dist[s], idx.cnt[s],
        idx.hub[t], idx.dist[t], idx.cnt[t],
        jnp.asarray(s, jnp.int32))


batched_query = jax.vmap(pair_query, in_axes=(None, 0, 0))


@partial(jax.jit, static_argnames=())
def batched_query_jit(idx: SPCIndex, s: jax.Array, t: jax.Array):
    return batched_query_merge(idx, s, t)


# --------------------------------------------------------------------------
# Dense one-vs-all queries.
# --------------------------------------------------------------------------
def dense_tables(idx: SPCIndex, h, limit=None):
    """Scatter L(h) into dense (dist, cnt) tables of shape [n + 1].

    ``limit`` (optional) drops entries of L(h) whose hub id >= limit
    (PreQuery restriction on the source side).
    """
    row_hub = idx.hub[h]
    row_dist = idx.dist[h]
    row_cnt = idx.cnt[h]
    if limit is not None:
        keep = row_hub < limit
        row_hub = jnp.where(keep, row_hub, jnp.int32(idx.n))  # scatter to dump
    dense_d = jnp.full(idx.n + 1, INF, dtype=jnp.int32).at[row_hub].set(row_dist)
    dense_c = jnp.zeros(idx.n + 1, dtype=jnp.int64).at[row_hub].set(row_cnt)
    # The dump slot may have been overwritten by masked/pad entries:
    dense_d = dense_d.at[idx.n].set(INF)
    dense_c = dense_c.at[idx.n].set(0)
    return dense_d, dense_c


def one_to_all(idx: SPCIndex, h, limit=None):
    """(dist[n+1], cnt[n+1]) = SpcQuery(h, v) for every v.

    With ``limit=h`` this evaluates PreQuery(h, v) for every v.
    """
    dense_d, dense_c = dense_tables(idx, h, limit)
    hubs = idx.hub            # [n+1, L]
    cand = dense_d[hubs] + idx.dist          # int32 [n+1, L]
    if limit is not None:
        cand = jnp.where(hubs < limit, cand, _BIG)
    cand = jnp.where(hubs < idx.n, cand, _BIG)   # drop pads
    d = jnp.min(cand, axis=1)
    # saturating only where the largest counts could reach 2^63
    c = jax.lax.cond(
        products_fit(jnp.max(idx.cnt), jnp.max(dense_c), idx.l_cap),
        partial(_hub_count, wide=False, axis=1),
        partial(_hub_count, wide=True, axis=1),
        cand == d[:, None], idx.cnt, dense_c[hubs])
    disconnected = d >= INF
    return (jnp.where(disconnected, INF, d).astype(jnp.int32),
            jnp.where(disconnected, 0, c))


def one_to_all_dists(idx: SPCIndex, roots, limit, cols: int = 64):
    """dist int32[R, n+1]: ``one_to_all(idx, r, limit)[0]`` for each root.

    The roots' dense distance tables sit side by side as [n + 1, R], so
    each label entry of v fetches one R-wide row, and the label axis is
    reduced ``cols`` columns at a time: the live candidates are
    [n + 1, cols, R] at any l_cap.  (Gathering [n + 1, L] once per root
    is several times slower on a TPU; a vmap over roots needs
    [n + 1, L, R] at once, past one chip's HBM at l_cap 512 for
    n = 65,536.)
    """
    n = idx.n
    dense = jax.vmap(lambda r: dense_tables(idx, r, limit)[0],
                     out_axes=1)(roots)                     # [n+1, R]
    l = idx.hub.shape[1]
    cols = min(cols, l)
    pad = -l % cols
    hub = jnp.pad(idx.hub, ((0, 0), (0, pad)), constant_values=n)
    dist = jnp.pad(idx.dist, ((0, 0), (0, pad)), constant_values=INF)

    def chunk(j, best):
        h = jax.lax.dynamic_slice_in_dim(hub, j * cols, cols, axis=1)
        cand = dense[h] + jax.lax.dynamic_slice_in_dim(
            dist, j * cols, cols, axis=1)[:, :, None]      # [n+1, cols, R]
        live = (h < limit) & (h < n)                        # drop pads
        cand = jnp.where(live[:, :, None], cand, _BIG)
        return jnp.minimum(best, jnp.min(cand, axis=1))

    best = jax.lax.fori_loop(0, (l + pad) // cols, chunk,
                             jnp.full(dense.shape, _BIG, jnp.int32))
    return jnp.where(best >= INF, INF, best).T.astype(jnp.int32)
