"""Jit'd public wrappers: query an SPCIndex through the Pallas kernel.

Exactness contract: the kernel accumulates counts in fp32 (the TPU VPU
has no int64), which represents integers exactly only up to
``EXACT_COUNT_MAX = 2^24``.  ``index_query_batch`` therefore checks a
cheap per-row bound (``sum(cnt_s) * sum(cnt_t)``, which dominates the
true count and every fp32 partial sum -- see
``repro.core.query.count_upper_bound_rows``) and answers every row that
might exceed it on the int64 sorted-merge path instead of returning
silently wrong counts.  The bound is enforced *per row*: in a mixed
batch the kernel answers every row and one jitted dispatch re-answers
the unprovable rows in int64 and patches them in (route
``"pallas+merge"``); a batch where no row is provably exact degrades to
the all-merge fallback (route ``"pallas->merge"``).  ``exact=False``
restores the raw fp32 kernel contract for benchmarking.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.labels import SPCIndex
from repro.core.query import (cached_count_bound, gather_rows, merge_rows,
                              merge_rows_jit)
from repro.kernels.spc_query.kernel import spc_query_pallas
from repro.spans import span

#: Largest integer count the fp32 kernel is guaranteed to report exactly.
EXACT_COUNT_MAX = 2 ** 24


def prep_rows(idx: SPCIndex, s, t):
    """Gather the six label-row operands for a pair batch, kernel-ready.

    The sentinel hub id on the s side keeps its pad value (n) and the t
    side is re-padded to n + 1 so pad rows never produce spurious
    equality hits inside the L x L table.
    """
    hub_s, dist_s, cnt_s = gather_rows(idx, s)
    hub_t, dist_t, cnt_t = gather_rows(idx, t)
    hub_t = jnp.where(hub_t == idx.n, idx.n + 1, hub_t)
    return hub_s, dist_s, cnt_s, hub_t, dist_t, cnt_t


@jax.jit
def gather_rows_with_bounds(idx: SPCIndex, s, t):
    """One dispatch: kernel-ready rows + the per-row exactness bounds.

    The rows feed *either* the Pallas kernel or the int64 merge fallback
    (``merge_rows`` tolerates the re-padded t side), so the host-side
    per-row route decision costs one gather and one [B]-vector sync.
    The bound comes from the index's cached per-vertex ``cnt_sum`` field
    (O(1) per row; equal to ``count_upper_bound_rows`` on the gathered
    rows because the cache is maintained by every update engine).
    """
    return prep_rows(idx, s, t), cached_count_bound(idx, s, t)


def rows_query_pallas(hub_s, dist_s, cnt_s, hub_t, dist_t, cnt_t, *,
                      block_b: int = 128, interpret: bool | None = None):
    """Kernel entry on pre-gathered rows (t side already re-padded)."""
    return spc_query_pallas(
        hub_s.astype(jnp.int32), dist_s.astype(jnp.int32),
        cnt_s.astype(jnp.float32),
        hub_t.astype(jnp.int32), dist_t.astype(jnp.int32),
        cnt_t.astype(jnp.float32),
        block_b=block_b, interpret=interpret)


@jax.jit
def merge_patch(rows, iex, d, c):
    """Overwrite the kernel's answers (``d``, fp32 ``c``) at rows ``iex``
    with their int64 merge answers: one program gathers those rows, merges
    them and scatters the result.  ``iex`` may repeat an index (its merge
    writes the same value again), so the host pads it to a power of two
    and the program count stays one per padded length.
    Returns (dist int32[B], count int64[B])."""
    d_in, c_in = merge_rows(*(r[iex] for r in rows))
    return d.at[iex].set(d_in), c.astype(jnp.int64).at[iex].set(c_in)


def _pow2_at_least(k: int, floor: int = 8) -> int:
    p = floor
    while p < k:
        p *= 2
    return p


def exact_query_batch(idx: SPCIndex, s, t, *, block_b: int = 128,
                      interpret: bool | None = None,
                      real_rows: int | None = None):
    """THE exactness-routed kernel call, shared by ``index_query_batch``
    and the serving engine (through :func:`exact_query_split`): gather
    once, check the per-row bound, keep the fp32 kernel's answer for
    every row that is provably exact under it.

    ``real_rows`` (optional) marks the tail beyond it as padding whose
    answers the caller discards -- the serving engine bucket-pads with
    dump-row pairs (bound 0, trivially exact), and those must not drag
    an all-inexact real batch onto the kernel.  The route is decided on
    the real rows only; padding is never merged on a mixed batch.

    Returns (dist int32[B], count int64[B], route) with route one of
    ``"pallas"`` (all rows exact), ``"pallas+merge"`` (kernel on the
    whole batch, the rows over the bound patched from the int64 merge)
    or ``"pallas->merge"`` (no row provably exact; whole batch on the
    int64 fallback).
    """
    d, c, route, _ = exact_query_split(idx, s, t, block_b=block_b,
                                       interpret=interpret,
                                       real_rows=real_rows)
    return d, c, route


def exact_query_split(idx: SPCIndex, s, t, *, block_b: int = 128,
                      interpret: bool | None = None,
                      real_rows: int | None = None):
    """:func:`exact_query_batch`, also returning how many real rows the
    int64 merge answered: ``(dist, count, route, merged)``.  The host
    steps between its dispatches are ``spc.read.*`` spans
    (``repro.spans``)."""
    with span("spc.read.gather"):
        rows, bounds = gather_rows_with_bounds(idx, s, t)
    with span("spc.read.bound_wait"):
        inexact = np.asarray(bounds) >= EXACT_COUNT_MAX  # one host sync
    real = inexact if real_rows is None else inexact[:real_rows]
    if not real.any():
        with span("spc.read.kernel"):
            d, c = rows_query_pallas(*rows, block_b=block_b,
                                     interpret=interpret)
            c = c.astype(jnp.int64)
        return d, c, "pallas", 0
    if real.all():
        with span("spc.read.merge"):
            d, c = merge_rows_jit(*rows)
        return d, c, "pallas->merge", real.size
    # Mixed batch: the kernel answers the whole batch (each row on its
    # own, so the exact rows' answers are final), then one dispatch
    # re-answers the inexact rows in int64 and patches them in.  The
    # index vector is padded to a power of two with a repeat of a real
    # inexact row, so the merge compiles once per padded length.
    with span("spc.read.kernel"):
        d, c = rows_query_pallas(*rows, block_b=block_b,
                                 interpret=interpret)
    with span("spc.read.merge"):
        iex = np.nonzero(inexact)[0].astype(np.int32)
        iex = np.pad(iex, (0, _pow2_at_least(iex.size) - iex.size),
                     mode="edge")
        d, c = merge_patch(rows, iex, d, c)
    return d, c, "pallas+merge", int(real.sum())


def index_query_batch(idx: SPCIndex, s, t, *, block_b: int = 128,
                      interpret: bool | None = None, exact: bool = True):
    """Batched (s, t) queries against the label matrices.

    With ``exact=True`` (default) the per-row count bound is checked
    host-side: rows provably < 2^24 run through the fp32 kernel, the
    rest fall back to the int64 sorted-merge path; either way the result
    is (dist int32[B], count int64[B]).  ``exact=False`` skips the check
    and returns the kernel's raw (int32[B], float32[B]).
    """
    s = jnp.asarray(s)
    t = jnp.asarray(t)
    if exact:
        d, c, _ = exact_query_batch(idx, s, t, block_b=block_b,
                                    interpret=interpret)
        return d, c
    return rows_query_pallas(*prep_rows(idx, s, t), block_b=block_b,
                             interpret=interpret)
