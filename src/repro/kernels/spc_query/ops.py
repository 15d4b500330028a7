"""Jit'd public wrappers: query an SPCIndex through the Pallas kernel.

Exactness contract: the kernel accumulates counts in fp32 (the TPU VPU
has no int64), which represents integers exactly only up to
``EXACT_COUNT_MAX = 2^24``.  ``index_query_batch`` therefore runs the
kernel on the whole batch and decides each row's exactness from the
kernel's own fp32 answer: a real row that reads ``PATCH_AT = 2^23`` or
more (or inf) is re-answered on the int64 sorted-merge path and patched
in by one jitted dispatch (route ``"pallas+merge"``); a batch with no
such row keeps the kernel's answers (route ``"pallas"``).  A row that
reads under 2^23 is exact (see :func:`exact_query_split` for why), so
no count is ever served rounded.  ``exact=False`` restores the raw fp32
kernel contract for benchmarking.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.labels import SPCIndex
from repro.core.query import gather_rows, merge_rows
from repro.kernels.spc_query.kernel import spc_query_pallas
from repro.spans import span

#: Largest integer count the fp32 kernel is guaranteed to report exactly.
EXACT_COUNT_MAX = 2 ** 24
#: A real row whose fp32 kernel count is not below this is re-answered in
#: int64; a count under it is exact (:func:`exact_query_split`).
PATCH_AT = 2 ** 23


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


#: One dispatch of :func:`prep_rows`: the rows feed the kernel and, for
#: the rows it patches, the int64 merge (``merge_rows`` tolerates the
#: re-padded t side).
prep_rows_jit = jax.jit(prep_rows)


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
    and the serving engine (through :func:`exact_query_split`).

    ``real_rows`` (optional) marks the tail beyond it as padding whose
    answers the caller discards -- the serving engine bucket-pads with
    dump-row pairs -- and padding is never patched.

    Returns (dist int32[B], count int64[B], route) with route
    ``"pallas"`` (the kernel's answers, no real row read 2^23 or more)
    or ``"pallas+merge"`` (kernel on the whole batch, the real rows that
    read 2^23 or more patched from the int64 merge).
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
    (``repro.spans``).

    The gather and the kernel are dispatched back to back; the one host
    sync is on the kernel's fp32 counts (``spc.read.count_wait``).  A
    real row is patched from the int64 merge iff its count ``c`` fails
    ``c < PATCH_AT`` (so inf and NaN are patched too).  Every row left
    unpatched is exact under the count contract, because the kernel
    (VPU only: no MXU, no bf16 passes) adds, within a row, at most one
    term ``fl(fl(cs) * fl(ct))`` per column (hubs in a row are unique),
    and picks the hubs at the minimum distance in exact int32:

    - a true count C < 2^24: every label count is >= 1, so every label
      count, product and partial sum of the row is an integer below 2^24
      and exact in fp32; the kernel reads R = C;
    - C >= 2^24: each cast, product and add loses at most a factor
      (1 - 2^-24), over at most L + 3 of them in the row's chain, so
      R >= C (1 - 2^-24)^(L+3) >= 2^24 - (L + 3) > 2^23 for L < 2^22;
    - a product is at most 2^126, finite; only a sum reaches +inf, and
      inf fails ``c < PATCH_AT``.

    So R < 2^23 implies C < 2^24, which implies R = C.  No count bound
    is consulted.
    """
    with span("spc.read.gather"):
        rows = prep_rows_jit(idx, s, t)
    with span("spc.read.kernel"):
        d, c = rows_query_pallas(*rows, block_b=block_b,
                                 interpret=interpret)
    with span("spc.read.count_wait"):
        counts = np.asarray(c)                       # one host sync
    real = counts if real_rows is None else counts[:real_rows]
    iex = np.flatnonzero(~(real < PATCH_AT)).astype(np.int32)
    if not iex.size:
        with span("spc.read.kernel"):
            c = c.astype(jnp.int64)
        return d, c, "pallas", 0
    # One dispatch re-answers the patched rows in int64 and scatters them
    # into the kernel's answers.  The index vector is padded to a power
    # of two with a repeat of a real patched row, so the merge compiles
    # once per padded length.
    with span("spc.read.merge"):
        k = iex.size
        iex = np.pad(iex, (0, _pow2_at_least(k) - k), mode="edge")
        d, c = merge_patch(rows, iex, d, c)
    return d, c, "pallas+merge", k


def index_query_batch(idx: SPCIndex, s, t, *, block_b: int = 128,
                      interpret: bool | None = None, exact: bool = True):
    """Batched (s, t) queries against the label matrices.

    With ``exact=True`` (default) the kernel answers every row and the
    rows whose fp32 count reads 2^23 or more are re-answered in int64
    (:func:`exact_query_split`); the result is (dist int32[B], count
    int64[B]).  ``exact=False`` skips the patch and returns the kernel's
    raw (int32[B], float32[B]).
    """
    s = jnp.asarray(s)
    t = jnp.asarray(t)
    if exact:
        d, c, _ = exact_query_batch(idx, s, t, block_b=block_b,
                                    interpret=interpret)
        return d, c
    return rows_query_pallas(*prep_rows(idx, s, t), block_b=block_b,
                             interpret=interpret)
