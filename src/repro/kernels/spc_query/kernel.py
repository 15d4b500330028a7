"""Pallas TPU kernel: batched SPC-Index pair queries (Algorithm 1).

Serving hot path: given B (s, t) pairs with their label rows resident, the
kernel evaluates the hub intersection as an L x L comparison per pair -- a
dense VPU pattern replacing the paper's sorted merge-join (data-dependent
control flow does not map to the TPU vector unit).

Layout: the operands arrive transposed, [L, B], so the pair batch lies on
the 128 vector lanes and the label axis on sublanes.  A block holds
``block_b`` pairs (a multiple of 128) of all six operands; one
``fori_loop`` step takes the j-th t-side label of every pair ([1, block_b]),
compares it against the whole s-side row ([L, block_b]) and folds the
column's minimum distance and its count into running [1, block_b]
accumulators.  No [block_b, L, L] table is ever live: VMEM holds the six
operand blocks (6 * L * block_b * 4 bytes, 768 KiB at L = 256, double
buffered) plus a few [L, block_b] temporaries.

Counts are fp32 *in the kernel only* (TPU VPU has no int64): exact up to
2^24.  Callers must not serve this kernel's counts blind on dense/high-
multiplicity graphs -- ``ops.exact_query_split`` (behind
``ops.index_query_batch`` and the serving engine ``repro.serve``) reads
each row's fp32 count and re-answers on the int64 sorted-merge path
every row that reads 2^23 or more.  That is sound because of three facts
of this body: hubs in a row are unique, so a column adds at most one
term ``fl(fl(cs) * fl(ct))`` to its row's running sum; the minimum
distance is exact int32 arithmetic, so the set of hubs at the minimum
is exact; and the body uses the VPU only (no MXU, no bf16 passes).
Hence a true count C below 2^24 makes every label count, product and
partial sum an integer below 2^24 (real label counts are >= 1), so the
kernel reads exactly C whatever the order of the adds; and C >= 2^24
reads at least C (1 - 2^-24)^(L+3) >= 2^24 - (L + 3) > 2^23 for any L
below 2^22.  A product is at most 2^126, finite: only a sum can reach
+inf, and inf reads "2^23 or more" too.

All scalars in the body are explicit int32/fp32: the package enables
x64, and a weakly typed Python int would trace as int64, which Mosaic
cannot lower.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import ceil_div, pad_to, resolve_interpret

INF = 1 << 28
_BIG = INF * 2

#: The pair batch lies on the vector lanes: ``block_b`` must be a
#: positive multiple of this.
LANES = 128


def check_block_b(block_b) -> int:
    """Validate a kernel row-block size (a positive multiple of LANES)."""
    if not isinstance(block_b, int) or block_b <= 0 or block_b % LANES:
        raise ValueError(f"block_b must be a positive multiple of {LANES}, "
                         f"got {block_b!r}")
    return block_b


def _kernel(hub_s, dist_s, cnt_s, hub_t, dist_t, cnt_t, d_out, c_out):
    l = hub_t.shape[0]
    big = jnp.int32(_BIG)

    def column(j, carry):
        best_d, best_c = carry                       # [1, b] each
        row = pl.ds(j, 1)                            # j-th t label
        dsum = jnp.where(hub_s[...] == hub_t[row, :],
                         dist_s[...] + dist_t[row, :], big)   # [L, b]
        m = jnp.min(dsum, axis=0, keepdims=True)
        hit = (dsum == m) & (dsum < big)
        c = jnp.sum(jnp.where(hit, cnt_s[...], jnp.float32(0)), axis=0,
                    keepdims=True) * cnt_t[row, :]
        best_c = jnp.where(m < best_d, c,
                           jnp.where(m == best_d, best_c + c, best_c))
        return jnp.minimum(best_d, m), best_c

    shape = d_out.shape
    d, c = jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(l), column,
        (jnp.full(shape, big, jnp.int32), jnp.zeros(shape, jnp.float32)))
    connected = d < jnp.int32(INF)
    d_out[...] = jnp.where(connected, d, jnp.int32(INF))
    c_out[...] = jnp.where(connected, c, jnp.float32(0))


def spc_query_pallas(hub_s, dist_s, cnt_s, hub_t, dist_t, cnt_t,
                     *, block_b: int = 128, interpret: bool | None = None):
    """Batched pair query.

    Args:
      hub_s, hub_t: int32[B, L] label hub ids (pad rows with a sentinel
        whose dist is INF).
      dist_s, dist_t: int32[B, L] hub distances (pad INF).
      cnt_s, cnt_t: float32[B, L] hub counts (pad 0).
      block_b: pairs per grid step, a positive multiple of ``LANES``.
    Returns:
      (dist int32[B], count float32[B]); disconnected pairs -> (INF, 0).

    ``interpret`` resolves through ``resolve_interpret`` HERE,
    outside the jit boundary: flipping REPRO_PALLAS_INTERPRET takes
    effect on the next call instead of being baked into the first
    call's cached trace.
    """
    return _spc_query_jit(hub_s, dist_s, cnt_s, hub_t, dist_t, cnt_t,
                          block_b=check_block_b(block_b),
                          interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def _spc_query_jit(hub_s, dist_s, cnt_s, hub_t, dist_t, cnt_t,
                   *, block_b: int, interpret: bool):
    b, l = hub_s.shape
    bp = ceil_div(b, block_b) * block_b
    args = [pad_to(x.T, block_b, 1, value=pad) for x, pad in (
        (hub_s, 0), (dist_s, INF), (cnt_s, 0.0),
        (hub_t, 1), (dist_t, INF), (cnt_t, 0.0))]
    # the block index is (0, i): an int32 zero, not a Python 0 that x64
    # would trace as int64 into the index map
    col = pl.BlockSpec((l, block_b), lambda i: (i * 0, i))
    out = pl.BlockSpec((1, block_b), lambda i: (i * 0, i))
    d, c = pl.pallas_call(
        _kernel,
        grid=(bp // block_b,),
        in_specs=[col] * 6,
        out_specs=[out, out],
        out_shape=[
            jax.ShapeDtypeStruct((1, bp), jnp.int32),
            jax.ShapeDtypeStruct((1, bp), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return d[0, :b], c[0, :b]
