"""The count contract: int64 path counts that saturate instead of wrapping.

A shortest-path count is right if and only if it equals
``min(true count, COUNT_MAX)`` with ``COUNT_MAX = 2^63 - 1``: the value
reads as "at least that many", and every count below it is exact.  The
label slot stays int64 and answers stay ``int64[B]``.  For x_i >= 0,
``min(sum min(x_i, S), S) = min(sum x_i, S)``, and likewise for
products, so an engine keeps the contract by clamping at each add and
each product -- the helpers below.

Each saturating helper costs a few times the plain int64 op, so the
engines keep today's arithmetic wherever a cheap bound proves no sum
can reach 2^63 (:func:`sums_fit`, :func:`products_fit`) and switch to
the saturating form under a ``lax.cond`` only where it does not.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

COUNT_MAX = 2 ** 63 - 1

#: Sums split each count into a high part (bits 31..62, < 2^32) and a
#: low part (< 2^31): summed separately, each stays exact for fewer
#: than 2^31 terms.
_LO_BITS = 31
_LO_MASK = (1 << _LO_BITS) - 1
_HI_LIMIT = 1 << (63 - _LO_BITS)    # hi * 2^31 reaches 2^63 from here
_MAX_TERMS = 1 << _LO_BITS


def _s64(x) -> jax.Array:
    return jnp.asarray(x, jnp.int64)


def _bit_length(x: jax.Array) -> jax.Array:
    """Bits of each non-negative int64 (0 for 0)."""
    return 64 - jax.lax.clz(_s64(x))


def sat_add(a, b) -> jax.Array:
    """``min(a + b, COUNT_MAX)`` for counts a, b in [0, COUNT_MAX]: the
    int64 sum wraps negative exactly when it passes COUNT_MAX."""
    s = _s64(a) + _s64(b)
    return jnp.where(s < 0, jnp.int64(COUNT_MAX), s)


def sat_mul(a, b) -> jax.Array:
    """``min(a * b, COUNT_MAX)`` for counts a, b in [0, COUNT_MAX].

    With bit lengths summing to at most 64 the product is below 2^64 and
    exact in uint64; past 64 it is at least 2^63."""
    a, b = _s64(a), _s64(b)
    p = a.astype(jnp.uint64) * b.astype(jnp.uint64)
    over = ((_bit_length(a) + _bit_length(b) > 64)
            | (p > jnp.uint64(COUNT_MAX)))
    return jnp.where(over, jnp.int64(COUNT_MAX), p.astype(jnp.int64))


def _join(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """``min(hi * 2^31 + lo, COUNT_MAX)`` for the split sums' parts
    (hi, lo >= 0 and below 2^63)."""
    over = hi >= _HI_LIMIT
    base = jnp.where(over, 0, hi) << _LO_BITS      # < 2^63
    total = base + lo                               # < 2^64: wraps negative
    return jnp.where(over | (total < 0), jnp.int64(COUNT_MAX), total)


def _split(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The high and low parts of counts in [0, COUNT_MAX]."""
    x = _s64(x)
    return x >> _LO_BITS, x & _LO_MASK


def _check_terms(terms: int) -> None:
    if terms >= _MAX_TERMS:
        raise ValueError(f"a saturating sum takes fewer than 2^31 terms, "
                         f"got {terms}")


def sat_sum(x: jax.Array, axis=-1) -> jax.Array:
    """``min(sum(x, axis), COUNT_MAX)`` for counts in [0, COUNT_MAX];
    ``axis=None`` sums every element."""
    x = _s64(x)
    if axis is None:
        x, axis = x.reshape(-1), -1
    _check_terms(x.shape[axis])
    hi, lo = _split(x)
    return _join(jnp.sum(hi, axis=axis), jnp.sum(lo, axis=axis))


def _segment_sums(x: jax.Array, seg: jax.Array, num_segments: int):
    """``segment_sum`` over the last axis of ``x``, any leading axes
    kept (vmapped): the relaxation's per-destination sums."""
    if x.ndim == 1:
        return jax.ops.segment_sum(x, seg, num_segments=num_segments)
    return jax.vmap(lambda r: _segment_sums(r, seg, num_segments))(x)


def sums_fit(max_term: jax.Array, terms: int) -> jax.Array:
    """True where a sum of at most ``terms`` counts, none above
    ``max_term``, provably stays below 2^63: ``max_term`` below
    ``2^(63 - terms.bit_length())``, a threshold fixed by the shape."""
    return _s64(max_term) < (1 << max(0, 63 - int(terms).bit_length()))


def products_fit(max_a: jax.Array, max_b: jax.Array, terms: int
                 ) -> jax.Array:
    """True where a sum of at most ``terms`` products ``a * b``, with
    ``a <= max_a`` and ``b <= max_b``, provably stays below 2^63: both
    below ``2^((63 - terms.bit_length()) // 2)``."""
    cap = 1 << max(0, (63 - int(terms).bit_length()) // 2)
    return (_s64(max_a) < cap) & (_s64(max_b) < cap)


def relax_sums(contrib: jax.Array, dst: jax.Array, num_segments: int,
               max_term: jax.Array, terms: int, total=None) -> jax.Array:
    """Per-destination sums of ``contrib`` (counts, last axis over the
    edge list ``dst``), saturated at COUNT_MAX.

    Where ``sums_fit(max_term, terms)`` holds -- ``max_term`` bounds
    every term and ``terms`` their number -- this is one plain
    ``segment_sum``; otherwise the counts are split and their high and
    low parts summed on their own, then joined with saturation.
    ``total`` (optional) combines the partial sums across devices (a
    ``psum``); it sees one operand either way, so a level still costs
    one collective."""
    _check_terms(terms)
    total = total if total is not None else (lambda x: x)

    def plain(c):
        return total(_segment_sums(c, dst, num_segments))

    def wide(c):
        hi, lo = _split(c)
        parts = total(jnp.stack([_segment_sums(hi, dst, num_segments),
                                 _segment_sums(lo, dst, num_segments)]))
        return _join(parts[0], parts[1])

    return jax.lax.cond(sums_fit(max_term, terms), plain, wide, contrib)
