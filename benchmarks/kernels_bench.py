"""Kernel microbenchmarks (CPU wall-clock is indicative only; the
structural comparison -- op counts, shapes -- carries to TPU, see
EXPERIMENTS.md SPerf)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _bench(fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def query_kernel_vs_jnp(b=4096, l=64, seed=0):
    """Pallas spc_query vs the jnp intersection path (the kernel runs
    compiled on a TPU and in interpret mode elsewhere)."""
    from repro.kernels.spc_query.kernel import spc_query_pallas
    from repro.kernels.spc_query.ref import spc_query_ref
    r = np.random.default_rng(seed)
    hub = lambda: jnp.asarray(np.sort(r.integers(0, 500, (b, l))), jnp.int32)
    dist = lambda: jnp.asarray(r.integers(0, 20, (b, l)), jnp.int32)
    cnt = lambda: jnp.asarray(r.integers(1, 9, (b, l)), jnp.float32)
    args = (hub(), dist(), cnt(), hub(), dist(), cnt())
    t_ref = _bench(jax.jit(spc_query_ref), *args)
    t_pal = _bench(spc_query_pallas, *args)
    rows = [{"name": "spc_query", "batch": b, "l_cap": l,
             "jnp_us_per_q": round(t_ref / b * 1e6, 3),
             "pallas_us_per_q": round(t_pal / b * 1e6, 3),
             "device": jax.devices()[0].device_kind}]
    _print(rows)
    return rows


def query_kernel_vs_merge(b=1024, ls=(64, 256, 1024), n=65536, seed=0):
    """Pallas spc_query vs the int64 sorted-merge route on the same label
    rows: sorted hub ids skewed toward low ranks (as in a built index),
    a random fill of each row, pads as ``prep_rows`` lays them out.  The
    answers must be equal; rows stay under the 2^24 count bound."""
    from repro.core.graph import INF
    from repro.core.query import merge_rows_jit
    from repro.kernels.spc_query.ops import rows_query_pallas
    r = np.random.default_rng(seed)

    def side(l, pad):
        hub = np.sort((n * r.random((b, l)) ** 3).astype(np.int64), axis=1)
        dup = np.zeros_like(hub, bool)
        dup[:, 1:] = hub[:, 1:] == hub[:, :-1]
        fill = np.arange(l) >= r.integers(l // 4, l + 1, (b, 1))
        hub = np.sort(np.where(dup | fill, n, hub), axis=1)
        real = hub < n
        dist = np.where(real, r.integers(0, 12, (b, l)), int(INF))
        cnt = np.where(real, r.integers(1, 9, (b, l)), 0)
        return (jnp.asarray(np.where(real, hub, pad), jnp.int32),
                jnp.asarray(dist, jnp.int32), jnp.asarray(cnt, jnp.int64))

    rows = []
    for l in ls:
        args = side(l, n) + side(l, n + 1)
        d_k, c_k = rows_query_pallas(*args)
        d_m, c_m = merge_rows_jit(*args)
        if not (np.array_equal(d_k, d_m) and
                np.array_equal(np.asarray(c_k).astype(np.int64), c_m)):
            raise AssertionError(f"kernel and merge differ at L={l}")
        t_pal = _bench(rows_query_pallas, *args, iters=20)
        t_mrg = _bench(merge_rows_jit, *args, iters=20)
        rows.append({"name": "spc_query_vs_merge", "batch": b, "l_cap": l,
                     "pallas_ms": t_pal * 1e3, "merge_ms": t_mrg * 1e3,
                     "device": jax.devices()[0].device_kind})
    _print(rows)
    return rows


def segment_matmul_vs_segment_sum(e=16384, n=2048, d=128, seed=0):
    from repro.kernels.segment_matmul.kernel import segment_matmul_pallas
    r = np.random.default_rng(seed)
    vals = jnp.asarray(r.normal(size=(e, d)), jnp.float32)
    dst = jnp.asarray(np.sort(r.integers(0, n, e)), jnp.int32)
    f_ref = jax.jit(lambda v, s: jax.ops.segment_sum(v, s, num_segments=n))
    t_ref = _bench(f_ref, vals, dst)
    t_pal = _bench(lambda v, s: segment_matmul_pallas(
        v, s, num_segments=n), vals, dst)
    rows = [{"name": "segment_matmul", "edges": e, "nodes": n, "d": d,
             "segment_sum_ms": round(t_ref * 1e3, 3),
             "pallas_ms": round(t_pal * 1e3, 3),
             "device": jax.devices()[0].device_kind}]
    _print(rows)
    return rows


def _print(rows):
    cols = list(rows[0])
    print(",".join(cols))
    for r in rows:
        print(",".join(str(r[c]) for c in cols))
    print()
