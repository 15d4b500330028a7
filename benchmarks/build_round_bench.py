"""Time batched-build rounds under three ways of computing a round's
pruning distances.

Each round of ``build_index_batched`` first computes, for every hub of
the round, its PreQuery distance to all vertices, then runs the lockstep
pruned BFS.  The forms compared:

- ``chunked``: the builder's own round (``one_to_all_dists``: all roots
  at once, the label axis reduced a column chunk at a time);
- ``per_root``: ``one_to_all`` once per root under ``lax.map``;
- ``vmap``: ``one_to_all`` vmapped over the roots (needs [n + 1, L, R]
  at once: past one v5e chip's HBM at l_cap 512 for n = 65,536).

Each form builds the same hub range [0, hubs) at a fixed label capacity
``l_cap >= hubs`` (so no round can overflow and regrow), after one
warm-up round that pays the compile; every form must build the same
labels.  A form may be named twice to time it again (A, B, B, A).

    PYTHONPATH=src python -m benchmarks.build_round_bench --n 8192 \\
        --m 65536 --l-caps 128,256 --hubs 256 --forms vmap,chunked,per_root
"""

from __future__ import annotations

import argparse
import json
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bfs import multi_pruned_spc_bfs
from repro.core.construct import _hub_batch_round
from repro.core.labels import bulk_append_batch, empty_index
from repro.core.query import one_to_all


def _round_with(dbar_fn):
    @partial(jax.jit, static_argnames=("hub_batch",))
    def round_(g, idx, h0, hub_batch):
        h0 = jnp.asarray(h0, jnp.int32)
        roots = h0 + jnp.arange(hub_batch, dtype=jnp.int32)
        dbar = dbar_fn(idx, jnp.minimum(roots, jnp.int32(g.n)), h0)
        res = multi_pruned_spc_bfs(g, roots, dbar)
        return (bulk_append_batch(idx, h0, res.dist, res.cnt, res.keep),
                res.levels)
    return round_


def _one(idx, h0):
    return lambda r: one_to_all(idx, r, limit=h0)[0]


FORMS = {
    "chunked": _hub_batch_round,
    "per_root": _round_with(lambda idx, roots, h0: jax.lax.map(
        _one(idx, h0), roots)),
    "vmap": _round_with(lambda idx, roots, h0: jax.vmap(
        _one(idx, h0))(roots)),
}


def round_seconds(round_fn, g, l_cap: int, hubs: int, hub_batch: int):
    """(seconds for rounds over hubs [0, hubs), warm-up seconds, index)."""
    idx = empty_index(g.n, l_cap)
    t0 = time.perf_counter()
    jax.block_until_ready(round_fn(g, idx, 0, hub_batch))
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for h0 in range(0, hubs, hub_batch):
        idx, _ = round_fn(g, idx, h0, hub_batch)
    jax.block_until_ready(idx)
    return time.perf_counter() - t0, warm_s, idx


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=8192)
    ap.add_argument("--m", type=int, default=65536)
    ap.add_argument("--l-caps", default="128,256")
    ap.add_argument("--hubs", type=int, default=128)
    ap.add_argument("--hub-batch", type=int, default=32)
    ap.add_argument("--forms", default="chunked,per_root,vmap")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    forms = args.forms.split(",")
    unknown = set(forms) - set(FORMS)
    if unknown:
        raise SystemExit(f"unknown form(s) {sorted(unknown)}; "
                         f"known: {sorted(FORMS)}")

    from repro.compile_cache import enable_compile_cache
    from repro.core.graph import from_edges
    from repro.data import random_graph_edges

    enable_compile_cache()
    g = from_edges(args.n, random_graph_edges(args.n, args.m,
                                              seed=args.seed))
    device = jax.devices()[0].device_kind
    for l_cap in (int(x) for x in args.l_caps.split(",")):
        hubs = min(args.hubs, l_cap, args.n)
        ref = None
        for form in forms:
            secs, warm_s, idx = round_seconds(FORMS[form], g, l_cap, hubs,
                                              args.hub_batch)
            if int(idx.overflow):
                raise SystemExit(f"l_cap {l_cap} overflowed within {hubs} "
                                 f"hubs")
            leaves = [np.asarray(x) for x in (idx.hub, idx.dist, idx.cnt)]
            if ref is None:
                ref = leaves
            if not all(np.array_equal(a, b) for a, b in zip(ref, leaves)):
                raise SystemExit(f"{form} built different labels")
            print(json.dumps({
                "n": args.n, "m": args.m, "l_cap": l_cap, "hubs": hubs,
                "hub_batch": args.hub_batch, "form": form,
                "seconds": secs,
                "ms_per_round": 1e3 * secs / -(-hubs // args.hub_batch),
                "warm_up_round_s": warm_s,
                "entries": int(np.sum(np.asarray(idx.size)[:args.n])),
                "device": device}), flush=True)


if __name__ == "__main__":
    main()
