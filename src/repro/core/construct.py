"""HP-SPC index construction (Section 2.2) -- sequential and batched.

Two builders share the pruned-BFS machinery of ``repro.core.bfs``:

* :func:`build_index` -- the paper-faithful sequential builder: one hub
  at a time, fully jitted (one ``fori_loop`` over all n hubs).  Kept as
  the differential oracle for everything below.

* :func:`build_index_batched` -- PSPC-style batched construction
  (arXiv:2212.00977): ``hub_batch`` hubs run their pruned BFS *in
  lockstep* inside one jitted ``lax.while_loop``
  (:func:`repro.core.bfs.multi_pruned_spc_bfs`), pruning against the
  labels committed by all earlier batches plus rank-masked in-batch
  pruning, and commit a whole batch of labels in one bulk scatter
  (:func:`repro.core.labels.bulk_append_batch`).  The result is
  order-identical to the sequential builder on the same graph -- only
  the schedule changes.  The hub-batch outer loop is host-driven so a
  capacity overflow retries *from the pre-round snapshot* (the update
  engines' pre-chunk-snapshot pattern) instead of failing mid-build.

Vertex-ordering strategies (``order="degree"|"id"``) plug in by
relabeling the graph into rank space (see ``repro.core.order``); the
rank == id invariant of every engine is untouched.

The relaxation primitive is pluggable (see ``repro.core.bfs.RelaxFn`` /
``MultiRelaxFn``): ``build_index(..., relax_fn=...)`` or
``build_index_batched(..., multi_relax_fn=...)`` with the edge-sharded
relaxations from ``repro.core.distributed`` ARE the distributed
builders -- there is no separate construction loop.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bfs import (MultiRelaxFn, RelaxFn, multi_pruned_spc_bfs,
                            pruned_spc_bfs)
from repro.core.graph import Graph, degrees
from repro.core.labels import (SPCIndex, bulk_append, bulk_append_batch,
                               empty_index, repad)
from repro.core.order import graph_ordering, relabel_graph
from repro.core.query import one_to_all, one_to_all_dists
from repro.spans import span


def _hub_round(g: Graph, idx: SPCIndex, v,
               relax_fn: RelaxFn | None = None) -> SPCIndex:
    dbar, _ = one_to_all(idx, v, limit=v)  # PreQuery(v, .) for every vertex
    res = pruned_spc_bfs(g, v, 0, 1, dbar, rank_floor=v, relax_fn=relax_fn)
    return bulk_append(idx, v, res.dist, res.cnt, res.keep)


@partial(jax.jit, static_argnames=("l_cap", "relax_fn"))
def build_index(g: Graph, l_cap: int,
                relax_fn: RelaxFn | None = None) -> SPCIndex:
    """Construct the SPC-Index of ``g`` with label capacity ``l_cap``.

    Returns an index whose ``overflow`` field is > 0 if any label did not
    fit; callers should then retry with a larger ``l_cap`` (see
    ``repro.core.dynamic.DynamicSPC`` and :func:`provision_l_cap`).
    """
    idx0 = empty_index(g.n, l_cap)
    body = lambda v, idx: _hub_round(g, idx, v, relax_fn)
    return jax.lax.fori_loop(0, g.n, body, idx0)


# --------------------------------------------------------------------------
# Batched (PSPC-style) construction.
# --------------------------------------------------------------------------
def provision_l_cap(g: Graph, floor: int = 4) -> int:
    """Pre-provision a label capacity from the graph's degree statistics.

    2-hop-cover label sizes on the synthetic power-law graphs of the
    benchmarks track the average degree (denser graphs reach more
    vertices before pruning bites); a spread term absorbs the skewed
    tail.  The estimate is a *starting* capacity only -- both builders
    still detect overflow and regrow -- its job is to make the
    grow-retry path the exception rather than three guaranteed
    doublings from a tiny default.  Rounded to the next power of two so
    repeated builds of similar graphs share compile caches.
    """
    n = g.n
    if n == 0:
        return floor
    deg = np.asarray(degrees(g))[:n].astype(np.float64)
    mean = float(deg.mean())
    est = int(np.ceil(mean + 2.0 * np.sqrt(mean) + 1.0))
    cap = floor
    while cap < max(est, floor):
        cap *= 2
    return min(cap, n + 1)


@partial(jax.jit, static_argnames=("hub_batch", "multi_relax_fn"))
def _hub_batch_round(g: Graph, idx: SPCIndex, h0, hub_batch: int,
                     multi_relax_fn: MultiRelaxFn | None = None
                     ) -> tuple[SPCIndex, jax.Array]:
    """One batch of ``hub_batch`` consecutive hubs [h0, h0 + B), and the
    relaxation rounds (BFS levels) its lockstep BFS ran.

    Committed pruning distances are PreQuery of each root against the
    index *as of h0* (``limit=h0`` equals the sequential ``limit=h_b``
    because only hubs < h0 exist in the index yet); in-batch pruning is
    handled inside the lockstep BFS.  Tail lanes with ``h0 + b >= n``
    are inactive and append nothing.
    """
    h0 = jnp.asarray(h0, jnp.int32)
    roots = h0 + jnp.arange(hub_batch, dtype=jnp.int32)
    roots_c = jnp.minimum(roots, jnp.int32(g.n))  # inactive -> dump row
    dbar = one_to_all_dists(idx, roots_c, h0)
    res = multi_pruned_spc_bfs(g, roots, dbar,
                               multi_relax_fn=multi_relax_fn)
    return (bulk_append_batch(idx, h0, res.dist, res.cnt, res.keep),
            res.levels)


def build_index_batched(
    g: Graph,
    l_cap: int | None = None,
    *,
    hub_batch: int = 32,
    order: str = "id",
    multi_relax_fn: MultiRelaxFn | None = None,
    on_regrow: Callable[[int], None] | None = None,
    on_round: Callable[[int], None] | None = None,
) -> SPCIndex:
    """Batched SPC-Index construction; order-identical to
    :func:`build_index` on the same (relabeled) graph.

    Host-driven loop over ``ceil(n / hub_batch)`` rounds of the jitted
    :func:`_hub_batch_round`.  A round that overflows label capacity is
    retried from its pre-round snapshot with doubled ``l_cap`` (labels
    committed by earlier rounds survive the repad verbatim, so the
    retry is sound); the returned index therefore always has
    ``overflow == 0``, unlike the sequential builder which leaves the
    grow-retry loop to its caller.

    Args:
      g: the graph.
      l_cap: starting label capacity; default: :func:`provision_l_cap`.
      hub_batch: hubs per lockstep round (the PSPC batch size).
      order: vertex-ordering strategy, ``"id"`` (the seed behavior) or
        ``"degree"``.  Non-identity orders relabel the graph into rank
        space first -- the returned index is over *rank* ids and the
        caller translates via the deterministic
        ``repro.core.order.graph_ordering(g, order)`` (this is what
        ``repro.core.dynamic.DynamicSPC(vertex_order=...)`` does at its
        id boundary).
      multi_relax_fn: multi-source relaxation primitive; default
        single-device.  Distributed callers pass
        ``repro.core.distributed.make_sharded_multi_relax`` (and a
        graph padded via ``pad_graph_for``).
      on_regrow: optional callback invoked with the new capacity on
        every overflow-retry (stats hook for the drivers).
      on_round: optional callback invoked with the relaxation rounds
        (``MultiBFSResult.levels``) of every round run, retried ones
        included, read with the overflow counter.

    Each round's dispatch and overflow check is a ``spc.build.round``
    span (``repro.spans``).
    """
    if hub_batch < 1:
        raise ValueError(f"hub_batch must be >= 1, got {hub_batch}")
    ordering = graph_ordering(g, order)
    g = relabel_graph(g, ordering)
    if l_cap is None:
        l_cap = provision_l_cap(g)
    idx = empty_index(g.n, l_cap)
    for h0 in range(0, g.n, hub_batch):
        snap = idx
        while True:
            with span("spc.build.round"):
                idx, levels = _hub_batch_round(g, snap, h0, hub_batch,
                                               multi_relax_fn)
                overflow, levels = jax.device_get((idx.overflow, levels))
            if on_round is not None:
                on_round(int(levels))
            if int(overflow) == 0:
                break
            snap = repad(snap, snap.l_cap * 2)
            if on_regrow is not None:
                on_regrow(snap.l_cap)
    return idx
