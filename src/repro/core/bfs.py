"""Level-synchronous SPC-counting BFS over the edge list.

One BFS level = one relaxation of the *whole* directed edge list:

    contribution[w] = sum over edges (v, w) with v in frontier of cnt[v]

implemented as a segment-sum keyed by edge destination.  This is the
TPU-native replacement for the paper's FIFO queue (see DESIGN.md): the
frontier becomes a boolean vector, a level becomes a dense map-reduce, and
the queue-order count accumulation of Algorithms 3/5/6 (``C[w] += C[v]``
for same-level parents) is exactly the segment-sum semantics.

Pruning contract: ``dbar`` is precomputed per BFS (constant during one
hub's search -- see ``repro.core.query.one_to_all``); a vertex discovered
at distance d is pruned iff ``dbar[v] < d``.  Pruned vertices keep their
(dist, cnt) so they are not re-discovered, but they never expand and are
excluded from the ``keep`` mask handed to the label-update pass.

The relaxation primitive is *pluggable*: every BFS below accepts a
``relax_fn(src, dst, cnt, frontier) -> sums`` callable and defaults to the
single-device :func:`edge_relax`.  This is the one seam the paper's
Limitations section admits for parallelism -- vertices of one BFS level
are independent -- so the distributed engines
(``repro.core.distributed``) swap in an edge-sharded shard_map relaxation
(local segment-sum per edge shard + one ``psum`` per level) and every
algorithm layer above (construction, IncSPC, DecSPC, HybSPC) is written
once against the abstract relaxation.

Level sums keep the count contract (``repro.core.counts``): a sum that
could pass 2^63 - 1 saturates there instead of wrapping, since a
wrapped sum <= 0 would also leave its vertex undiscovered.  While the
frontier's largest count times the number of edge slots stays under
2^63 a level is today's single ``segment_sum``.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.counts import relax_sums
from repro.core.graph import INF, Graph

#: ``relax_fn(src, dst, cnt, frontier) -> int64[n + 1]`` per-destination
#: sums of frontier counts over the (possibly sharded) edge list.
RelaxFn = Callable[[jax.Array, jax.Array, jax.Array, jax.Array], jax.Array]

#: ``multi_relax_fn(src, dst, cnt, frontier) -> int64[B, n + 1]``: the
#: multi-source generalization of :data:`RelaxFn` -- ``cnt`` and
#: ``frontier`` carry a leading hub-batch axis and the relaxation
#: advances all B independent BFS one level in a single pass over the
#: (possibly sharded) edge list.  This is the PSPC seam: batched index
#: construction builds many hubs' labels per dispatch against it, and
#: the distributed variant (``repro.core.distributed
#: .make_sharded_multi_relax``) keeps the one-psum-per-level contract
#: of the single-source path.
MultiRelaxFn = Callable[
    [jax.Array, jax.Array, jax.Array, jax.Array], jax.Array]


class BFSResult(NamedTuple):
    dist: jax.Array   # int32[n + 1] (INF where unreached)
    cnt: jax.Array    # int64[n + 1]
    keep: jax.Array   # bool[n + 1]: visited AND not pruned
    levels: jax.Array  # int32: number of relaxation rounds executed


class RepairWork(NamedTuple):
    """Work an update engine did, counted inside its trace (int32
    scalars): pruned repair BFSs run (one per affected hub), relaxation
    rounds run (each one full-edge ``relax_fn`` pass; the repairs'
    ``levels`` plus SRRSearch's), and deletions that took the
    isolated-vertex fast path."""

    hub_repairs: jax.Array
    relax_rounds: jax.Array
    isolated_fast_path: jax.Array

    @classmethod
    def zero(cls) -> "RepairWork":
        return cls(jnp.int32(0), jnp.int32(0), jnp.int32(0))

    def plus(self, other: "RepairWork") -> "RepairWork":
        return RepairWork(*(a + b for a, b in zip(self, other)))


class MultiBFSResult(NamedTuple):
    """Per-hub-batch BFS state: every array carries a leading [B] axis."""

    dist: jax.Array   # int32[B, n + 1] (INF where unreached)
    cnt: jax.Array    # int64[B, n + 1]
    keep: jax.Array   # bool[B, n + 1]: visited AND not pruned
    levels: jax.Array  # int32: relaxation rounds until EVERY BFS drained


def edge_relax(src: jax.Array, dst: jax.Array, cnt: jax.Array,
               frontier: jax.Array) -> jax.Array:
    """One edge relaxation: per-destination sums of frontier counts.

    The single-device default ``RelaxFn``; ``n + 1`` is recovered from
    ``cnt`` so the same signature serves sharded edge blocks.  Sums
    saturate at 2^63 - 1 (the count contract).
    """
    masked = compress_frontier(cnt, frontier)
    return relax_sums(masked[src], dst, cnt.shape[0], jnp.max(masked),
                      src.shape[0])


def relax(g: Graph, cnt: jax.Array, frontier: jax.Array) -> jax.Array:
    """Graph-level convenience wrapper over :func:`edge_relax`."""
    return edge_relax(g.src, g.dst, cnt, frontier)


def compress_frontier(cnt: jax.Array, frontier: jax.Array) -> jax.Array:
    """Fuse (frontier, cnt) into one masked-count operand, int64[B, n+1].

    The frontier-compression step of the multi-source relaxation: the
    naive transcription gathers ``frontier[:, src]`` AND ``cnt[:, src]``
    per edge ([B, E] each) and multiplies.  Frontier and counts only
    ever appear as the product ``frontier * cnt``, so masking once on
    the [B, n + 1] vertex side halves the edge-gather traffic -- the
    only O(B E) term of a level -- and hands shard_map a single operand
    to slice.
    """
    return jnp.where(frontier, cnt, jnp.int64(0))


def multi_edge_relax(src: jax.Array, dst: jax.Array, cnt: jax.Array,
                     frontier: jax.Array) -> jax.Array:
    """One edge relaxation of B independent BFS: int64[B, n + 1] sums.

    The single-device default :data:`MultiRelaxFn`: per-destination
    segment-sums of compressed frontier counts, vectorized over the
    hub-batch axis.  ``n + 1`` is recovered from ``cnt`` so the same
    signature serves sharded edge blocks.  Sums saturate at 2^63 - 1
    (the count contract).
    """
    masked = compress_frontier(cnt, frontier)
    contrib = masked[:, src]  # [B, E] -- the single per-level edge gather
    return relax_sums(contrib, dst, cnt.shape[1], jnp.max(masked),
                      src.shape[0])


def pruned_spc_bfs(
    g: Graph,
    root,
    root_dist,
    root_cnt,
    dbar: jax.Array,
    rank_floor=None,
    max_levels: int | None = None,
    relax_fn: RelaxFn | None = None,
) -> BFSResult:
    """Pruned counting BFS used by construction, IncSPC and DecSPC.

    Args:
      g: the graph (edge list).
      root: seed vertex (traced ok).
      root_dist / root_cnt: seed distance / count (Algorithm 3 starts at
        ``d + 1`` / ``c`` rather than 0 / 1).
      dbar: int32[n + 1] pruning distances (full or Pre query against the
        current hub, precomputed once).
      rank_floor: if given, only vertices with id >= rank_floor may be
        discovered (the paper's ``h <= w`` rank pruning).
      max_levels: loop bound (defaults to n, the worst-case diameter).
      relax_fn: relaxation primitive; default :func:`edge_relax`
        (single-device).  Distributed callers pass the edge-sharded
        variant from ``repro.core.distributed.make_sharded_relax``.
    """
    if relax_fn is None:
        relax_fn = edge_relax
    n1 = g.n + 1
    ids = jnp.arange(n1, dtype=jnp.int32)
    eligible = ids < g.n  # never the dump row
    if rank_floor is not None:
        eligible &= ids >= jnp.asarray(rank_floor, jnp.int32)

    dist = jnp.full(n1, INF, dtype=jnp.int32).at[root].set(
        jnp.asarray(root_dist, jnp.int32))
    cnt = jnp.zeros(n1, dtype=jnp.int64).at[root].set(
        jnp.asarray(root_cnt, jnp.int64))
    root_keep = dbar[root] >= jnp.asarray(root_dist, jnp.int32)
    frontier = jnp.zeros(n1, dtype=bool).at[root].set(root_keep)
    keep = frontier
    level = jnp.asarray(root_dist, jnp.int32)
    if max_levels is None:
        max_levels = g.n

    def cond(state):
        _, _, frontier, _, level, rounds = state
        return jnp.any(frontier) & (rounds < max_levels)

    def body(state):
        dist, cnt, frontier, keep, level, rounds = state
        sums = relax_fn(g.src, g.dst, cnt, frontier)
        newly = (sums > 0) & (dist == INF) & eligible
        dist = jnp.where(newly, level + 1, dist)
        cnt = jnp.where(newly, sums, cnt)
        pruned = newly & (dbar < dist)
        frontier = newly & ~pruned
        keep = keep | frontier
        return dist, cnt, frontier, keep, level + 1, rounds + 1

    dist, cnt, frontier, keep, level, rounds = jax.lax.while_loop(
        cond, body, (dist, cnt, frontier, keep, level, jnp.int32(0)))
    return BFSResult(dist=dist, cnt=cnt, keep=keep, levels=rounds)


def multi_pruned_spc_bfs(
    g: Graph,
    roots: jax.Array,
    dbar: jax.Array,
    rank_floor: bool = True,
    batch_rank_prune: bool = True,
    max_levels: int | None = None,
    multi_relax_fn: MultiRelaxFn | None = None,
) -> MultiBFSResult:
    """B pruned counting BFS advanced in lockstep (PSPC-style batching).

    One iteration of the single ``lax.while_loop`` relaxes *every*
    BFS of the batch one level (:func:`multi_edge_relax`), so a whole
    batch of hubs costs one loop's worth of dispatch overhead instead
    of B sequential loops.  Used by batched index construction
    (``repro.core.construct.build_index_batched``).

    Args:
      g: the graph (edge list).
      roots: int32[B] seed vertices, strictly ascending ids.  A root
        ``>= g.n`` marks an inactive tail lane (last batch of a build):
        its BFS never starts and its ``keep`` row stays all-False.
      dbar: int32[B, n + 1] *committed* pruning distances -- PreQuery of
        each root against the labels of all hubs ranked above the whole
        batch, precomputed once (constant during the batch).
      rank_floor: apply the paper's rank pruning per lane (only
        vertices with id >= roots[b] may be discovered).
      batch_rank_prune: rank-masked IN-batch pruning -- the step that
        makes lockstep construction order-identical to sequential.  A
        vertex w newly discovered by lane b at distance d is also
        pruned if some earlier lane b' < b (a higher-ranked in-batch
        hub) yields ``dist_b'[roots[b]] + dist_b'[w] < d`` through
        vertices it *kept*: exactly the label pair
        ``(L(roots[b])[h_b'], L(w)[h_b'])`` the sequential build would
        have committed before lane b ran.  Both terms of any pruning
        sum are < d, i.e. discovered at strictly earlier levels, so the
        lockstep state always already holds them -- no replay needed.
      max_levels: loop bound (defaults to n, the worst-case diameter).
      multi_relax_fn: multi-source relaxation primitive; default
        :func:`multi_edge_relax` (single-device).  Distributed callers
        pass ``repro.core.distributed.make_sharded_multi_relax``.
    """
    if multi_relax_fn is None:
        multi_relax_fn = multi_edge_relax
    n1 = g.n + 1
    b = roots.shape[0]
    ids = jnp.arange(n1, dtype=jnp.int32)
    roots = jnp.asarray(roots, jnp.int32)
    valid = roots < g.n                                    # [B]
    roots_c = jnp.minimum(roots, g.n)                      # safe gather index
    eligible = jnp.broadcast_to(ids[None, :] < g.n, (b, n1))
    if rank_floor:
        eligible &= ids[None, :] >= roots[:, None]

    at_root = (ids[None, :] == roots[:, None]) & valid[:, None]
    dist = jnp.where(at_root, jnp.int32(0), INF)
    cnt = jnp.where(at_root, jnp.int64(1), jnp.int64(0))
    # root keep mirrors the sequential builder: dbar[root] >= 0 always
    # holds during construction, so valid roots are always kept
    frontier = at_root & (jnp.take_along_axis(
        dbar, roots_c[:, None], axis=1) >= 0)
    keep = frontier
    if max_levels is None:
        max_levels = g.n
    lane = jnp.arange(b, dtype=jnp.int32)

    def cond(state):
        _, _, frontier, _, rounds = state
        return jnp.any(frontier) & (rounds < max_levels)

    def body(state):
        dist, cnt, frontier, keep, rounds = state
        sums = multi_relax_fn(g.src, g.dst, cnt, frontier)
        newly = (sums > 0) & (dist == INF) & eligible
        d_new = rounds + 1
        dist2 = jnp.where(newly, d_new, dist)
        cnt2 = jnp.where(newly, sums, cnt)
        pruned = newly & (dbar < d_new)
        if batch_rank_prune:
            # dbar_in[b, w] = min over lanes b' < b of
            #   dist_b'[roots[b]] + dist_b'[w], keep-masked on both ends
            # -- evaluated on the PRE-level state: every term of a sum
            # <= rounds was discovered at a level < d_new, so later
            # discoveries can never contribute a pruning pair.
            hub_d = dist[:, roots_c]                       # [B', B]
            hub_ok = keep[:, roots_c] & (lane[:, None] < lane[None, :])
            a = jnp.where(hub_ok, hub_d, INF)              # [B', B]
            dm = jnp.where(keep, dist, INF)                # [B', n+1]
            dbar_in = jnp.min(a[:, :, None] + dm[:, None, :], axis=0)
            pruned |= newly & (dbar_in < d_new)
        frontier2 = newly & ~pruned
        return dist2, cnt2, frontier2, keep | frontier2, rounds + 1

    dist, cnt, frontier, keep, rounds = jax.lax.while_loop(
        cond, body, (dist, cnt, frontier, keep, jnp.int32(0)))
    return MultiBFSResult(dist=dist, cnt=cnt, keep=keep, levels=rounds)


def plain_spc_bfs(g: Graph, root, max_levels: int | None = None) -> BFSResult:
    """Unpruned counting BFS (the online baseline; also the test oracle)."""
    no_prune = jnp.full(g.n + 1, INF, dtype=jnp.int32)
    return pruned_spc_bfs(g, root, 0, 1, dbar=no_prune, max_levels=max_levels)


def conditional_spc_bfs(
    g: Graph,
    root,
    stop_mask_fn,
    max_levels: int | None = None,
    relax_fn: RelaxFn | None = None,
) -> BFSResult:
    """BFS whose expansion stops at vertices failing ``stop_mask_fn``.

    ``stop_mask_fn(dist, cnt, newly) -> bool[n + 1]`` returns the vertices
    that may continue expanding (evaluated on newly discovered vertices
    with their final dist/cnt for the level).  Used by SRRSearch where the
    continue test is ``dist[v] + 1 == sd(v, b)``.
    """
    if relax_fn is None:
        relax_fn = edge_relax
    n1 = g.n + 1
    ids = jnp.arange(n1, dtype=jnp.int32)
    eligible = ids < g.n
    dist = jnp.full(n1, INF, dtype=jnp.int32).at[root].set(0)
    cnt = jnp.zeros(n1, dtype=jnp.int64).at[root].set(1)
    newly0 = jnp.zeros(n1, dtype=bool).at[root].set(True)
    frontier = newly0 & stop_mask_fn(dist, cnt, newly0)
    if max_levels is None:
        max_levels = g.n

    def cond(state):
        _, _, frontier, rounds = state
        return jnp.any(frontier) & (rounds < max_levels)

    def body(state):
        dist, cnt, frontier, rounds = state
        sums = relax_fn(g.src, g.dst, cnt, frontier)
        newly = (sums > 0) & (dist == INF) & eligible
        dist = jnp.where(newly, rounds + 1, dist)
        cnt = jnp.where(newly, sums, cnt)
        frontier = newly & stop_mask_fn(dist, cnt, newly)
        return dist, cnt, frontier, rounds + 1

    dist, cnt, frontier, rounds = jax.lax.while_loop(
        cond, body, (dist, cnt, frontier, jnp.int32(0)))
    return BFSResult(dist=dist, cnt=cnt, keep=dist < INF, levels=rounds)
