"""DecSPC: decremental SPC-Index maintenance for edge deletion
(Algorithms 4, 5 and 6), fully jitted.

Phase 1 (SRRSearch) runs two conditional BFSs from the deletion endpoints
*before* the edge is removed; the affected sets SR/R are boolean vertex
masks.  Phase 2 walks the affected hubs in rank order; per hub one
PreQuery table + one pruned BFS + one bulk upsert + (for common hubs of a
and b) one bulk removal.

The isolated-vertex optimization (Section 3.2.3) lives in the host-side
driver (``repro.core.dynamic``) since it short-circuits the whole
procedure; the traced path below is correct for that case too, just
slower.

Every entry point accepts a ``relax_fn`` (static under jit) so both the
SRRSearch BFSs and the per-hub repair BFS run against the abstract
relaxation -- the distributed engines pass the edge-sharded shard_map
variant (see ``repro.core.distributed.make_distributed_updater``).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import graph as G
from repro.core.bfs import (RelaxFn, RepairWork, conditional_spc_bfs,
                            pruned_spc_bfs)
from repro.core.graph import INF, Graph
from repro.core.labels import (SPCIndex, bulk_remove, bulk_upsert,
                               reset_isolated_row)
from repro.core.query import one_to_all


class SRRSets(NamedTuple):
    sr_a: jax.Array  # bool[n + 1]
    sr_b: jax.Array
    r_a: jax.Array
    r_b: jax.Array
    l_ab: jax.Array  # bool[n + 1]: common hubs of a and b
    rounds: jax.Array  # int32: relaxation rounds of the two BFSs


def _side(g: Graph, idx: SPCIndex, root, d_other, c_other, l_ab,
          relax_fn: RelaxFn | None = None):
    """One direction of Algorithm 5 (run with the edge still present)."""
    stop = lambda dist, cnt, newly: dist + 1 == d_other
    res = conditional_spc_bfs(g, root, stop, relax_fn=relax_fn)
    visited = res.dist < INF
    unpruned = visited & (res.dist + 1 == d_other)
    sr = unpruned & (l_ab | (res.cnt == c_other))
    r = unpruned & ~sr
    return sr, r, res.levels


def srr_search(g: Graph, idx: SPCIndex, a, b,
               relax_fn: RelaxFn | None = None) -> SRRSets:
    """Algorithm 5 for both sides."""
    n = idx.n
    hubs_a = idx.hub[a]
    hubs_b = idx.hub[b]
    in_a = jnp.zeros(n + 1, dtype=bool).at[hubs_a].set(hubs_a < n).at[n].set(False)
    in_b = jnp.zeros(n + 1, dtype=bool).at[hubs_b].set(hubs_b < n).at[n].set(False)
    l_ab = in_a & in_b
    d_b, c_b = one_to_all(idx, b)  # SpcQuery(v, b) for every v
    d_a, c_a = one_to_all(idx, a)
    sr_a, r_a, rounds_a = _side(g, idx, a, d_b, c_b, l_ab, relax_fn)
    sr_b, r_b, rounds_b = _side(g, idx, b, d_a, c_a, l_ab, relax_fn)
    return SRRSets(sr_a=sr_a, sr_b=sr_b, r_a=r_a, r_b=r_b, l_ab=l_ab,
                   rounds=rounds_a + rounds_b)


def _dec_update(g: Graph, idx: SPCIndex, h, affected, h_ab,
                relax_fn: RelaxFn | None = None
                ) -> tuple[SPCIndex, jax.Array]:
    """Algorithm 6, bulk form (post-deletion graph).  Returns the index
    and the repair BFS's relaxation rounds."""
    dpre, _ = one_to_all(idx, h, limit=h)  # PreQuery(h, v) for every v
    res = pruned_spc_bfs(g, h, 0, 1, dbar=dpre, rank_floor=h,
                         relax_fn=relax_fn)
    upd = res.keep & affected  # U[.]
    idx = bulk_upsert(idx, h, res.dist, res.cnt, upd)
    remove_mask = affected & ~upd
    idx = jax.lax.cond(
        h_ab,
        lambda i: bulk_remove(i, h, remove_mask),
        lambda i: i, idx)
    return idx, res.levels


def _dec_spc_work(g: Graph, idx: SPCIndex, a, b,
                  relax_fn: RelaxFn | None = None
                  ) -> tuple[Graph, SPCIndex, RepairWork]:
    """Algorithm 4 (traced body; see :func:`dec_spc`), with the work it
    did: one hub repair per hub of SR, and the rounds of those repairs
    and of SRRSearch."""
    a = jnp.asarray(a, jnp.int32)
    b = jnp.asarray(b, jnp.int32)
    n = idx.n
    sets = srr_search(g, idx, a, b, relax_fn)
    g2 = G.delete_edge(g, a, b)

    ids = jnp.arange(n + 1, dtype=jnp.int32)
    sr_all = (sets.sr_a | sets.sr_b) & (ids < n)
    sr_ids = jnp.sort(jnp.where(sr_all, ids, n))  # ascending id = rank order
    aff_b = sets.sr_b | sets.r_b
    aff_a = sets.sr_a | sets.r_a

    k_max = sr_ids.shape[0]

    def cond(state):
        k, _, _ = state
        return (k < k_max) & (sr_ids[jnp.minimum(k, k_max - 1)] < n)

    def body(state):
        k, idx, rounds = state
        h = sr_ids[k]
        is_a_side = sets.sr_a[h]
        affected = jnp.where(is_a_side, aff_b, aff_a)
        idx, levels = _dec_update(g2, idx, h, affected, sets.l_ab[h],
                                  relax_fn)
        return k + 1, idx, rounds + levels

    repairs, idx, rounds = jax.lax.while_loop(
        cond, body, (jnp.int32(0), idx, jnp.int32(0)))
    return g2, idx, RepairWork(hub_repairs=repairs,
                               relax_rounds=sets.rounds + rounds,
                               isolated_fast_path=jnp.int32(0))


def _dec_spc(g: Graph, idx: SPCIndex, a, b,
             relax_fn: RelaxFn | None = None) -> tuple[Graph, SPCIndex]:
    """Algorithm 4 (traced body; see :func:`dec_spc`)."""
    g2, idx, _ = _dec_spc_work(g, idx, a, b, relax_fn)
    return g2, idx


#: Algorithm 4: delete edge (a, b) and repair the index.
dec_spc = jax.jit(_dec_spc, static_argnames=("relax_fn",))


def dec_spc_step_work(g: Graph, idx: SPCIndex, a, b,
                      relax_fn: RelaxFn | None = None
                      ) -> tuple[Graph, SPCIndex, RepairWork]:
    """Traced single deletion with the Section 3.2.3 isolated-vertex fast
    path folded in, and the work it did (a fast path counts as one
    ``isolated_fast_path`` and no repair).

    Mirrors the host driver's ``delete_edge`` exactly: when the
    lower-ranked endpoint has degree 1 it becomes isolated, is never a
    hub in any other row, and its row collapses to the self label -- a
    cheap masked reset instead of the full SRRSearch + per-hub repair.
    Used by :func:`dec_spc_batch` and the hybrid engine
    (``repro.core.hybrid``) so batched replay is bit-identical to the
    per-event driver path.
    """
    a = jnp.asarray(a, jnp.int32)
    b = jnp.asarray(b, jnp.int32)
    hi = jnp.maximum(a, b)
    deg_hi = G.degrees(g)[hi]

    def fast(args):
        g, idx = args
        return (G.delete_edge(g, a, b), reset_isolated_row(idx, hi),
                RepairWork.zero()._replace(isolated_fast_path=jnp.int32(1)))

    def full(args):
        g, idx = args
        return _dec_spc_work(g, idx, a, b, relax_fn)

    return jax.lax.cond(deg_hi == 1, fast, full, (g, idx))


def dec_spc_step(g: Graph, idx: SPCIndex, a, b,
                 relax_fn: RelaxFn | None = None) -> tuple[Graph, SPCIndex]:
    """:func:`dec_spc_step_work` without the work counts."""
    g2, idx, _ = dec_spc_step_work(g, idx, a, b, relax_fn)
    return g2, idx


#: One-dispatch variant of :func:`dec_spc_step` (the distributed updater
#: and other single-delete callers jit here; the batch engines inline the
#: traced body instead).
dec_spc_step_jit = jax.jit(dec_spc_step, static_argnames=("relax_fn",))


def _dec_spc_batch(g: Graph, idx: SPCIndex, edges: jax.Array,
                   relax_fn: RelaxFn | None = None) -> tuple[Graph, SPCIndex]:
    def step(carry, edge):
        g, idx = carry
        a, b = edge[0], edge[1]

        def apply(args):
            g, idx = args
            return dec_spc_step(g, idx, a, b, relax_fn)

        g, idx = jax.lax.cond(a != b, apply, lambda x: x, (g, idx))
        return (g, idx), None

    (g, idx), _ = jax.lax.scan(step, (g, idx),
                               edges.astype(jnp.int32))
    return g, idx


#: Batched DecSPC: delete ``edges`` int32[B, 2] sequentially inside ONE
#: jitted call -- the decremental sibling of
#: ``incremental.inc_spc_batch``.  Rows with a == b are skipped (use as
#: padding for fixed batch shapes).  Caller guarantees every listed edge
#: is present at its turn in the sequence.  Overflow from any step
#: accumulates in the returned index's counter; the driver replays the
#: pre-batch snapshot at a larger capacity.
dec_spc_batch = jax.jit(_dec_spc_batch, static_argnames=("relax_fn",))
