"""IncSPC: incremental SPC-Index maintenance for edge insertion
(Algorithms 2 and 3), fully jitted.

Differences from a literal transcription (all semantics-preserving; see
DESIGN.md for the argument):

* The affected-hub loop runs over the *sorted union slots* of L(a) and
  L(b) hub ids (fixed shape 2 x L_cap) with first-occurrence masking.
* Per affected hub the full SpcQuery(h, .) pruning distances are
  evaluated once via the dense one-vs-all table -- they are invariant
  during that hub's BFS because the BFS only writes (h, .) entries and
  each vertex's own (h, .) entry is read before it is written.
* All label writes of one BFS are applied as a single masked bulk
  upsert over the label matrices.

Every entry point accepts a ``relax_fn`` (static under jit): the
single-device default relaxes the whole edge list, the distributed
engines (``repro.core.distributed.make_distributed_updater``) pass the
edge-sharded shard_map relaxation so the same algorithm runs over an
edge-partitioned mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import graph as G
from repro.core.bfs import RelaxFn, RepairWork, pruned_spc_bfs
from repro.core.counts import sat_add
from repro.core.graph import Graph
from repro.core.labels import SPCIndex, bulk_upsert
from repro.core.query import one_to_all


def _inc_update(g: Graph, idx: SPCIndex, h, va, vb,
                relax_fn: RelaxFn | None = None) -> tuple[SPCIndex, jax.Array]:
    """Algorithm 3, bulk form.  Returns the index and the repair BFS's
    relaxation rounds."""
    # Seed from the (h, d, c) entry of L(va):
    eq_a = idx.hub[va] == h
    pos = jnp.argmax(eq_a)
    d0 = idx.dist[va, pos] + 1
    c0 = idx.cnt[va, pos]
    d_full, _ = one_to_all(idx, h)  # SpcQuery(h, v) for every v
    res = pruned_spc_bfs(g, vb, d0, c0, dbar=d_full, rank_floor=h,
                         relax_fn=relax_fn)
    # Existing (h, ., .) entries (pre-update values):
    eq = idx.hub == h
    has = jnp.any(eq, axis=1)
    at = jnp.argmax(eq, axis=1)
    rows = jnp.arange(idx.n + 1)
    d_i = idx.dist[rows, at]
    c_i = idx.cnt[rows, at]
    # "if d = d_i then c <- c + c_i": accumulate equal-length counts
    # (saturating: the count contract).
    c_new = sat_add(res.cnt, jnp.where(has & (res.dist == d_i), c_i, 0))
    return bulk_upsert(idx, h, res.dist, c_new, res.keep), res.levels


def _inc_spc_work(g: Graph, idx: SPCIndex, a, b,
                  relax_fn: RelaxFn | None = None
                  ) -> tuple[Graph, SPCIndex, RepairWork]:
    """Algorithm 2 (traced body; see :func:`inc_spc`), with the work it
    did: one hub repair per ``_inc_update`` taken, and its rounds."""
    a = jnp.asarray(a, jnp.int32)
    b = jnp.asarray(b, jnp.int32)
    n = idx.n
    hubs_a = idx.hub[a]  # snapshot: AFF is defined on L_i
    hubs_b = idx.hub[b]
    in_a = jnp.zeros(n + 1, dtype=bool).at[hubs_a].set(hubs_a < n)
    in_b = jnp.zeros(n + 1, dtype=bool).at[hubs_b].set(hubs_b < n)
    in_a = in_a.at[n].set(False)
    in_b = in_b.at[n].set(False)
    aff = jnp.sort(jnp.concatenate([hubs_a, hubs_b]))
    first = jnp.concatenate([jnp.ones(1, dtype=bool), aff[1:] != aff[:-1]])

    g2 = G.insert_edge(g, a, b)

    def slot(k, carry):
        h = aff[k]
        valid = first[k] & (h < n)

        def repair(take, va, vb, carry):
            idx, work = carry
            idx, rounds = jax.lax.cond(
                take,
                lambda i: _inc_update(g2, i, h, va, vb, relax_fn),
                lambda i: (i, jnp.int32(0)), idx)
            return idx, work._replace(
                hub_repairs=work.hub_repairs + take.astype(jnp.int32),
                relax_rounds=work.relax_rounds + rounds)

        carry = repair(valid & in_a[h] & (h <= b), a, b, carry)
        return repair(valid & in_b[h] & (h <= a), b, a, carry)

    idx, work = jax.lax.fori_loop(0, aff.shape[0], slot,
                                  (idx, RepairWork.zero()))
    return g2, idx, work


def _inc_spc(g: Graph, idx: SPCIndex, a, b,
             relax_fn: RelaxFn | None = None) -> tuple[Graph, SPCIndex]:
    """Algorithm 2 (traced body; see :func:`inc_spc`)."""
    g2, idx, _ = _inc_spc_work(g, idx, a, b, relax_fn)
    return g2, idx


#: Algorithm 2: insert edge (a, b) and repair the index.  The caller
#: guarantees the edge is absent and capacity is available.  The
#: service applies events through the hybrid engine
#: (``repro.core.hybrid``), which runs the same traced body; this
#: one-event entry point is the oracle the tests hold that engine to.
inc_spc = jax.jit(_inc_spc, static_argnames=("relax_fn",))

