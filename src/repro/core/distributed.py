"""Distributed DSPC: shard_map variants of the hot paths.

The paper's Limitations section sketches the only admissible parallelism:
within one affected hub's BFS, vertices at the same distance level can be
processed simultaneously.  Our level-synchronous formulation makes that
parallelism *spatial*: one BFS level is a segment-sum over the edge list,
so we

* shard the **edge list** over a mesh axis -- each device relaxes its
  edge shard into a full [n + 1] contribution vector, combined with a
  single ``psum`` per level (this is the classic 1D vertex-replicated /
  edge-partitioned graph decomposition);
* shard **query batches** over the data axis -- the index is a read-only
  replica per device group (serving-style), so queries are embarrassingly
  parallel;
* keep the **label matrices replicated** inside an update group: bulk
  label updates are O(n L) dense passes that every device executes
  identically (cheaper than communicating masked scatters at our scales;
  revisited in EXPERIMENTS.md SPerf).

Because every algorithm layer (construction, IncSPC, DecSPC, HybSPC) is
written against the abstract relaxation ``repro.core.bfs.RelaxFn``, this
module contains **no BFS loop of its own**: :func:`make_sharded_relax`
builds the edge-sharded primitive and :func:`make_distributed_builder` /
:func:`make_distributed_updater` jit the shared algorithm bodies with it
baked in as a static argument.

On the production mesh (see ``repro.launch.mesh``) the edge axis maps to
``"model"`` and the query-batch axis to ``"data"`` x ``"pod"``.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import decremental as D
from repro.core import hybrid as H
from repro.core import incremental as I
from repro.core.bfs import compress_frontier
from repro.core.construct import build_index, build_index_batched
from repro.core.counts import relax_sums
from repro.core.graph import Graph
from repro.core.query import gather_rows, merge_rows


def pad_graph_for(g: Graph, num_shards: int) -> Graph:
    """Pad the edge arrays so cap_e divides evenly over the shard axis."""
    rem = (-g.cap_e) % num_shards
    if rem == 0:
        return g
    src = jnp.pad(g.src, (0, rem), constant_values=g.n)
    dst = jnp.pad(g.dst, (0, rem), constant_values=g.n)
    return Graph(src=src, dst=dst, m2=g.m2, n=g.n)


def make_sharded_relax(mesh: Mesh, edge_axis: str):
    """Edge-sharded relaxation: local segment-sum + one psum per level.

    The returned callable has the ``repro.core.bfs.RelaxFn`` signature,
    so it plugs directly into every BFS / update engine.  The edge
    arrays it receives must have ``cap_e`` divisible by the size of
    ``edge_axis`` (see :func:`pad_graph_for`).
    """

    shards = int(mesh.shape[edge_axis])

    def local_relax(src_blk, dst_blk, cnt, frontier):
        masked = compress_frontier(cnt, frontier)
        return relax_sums(masked[src_blk], dst_blk, cnt.shape[0],
                          jnp.max(masked), src_blk.shape[0] * shards,
                          total=lambda x: jax.lax.psum(x, edge_axis))

    return shard_map(
        local_relax,
        mesh=mesh,
        in_specs=(P(edge_axis), P(edge_axis), P(), P()),
        out_specs=P(),
    )


def make_sharded_multi_relax(mesh: Mesh, edge_axis: str):
    """Edge-sharded *multi-source* relaxation (``bfs.MultiRelaxFn``).

    The batched-construction analogue of :func:`make_sharded_relax`:
    ``cnt`` / ``frontier`` carry a leading hub-batch axis and stay
    replicated; each device gathers its edge shard's contributions for
    ALL B lockstep BFS ([B, E/shards]) and segment-sums locally, so one
    level of a whole hub batch still costs exactly **one psum** -- the
    [B, n + 1] partial sums combine in a single collective, preserving
    the per-level communication contract of the single-source path.
    Frontier compression happens on the replicated vertex side
    (:func:`repro.core.bfs.compress_frontier`) so the per-shard gather
    moves one operand, not two.
    """

    shards = int(mesh.shape[edge_axis])

    def local_multi_relax(src_blk, dst_blk, cnt, frontier):
        masked = compress_frontier(cnt, frontier)
        contrib = masked[:, src_blk]  # [B, E/shards]
        return relax_sums(contrib, dst_blk, cnt.shape[1], jnp.max(masked),
                          src_blk.shape[0] * shards,
                          total=lambda x: jax.lax.psum(x, edge_axis))

    return shard_map(
        local_multi_relax,
        mesh=mesh,
        in_specs=(P(edge_axis), P(edge_axis), P(), P()),
        out_specs=P(),
    )


def make_distributed_builder(mesh: Mesh, edge_axis: str = "model"):
    """HP-SPC construction with edge-sharded BFS levels.

    Returns ``build(g, l_cap) -> SPCIndex``; ``g`` must be padded via
    :func:`pad_graph_for` with the size of ``edge_axis``.  Delegates to
    the memoized updater so equal meshes share one ``relax_fn`` identity
    (= one jit compile cache) across builders and ``DynamicSPC`` modes.
    """
    return make_distributed_updater(mesh, edge_axis).build_index


@dataclasses.dataclass(frozen=True)
class DistributedUpdater:
    """Edge-sharded update engine over one mesh axis.

    Each member is the corresponding replicated engine jitted with the
    mesh's sharded relaxation baked in (static), so the update
    algorithms themselves are the shared single-source bodies: local
    segment-sum per edge shard, one ``psum`` per BFS level, label
    matrices replicated (the module's 1D decomposition).  Graphs handed
    to any member must satisfy ``cap_e % num_shards == 0`` -- call
    :meth:`pad` after every capacity change (``DynamicSPC`` does).
    """

    mesh: Mesh
    edge_axis: str
    num_shards: int
    relax_fn: Callable
    multi_relax_fn: Callable  # bfs.MultiRelaxFn, edge-sharded
    build_index: Callable    # (g, l_cap) -> SPCIndex
    build_index_batched: Callable  # (g, l_cap=None, hub_batch=, ...) -> SPCIndex
    inc_spc: Callable        # (g, idx, a, b) -> (g, idx)
    inc_spc_batch: Callable  # (g, idx, edges[B, 2]) -> (g, idx)
    dec_spc: Callable        # (g, idx, a, b) -> (g, idx), no fast path
    dec_spc_step: Callable   # dec_spc + traced isolated-vertex fast path
    dec_spc_batch: Callable  # (g, idx, edges[B, 2]) -> (g, idx)
    hyb_spc_batch: Callable  # (g, idx, events[B, 3]) -> ((g, work), idx)

    def pad(self, g: Graph) -> Graph:
        return pad_graph_for(g, self.num_shards)


@lru_cache(maxsize=None)
def make_distributed_updater(mesh: Mesh,
                             edge_axis: str = "model") -> DistributedUpdater:
    """Edge-sharded IncSPC/DecSPC/HybSPC variants (ROADMAP "sharded
    update path").

    Memoized on (mesh, edge_axis): jit keys the static ``relax_fn`` by
    identity, so handing every caller the SAME shard_map closure for
    equal meshes is what lets all ``DynamicSPC(mesh=...)`` replicas of
    one process share their compiled update executables.

    The one admissible parallelism inside an update (paper Limitations
    section) is the per-level frontier relaxation of each affected hub's
    repair BFS; sharding the edge list over ``edge_axis`` parallelizes
    exactly that while the hub loop and the label matrices stay
    replicated.  All returned engines preserve the replicated engines'
    contract bit-for-bit (same overflow counter, same padding-row
    semantics), so ``DynamicSPC`` reuses its capacity pre-provision /
    overflow-retry machinery unchanged in ``mesh=`` mode.
    """
    relax_fn = make_sharded_relax(mesh, edge_axis)
    multi_relax_fn = make_sharded_multi_relax(mesh, edge_axis)
    num_shards = int(mesh.shape[edge_axis])
    # partial() over the module-level jitted entry points: all updaters
    # (and the replicated default, relax_fn=None) share one compile
    # cache per algorithm, keyed by the static relax_fn.
    return DistributedUpdater(
        mesh=mesh,
        edge_axis=edge_axis,
        num_shards=num_shards,
        relax_fn=relax_fn,
        multi_relax_fn=multi_relax_fn,
        build_index=partial(build_index, relax_fn=relax_fn),
        build_index_batched=partial(build_index_batched,
                                    multi_relax_fn=multi_relax_fn),
        inc_spc=partial(I.inc_spc, relax_fn=relax_fn),
        inc_spc_batch=partial(I.inc_spc_batch, relax_fn=relax_fn),
        dec_spc=partial(D.dec_spc, relax_fn=relax_fn),
        dec_spc_step=partial(D.dec_spc_step_jit, relax_fn=relax_fn),
        dec_spc_batch=partial(D.dec_spc_batch, relax_fn=relax_fn),
        hyb_spc_batch=partial(H.hyb_spc_batch, relax_fn=relax_fn),
    )


def replicate_index(mesh: Mesh, idx) -> "SPCIndex":  # noqa: F821
    """Device-put an SPCIndex fully replicated over ``mesh``.

    This is the *staging* half of the snapshot publish protocol
    (``repro.serve.publish.SnapshotStore``): the updater's freshly
    committed index -- host arrays or single-device -- is laid out onto
    every serving device BEFORE the store's atomic swap, so replicas
    that pin the new version never pay a cross-device transfer (or see a
    half-placed pytree) mid-batch.  Labels are replicated, matching
    :func:`make_sharded_query`'s ``in_specs=(P(), ...)`` contract.
    """
    sharding = jax.sharding.NamedSharding(mesh, P())
    return jax.tree.map(lambda x: jax.device_put(x, sharding), idx)


def make_sharded_query(mesh: Mesh, batch_axes: Tuple[str, ...] = ("data",)):
    """Batched SPC queries sharded over the query batch.

    The index is replicated (read-only serving replica); each device
    gathers its slice's label rows once and answers through the same
    row-level merge core the serving engine uses
    (``repro.serve.QueryEngine.sharded`` wraps this with bucket padding
    so callers keep arbitrary batch sizes).
    """
    spec = P(batch_axes)

    def local_query(idx, s_blk, t_blk):
        rows = gather_rows(idx, s_blk) + gather_rows(idx, t_blk)
        return merge_rows(*rows)

    fn = shard_map(
        local_query,
        mesh=mesh,
        in_specs=(P(), spec, spec),
        out_specs=(spec, spec),
    )
    return jax.jit(fn)
