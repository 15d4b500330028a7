"""The paper's primary contribution: dynamic SPC-Index maintenance in JAX.

Layers (bottom-up):

* ``graph``       -- fixed-capacity dynamic edge-list graph.
* ``labels``      -- the SPC-Index as padded label matrices + bulk ops.
* ``query``       -- Algorithm 1 (pair queries) and dense one-vs-all.
* ``bfs``         -- level-synchronous counting BFS (the TPU adaptation).
* ``construct``   -- HP-SPC construction (sequential oracle + batched).
* ``incremental`` -- IncSPC (Algorithms 2-3), one event.
* ``decremental`` -- DecSPC (Algorithms 4-6), one event.
* ``hybrid``      -- the update engine: mixed insert/delete chunks in
  one dispatch each (Section 4.4 workloads).
* ``dynamic``     -- host-side service driver (capacity, events, state).
* ``refimpl``     -- paper-faithful sequential oracle & baselines.
* ``distributed`` -- shard_map variants: edge-sharded relaxation plugged
  into the shared BFS/update bodies (``make_distributed_builder``,
  ``make_distributed_updater``) and batch-sharded queries.

The serving read path lives one package up in ``repro.serve``: a routed,
bucket-padded engine over the row-level cores exported by ``query``.
"""

import repro  # noqa: F401  (enables x64 before any array is created)

from repro.core.graph import Graph, from_edges, INF
from repro.core.labels import SPCIndex, empty_index
from repro.core.query import (pair_query, pre_pair_query, batched_query,
                              batched_query_merge, gather_rows, merge_rows,
                              one_to_all)
from repro.core.bfs import plain_spc_bfs, pruned_spc_bfs
from repro.core.construct import build_index
from repro.core.incremental import inc_spc
from repro.core.decremental import dec_spc, srr_search
from repro.core.hybrid import OP_DELETE, OP_INSERT, hyb_spc_batch
from repro.core.dynamic import DynamicSPC

__all__ = [
    "Graph", "from_edges", "INF",
    "SPCIndex", "empty_index",
    "pair_query", "pre_pair_query", "batched_query", "batched_query_merge",
    "gather_rows", "merge_rows", "one_to_all",
    "plain_spc_bfs", "pruned_spc_bfs",
    "build_index", "inc_spc", "dec_spc", "srr_search",
    "hyb_spc_batch", "OP_INSERT", "OP_DELETE",
    "DynamicSPC",
]
