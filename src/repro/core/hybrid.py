"""HybSPC: hybrid batched update engine -- mixed insert/delete streams
in ONE jitted dispatch (the Section 4.4 scenario, batched).

Why batching, and why *sequential-inside-scan*
----------------------------------------------
The paper's headline result is that maintaining the SPC-Index beats
reconstruction by up to three orders of magnitude on hybrid update
streams.  The per-event algorithms (``incremental.inc_spc``,
``decremental.dec_spc``) achieve the algorithmic part of that, but
driven one event at a time they pay one Python->XLA dispatch per
event: for the small
repaired regions typical of real streams, dispatch overhead -- argument
flattening, executable lookup, device sync for the overflow check --
dominates the actual repair work.  This is the same observation that
motivates BatchHL for plain distance labelling (Farhan et al., "Efficient
Maintenance of Distance Labelling for Incremental Updates in Large
Dynamic Graphs"; see PAPERS.md): amortize fixed per-update costs over a
batch.

Unlike BatchHL we do NOT reorder or coalesce events.  IncSPC/DecSPC are
correct with respect to the graph state *at the moment the event is
applied* -- an insertion's affected-hub set AFF is defined on the label
state L_i right before it, and a deletion's SRRSearch runs two BFSs on
the graph with the edge still present.  Replaying events in stream order
inside a single ``lax.scan`` therefore preserves the ESPC invariant
(index answers == BFS counting) after EVERY prefix of the stream, not
just at the end: step k of the scan sees exactly the (graph, index) pair
a one-event-at-a-time replay through ``inc_spc``/``dec_spc`` would have
seen, so by induction over the stream the scan's carry equals that
trajectory state-for-state (the tests hold it to exactly that).  What
the batch buys is not a different algorithm but a different *execution*:
one fused executable, one host round-trip for the overflow check, one
capacity pre-provision -- the per-event overhead is paid once per chunk
instead of once per event.

Engine contract
---------------
Events are a tagged ``int32[B, 3]`` array of ``(op, a, b)`` rows:

* ``op == OP_INSERT`` (1): insert undirected edge (a, b);
* ``op == OP_DELETE`` (2): delete undirected edge (a, b), taking the
  Section 3.2.3 isolated-vertex fast path when the lower-ranked
  endpoint has degree 1 (``decremental.dec_spc_step_work``);
* rows with ``a == b`` (any op, canonically ``(0, 0, 0)``) are padding
  and are skipped -- drivers pad chunks to a fixed B so the engine
  compiles once per shape.

The caller (``repro.core.dynamic.DynamicSPC.apply_events``, the one
path by which the service mutates its index) guarantees
edge-slot capacity for all insertions in the batch and validates the
stream host-side (op tags resolved with the first bad row named --
unknown tags hit the padding branch *inside the trace* and would
otherwise silently drop updates -- plus no duplicate inserts, no
deletes of absent edges).
Label-capacity overflow anywhere in the batch accumulates in the
returned index's ``overflow`` counter; because every op is functional,
the driver recovers by re-padding the *pre-batch* snapshot and replaying
the whole chunk.

The engine also counts its work inside the same dispatch
(``bfs.RepairWork``: hub repairs, relaxation rounds, isolated-vertex
fast paths), one int32 add per round, so the driver reads it in the
same host fetch as the overflow counter.  It returns
``((graph, work), index)``: the index comes last, as from the per-event
engines, and the graph carries the replay's work beside it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bfs import RelaxFn, RepairWork
from repro.core.decremental import dec_spc_step_work
from repro.core.graph import Graph
from repro.core.incremental import _inc_spc_work
from repro.core.labels import SPCIndex

OP_INSERT = 1
OP_DELETE = 2


def _hyb_spc_batch(g: Graph, idx: SPCIndex, events: jax.Array,
                   relax_fn: RelaxFn | None = None
                   ) -> tuple[tuple[Graph, RepairWork], SPCIndex]:
    def step(carry, ev):
        g, idx, work = carry
        op, a, b = ev[0], ev[1], ev[2]

        def noop(args):
            g, idx = args
            return g, idx, RepairWork.zero()

        def ins(args):
            g, idx = args
            return _inc_spc_work(g, idx, a, b, relax_fn)

        def dele(args):
            g, idx = args
            return dec_spc_step_work(g, idx, a, b, relax_fn)

        known = (op == OP_INSERT) | (op == OP_DELETE)
        branch = jnp.where((a == b) | ~known, 0,
                           jnp.where(op == OP_INSERT, 1, 2))
        g, idx, done = jax.lax.switch(branch, [noop, ins, dele], (g, idx))
        return (g, idx, work.plus(done)), None

    (g, idx, work), _ = jax.lax.scan(step, (g, idx, RepairWork.zero()),
                                     events.astype(jnp.int32))
    return (g, work), idx


#: Apply a tagged ``(op, a, b)`` int32[B, 3] event stream in stream
#: order inside ONE jitted ``lax.scan`` (see module docstring for the
#: contract and the correctness argument); returns ``((graph, work),
#: index)``.  ``relax_fn`` (static) swaps in the edge-sharded relaxation
#: for distributed replay.
hyb_spc_batch = jax.jit(_hyb_spc_batch, static_argnames=("relax_fn",))
