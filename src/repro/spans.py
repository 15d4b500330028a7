"""Named host spans on the profiler's clock.

``span(name)`` is a ``jax.profiler.TraceAnnotation``: under a running
profiler (``jax.profiler.start_trace``) it records one host event, on the
same clock as the device's ops, so a trace shows which host step the
device waited on; with no profiler running it costs about a microsecond
and records nothing.  There is no switch.

Rules for the spans of this package:

- host code only: never inside a jitted or traced function (the trace
  would record the span once, at tracing time);
- every name starts with ``spc.``; a name ending in ``_wait`` marks a host
  thread blocked on another thread or on the device;
- no keyword arguments (TraceAnnotation formats them into the name on
  every entry).

The spans: ``spc.read`` (one reader batch) with ``spc.read.ryw_wait``,
``.prep``, ``.gather``, ``.bound_wait``, ``.kernel`` and ``.merge``
inside it (``serve/service.py``, ``serve/engine.py``,
``kernels/spc_query/ops.py``); on a mixed-exactness batch ``.merge``
holds the one merge-and-patch dispatch of the rows over the count bound;
``spc.update.validate``, ``spc.update.apply`` (one event chunk) and
``spc.update.publish`` (``core/dynamic.py``); ``spc.build.round`` (one
hub round of the batched build: its dispatch and overflow check,
``core/construct.py``).
"""

from __future__ import annotations

import jax


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A context manager that records ``name`` as a host span while a
    profiler runs, and does nothing otherwise.  Spans nest."""
    return jax.profiler.TraceAnnotation(name)
