"""Repo-root pytest configuration.

Puts ``src`` on ``sys.path`` so the suite runs with a bare ``pytest``
invocation (no ``PYTHONPATH=src`` needed, e.g. in CI or an IDE).  Marker
registration and default deselection of ``slow`` live in ``pytest.ini``.
"""

import sys
from pathlib import Path

_SRC = str(Path(__file__).resolve().parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import pytest  # noqa: E402


@pytest.fixture()
def per_event():
    """``replay(graph, index, events) -> (graph, index)``: ``(op, a, b)``
    events applied one at a time through the paper's per-event engines
    (``inc_spc`` / ``dec_spc``), the oracle the update engine
    (``hyb_spc_batch``) is held to.  The label capacity must hold the
    whole replay: an overflow fails the test instead of growing."""
    from repro.core import graph as G
    from repro.core.decremental import dec_spc
    from repro.core.incremental import inc_spc

    def replay(graph, index, events):
        for op, a, b in events:
            if op == "+":
                graph, index = inc_spc(G.ensure_capacity(graph, 2), index,
                                       a, b)
            else:
                graph, index = dec_spc(graph, index, a, b)
            assert int(index.overflow) == 0, "oracle label capacity too small"
        return graph, index

    return replay
