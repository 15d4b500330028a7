"""spc_query_roofline: share (%) of its bytes roofline the Pallas query
kernel reached in the traced window: the bytes its launches must move
(``peaks.spc_query_bytes`` of each launch's shape) at the chip's HBM
bandwidth, over the kernel's device time (``trace.py``)."""

from benchmarks.chip import peaks
from benchmarks.chip import trace as tr


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window()
    launches = tr.kernel_launches(tr.events_in(run.trace.ops, lo, hi))
    seconds = sum(e.seconds for e, _ in launches)
    calls = [shape for _, shape in launches if shape is not None]
    if not launches or len(calls) != len(launches):
        return None
    return peaks.spc_query_roofline(calls, seconds, run.device["kind"])
