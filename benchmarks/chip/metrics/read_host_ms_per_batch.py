"""read_host_ms_per_batch: host time of a served read batch between its
device dispatches: the summed durations of the program's ``spc.read.*``
spans that are not waits, over the number of ``spc.read`` spans (one a
batch), all wholly inside the traced window.  Nothing where the trace
holds no ``spc.read`` span."""

from benchmarks.chip import trace as tr

BATCH = "spc.read"


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window()
    spans = tr.events_in(run.trace.spans, lo, hi)
    batches = sum(s.name == BATCH for s in spans)
    if not batches:
        return None
    host = sum(s.seconds for s in spans
               if s.name.startswith(BATCH + ".")
               and not s.name.endswith("_wait"))
    return 1e3 * host / batches
