"""merge_device_us_per_pair: device time, in the traced window, of the
executables that run the int64 merge -- ``merge_patch`` (the inexact
rows of a mixed batch) and ``merge_rows`` (a batch with no provably
exact row), as the trace's ``XLA Modules`` line names them -- over the
pairs the merge answered (``route_pairs["merge"]``).  Nothing where no
such executable ran or no pair took the merge."""

from benchmarks.chip import trace as tr

EXECUTABLES = ("merge_patch", "merge_rows")


def read(run):
    pairs = (run.window.get("route_pairs") or {}).get("merge", 0)
    if run.trace is None or not pairs:
        return None
    lo, hi = run.trace.window()
    t = sum(tr.time_by_name(
        tr.events_in(run.trace.modules, lo, hi),
        lambda name: any(x in name for x in EXECUTABLES)).values())
    if t <= 0:
        return None
    return 1e6 * t / pairs
