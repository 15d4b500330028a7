"""update_device_ms_per_event: device time of the jitted hybrid update
executable (``hyb_spc_batch``) in the traced window, over the events
whose read-back returned in it."""

from benchmarks.chip import trace as tr

EXECUTABLE = "hyb_spc_batch"


def read(run):
    if run.trace is None or run.writer is None:
        return None
    events = run.writer["events_in_window"]
    lo, hi = run.trace.window()
    t = sum(tr.time_by_name(tr.events_in(run.trace.modules, lo, hi),
                            lambda name: EXECUTABLE in name).values())
    if not events or t <= 0:
        return None
    return 1e3 * t / events
