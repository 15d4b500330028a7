"""device_idle.<cell kind>: share (%) of the traced window in which no
operation ran on the device (1 - busy / window, ``trace.py``)."""

from benchmarks.chip import trace as tr


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    lo, hi = run.trace.window()
    return 100.0 * (1.0 - tr.busy_seconds(run.trace, lo, hi)
                    / ((hi - lo) * 1e-9))
