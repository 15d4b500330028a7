"""update_device_us_per_round: device time of the hybrid update executable
(``hyb_spc_batch``) in the traced window, taken as
``update_device_ms_per_event`` takes it, over the relaxation rounds the
engine counted in the window (``UpdateStats.relax_rounds``).  The
counters span the window and the event that ends past it, the device
time the executable runs wholly inside it."""

from benchmarks.chip import trace as tr

EXECUTABLE = "hyb_spc_batch"


def read(run):
    update = run.window.get("update") or {}
    if run.trace is None or not update.get("relax_rounds"):
        return None
    lo, hi = run.trace.window()
    t = sum(tr.time_by_name(tr.events_in(run.trace.modules, lo, hi),
                            lambda name: EXECUTABLE in name).values())
    if t <= 0:
        return None
    return 1e6 * t / update["relax_rounds"]
