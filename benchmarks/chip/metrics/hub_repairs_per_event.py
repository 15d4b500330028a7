"""hub_repairs_per_event: per-hub repair BFSs the update engine ran in the
window (``UpdateStats.hub_repairs``, counted inside ``hyb_spc_batch``),
over the events it applied (``batched_events``).  Nothing where the
program does not count them."""


def read(run):
    update = run.window.get("update") or {}
    if "hub_repairs" not in update or not update.get("batched_events"):
        return None
    return update["hub_repairs"] / update["batched_events"]
