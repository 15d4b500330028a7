"""relax_rounds_per_event: relaxation rounds (each one ``segment_sum``
over every edge slot) the update engine ran in the window
(``UpdateStats.relax_rounds``: its hub repairs' BFS levels and
SRRSearch's), over the events it applied (``batched_events``).  Nothing
where the program does not count them."""


def read(run):
    update = run.window.get("update") or {}
    if "relax_rounds" not in update or not update.get("batched_events"):
        return None
    return update["relax_rounds"] / update["batched_events"]
