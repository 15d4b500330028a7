"""frontdoor_fill: pairs per coalesced front-door dispatch over the
window (``FrontDoor.stats()`` pairs / batches)."""


def read(run):
    fd = run.window.get("frontdoor")
    if not fd or not fd["batches"]:
        return None
    return fd["pairs"] / fd["batches"]
