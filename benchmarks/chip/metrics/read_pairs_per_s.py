"""read_pairs_per_s: pairs of the closed-loop batches that completed
inside the window, over the window's seconds."""


def read(run):
    if run.closed is None:
        return None
    return run.closed["pairs_in_window"] / run.seconds
