"""update_events_per_s: events whose read-your-writes read-back returned
inside the window, over the window's seconds."""


def read(run):
    if run.writer is None:
        return None
    return run.writer["events_in_window"] / run.seconds
