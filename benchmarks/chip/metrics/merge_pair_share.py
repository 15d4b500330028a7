"""merge_pair_share: share (%) of the window's real query pairs that the
int64 merge answered, from the serving engines' pairs per evaluation
path (``ServeStats.route_pairs["merge"]``, summed over engines under
``route_pairs`` in the window's counters).  Nothing where the window's
counters do not carry them."""


def read(run):
    pairs = run.window.get("route_pairs") or {}
    total = sum(pairs.values())
    if not total:
        return None
    return 100.0 * pairs.get("merge", 0) / total
