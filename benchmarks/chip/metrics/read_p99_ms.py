"""read_p99_ms: 99th percentile latency of every open-loop request due in
the window, timed from when it was due; a failed or refused request
counts as missing (infinite), so a run with more than 1% missing reports
nothing."""

import numpy as np


def read(run):
    if run.open is None or not run.open["latency_s"].size:
        return None
    p99 = float(np.percentile(run.open["latency_s"], 99))
    return 1e3 * p99 if np.isfinite(p99) else None
