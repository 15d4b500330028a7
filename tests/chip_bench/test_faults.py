"""The comparison that decides ``correct`` catches a broken timed path.

Each case drives a whole rehearsal run (the harness's look for a chip
skipped, everything else as on the chip) with one fault planted in the
program underneath, and sees ``correct`` come out false:

- ``unchanged``: the update step returns the index it was given;
- ``altered``: the serving engine adds one to the count of the first
  answer of every batch, where the answer is produced;
- ``half``: the serving engine answers only the first half of every
  batch and leaves the rest as "no path".

The cells run on one chip, so there is no exchange between chips to
leave out.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from open_cell import OPEN_CELL, bench_with_open

ROOT = pathlib.Path(__file__).resolve().parents[2]

DRIVER = r'''
import pathlib, sys, time
root = pathlib.Path(sys.argv[1])
sys.path[0:1] = [str(root), str(root / "src")]
fault, cell_name, bench = sys.argv[2], sys.argv[3], sys.argv[4]
from benchmarks.chip import harness
if fault == "unchanged":
    import repro.core.hybrid as hybrid
    real = hybrid.hyb_spc_batch
    def broken(g, idx, ev, *a, **k):
        g2, _ = real(g, idx, ev, *a, **k)
        return g2, idx
    hybrid.hyb_spc_batch = broken
else:
    from repro.serve.engine import QueryEngine
    real = QueryEngine.query_batch
    def broken(self, idx, s, t, route=None):
        d, c = real(self, idx, s, t, route)
        if fault == "altered":
            return d, c.at[0].add(1)
        h = d.shape[0] // 2
        return d.at[h:].set(1 << 28), c.at[h:].set(0)
    QueryEngine.query_batch = broken
cell = harness.load_cell(cell_name, bench_path=pathlib.Path(bench))
out = harness.run_cell(cell, 3, 2.0, False, True, time.monotonic(),
                       log=lambda m: print(m, file=sys.stderr))
harness.emit(out)
'''

CASES = [("g500-s10-stream.ryw", "unchanged"),
         ("g500-s10-stream.ryw", "altered"),
         ("g500-s13-serve.bulk", "altered"),
         ("g500-s13-serve.bulk", "half"),
         (OPEN_CELL, "altered"),
         (OPEN_CELL, "half")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_a_planted_fault_is_not_correct(cell, fault, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "-c", DRIVER, str(ROOT), fault, cell,
         str(bench_with_open(tmp_path))],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
