"""The control comes out as not correct, where the program does.

The control is the reference with one guarantee broken, put in the
program's place (``control.py``):

- pair reads: path counts accumulated in bfloat16.  bfloat16 holds integers
  exactly only up to 256, and a Kronecker graph has pairs with more
  shortest paths than that only from about scale 12 (0.06% of pairs at
  scale 12, none up to scale 9), so this case runs at scale 12, not at
  the rehearsal's scale 7;
- the writer: its reads taken ``pinned`` instead of read-your-writes.
"""

import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]

DRIVER = r'''
import json, pathlib, sys, time
root = pathlib.Path(sys.argv[1])
sys.path[0:1] = [str(root), str(root / "src")]
from benchmarks.chip import harness
cell = harness.load_cell(sys.argv[2])
over = json.loads(sys.argv[3])
for key, value in over.items():
    cell.config["rehearsal"][key].update(value)
out = harness.run_cell(cell, int(sys.argv[4]), 2.0, False, True,
                       time.monotonic(), log=lambda m: None, control=True)
print(json.dumps({"program": {k: v["value"]
                              for k, v in out["checks"].items()},
                  "control": out["control"], "correct": out["correct"],
                  "checked": out["reference"]["answers_checked"]}))
'''


def drive(cell, over, seed, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "-c", DRIVER, str(ROOT), cell, json.dumps(over),
         str(seed)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_bf16_counts_fail_where_the_program_passes(tmp_path):
    out = drive("g500-s13-serve.bulk",
                {"graph": {"scale": 12},
                 "service": {"l_cap": 1024, "cap_e": 131072,
                             "route": "merge"}}, 2 ** 31 + 5, tmp_path)
    assert out["correct"] is True
    assert out["program"]["answers_wrong"] == 0
    assert out["checked"] > 0
    assert out["control"]["answers_wrong.bfloat16"] > 0
    # float32, the kernel's precision, is exact on every count under 2^24
    assert out["control"]["max_count"] < 2 ** 24
    assert out["control"]["answers_wrong.float32"] == 0


def test_pinned_reads_fail_the_writer(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "chip" / "control.py"),
         "--workload", "g500-s10-stream.ryw", "--seconds", "2",
         "--seeds", "3", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert all(v == 0 for v in line["program"].values())
    assert line["control"]["readback_wrong"] > 0
