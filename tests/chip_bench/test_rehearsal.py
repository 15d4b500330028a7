"""Every cell rehearsed on the CPU at its tiny size, end to end: the
traffic runs, the counters move, the result line has its shape and the
comparison with the reference passes.  Each run is a process of its own,
as on the chip (the harness sets process-wide JAX options)."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from open_cell import OPEN_CELL, bench_with_open

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

#: ``run.py`` for a cell of a benchmark file other than the checkout's.
DRIVER = r'''
import pathlib, sys, time
root, bench, cell = pathlib.Path(sys.argv[1]), sys.argv[2], sys.argv[3]
sys.path[0:1] = [str(root), str(root / "src")]
from benchmarks.chip import harness
cell = harness.load_cell(cell, bench_path=pathlib.Path(bench))
harness.emit(harness.run_cell(
    cell, int(sys.argv[4]), 2.0, False, True, time.monotonic(),
    log=lambda m: print(m, file=sys.stderr, flush=True)))
'''


def rehearse(cell, seed, cache, trace=0, bench=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    if bench is None:
        cmd = [str(ROOT / "benchmarks" / "chip" / "run.py"),
               "--workload", cell, "--seed", str(seed), "--seconds", "2",
               "--trace", str(trace), "--rehearse"]
    else:
        cmd = ["-c", DRIVER, str(ROOT), str(bench), cell, str(seed)]
    proc = subprocess.run(
        [sys.executable, *cmd],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]]
                         + [OPEN_CELL])
def test_rehearsal_result_line(cell, tmp_path):
    bench = bench_with_open(tmp_path) if cell == OPEN_CELL else None
    out, err = rehearse(cell, 2 ** 31 + 7, tmp_path / "cache", bench=bench)
    spec = json.loads((bench or ROOT / "BENCHMARK.json").read_text())
    assert out["correct"] is True
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-2:] == ["reference", "checks"]
    assert out["reference"]["reference_s"] > 0
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu" and out["rehearsal"] is True
    want = {m["name"] for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    # the compared numbers are the last lines of standard error
    tail = err.strip().splitlines()[-len(out["checks"]):]
    assert all(line.startswith("check: ") for line in tail)
    assert out["window"]["compiles"] == 0


def test_traced_rehearsal_reports_no_device_metric(tmp_path):
    cell = "g500-s13-serve.bulk"
    out, _ = rehearse(cell, 5, tmp_path, trace=1)
    sources = {m["name"]: m["source"] for m in BENCH["per_layer"]}
    assert out["metrics"], "counter metrics are still read"
    assert all(sources[k] != "device_trace" for k in out["metrics"])
    # the read path's counter and spans reach their readers in the
    # harness's own traced run
    assert 0 < out["metrics"]["pallas_pair_share"]["value"] <= 100
    assert out["metrics"]["read_host_ms_per_batch"]["value"] > 0
    assert "breakdown" not in out and "busy_s" not in out["device"]
