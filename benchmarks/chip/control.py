"""Readings of the program and of its control, for setting the limits of
the comparison that decides ``correct``.  Not part of a benchmark run.

    python benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--rehearse]

For each seed the cell runs once as the benchmark runs it, and the
control is read beside it:

- a cell with pair reads (``open``, ``closed``): the reference with
  bfloat16 counts, and with float32 counts, put in the program's place on
  the same sampled pairs, and the largest exact count among them
  (``checks.compare(control=True)``);
- a cell with a ``writer``: the same run again with the writer's reads
  switched from ``read_your_writes`` to ``pinned``, the program's own
  path that drops the read-your-writes guarantee.

Each reading is one JSON line on standard output:
``{"seed", "program": {check: value}, "control": {check: value}}``.
"""

import argparse
import copy
import json
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]


def readings(result: dict) -> dict:
    return {k: v["value"] for k, v in result["checks"].items()}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    for seed in args.seeds:
        res = harness.run_cell(cell, seed, args.seconds, False,
                               args.rehearse, time.monotonic(), log,
                               control=True)
        line = {"seed": seed, "correct": res["correct"],
                "program": readings(res), "control": res["control"],
                "answers_checked": res["reference"].get("answers_checked")}
        if "writer" in cell.traffic:
            pinned = copy.deepcopy(cell)
            pinned.traffic["writer"]["consistency"] = "pinned"
            ctl = harness.run_cell(pinned, seed, args.seconds, False,
                                   args.rehearse, time.monotonic(), log)
            line["control"] = readings(ctl)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
