"""Run one benchmark cell once and print its result line.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the checkout root on a machine that holds the chips the cell
asks for.  The last line of standard output is one JSON object (the
result); the numbers compared against the reference are the last lines of
standard error.  Without a TPU the run exits non-zero before any result.

``--rehearse`` runs the cell at the tiny size its files give under
``rehearsal``, on the CPU with the Pallas kernel interpreted: it checks
the traffic, the counters and the result's shape, and reports no device
metric.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parents[2]
# the checkout root (for ``benchmarks.chip``) and the program's ``src``,
# in place of this script's own directory
sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU (never on the chip)")
    args = ap.parse_args()

    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), args.rehearse,
        T_START, log=lambda msg: print(msg, file=sys.stderr, flush=True))
    harness.emit(result)


if __name__ == "__main__":
    main()
