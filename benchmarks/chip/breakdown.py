"""Run one benchmark cell traced, and break its window down by the
program's own spans.

    python benchmarks/chip/breakdown.py --workload <cell> --seed <n> \
        --seconds <s> [--rehearse]

Run from the checkout root, like ``run.py``.  The run is ``run.py``'s
``--trace 1`` run, its profile in a directory of this process's own (the
harness deletes its fixed one, which another run may be using).  The last
line of standard output is the result line, with every per-layer metric
of the cell, the cell's end-to-end metrics as the traced run reads them
(what tracing costs shows against an untraced run), and under
``breakdown``: ``idle_by_span`` (the device's idle seconds by the name of
each gap, every gap counted; the rules are ``trace.py``'s),
``longest_gaps`` (the ten longest, named, with their start in the
window) and ``span_seconds`` (count and summed seconds per span name in
the window).
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU (never on the chip)")
    args = ap.parse_args()

    from benchmarks.chip import harness
    from benchmarks.chip import trace as tr

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(args.workload)
    if entry is None:
        raise SystemExit(f"unknown workload {args.workload!r}")
    extra = [m for m in bench["end_to_end"]
             if args.workload in m.get("workloads", [args.workload])]
    bench["per_layer"] = bench["per_layer"] + [
        dict(m, workloads=[args.workload]) for m in extra]
    cell = harness.make_cell(entry, bench)
    kept: dict = {}
    load = tr.load

    def keep(path):
        kept["trace"] = load(path)
        return kept["trace"]

    tr.load = keep
    harness.TRACE_DIR = pathlib.Path(tempfile.mkdtemp(prefix="breakdown"))
    result = harness.run_cell(
        cell, args.seed, args.seconds, True, args.rehearse, T_START,
        log=lambda msg: print(msg, file=sys.stderr, flush=True))
    trace = kept["trace"]
    extra = {"idle_by_span": tr.idle_by_span(trace),
             "longest_gaps": tr.named_gaps(trace, 10),
             "span_seconds": tr.span_seconds(trace)}
    result["breakdown"] = {**result.get("breakdown", {}), **extra}
    harness.emit(result)


if __name__ == "__main__":
    main()
