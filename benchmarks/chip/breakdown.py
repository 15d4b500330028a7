"""Run one benchmark cell traced, and break its window down by the
program's own spans and counters.

    python benchmarks/chip/breakdown.py --workload <cell> --seed <n> \
        --seconds <s> [--rehearse]

Run from the checkout root, like ``run.py``.  The run is ``run.py``'s
``--trace 1`` run, with three things the accepted harness leaves out:

- the program's ``spc.*`` host spans (``repro.spans``) beside the
  ``bench.*`` ones, each with its thread (``program_spans.load``);
- the serving engines' pairs per evaluation path
  (``ServeStats.route_pairs``) in the window's counters;
- a ``bench.gc`` span for each full (generation 2) collection of
  Python's collector, from ``gc.callbacks``, installed for this run only.

The last line of standard output is the result line, with every
per-layer metric of the cell, ``read_host_ms_per_batch`` and
``pallas_pair_share`` besides where the cell reads pairs, the cell's
end-to-end metrics as the traced run reads them (what tracing costs
shows against an untraced run), and under
``breakdown``: ``idle_by_span`` (the device's idle seconds by the name of
each gap, every gap counted; the rules are ``program_spans``'s),
``longest_gaps`` (the ten longest, named, with their start in the
window) and ``span_seconds`` (count and summed seconds per span name in
the window).
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]

#: Per-layer metrics the result line carries beside ``BENCHMARK.json``'s,
#: in the cells that read pairs: they read what only this run keeps.
READ_METRICS = [
    {"name": "read_host_ms_per_batch", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "service read path",
     "moves": "read_pairs_per_s"},
    {"name": "pallas_pair_share", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": "service read path",
     "moves": "read_pairs_per_s"},
]


class GcSpans:
    """``gc.callbacks`` entry: a ``bench.gc`` host span around each
    generation-2 collection (the collector runs on the thread that
    triggered it, so the span opens and closes on that thread)."""

    def __init__(self) -> None:
        import jax

        from benchmarks.chip import program_spans

        # bound now: a collection can start in the middle of an import
        self.annotation = jax.profiler.TraceAnnotation
        self.name = program_spans.GC_SPAN
        self.open = None

    def __call__(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self.open = self.annotation(self.name)
            self.open.__enter__()
        elif self.open is not None:
            self.open.__exit__(None, None, None)
            self.open = None


@contextlib.contextmanager
def widened(kept: dict):
    """For one run: ``trace.load`` also keeps the program's spans (the
    trace it returns lands in ``kept["trace"]``), the window's counters
    also carry ``route_pairs``, full collections are spans, and the
    profile goes to a directory of this process's own (the harness
    deletes its fixed one, which another run may be using)."""
    from benchmarks.chip import harness, program_spans
    from benchmarks.chip import trace as tr

    load, counters, trace_dir = tr.load, harness.counters, harness.TRACE_DIR

    def load_all(path):
        trace = load(path)
        trace.spans = program_spans.load(path)
        kept["trace"] = trace
        return trace

    def counters_with_pairs(svc, door):
        out = counters(svc, door)
        pairs: dict = {}
        for view in svc.stats()["serve"]:
            for path, n in view.route_pairs.items():
                pairs[path] = pairs.get(path, 0) + n
        out["route_pairs"] = pairs
        return out

    spans = GcSpans()
    tr.load, harness.counters = load_all, counters_with_pairs
    harness.TRACE_DIR = pathlib.Path(tempfile.mkdtemp(prefix="breakdown"))
    gc.callbacks.append(spans)
    try:
        yield
    finally:
        gc.callbacks.remove(spans)
        tr.load, harness.counters = load, counters
        harness.TRACE_DIR = trace_dir


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU (never on the chip)")
    args = ap.parse_args()

    from benchmarks.chip import harness, program_spans

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(args.workload)
    if entry is None:
        raise SystemExit(f"unknown workload {args.workload!r}")
    reads = harness.load_json(harness.HERE / "traffic"
                              / f"{entry['traffic']}.json")
    extra = [m for m in bench["end_to_end"]
             if args.workload in m.get("workloads", [args.workload])]
    if "closed" in reads or "open" in reads:
        extra += READ_METRICS
    bench["per_layer"] = bench["per_layer"] + [
        dict(m, workloads=[args.workload]) for m in extra]
    cell = harness.make_cell(entry, bench)
    kept: dict = {}
    with widened(kept):
        result = harness.run_cell(
            cell, args.seed, args.seconds, True, args.rehearse, T_START,
            log=lambda msg: print(msg, file=sys.stderr, flush=True))
    trace = kept["trace"]
    extra = {"idle_by_span": program_spans.idle_by_span(trace),
             "longest_gaps": program_spans.named_gaps(trace)[:10],
             "span_seconds": program_spans.span_seconds(trace)}
    result["breakdown"] = {**result.get("breakdown", {}), **extra}
    harness.emit(result)


if __name__ == "__main__":
    main()
