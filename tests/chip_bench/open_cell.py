"""The open-loop read cell, which ``BENCHMARK.json`` does not hold yet
(PERF.md, Open questions), as tests reach it: a copy of the benchmark file
with the cell and its metrics added."""

import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]

OPEN_CELL = "g500-s13-serve.open"


def bench_with_open(directory: pathlib.Path) -> pathlib.Path:
    """A copy of ``BENCHMARK.json`` with the open-loop cell and its
    metrics added, written under ``directory``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": OPEN_CELL, "config": "g500-s13-serve", "traffic": "open",
         "chips": 1, "why": "open-loop single-pair front-door reads"})
    bench["end_to_end"].append(
        {"name": "read_p99_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock", "workloads": [OPEN_CELL]})
    bench["per_layer"] += [
        {"name": "frontdoor_fill", "unit": "pairs", "better": "higher",
         "source": "program_counter", "layer": "front door",
         "moves": "read_p99_ms", "workloads": [OPEN_CELL]},
        {"name": "device_idle.open", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device",
         "moves": "read_p99_ms", "workloads": [OPEN_CELL]}]
    path = directory / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path
