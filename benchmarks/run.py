"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Runs one experiment per paper table/figure (Section 4) at CPU scale plus
the kernel microbenches.  ``--fast`` shrinks sizes further (CI),
``--list`` prints the registry, ``--only a,b`` selects a subset.

Every ``benchmarks.paper_tables.*_table`` emitter MUST be registered in
:data:`TABLES` below (its name, fast/full kwargs and the committed
``BENCH_*.json`` artifact, if any) -- ``tests/benchmarks`` asserts the
registry is complete, so a new table can never silently drop out of the
CI smoke step.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import time
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class TableSpec:
    """One registered experiment: ``table`` is the emitter attribute in
    ``benchmarks.paper_tables``; ``artifact`` the committed JSON (None:
    print-only); ``fast`` the CI-scale kwargs, ``full`` overrides for
    the default run (empty: emitter defaults)."""
    table: str
    fast: Dict
    full: Dict = dataclasses.field(default_factory=dict)
    artifact: Optional[str] = None


#: name -> spec, in run order.  ``dist_update`` needs forced host
#: devices and runs in its own subprocess unless it is the only
#: selection (see main()).
TABLES: Dict[str, TableSpec] = {
    "table4": TableSpec(
        "table4", fast=dict(sizes=((120, 300), (240, 700)), n_updates=5)),
    "figure7": TableSpec(
        "figure7", fast=dict(n=200, m=600, n_updates=8, n_queries=100)),
    "figure8_9": TableSpec(
        "figure8_9", fast=dict(n=150, m=400, n_updates=4)),
    "figure10": TableSpec(
        "figure10", fast=dict(n=150, m=400, n_insert=8, n_delete=2)),
    "figure11": TableSpec(
        "figure11", fast=dict(n=150, m=450, n_each=4)),
    "table5": TableSpec(
        "table5", fast=dict(n=150, m=400, n_edges_tested=5)),
    "hybrid": TableSpec(
        "hybrid_table",
        fast=dict(n=120, m=300, n_insert=12, n_delete=4, batch_size=8),
        artifact="BENCH_hybrid.json"),
    "serving": TableSpec(
        "serving_table",
        fast=dict(n=150, m=400, n_events=8, n_queries=512, batch=128),
        artifact="BENCH_serving.json"),
    "dist_update": TableSpec(
        "dist_update_table",
        fast=dict(n=100, m=240, n_events=8, batch_size=4),
        artifact="BENCH_dist_update.json"),
    "publish": TableSpec(
        "publish_table",
        fast=dict(n=120, m=300, n_events=12, update_batch=4,
                  query_batch=64),
        artifact="BENCH_publish.json"),
    "service": TableSpec(
        "service_table",
        fast=dict(n=120, m=300, n_events=12, update_batch=4,
                  query_batch=64),
        artifact="BENCH_service.json"),
    "frontdoor": TableSpec(
        "frontdoor_table",
        fast=dict(n=120, m=300, n_events=12, update_batch=4, readers=8,
                  queries_per_reader=80, reps=2),
        artifact="BENCH_frontdoor.json"),
    "construct": TableSpec(
        "construct_table",
        fast=dict(sizes=((400, 1200), (1000, 3000)), hub_batch=32),
        artifact="BENCH_construct.json"),
    "fleet": TableSpec(
        "fleet_table",
        fast=dict(n=120, m=300, n_events=12, update_batch=4,
                  query_batch=64, poll_intervals=(0.01, 0.1)),
        artifact="BENCH_fleet.json"),
    "analytics": TableSpec(
        "analytics_table",
        fast=dict(n=150, m=400, n_updates=5, events_per_update=2,
                  pair_sample=128, l_cap=32),
        artifact="BENCH_analytics.json"),
}


def list_tables() -> str:
    """The ``--list`` text: one registered experiment per line."""
    lines = []
    for name, spec in TABLES.items():
        artifact = spec.artifact or "-"
        lines.append(f"{name:12s} paper_tables.{spec.table:18s} {artifact}")
    lines.append(f"{'kernels':12s} {'kernels_bench (micro)':37s} -")
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--list", action="store_true",
                    help="print the experiment registry and exit")
    ap.add_argument("--only", default=None,
                    help="comma list of registry names (see --list), "
                         "plus 'kernels'")
    args = ap.parse_args()

    if args.list:
        print(list_tables())
        return

    wanted = set(args.only.split(",")) if args.only else None
    known = set(TABLES) | {"kernels"}
    if wanted is not None and not wanted <= known:
        raise SystemExit(f"unknown table(s): {sorted(wanted - known)}; "
                         f"run --list for the registry")

    # dist_update wants a real (multi-device) mesh, and host devices must
    # be forced before jax initializes.  Forcing them here would distort
    # every co-selected single-device benchmark (and the committed
    # artifacts), so unless dist_update is the ONLY selection it runs in
    # its own subprocess and this process never sees the flag.  Keep the
    # child spawned before this process imports JAX at all: a parent that
    # has touched JAX holds the chip, and a child that needs it then fails
    # or hangs.
    dist_selected = wanted is None or "dist_update" in wanted
    dist_done = False
    if dist_selected and wanted == {"dist_update"}:
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    elif dist_selected:
        cmd = [sys.executable, "-m", "benchmarks.run",
               "--only", "dist_update"]
        if args.fast:
            cmd.append("--fast")
        subprocess.run(cmd, check=True)  # writes BENCH_dist_update.json
        dist_done = True

    from benchmarks import kernels_bench, paper_tables as P
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    root = pathlib.Path(__file__).resolve().parent.parent
    for name, spec in TABLES.items():
        if wanted and name not in wanted:
            continue
        if name == "dist_update" and dist_done:
            continue  # already ran in the forced-device subprocess
        fn = getattr(P, spec.table)
        t0 = time.perf_counter()
        rows = fn(**(spec.fast if args.fast else spec.full))
        print(f"## {name} done in {time.perf_counter() - t0:.1f}s\n")
        if spec.artifact is not None and rows is not None:
            out = root / spec.artifact
            out.write_text(json.dumps(rows, indent=2) + "\n")
            print(f"wrote {out}")
    if wanted is None or "kernels" in wanted:
        t0 = time.perf_counter()
        kernels_bench.query_kernel_vs_jnp()
        kernels_bench.query_kernel_vs_merge(
            ls=(64,) if args.fast else (64, 256, 1024))
        kernels_bench.segment_matmul_vs_segment_sum()
        print(f"## kernels done in {time.perf_counter() - t0:.1f}s\n")


if __name__ == "__main__":
    main()
