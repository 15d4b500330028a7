"""The open-loop rate sweep behind an ``open`` mix's ``rate_per_s``.  Not
part of a benchmark run.

    python benchmarks/chip/sweep.py --config <config> --traffic <mix> \
        --seed <n> --seconds <s> --rates <r> [<r> ...] [--rehearse]

One process sets the configuration's system up once, warms it as a run of
a cell of that configuration and mix does, then runs the mix's open loop
at each rate in turn for ``--seconds`` on one chip, with a
pause between rates for the queue to drain.  Each rate prints one JSON
line on standard output: requests sent and failed, the latency's p50 and
p99 (from when each request was due), the generator's own lateness, the
median latency of the first and last quarter of the window, and the front
door's mean fill.

A rate is sustained when no request failed, the last quarter's median is
under twice the first's plus 2 ms (the queue did not grow), the p99 is
under one second, and the generator kept its schedule: its own lateness
at p99 under a tenth of the latency's p99 (the generator shares the
process, so a host that cannot keep up shows there first).  The sweep stops at the second rate that is not
sustained; its last line names the highest sustained rate (the knee) and
four fifths of it, rounded down to ten, the rate an ``open`` mix takes.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]

#: Seconds between two rates, for the front door's queue to drain.
DRAIN_S = 3.0


def sustained(rec: dict) -> bool:
    first, last = rec["p50_first_quarter_ms"], rec["p50_last_quarter_ms"]
    return (rec["failed"] == 0 and first is not None and last is not None
            and last < 2 * first + 2 and rec["p99_ms"] < 1000
            and rec["late_p99_ms"] < 0.1 * rec["p99_ms"])


def measure(system, params: dict, seconds: float) -> dict:
    import numpy as np

    from benchmarks.chip import generator, harness

    loop = generator.OpenLoop(system.ctx, params, seconds)
    before = system.door.stats()
    t0 = time.monotonic()
    loop.run(t0, seconds + harness.LATE_GRACE_S)
    res = loop.result(t0, t0 + seconds)
    after = system.door.stats()
    health = harness.open_health(res)
    lat = res["latency_s"]
    ms = (lambda x: None if x is None else 1e3 * x)
    batches = after["batches"] - before["batches"]
    return {"rate": params["rate_per_s"], "requests": res["requests"],
            "failed": res["failed"],
            "p50_ms": 1e3 * float(np.percentile(lat, 50)),
            "p99_ms": 1e3 * float(np.percentile(lat, 99)),
            "late_p99_ms": ms(health["late_p99_s"]),
            "late_max_ms": ms(health["late_max_s"]),
            "p50_first_quarter_ms": ms(health["p50_first_quarter_s"]),
            "p50_last_quarter_ms": ms(health["p50_last_quarter_s"]),
            "fill": (after["pairs"] - before["pairs"]) / max(1, batches),
            "compiles": system.compile_log.between(
                t0, t0 + seconds)["compiles"]}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmarks.chip import harness

    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    name = f"{args.config}.{args.traffic}"
    cell = harness.make_cell({"name": name, "config": args.config,
                              "traffic": args.traffic, "chips": 1},
                             {"end_to_end": [], "per_layer": []})
    if "open" not in cell.traffic:
        raise SystemExit(f"traffic {args.traffic!r} has no open loop")
    system = harness.start_system(cell, args.seed, args.rehearse, T_START,
                                  log)
    harness.warm(system, args.seed, T_START)
    log(f"setup: {time.monotonic() - T_START:.3f}s")
    best, misses = None, 0
    try:
        for rate in args.rates:
            params = dict(system.traffic["open"], rate_per_s=rate)
            rec = measure(system, params, args.seconds)
            rec["sustained"] = sustained(rec)
            print(json.dumps(rec), flush=True)
            if rec["sustained"]:
                best = rate if best is None else max(best, rate)
            else:
                misses += 1
                if misses == 2:
                    break
            time.sleep(DRAIN_S)
    finally:
        system.door.close()
        system.svc.close()
    rate = None if best is None else int(0.8 * best // 10 * 10)
    print(json.dumps({"knee": best, "rate_per_s": rate}), flush=True)


if __name__ == "__main__":
    main()
