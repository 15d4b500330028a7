"""The harness: finds a cell's files by name, builds the system under test
from them, runs the traffic for the window and reduces what it saw.

Everything that belongs to one configuration, traffic mix or metric sits
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the graph generator and its parameters, the
  service knobs, the source, what was cut and what was assumed;
- ``traffic/<traffic>.json``: the sections ``generator.py`` runs;
- ``graphs/<generator>.py``: a graph generator, ``generate(params, seed)``;
- ``metrics/<metric>.py``: a metric's reader, ``read(run)``, returning a
  number or None; a metric ``a.b`` falls back to ``metrics/a.py``.

A later change adds a cell, a configuration or a metric by adding such
files and entries; it edits none of these modules.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time
import types
import zlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]

#: Every blocking wait of the service (read-your-writes, drain) is bounded
#: by this; the first run of a cell compiles the update engine (minutes).
WAIT_TIMEOUT_S = 900.0
#: How long after the window's close the harness waits for answers due in
#: it before it calls them missing.
LATE_GRACE_S = 60.0
TRACE_DIR = ROOT / ".bench_trace"
#: JAX's persistent compilation cache on the chip.
CACHE_DIR = ROOT / ".jax_cache"


# -- finding things by name --------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    bench: dict
    base: pathlib.Path

    def metrics(self, kind: str) -> list:
        """The cell's ``end_to_end`` or ``per_layer`` metric entries."""
        return [m for m in self.bench[kind]
                if self.name in m.get("workloads", [self.name])]


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, base: pathlib.Path = HERE,
              bench_path: pathlib.Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    return make_cell(cells[name], bench, base)


def make_cell(entry: dict, bench: dict, base: pathlib.Path = HERE) -> Cell:
    """The cell of a ``workloads`` entry, its files read from ``base``."""
    config = load_json(base / "configs" / f"{entry['config']}.json")
    traffic = load_json(base / "traffic" / f"{entry['traffic']}.json")
    return Cell(entry["name"], int(entry["chips"]), config, traffic, bench,
                base)


def _load_module(path: pathlib.Path, label: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_{label}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader_for(metric: str, base: pathlib.Path = HERE):
    """``read(run)`` of ``metrics/<metric>.py``, else of the file named
    by the part before the first dot."""
    for stem in (metric, metric.split(".")[0]):
        path = base / "metrics" / f"{stem}.py"
        if path.exists():
            return _load_module(path, f"metric_{stem}").read
    raise SystemExit(f"no reader for metric {metric!r} under "
                     f"{base / 'metrics'}")


def graph_generator(name: str, base: pathlib.Path = HERE):
    return _load_module(base / "graphs" / f"{name}.py",
                        f"graph_{name}").generate


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over``'s keys replaced, one level of dicts deep."""
    out = dict(base)
    for k, v in over.items():
        out[k] = ({**base[k], **v} if isinstance(v, dict)
                  and isinstance(base.get(k), dict) else v)
    return out


# -- device ------------------------------------------------------------------
def check_device(chips: int) -> dict:
    """The chip this run measures, or exit non-zero before any result
    line: JAX must find a TPU with at least ``chips`` devices, a kind in
    the peaks table, and the Pallas kernel must compile (no interpret
    mode)."""
    import jax
    from repro.kernels.common import resolve_interpret

    from benchmarks.chip.peaks import PEAKS

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(f"device: JAX found no TPU (platform "
                         f"{d0.platform!r}); the benchmark runs on the chip "
                         f"only (--rehearse runs it on the CPU)")
    if len(devs) < chips:
        raise SystemExit(f"device: {chips} chips asked, {len(devs)} found")
    if resolve_interpret(None):
        raise SystemExit("device: the Pallas kernel would run in interpret "
                         "mode (REPRO_PALLAS_INTERPRET is set?)")
    if d0.device_kind not in PEAKS:
        raise SystemExit(f"device: kind {d0.device_kind!r} is not in the "
                         f"peaks table (benchmarks/chip/peaks.py)")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def rehearsal_device() -> dict:
    import jax

    d0 = jax.devices()[0]
    if d0.platform != "cpu":
        raise SystemExit("--rehearse runs on the CPU only "
                         "(JAX_PLATFORMS=cpu)")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class CompileLog:
    """Every executable JAX compiled or loaded from its persistent cache,
    with the time, so a run can say what fell inside its window."""

    def __init__(self) -> None:
        from jax import monitoring

        self.compiles: list = []        # (time, function name)
        self.cache_hits: list = []
        backend = "/jax/core/compile/backend_compile_duration"

        def on_duration(event, duration, fun_name="", **_):
            if event == backend:
                self.compiles.append((time.monotonic(), fun_name))

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits.append(time.monotonic())

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def between(self, lo: float, hi: float) -> dict:
        """What was made ready in ``[lo, hi]``: ``compiles`` counts
        executables compiled, ``cache_hits`` those loaded from the
        persistent cache instead (JAX times both as a backend compile),
        ``compiled`` names them all."""
        names = collections.Counter(f for t, f in self.compiles
                                    if lo <= t <= hi)
        hits = sum(lo <= t <= hi for t in self.cache_hits)
        return {"compiles": sum(names.values()) - hits,
                "cache_hits": hits, "compiled": dict(names)}


# -- the system under test ---------------------------------------------------
class Context:
    """What the traffic sections share: the seed, the graph, the service
    and its front door."""

    def __init__(self, seed: int, n: int, edges, svc, door, pairs) -> None:
        self.seed = seed
        self.n = n
        self.edges = edges
        self.svc = svc
        self.door = door
        self.pairs = pairs

    def rng(self, label: str):
        return seed_rng(self.seed, label)


def seed_rng(seed: int, label: str):
    import numpy as np

    return np.random.default_rng(
        [seed & (2 ** 64 - 1), zlib.crc32(label.encode())])


def build_service(config: dict, n: int, edges):
    """``SPCService.from_config`` with the configuration's knobs."""
    from repro.serve import SPCService

    knobs = dict(config["service"])
    fixed = {k: knobs.pop(k) for k in ("cap_e", "buckets") if k in knobs}
    if "buckets" in fixed:
        fixed["buckets"] = tuple(fixed["buckets"])
    for k in ("max_live_batches", "dispatchers", "deadline_s",
              "frontdoor_batch"):
        knobs.pop(k, None)
    cfg = types.SimpleNamespace(n=n, m=len(edges), **knobs)
    return SPCService.from_config(cfg, edges=edges,
                                  wait_timeout=WAIT_TIMEOUT_S, **fixed)


def counters(svc, door) -> dict:
    st = svc.stats()
    return {"routes": dict(served(svc, "routes")),
            "route_pairs": dict(served(svc, "route_pairs")),
            "queries": st["queries"],
            "frontdoor": door.stats() if door is not None else None,
            "update": dataclasses.asdict(st["update"]),
            "version": st["version"]}


def delta(after: dict, before: dict) -> dict:
    """Counter differences (nested dicts of numbers)."""
    out = {}
    for k, v in after.items():
        b = before.get(k) if isinstance(before, dict) else None
        if isinstance(v, dict):
            out[k] = delta(v, b or {})
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[k] = v - (b or 0)
        else:
            out[k] = v
    return out


def warm_reads(svc, pairs, sizes, rng) -> None:
    """Serve one pinned reader batch of each size in ``sizes``, drawn as
    the traffic draws its pairs, so that what the window's batches run
    is compiled (or loaded from the cache) before the window."""
    import numpy as np

    reader = svc.reader()
    for b in sizes:
        s, t = pairs.draw(int(b), rng)
        np.asarray(reader(s, t)[1])


def served(svc, counter: str) -> collections.Counter:
    """One ``ServeStats`` counter summed over the service's serving
    engines: ``routes``, batches per route, or ``route_pairs``, real
    pairs per evaluation path."""
    out = collections.Counter()
    for view in svc.stats()["serve"]:
        out.update(getattr(view, counter))
    return out


def warm_split(svc, pairs, buckets, kmax: int, rng,
               probes: int = 512) -> dict:
    """Serve, before the window, a batch with k = 1 .. ``kmax`` rows
    that the engine routes off its plain kernel path, in each bucket of
    ``buckets``: the engine answers such a batch in two parts whose
    shapes depend on k (PERF.md, Open questions), so each k is a program
    of its own.  Which pairs take that route is read off the engine's
    public route counter: batches of 8 pairs drawn as the traffic draws
    them are served through the reader, each pair of a batch that did
    not take the ``pallas`` route alone again.  Where no such pair turns
    up in ``probes`` batches, no split batch is served.  A batch of
    pairs that all take the ``pallas`` route is served too, in each
    bucket, since the traffic's draws are nearly all split at the
    largest."""
    import numpy as np

    reader = svc.reader()

    def route(s, t) -> str:
        before = served(svc, "routes")
        np.asarray(reader(s, t)[1])
        (name,) = (served(svc, "routes") - before).keys()
        return name

    plain = max(buckets)
    heavy, light_s, light_t = None, [], []
    tried = 0
    while tried < probes:
        tried += 1
        s, t = pairs.draw(8, rng)
        if route(s, t) == "pallas":
            light_s += list(s)
            light_t += list(t)
        elif heavy is None:
            for a, b in zip(s, t):
                if route([a], [b]) != "pallas":
                    heavy = (a, b)
                    break
        if heavy is not None and len(light_s) >= plain:
            break
    out = {"found": heavy is not None, "probed_batches": tried, "ks": {}}
    if len(light_s) < plain:
        return out
    for bucket in sorted(buckets):
        np.asarray(reader(np.asarray(light_s[:bucket]),
                          np.asarray(light_t[:bucket]))[1])
    if heavy is None:
        return out
    for bucket in sorted(buckets):
        top = min(kmax, bucket - 1)
        for k in range(1, top + 1):
            s = np.asarray([heavy[0]] * k + light_s[:bucket - k])
            t = np.asarray([heavy[1]] * k + light_t[:bucket - k])
            np.asarray(reader(s, t)[1])
        out["ks"][bucket] = top
    return out


def warm_plan(traffic: dict, knobs: dict) -> list:
    """The batch sizes the cell's traffic serves, each as often as its
    section asks: a writer's single-pair read-back; every coalesced size
    from 1 to ``warm_sizes``, ``warm_rounds`` times, for the open loop;
    ``warm_calls`` batches of the closed loop's size."""
    sizes = []
    if "writer" in traffic:
        sizes.append(1)
    if "open" in traffic:
        o = traffic["open"]
        upto = min(int(o.get("warm_sizes", 1)), int(knobs["frontdoor_batch"]))
        sizes += list(range(1, upto + 1)) * int(o.get("warm_rounds", 1))
    if "closed" in traffic:
        c = traffic["closed"]
        sizes += [int(c["pairs_per_batch"])] * int(c.get("warm_calls", 1))
    return sizes


def warm(system, seed: int, t_start: float) -> None:
    """Set-up's reads, all through the service's public paths: the batch
    sizes of ``warm_plan``; the split batches of ``warm_split``, up to
    ``split_k`` rows off the kernel path, in the buckets the traffic's
    batches land in; then, for an open loop with ``warm_seconds``, that
    loop at the cell's own rate through the front door."""
    from repro.serve.engine import bucket_size

    from benchmarks.chip import generator

    traffic, knobs = system.traffic, system.config["service"]
    rng = seed_rng(seed, "warm")
    warm_reads(system.svc, system.ctx.pairs, warm_plan(traffic, knobs), rng)
    system.phases["warm_reads"] = time.monotonic() - t_start
    # the buckets each section's batches land in: every one up to the
    # open loop's largest coalesced batch, the closed loop's own
    buckets = tuple(knobs["buckets"])
    lands, kmax = set(), 0
    if traffic.get("open", {}).get("split_k"):
        top = bucket_size(int(traffic["open"]["warm_sizes"]), buckets)
        lands |= {b for b in buckets if b <= top}
        kmax = max(kmax, int(traffic["open"]["split_k"]))
    if traffic.get("closed", {}).get("split_k"):
        lands.add(bucket_size(int(traffic["closed"]["pairs_per_batch"]),
                              buckets))
        kmax = max(kmax, int(traffic["closed"]["split_k"]))
    if lands:
        system.warmed_split = warm_split(system.svc, system.ctx.pairs,
                                         sorted(lands), kmax, rng)
        system.phases["warm_split"] = time.monotonic() - t_start
    secs = float(traffic.get("open", {}).get("warm_seconds", 0))
    if secs > 0:
        loop = generator.OpenLoop(system.ctx, traffic["open"], secs,
                                  label="open-warm")
        loop.run(time.monotonic(), secs + LATE_GRACE_S)
        system.phases["warm_open"] = time.monotonic() - t_start


@dataclasses.dataclass
class System:
    """The system under test as set-up leaves it."""

    config: dict
    traffic: dict
    device: dict
    n: int
    edges: list
    svc: object
    door: object
    ctx: Context
    build_s: float
    built: dict
    compile_log: CompileLog
    phases: dict
    warmed_split: dict | None = None


def start_system(cell: Cell, seed: int, rehearse: bool, t_start: float,
                 log=print) -> System:
    """Check the device, generate the graph from the seed, build the
    service to version 0, start it and its front door.  ``phases`` holds
    the seconds from ``t_start`` at which each step ended."""
    import numpy as np

    from benchmarks.chip import generator

    config, traffic = cell.config, cell.traffic
    if rehearse:
        config = merged(config, config.get("rehearsal", {}))
        traffic = merged(traffic, traffic.get("rehearsal", {}))
        device = rehearsal_device()
    else:
        device = check_device(cell.chips)
    import jax

    if rehearse:
        from repro.compile_cache import enable_compile_cache

        cache = enable_compile_cache()
    else:
        # inside the checkout at a fixed path, whatever the environment
        # names: the path is part of each entry's key
        cache = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache)
        # no eviction: a size limit from the environment would drop the
        # entries a later run needs
        jax.config.update("jax_compilation_cache_max_size", -1)
    # small executables too: the engine compiles some per batch size,
    # and a later run should find those in the cache as well
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compile_log = CompileLog()
    phases = {"device": time.monotonic() - t_start}
    log(f"device: {device['platform']} {device['kind']} x{device['count']}"
        f", compile cache {cache}")

    graph = config["graph"]
    gen = graph_generator(graph["generator"], cell.base)
    n, edges = gen(graph, seed_rng(seed, "graph"))
    edge_list = [(int(a), int(b)) for a, b in edges]
    degree = np.bincount(np.asarray(edges).ravel(), minlength=n)
    phases["graph"] = time.monotonic() - t_start
    t0 = time.monotonic()
    svc = build_service(config, n, edge_list)
    idx = svc.spc.index
    np.asarray(idx.size)  # the build has finished on the device
    build_s = time.monotonic() - t0
    phases["build"] = time.monotonic() - t_start
    built = counters(svc, None)["update"]
    log(f"setup: n={n} m={len(edge_list)} built in {build_s:.3f}s, "
        f"l_cap={idx.l_cap} cap_e={svc.spc.graph.cap_e} "
        f"regrows={built['label_regrows']}")

    knobs = config["service"]
    svc.start()
    door = None
    if "writer" in traffic or "open" in traffic:
        door = svc.frontdoor(
            max_live_batches=knobs["max_live_batches"],
            dispatchers=knobs["dispatchers"],
            deadline_s=knobs["deadline_s"],
            max_batch=knobs["frontdoor_batch"]).start()
    pairs = generator.PairSampler(degree)
    ctx = Context(seed, n, edge_list, svc, door, pairs)
    return System(config, traffic, device, n, edge_list, svc, door, ctx,
                  build_s, built, compile_log, phases)


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""

    cell: Cell
    seed: int
    seconds: float
    setup_s: float
    build_s: float
    l_cap: int
    device: dict
    rehearsal: bool
    writer: dict | None = None
    open: dict | None = None
    closed: dict | None = None
    window: dict = dataclasses.field(default_factory=dict)
    traced: bool = False
    trace: object = None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             rehearse: bool, t_start: float, log=print,
             control: bool = False) -> dict:
    """One run of one cell.  Returns the result line's object
    (``control=True``: with the control's readings beside the
    program's, see ``checks.compare``)."""
    import numpy as np

    from benchmarks.chip import checks, generator
    from benchmarks.chip import trace as tr

    system = start_system(cell, seed, rehearse, t_start, log)
    config, traffic = system.config, system.traffic
    svc, door, ctx = system.svc, system.door, system.ctx
    n, edge_list, build_s = system.n, system.edges, system.build_s
    built, compile_log, phases = system.built, system.compile_log, \
        system.phases
    idx = svc.spc.index

    writer = opened = closed = None
    if "writer" in traffic:
        writer = generator.Writer(ctx, traffic["writer"])
    if "open" in traffic:
        opened = generator.OpenLoop(ctx, traffic["open"], seconds)
    if "closed" in traffic:
        closed = generator.ClosedLoop(ctx, traffic["closed"])
    warm(system, seed, t_start)
    if writer is not None:
        writer.warm()
        phases["warm_writer"] = time.monotonic() - t_start

    before = counters(svc, door)
    setup_s = time.monotonic() - t_start
    log(f"setup: {setup_s:.3f}s from process start (build {build_s:.3f}s)"
        f"; steps ended at {phases}")

    if trace:
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        gc_spans = tr.GcSpans()
        gc.callbacks.append(gc_spans)
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    t_win = time.monotonic()
    t_end = t_win + seconds
    threads = []
    with generator.span(tr.WINDOW_SPAN):
        if opened is not None:
            threads += opened.threads(t_win)
        if closed is not None:
            threads += closed.threads(t_end)
        for th in threads:
            th.start()
        if writer is not None:
            writer.run(t_end)
        pause = t_end - time.monotonic()
        if pause > 0:
            time.sleep(pause)
    t_closed = time.monotonic()
    for th in threads:
        th.join(timeout=max(0.0, t_end + LATE_GRACE_S - time.monotonic()))
    after = counters(svc, door)
    if trace:
        jax.profiler.stop_trace()
        gc.callbacks.remove(gc_spans)
    in_window = compile_log.between(t_win, t_end)
    device = dict(system.device, memory_peak_bytes=memory_peak_bytes())

    run = Run(cell, seed, seconds, setup_s, build_s, idx.l_cap, device,
              rehearse, window=delta(after, before), traced=trace)
    run.window["compile_log"] = in_window
    run.window["setup_steps_s"] = phases
    run.window["split_warm"] = system.warmed_split
    run.window["late_close_s"] = t_closed - t_end
    if writer is not None:
        run.writer = writer.result(t_win, t_end)
    if opened is not None:
        run.open = opened.result(t_win, t_end)
    if closed is not None:
        run.closed = closed.result(t_win, t_end)

    # what the timed path left behind, read before the program is freed
    final = None
    if writer is not None:
        svc.drain()
        final = checks.read_index(svc, ctx.rng("check-index"),
                                  int(traffic["check"]["index_sources"]))
    regrows = counters(svc, door)["update"]
    if door is not None:
        door.close()
    svc.close()
    del svc, door, ctx, idx

    if trace:
        run.trace = tr.load(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    t_check = time.monotonic()
    results = checks.compare(run, n, edge_list, traffic, final,
                             seed_rng(seed, "check"), control)
    results["answers"]["reference_s"] = time.monotonic() - t_check
    out = finish(run, results, regrows, built, log)
    if control:
        out["control"] = results.get("control", {})
    return out


def finish(run: Run, results: dict, regrows: dict, built: dict,
           log) -> dict:
    """Assemble the result line: metrics by their readers, the device,
    the breakdown of a traced run, what the reference read, and the
    compared numbers last."""
    from benchmarks.chip import trace as tr

    kind = "per_layer" if run.traced else "end_to_end"
    metrics = {}
    for m in run.cell.metrics(kind):
        if run.rehearsal and m["source"] == "device_trace":
            continue
        value = reader_for(m["name"], run.cell.base)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": results["correct"],
           "attempted": results["attempted"],
           "failed": results["failed"],
           "metrics": metrics,
           "device": run.device}
    if run.rehearsal:
        out["rehearsal"] = True
    if run.trace is not None and not run.rehearsal:
        summary = tr.summarize(run.trace)
        out["device"] = dict(run.device, busy_s=summary["busy_s"],
                             window_s=summary["window_s"])
        out["breakdown"] = summary["breakdown"]
    out["window"] = {
        "compiles": run.window["compile_log"]["compiles"],
        "cache_hits": run.window["compile_log"]["cache_hits"],
        "compiled": run.window["compile_log"]["compiled"],
        "late_close_s": run.window["late_close_s"],
        "label_regrows": regrows["label_regrows"],
        "edge_regrows": regrows["edge_regrows"],
        "build_regrows": built["label_regrows"],
        "setup_steps_s": run.window["setup_steps_s"],
        "split_warm": run.window["split_warm"],
    }
    if run.open is not None:
        out["load"] = open_health(run.open)
    for k, v in out["window"].items():
        log(f"window: {k} {v}")
    for k, v in out.get("load", {}).items():
        log(f"load: {k} {v}")
    for k, v in metrics.items():
        log(f"metric: {k} {v['value']} {v['unit']}")
    # what the reference read and how long it took: no limits
    out["reference"] = results["answers"]
    for k, v in out["reference"].items():
        log(f"reference: {k} {v}")
    for name, c in results["checks"].items():
        log(f"check: {name} {c['value']} (limit {c['limit']})")
    out["checks"] = results["checks"]
    return out


def open_health(result: dict) -> dict:
    """Whether the open loop held its rate: how late the generator sent
    (p99 and worst, seconds), and the median latency of the requests due
    in the window's first and last quarters (a queue that grows all
    through the window shows as a last quarter far above the first)."""
    import numpy as np

    late = result["late_s"]
    lat = result["latency_s"]
    q = lat.size // 4

    def median(x):
        x = x[np.isfinite(x)]
        return float(np.median(x)) if x.size else None

    return {"late_p99_s": float(np.percentile(late, 99)) if late.size
            else 0.0,
            "late_max_s": float(late.max()) if late.size else 0.0,
            "p50_first_quarter_s": median(lat[:q]) if q else None,
            "p50_last_quarter_s": median(lat[-q:]) if q else None}


def emit(result: dict) -> None:
    """The last line of standard output: the result object alone."""
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
