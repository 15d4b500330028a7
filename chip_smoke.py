"""Chip smoke test: the DSPC service's main path once on a TPU, checked.

One process builds the ``CONFIG`` deployment (``repro.configs.dspc``:
65,536 vertices, 524,288 power-law edges generated from ``--seed``),
with n and m halved ``HALVINGS`` times, through
``SPCService.from_config``, ingests a mixed update stream through
``submit``, serves pinned, read-your-writes and ``FrontDoor`` reads, and
checks the answers: every kernel-routed batch against the int64 merge
route on the same pinned snapshot, and sampled sources against the
``bfs_spc`` oracle on the final graph.  Each phase prints one line; the
last line is the JSON verdict.

Run from the checkout root:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # edge-sharded updater + sharded
                                      # readers against one chip's service

The script exits non-zero, and prints no verdict, when JAX finds no TPU,
when the Pallas kernel would run in interpret mode, or when any check
fails.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402

BUCKETS = (8, 64, 256, 1024)

#: CONFIG's n and m are halved this many times (same degree law, 1/64 of
#: the vertices), and the stream is EVENTS long.  On a v5e chip the
#: full-size build reaches l_cap 1024 within its first 512 hubs at
#: seconds per 32-hub round, and at n = 1,024 the update engine applies
#: 0.6 events/s (every insert repairs one BFS per hub in the union of two
#: labels), so neither the full build nor a 512-event stream fits the
#: run's time limit.  Raise both as the build and update engines speed up.
HALVINGS = 6
EVENTS = 128
#: The four-chip phase replays its stream through two services (sharded
#: and one chip) on four chips' time, so its stream is shorter.
FOUR_CHIP_EVENTS = 16
KERNEL_ROUTES = ("pallas", "pallas+merge")


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAILED: {what}")


def check_device(chips: int):
    import jax
    from repro.kernels.common import resolve_interpret

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(f"device: JAX found no TPU (platform "
                         f"{d0.platform!r}); this smoke test runs on the "
                         f"chip only")
    require(len(devs) >= chips, f"{chips} chips asked, {len(devs)} found")
    require(not resolve_interpret(None),
            "the Pallas kernel would run in interpret mode "
            "(REPRO_PALLAS_INTERPRET is set?)")
    print(f"device: {d0.platform} {d0.device_kind} x{len(devs)}, "
          f"compile cache {enable_compile_cache()}", flush=True)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def build(cfg, edges, label: str, **kw):
    from repro.serve import SPCService

    t0 = time.perf_counter()
    svc = SPCService.from_config(cfg, edges=edges, wait_timeout=900.0, **kw)
    build_s = time.perf_counter() - t0
    require(svc.version == 0, f"{label}: no version published after build")
    idx = svc.spc.index
    print(f"setup[{label}]: n={cfg.n} m={len(edges)} built in "
          f"{build_s:.2f}s to v{svc.version}: l_cap={idx.l_cap} "
          f"entries={int(idx.total_entries())} "
          f"regrows={svc.spc.stats.snapshot().label_regrows}", flush=True)
    return svc


def ingest(svc, events, label: str) -> None:
    """Submit the stream in ``update_batch`` chunks and drain.  The first
    chunk compiles the update engine and is timed on its own."""
    chunk = svc.update_batch
    t0 = time.perf_counter()
    svc.submit(events[:chunk])
    svc.drain()
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for lo in range(chunk, len(events), chunk):
        svc.submit(events[lo:lo + chunk])
    svc.drain()
    rest_s = time.perf_counter() - t0
    rest = len(events) - chunk
    rate = (f"then {rest / rest_s:.2f} events/s" if rest > 0
            else "no further chunk")
    n_ins = sum(op == "+" for op, _, _ in events)
    require(svc.version == -(-len(events) // chunk),
            f"{label}: version {svc.version} after "
            f"{-(-len(events) // chunk)} chunks")
    print(f"updates[{label}]: {len(events)} events ({n_ins} inserts, "
          f"{len(events) - n_ins} deletes) in chunks of {chunk}: first "
          f"chunk {first_s:.2f}s (compile included), {rate}; published "
          f"v{svc.version}, l_cap={svc.spc.index.l_cap}", flush=True)


def answers_equal(d1, c1, d2, c2) -> int:
    """Number of pairs whose (dist, count) differ."""
    return int(np.sum((np.asarray(d1) != np.asarray(d2))
                      | (np.asarray(c1) != np.asarray(c2))))


def routed(reader, s, t):
    """Answer one batch and name the route the engine chose for it."""
    before = dict(reader.engine.stats.snapshot().routes)
    d, c = reader(s, t)
    after = reader.engine.stats.snapshot().routes
    route = [k for k in after if after[k] != before.get(k, 0)]
    return np.asarray(d), np.asarray(c), ",".join(route)


def check_reads(svc, rng) -> None:
    """Pinned batches at every bucket size, each checked against an
    explicit merge-route batch on the same snapshot."""
    n = svc.n
    pinned = svc.reader()
    merge = svc.reader(route="merge")
    lines = []
    for b in BUCKETS:
        s = rng.integers(0, n, b)
        t = rng.integers(0, n, b)
        t0 = time.perf_counter()
        d, c, route = routed(pinned, s, t)
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        routed(pinned, s, t)
        warm_s = time.perf_counter() - t0
        dm, cm = (np.asarray(x) for x in merge(s, t))
        require(pinned.last_version == merge.last_version,
                "kernel and merge batches pinned different versions")
        bad = answers_equal(d, c, dm, cm)
        require(bad == 0, f"{bad} of {b} pairs differ from the merge route "
                          f"(route {route})")
        lines.append(f"{b}:{route} {first_s:.2f}s/{1e6 * warm_s / b:.1f}us")
    print(f"reads: pinned at v{pinned.last_version}, 0 mismatches vs merge; "
          f"bucket:route first-call/warm-per-pair " + " ".join(lines),
          flush=True)


def check_read_your_writes(svc) -> None:
    from repro.data import graph_stream

    sess = svc.session()
    (op, a, b), = graph_stream(sorted(svc.spc._edge_set()), svc.n, 1, 0,
                               seed=11)
    ticket = sess.submit([(op, a, b)])
    ryw = svc.reader("read_your_writes", session=sess)
    d, c = ryw([a], [b])
    version = svc.ticket_version(ticket)
    require((int(d[0]), int(c[0])) == (1, 1),
            f"inserted edge ({a},{b}) read back as ({int(d[0])}, "
            f"{int(c[0])})")
    require(ryw.last_version >= version, "read-your-writes read too early")
    print(f"read_your_writes: inserted ({a},{b}) with ticket {ticket}, read "
          f"(1, 1) at v{ryw.last_version} >= v{version}", flush=True)


def check_frontdoor(svc, cfg, rng, callers: int = 8,
                    per_caller: int = 32) -> None:
    """Single-pair sessions coalesced by the front door while one writing
    session inserts.  Each reader answer must equal the merge answer of
    the snapshot before the write or the one after it."""
    from repro.data import graph_stream

    n = svc.n
    merge = svc.reader(route="merge")
    pairs = [(rng.integers(0, n, per_caller), rng.integers(0, n, per_caller))
             for _ in range(callers)]
    before = [merge(s, t) for s, t in pairs]
    got = [None] * callers

    with svc.frontdoor(max_live_batches=cfg.max_live_batches,
                       dispatchers=cfg.dispatchers,
                       deadline_s=600.0) as door:
        def reader_thread(k):
            sess = door.session()
            s, t = pairs[k]
            got[k] = [sess.query(int(a), int(b)) for a, b in zip(s, t)]

        threads = [threading.Thread(target=reader_thread, args=(k,))
                   for k in range(callers)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        writer = door.session("read_your_writes")
        more = graph_stream(sorted(svc.spc._edge_set()), n, 3, 1, seed=12)
        ticket = writer.submit(more)
        _, a, b = next(ev for ev in more if ev[0] == "+")
        dw, cw = writer.query(a, b)
        for th in threads:
            th.join()
        elapsed = time.perf_counter() - t0
        st = door.stats()
    require((dw, cw) == (1, 1),
            f"writer session read its insert ({a},{b}) as ({dw}, {cw})")
    after = [merge(s, t) for s, t in pairs]
    bad = 0
    for k in range(callers):
        d = np.asarray([x[0] for x in got[k]])
        c = np.asarray([x[1] for x in got[k]])
        ok_before = (d == np.asarray(before[k][0])) & \
                    (c == np.asarray(before[k][1]))
        ok_after = (d == np.asarray(after[k][0])) & \
                   (c == np.asarray(after[k][1]))
        bad += int(np.sum(~(ok_before | ok_after)))
    require(bad == 0, f"{bad} front-door answers match neither snapshot")
    print(f"frontdoor: {callers} sessions x {per_caller} single pairs + 1 "
          f"writer (ticket {ticket}, read its insert as (1, 1)) in "
          f"{elapsed:.2f}s: {st['batches']} dispatches, mean fill "
          f"{st['mean_fill']:.1f}, 0 mismatches", flush=True)


def route_counts(svc) -> dict:
    counts = collections.Counter()
    for view in svc.stats()["serve"]:
        counts.update(view.routes)
    return dict(counts)


def check_routes(svc) -> None:
    """At least one batch took the kernel (compiled: ``check_device``
    refused interpret mode before anything ran)."""
    counts = route_counts(svc)
    print(f"routes: {counts}", flush=True)
    require(sum(counts.get(r, 0) for r in KERNEL_ROUTES) >= 1,
            "no batch was served on a Pallas route")


def check_oracle(readers, edges, n: int, rng, sources: int = 4,
                 targets: int = 256) -> None:
    """Every reader answers sampled sources exactly as ``bfs_spc`` on the
    final graph (vertex 0, the top-ranked hub, plus random sources)."""
    from repro.core.graph import INF
    from repro.core.refimpl import RefGraph, bfs_spc

    ref = RefGraph(n, edges)
    srcs = [0] + [int(v) for v in rng.choice(np.arange(1, n), sources - 1,
                                             replace=False)]
    checked = 0
    for s in srcs:
        dist, cnt = bfs_spc(ref, s)
        t = rng.integers(0, n, targets)
        want_d = np.where(dist[t] >= int(INF), int(INF), dist[t])
        want_c = np.where(dist[t] >= int(INF), 0, cnt[t])
        for name, reader in readers.items():
            d, c = reader(np.full(targets, s), t)
            bad = answers_equal(d, c, want_d, want_c)
            require(bad == 0, f"{name}: {bad} of {targets} answers from "
                              f"source {s} differ from bfs_spc")
            checked += targets
    print(f"oracle: {checked} answers from sources {srcs} equal bfs_spc on "
          f"the final graph ({', '.join(readers)})", flush=True)


def run_one_chip(args, cfg) -> None:
    from repro.data import graph_stream, random_graph_edges

    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    edges = random_graph_edges(cfg.n, cfg.m, seed=args.seed)
    print(f"graph: {len(edges)} edges generated in "
          f"{time.perf_counter() - t0:.2f}s", flush=True)
    svc = build(cfg, edges, "1 chip")
    with svc:
        events = graph_stream(edges, cfg.n, 3 * EVENTS // 4, EVENTS // 4,
                              seed=args.seed + 1)
        ingest(svc, events, "1 chip")
        check_reads(svc, rng)
        check_read_your_writes(svc)
        check_frontdoor(svc, cfg, rng)
        check_routes(svc)
        readers = {"auto": svc.reader(), "merge": svc.reader(route="merge")}
        check_oracle(readers, sorted(svc.spc._edge_set()), cfg.n, rng)


def run_four_chips(args, cfg) -> None:
    """The edge-sharded updater and sharded readers over four chips,
    against a one-chip service in the same process on the same stream."""
    import jax
    from jax.sharding import Mesh

    from repro.data import graph_stream, random_graph_edges

    rng = np.random.default_rng(args.seed)
    devs = np.asarray(jax.devices()[:4])
    edges = random_graph_edges(cfg.n, cfg.m, seed=args.seed)
    events = graph_stream(edges, cfg.n, 3 * FOUR_CHIP_EVENTS // 4,
                          FOUR_CHIP_EVENTS // 4, seed=args.seed + 1)
    sharded = build(cfg, edges, "4 chips", mesh=Mesh(devs, ("model",)),
                    serve_mesh=Mesh(devs, ("data",)), route="sharded")
    single = build(cfg, edges, "1 chip")
    with sharded, single:
        ingest(sharded, events, "4 chips")
        ingest(single, events, "1 chip")
        require(sharded.spc._edge_set() == single.spc._edge_set(),
                "the two services hold different graphs")
        r4 = sharded.reader()
        r1 = single.reader(route="merge")
        for b in BUCKETS:
            s = rng.integers(0, cfg.n, b)
            t = rng.integers(0, cfg.n, b)
            bad = answers_equal(*r4(s, t), *r1(s, t))
            require(bad == 0, f"{bad} of {b} sharded answers differ from "
                              f"the one-chip service")
        print(f"sharded: {sum(BUCKETS)} pairs over buckets {BUCKETS} equal "
              f"the one-chip service; routes {route_counts(sharded)}",
              flush=True)
        check_oracle({"sharded": r4, "1 chip": r1},
                     sorted(single.spc._edge_set()), cfg.n, rng)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the edge-sharded four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs.dspc import CONFIG

    cfg = dataclasses.replace(CONFIG, n=CONFIG.n >> HALVINGS,
                              m=CONFIG.m >> HALVINGS)

    device = check_device(args.chips)
    if args.chips == 4:
        run_four_chips(args, cfg)
    else:
        run_one_chip(args, cfg)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
