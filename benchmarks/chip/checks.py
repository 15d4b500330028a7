"""The comparison that decides ``correct``.

Every number compared counts answers of the timed path that differ from
the plain reference (``reference.py``), or answers that never came; each
is exact, so each limit is 0.  A count is held to the count contract: it
is right if and only if it equals ``min(true count, 2^63 - 1)``.

- ``writer``: every event's read-back against a BFS of the reference's
  graph right after that event, and the index as the update engine left
  it (all targets of ``index_sources`` sources drawn from the seed, read
  through a pinned reader once the window has closed) against a BFS of
  the final graph;
- ``open`` / ``closed``: every answer the window produced whose source or
  target is one of ``sample_sources`` vertices drawn from the seed, each
  against a BFS from that vertex.

``control=True`` also puts the reference with rounded counts in the
program's place on the same pairs and returns its readings beside the
program's: the control (bfloat16 counts) and, beside it, float32 counts,
the kernel's own precision without its int64 fallback.

Beside the compared numbers, ``answers_saturated`` counts the checked
pairs whose reference count is ``INT64_MAX``: how far a cell reaches the
contract's edge.  It has no limit.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip.reference import (INT64_MAX, Adjacency, EdgeSet,
                                       bfs_counts)


def read_index(svc, rng, sources: int) -> dict:
    """All targets of ``sources`` random sources, read from the final
    published index through a pinned reader in the largest batches."""
    n = svc.n
    src = rng.choice(n, size=min(sources, n), replace=False)
    reader = svc.reader()
    top = 1024
    dist = np.zeros((src.size, n), np.int64)
    cnt = np.zeros((src.size, n), np.int64)
    t_all = np.arange(n)
    for i, s in enumerate(src):
        for lo in range(0, n, top):
            t = t_all[lo:lo + top]
            d, c = reader(np.full(t.size, s), t)
            dist[i, lo:lo + top] = np.asarray(d)
            cnt[i, lo:lo + top] = np.asarray(c)
    return {"sources": src, "dist": dist, "cnt": cnt,
            "version": reader.last_version}


def check_writer(writer: dict, n: int, edges, final: dict) -> dict:
    ref = EdgeSet(n, edges)
    wrong = missing = 0
    for op, a, b, d, c, _, _, err in writer["log"]:
        ref.apply(op, a, b)
        if err is not None or d is None:
            missing += 1
            continue
        dist, cnt = bfs_counts(ref.adjacency(), a)
        wrong += int((dist[b], cnt[b]) != (d, c))
    adj = ref.adjacency()
    index_wrong = 0
    for i, s in enumerate(final["sources"]):
        dist, cnt = bfs_counts(adj, int(s))
        index_wrong += int(np.sum((dist != final["dist"][i])
                                  | (cnt != final["cnt"][i])))
    return {"readback_wrong": wrong, "readback_missing": missing,
            "index_wrong": index_wrong}


#: The lower precisions read with ``control=True``.
CONTROL_COUNTS = ("bfloat16", "float32")


def check_pairs(s, t, dist, cnt, adj: Adjacency, sample: np.ndarray,
                control: bool = False) -> dict:
    """Mismatches among the pairs whose source or target is in
    ``sample``, and how many of those pairs the reference counts at
    ``INT64_MAX``; with ``control`` also the mismatches of each precision
    in ``CONTROL_COUNTS``, and the largest count among the pairs."""
    pos = np.full(adj.n, -1)
    pos[sample] = np.arange(sample.size)
    ps, pt = pos[s], pos[t]
    use_s = ps >= 0
    use_t = (pt >= 0) & ~use_s
    out = {"checked": int(use_s.sum() + use_t.sum())}
    ref_d = np.zeros((sample.size, adj.n), np.int64)
    ref_c = np.zeros((sample.size, adj.n), np.int64)
    ctl_c = {p: np.zeros((sample.size, adj.n), np.int64)
             for p in (CONTROL_COUNTS if control else ())}
    for i, v in enumerate(sample):
        ref_d[i], ref_c[i] = bfs_counts(adj, int(v))
        for p, c in ctl_c.items():
            c[i] = bfs_counts(adj, int(v), counts=p)[1]
    want_d = np.concatenate([ref_d[ps[use_s], t[use_s]],
                             ref_d[pt[use_t], s[use_t]]])
    want_c = np.concatenate([ref_c[ps[use_s], t[use_s]],
                             ref_c[pt[use_t], s[use_t]]])
    got_d = np.concatenate([dist[use_s], dist[use_t]])
    got_c = np.concatenate([cnt[use_s], cnt[use_t]])
    out["wrong"] = int(np.sum((got_d != want_d) | (got_c != want_c)))
    out["saturated"] = int(np.sum(want_c == INT64_MAX))
    for p, c in ctl_c.items():
        ctl = np.concatenate([c[ps[use_s], t[use_s]],
                              c[pt[use_t], s[use_t]]])
        out[f"wrong.{p}"] = int(np.sum(ctl != want_c))
    if control:
        out["max_count"] = int(want_c.max()) if want_c.size else 0
    return out


def sample_vertices(edges, n: int, k: int, rng) -> np.ndarray:
    deg = np.bincount(np.asarray(edges, np.int64).ravel(), minlength=n)
    live = np.nonzero(deg)[0]
    return np.sort(rng.choice(live, size=min(k, live.size), replace=False))


def compare(run, n: int, edges, traffic: dict, final, rng,
            control: bool = False) -> dict:
    """The compared numbers, each with its limit, and the verdict."""
    checks, attempted, failed = {}, 0, 0
    answers, controls = {}, {}
    if run.writer is not None:
        w = check_writer(run.writer, n, edges, final)
        checks["readback_wrong"] = w["readback_wrong"]
        checks["readback_missing"] = w["readback_missing"]
        checks["index_wrong"] = w["index_wrong"]
        attempted += run.writer["events"]
        failed += run.writer["failed"]
    pair_sets = []
    if run.open is not None:
        o = run.open
        ok = o["answered"]
        pair_sets.append((o["s"][ok], o["t"][ok], o["dist"][ok],
                          o["cnt"][ok]))
        checks["requests_failed"] = o["failed"]
        attempted += o["requests"]
        failed += o["failed"]
    if run.closed is not None:
        rows = run.closed["rows"]
        if rows:
            pair_sets.append(tuple(np.concatenate([r[i] for r in rows])
                                   for i in range(4)))
        lost = run.closed["failed"] * run.closed["pair_batch"]
        checks["pairs_failed"] = lost
        attempted += sum(r[0].size for r in rows) + lost
        failed += lost
    if pair_sets:
        s, t, d, c = (np.concatenate([p[i] for p in pair_sets])
                      for i in range(4))
        sample = sample_vertices(edges, n,
                                 int(traffic["check"]["sample_sources"]), rng)
        p = check_pairs(s, t, d, c, Adjacency(n, edges), sample, control)
        checks["answers_wrong"] = p["wrong"]
        answers = {"answers_checked": p["checked"],
                   "answers_saturated": p["saturated"]}
        if control:
            controls = {f"answers_wrong.{q}": p[f"wrong.{q}"]
                        for q in CONTROL_COUNTS}
            controls["max_count"] = p["max_count"]
    correct = all(v <= 0 for v in checks.values())
    if answers.get("answers_checked") == 0:
        correct = False
    table = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "checks": table, "answers": answers}
    if control:
        out["control"] = controls
    return out
