"""The one traffic generator.  A traffic mix is a data file
(``traffic/<name>.json``); this module reads its sections and drives the
service with them.  Three sections exist, and a mix may hold any of them,
all running at once over the same window:

``writer``  closed loop of one read-your-writes front-door session: submit
            one event, read its endpoints back, repeat.  Events follow the
            DSPC paper's random-update protocol (section 4.4): inserts are
            uniform fresh non-edges, deletes take a uniformly chosen present
            edge, ``inserts``:``deletes`` per shuffled block.
``open``    open loop of single-pair front-door queries: ``rate_per_s``
            arrivals, a fixed count per window, due times uniform over the
            window (a Poisson process given its count), sent by
            ``senders`` threads and timed from when each was due.  Set-up
            runs the same loop for ``warm_seconds`` on pairs of its own.
``closed``  ``callers`` threads, each sending ``pairs_per_batch`` pairs to a
            pinned ``SPCService.reader`` back to back.

Query pairs are uniform over the vertices of degree >= 1.  Everything
is drawn from the run's seed; the program receives only the generated
inputs.
"""

from __future__ import annotations

import bisect
import threading
import time

import jax
import numpy as np

SPAN_SUBMIT = "bench.submit"
SPAN_RYW = "bench.ryw_wait"
SPAN_FRONTDOOR = "bench.frontdoor_query"
SPAN_READER = "bench.reader"


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


# -- inputs ------------------------------------------------------------------
class PairSampler:
    """Draws (s, t) query pairs uniformly over the vertices of degree
    >= 1."""

    def __init__(self, degree: np.ndarray) -> None:
        self.vertices = np.nonzero(degree)[0]

    def draw(self, k: int, rng: np.random.Generator):
        s = rng.choice(self.vertices, k)
        t = rng.choice(self.vertices, k)
        return s.astype(np.int64), t.astype(np.int64)


class EventStream:
    """Endless update stream over a copy of the edge set (the protocol of
    ``repro.data.graph_stream``, drawn lazily so a faster updater never
    runs out)."""

    def __init__(self, n: int, edges, params: dict,
                 rng: np.random.Generator) -> None:
        self.n = n
        self.rng = rng
        self.present = {(int(a), int(b)) for a, b in edges}
        self.ordered = sorted(self.present)
        self.block = (["+"] * int(params.get("inserts", 3))
                      + ["-"] * int(params.get("deletes", 1)))
        self.pending: list = []

    def __iter__(self):
        return self

    def isolated_insert(self):
        """An insert joining two vertices that no edge touches, drawn
        from the stream's rng, or None where fewer than two exist."""
        touched = np.zeros(self.n, bool)
        if self.ordered:
            touched[np.asarray(self.ordered).ravel()] = True
        free = np.nonzero(~touched)[0]
        if free.size < 2:
            return None
        a, b = sorted(int(x) for x in self.rng.choice(free, 2, replace=False))
        self.present.add((a, b))
        bisect.insort(self.ordered, (a, b))
        return ("+", a, b)

    def __next__(self):
        if not self.pending:
            self.pending = list(self.rng.permutation(self.block))
        op = self.pending.pop()
        if op == "-" and self.ordered:
            key = self.ordered.pop(int(self.rng.integers(0, len(self.ordered))))
            self.present.discard(key)
            return ("-", key[0], key[1])
        while True:
            a, b = (int(x) for x in self.rng.integers(0, self.n, size=2))
            key = (min(a, b), max(a, b))
            if a != b and key not in self.present:
                self.present.add(key)
                bisect.insort(self.ordered, key)
                return ("+", key[0], key[1])


# -- sections ----------------------------------------------------------------
class Writer:
    """The ``writer`` section: submit one event, read it back, repeat."""

    def __init__(self, ctx, params: dict) -> None:
        self.params = params
        self.stream = EventStream(ctx.n, ctx.edges, params, ctx.rng("writer"))
        self.session = ctx.door.session(params.get("consistency",
                                                   "read_your_writes"))
        #: (op, a, b, dist, cnt, t_submit, t_done, error) per event
        self.log: list = []

    def step(self, event=None) -> None:
        op, a, b = event or next(self.stream)
        t0 = time.monotonic()
        d = c = err = None
        try:
            with span(SPAN_SUBMIT):
                self.session.submit([(op, a, b)])
            with span(SPAN_RYW):
                d, c = self.session.query(a, b)
        except Exception as e:  # recorded: a read-back that never came
            err = repr(e)
        self.log.append((op, a, b, d, c, t0, time.monotonic(), err))

    def warm(self) -> None:
        """Set-up: ``warmup_events`` events through the window's own
        path, each joining two vertices no edge touches, so that the
        repair is small and alike whatever the seed (a stream event where
        no such pair is left)."""
        for _ in range(int(self.params.get("warmup_events", 1))):
            self.step(self.stream.isolated_insert())
        self.warm_events = len(self.log)

    def run(self, t_end: float) -> None:
        while time.monotonic() < t_end:
            self.step()

    def result(self, t0: float, t_end: float) -> dict:
        window = self.log[self.warm_events:]
        done = [r for r in window if r[7] is None and r[6] <= t_end]
        return {"events": len(window), "events_in_window": len(done),
                "failed": sum(r[7] is not None for r in window),
                "log": self.log}


class OpenLoop:
    """The ``open`` section: timed single-pair front-door queries."""

    def __init__(self, ctx, params: dict, seconds: float,
                 label: str = "open") -> None:
        rng = ctx.rng(label)
        self.params = params
        self.count = int(round(float(params["rate_per_s"]) * seconds))
        self.offsets = np.sort(rng.random(self.count) * seconds)
        self.s, self.t = ctx.pairs.draw(self.count, rng)
        self.senders = int(params.get("senders", 32))
        consistency = params.get("consistency", "pinned")
        self.sessions = [ctx.door.session(consistency)
                         for _ in range(self.senders)]
        self.started = np.full(self.count, np.nan)
        self.done = np.full(self.count, np.nan)
        self.dist = np.zeros(self.count, np.int64)
        self.cnt = np.zeros(self.count, np.int64)
        self.errors: list = []

    def _send(self, k: int, t0: float) -> None:
        sess = self.sessions[k]
        for i in range(k, self.count, self.senders):
            due = t0 + self.offsets[i]
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            self.started[i] = time.monotonic()
            try:
                with span(SPAN_FRONTDOOR):
                    d, c = sess.query(int(self.s[i]), int(self.t[i]))
            except Exception as e:  # recorded: a refused/expired request
                self.errors.append((i, repr(e)))
                continue
            self.done[i] = time.monotonic()
            self.dist[i] = d
            self.cnt[i] = c

    def threads(self, t0: float) -> list:
        return [threading.Thread(target=self._send, args=(k, t0),
                                 name=f"bench-open-{k}", daemon=True)
                for k in range(self.senders)]

    def run(self, t0: float, timeout: float) -> None:
        """Send every request, from ``t0``, and wait for the answers
        until ``timeout`` seconds past ``t0``."""
        threads = self.threads(t0)
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=max(0.0, t0 + timeout - time.monotonic()))

    def result(self, t0: float, t_end: float) -> dict:
        due = t0 + self.offsets
        ok = ~np.isnan(self.done)
        lat = np.where(ok, self.done - due, np.inf)
        late = self.started - due
        return {"requests": self.count, "failed": int((~ok).sum()),
                "latency_s": lat, "late_s": late[~np.isnan(late)],
                "s": self.s, "t": self.t, "dist": self.dist,
                "cnt": self.cnt, "answered": ok}


class ClosedLoop:
    """The ``closed`` section: pinned reader batches, back to back."""

    def __init__(self, ctx, params: dict) -> None:
        self.params = params
        self.callers = int(params.get("callers", 1))
        self.batch = int(params["pairs_per_batch"])
        self.ctx = ctx
        self.readers = [ctx.svc.reader(params.get("consistency", "pinned"))
                        for _ in range(self.callers)]
        self.rngs = [ctx.rng(f"closed{k}") for k in range(self.callers)]
        #: (s, t, dist, cnt, t_start, t_done) per batch, per caller
        self.logs = [[] for _ in range(self.callers)]
        self.errors: list = []

    def _call(self, k: int, t_end: float, log: list) -> None:
        reader = self.readers[k]
        while time.monotonic() < t_end:
            s, t = self.ctx.pairs.draw(self.batch, self.rngs[k])
            t0 = time.monotonic()
            try:
                with span(SPAN_READER):
                    d, c = reader(s, t)
                    d = np.asarray(d)
                    c = np.asarray(c)
            except Exception as e:  # recorded: a batch that never came
                self.errors.append(repr(e))
                return
            log.append((s, t, d, c, t0, time.monotonic()))

    def threads(self, t_end: float) -> list:
        return [threading.Thread(target=self._call,
                                 args=(k, t_end, self.logs[k]),
                                 name=f"bench-closed-{k}", daemon=True)
                for k in range(self.callers)]

    def result(self, t0: float, t_end: float) -> dict:
        rows = [r for log in self.logs for r in log]
        in_window = [r for r in rows if r[5] <= t_end]
        return {"batches": len(rows), "failed": len(self.errors),
                "pairs_in_window": sum(r[0].size for r in in_window),
                "batches_in_window": len(in_window),
                "pair_batch": self.batch, "rows": rows}
