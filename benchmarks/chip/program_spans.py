"""The program's own host spans in a profiler trace, and the device's
idle time named by them.

The program records ``spc.*`` spans (``repro.spans``) on the profiler's
host plane, on the same clock as the device's ops and the benchmark's
``bench.*`` spans; ``trace.load`` keeps only the latter.  ``load`` reads
both from the same file, with the thread each ran on, so that nested
spans can be told apart.

An idle gap of the device is named, in this order, by:

0. ``bench.gc``, where a full collection of Python's collector covers at
   least half the gap (it holds the interpreter lock, so no span open on
   another thread runs meanwhile);
1. the ``spc.*`` span, not a wait, that covers most of the gap;
2. else the ``spc.*_wait`` span that covers most of it;
3. else the ``bench.*`` span that covers most of it (``bench.window``
   aside);
4. else ``no span``.

A span covers the part of the gap that the spans nested in it, on its
thread, do not: a gap inside ``spc.read.split`` is named by that span,
not by the ``spc.read`` or ``bench.reader`` around it.
"""

from __future__ import annotations

import bisect
import dataclasses
import os

from benchmarks.chip import trace as tr

PROGRAM_PREFIX = "spc."
WAIT_SUFFIX = "_wait"
GC_SPAN = "bench.gc"
NO_SPAN = "no span"


@dataclasses.dataclass(frozen=True)
class Span(tr.Event):
    """A host span with the thread (profiler line) it ran on."""

    thread: str = ""


def load(path: str) -> list:
    """Every ``bench.*`` and ``spc.*`` host span of a trace file (or of
    the newest one under a profile log dir), as ``Span``."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = tr.find_xplane(path)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith((tr.SPAN_PREFIX, PROGRAM_PREFIX)):
                    out.append(Span(e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    thread=f"{plane.name}#{k}"))
    return out


def is_program(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIX)


def is_wait(name: str) -> bool:
    return name.endswith(WAIT_SUFFIX)


def _own_intervals(spans) -> list:
    """``(span, [(start, end), ...])`` per span: its interval less those
    of the spans directly nested in it on its thread."""
    by_thread: dict = {}
    for s in spans:
        by_thread.setdefault(getattr(s, "thread", ""), []).append(s)
    out = []
    for group in by_thread.values():
        group.sort(key=lambda s: (s.start_ns, -s.end_ns))
        kids = {id(s): [] for s in group}
        stack = []
        for s in group:
            while stack and stack[-1].end_ns <= s.start_ns:
                stack.pop()
            if stack and s.end_ns <= stack[-1].end_ns:
                kids[id(stack[-1])].append((s.start_ns, s.end_ns))
            stack.append(s)
        for s in group:
            own, cur = [], s.start_ns
            for a, b in tr.merge_intervals(kids[id(s)], s.start_ns,
                                           s.end_ns):
                if a > cur:
                    own.append((cur, a))
                cur = max(cur, b)
            if cur < s.end_ns:
                own.append((cur, s.end_ns))
            out.append((s, own))
    return out


class GapNamer:
    """Names idle gaps by the rules of the module docstring; built once
    per trace, so that each gap looks only at the spans near it."""

    def __init__(self, spans) -> None:
        spans = [s for s in spans if s.name != tr.WINDOW_SPAN]
        self.gc = sorted((s.start_ns, s.end_ns) for s in spans
                         if s.name == GC_SPAN)
        self.items = sorted(_own_intervals(spans),
                            key=lambda item: item[0].start_ns)
        self.starts = [s.start_ns for s, _ in self.items]
        self.longest = max((s.end_ns - s.start_ns for s, _ in self.items),
                           default=0.0)

    def name(self, start: float, end: float) -> str:
        gc = sum(e - s for s, e in tr.merge_intervals(self.gc, start, end))
        if gc >= 0.5 * (end - start):
            return GC_SPAN
        best = {}  # rule -> (cover, name)
        i = bisect.bisect_left(self.starts, end) - 1
        while i >= 0 and self.starts[i] > start - self.longest:
            sp, own = self.items[i]
            i -= 1
            cover = sum(min(end, b) - max(start, a) for a, b in own
                        if b > start and a < end)
            if cover <= 0:
                continue
            rule = ((1 if not is_wait(sp.name) else 2)
                    if is_program(sp.name) else 3)
            if cover > best.get(rule, (0.0, ""))[0]:
                best[rule] = (cover, sp.name)
        return best[min(best)][1] if best else NO_SPAN


def named_gaps(trace: tr.Trace) -> list:
    """``[name, seconds, start]`` per idle gap of the window, ``start``
    in seconds from the window's start, longest first."""
    lo, hi = trace.window()
    namer = GapNamer([s for s in trace.spans
                      if s.end_ns > lo and s.start_ns < hi])
    gaps = sorted(tr.idle_gaps(trace, lo, hi), key=lambda g: g[0] - g[1])
    return [[namer.name(s, e), (e - s) * 1e-9, (s - lo) * 1e-9]
            for s, e in gaps]


def idle_by_span(trace: tr.Trace) -> dict:
    """Idle seconds of the window by the name of each gap, every gap
    counted, largest first."""
    out: dict = {}
    for name, secs, _ in named_gaps(trace):
        out[name] = out.get(name, 0.0) + secs
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def span_seconds(trace: tr.Trace) -> dict:
    """``[count, seconds]`` per ``spc.*`` and ``bench.*`` span name among
    the spans wholly inside the window (the window itself aside), summed
    over threads."""
    lo, hi = trace.window()
    out: dict = {}
    for s in tr.events_in(trace.spans, lo, hi):
        if s.name == tr.WINDOW_SPAN:
            continue
        count, secs = out.get(s.name, (0, 0.0))
        out[s.name] = (count + 1, secs + s.seconds)
    return {k: list(v) for k, v in sorted(out.items())}
