"""Reduce a profiler trace to the benchmark's device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:TPU:<i>``; their ``XLA Ops`` line holds one event per operation
that ran, their ``XLA Modules`` line one per executable.  Host planes hold
the benchmark's own spans (``jax.profiler.TraceAnnotation``), all named
``bench.<what>``, and the program's (``repro.spans``), all named
``spc.<what>``, on the same clock; each is kept with the thread (profiler
line) it ran on, so that nested spans can be told apart.

The rules, kept here so that every PR computes them the same way:

- device busy time is the union of the op intervals of a device, clipped
  to the traced window (the ``bench.window`` span), averaged over the
  devices that ran anything;
- a kernel's or an executable's time is the sum of its events' durations;
- an idle gap is a stretch of the window in which no op ran on the first
  device.  It is named, in this order, by:

  0. ``bench.gc``, where a full collection of Python's collector covers
     at least half the gap (it holds the interpreter lock, so no span
     open on another thread runs meanwhile);
  1. the ``spc.*`` span, not a wait, that covers most of the gap;
  2. else the ``spc.*_wait`` span that covers most of it;
  3. else the ``bench.*`` span that covers most of it (``bench.window``
     aside);
  4. else ``no span``.

  A span covers the part of the gap that the spans nested in it, on its
  thread, do not: a gap inside ``spc.read.gather`` is named by that span,
  not by the ``spc.read`` or ``bench.reader`` around it.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "spc."
WAIT_SUFFIX = "_wait"
GC_SPAN = "bench.gc"
NO_SPAN = "no span"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    device: str = ""
    stats: tuple = ()
    thread: str = ""

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclasses.dataclass
class Trace:
    ops: list          # Event per device op, all devices
    modules: list      # Event per executable run, all devices
    spans: list        # Event per bench.* and spc.* host span

    @property
    def devices(self) -> list:
        return sorted({e.device for e in self.ops})

    def window(self):
        """(start_ns, end_ns) of the traced window."""
        wins = [s for s in self.spans if s.name == WINDOW_SPAN]
        if not wins:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        w = max(wins, key=lambda s: s.end_ns - s.start_ns)
        return w.start_ns, w.end_ns


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str) -> Trace:
    """Read a trace file (or the newest one under a profile log dir)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                out = ops if line.name == OPS_LINE else modules
                for e in line.events:
                    out.append(Event(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns,
                                     plane.name, tuple(_stats(e))))
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith((SPAN_PREFIX, PROGRAM_PREFIX)):
                        spans.append(Event(e.name, e.start_ns,
                                           e.start_ns + e.duration_ns,
                                           thread=f"{plane.name}#{k}"))
    return Trace(ops, modules, spans)


def _stats(e):
    try:
        items = list(e.stats)
    except (TypeError, ValueError):
        return []
    out = []
    for k, v in items:
        if isinstance(v, (str, int, float)):
            out.append((k, v))
    return out


def merge_intervals(intervals, lo: float, hi: float) -> list:
    """Union of ``(start, end)`` intervals clipped to ``[lo, hi]``, as a
    sorted list of disjoint intervals."""
    clipped = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                     if e > lo and s < hi)
    merged = []
    for s, e in clipped:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Device busy time in ``[lo, hi]``, averaged over the devices that
    ran an op in the trace."""
    devices = trace.devices
    if not devices:
        return 0.0
    total = 0.0
    for dev in devices:
        spans = [(e.start_ns, e.end_ns) for e in trace.ops if e.device == dev]
        total += sum(e - s for s, e in merge_intervals(spans, lo, hi))
    return total * 1e-9 / len(devices)


def idle_gaps(trace: Trace, lo: float, hi: float) -> list:
    """``(start, end)`` stretches of ``[lo, hi]`` in which no op ran on
    the first device."""
    devices = trace.devices
    if not devices:
        return [(lo, hi)]
    busy = merge_intervals([(e.start_ns, e.end_ns) for e in trace.ops
                            if e.device == devices[0]], lo, hi)
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    return gaps


def is_program(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIX)


def is_wait(name: str) -> bool:
    return name.endswith(WAIT_SUFFIX)


def _own_intervals(spans) -> list:
    """``(span, [(start, end), ...])`` per span: its interval less those
    of the spans directly nested in it on its thread."""
    by_thread: dict = {}
    for s in spans:
        by_thread.setdefault(s.thread, []).append(s)
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
            for a, b in merge_intervals(kids[id(s)], s.start_ns, s.end_ns):
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
        spans = [s for s in spans if s.name != WINDOW_SPAN]
        self.gc = sorted((s.start_ns, s.end_ns) for s in spans
                         if s.name == GC_SPAN)
        self.items = sorted(_own_intervals(spans),
                            key=lambda item: item[0].start_ns)
        self.starts = [s.start_ns for s, _ in self.items]
        self.longest = max((s.end_ns - s.start_ns for s, _ in self.items),
                           default=0.0)

    def name(self, start: float, end: float) -> str:
        gc = sum(e - s for s, e in merge_intervals(self.gc, start, end))
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


def named_gaps(trace: Trace, top: int | None = None) -> list:
    """``[name, seconds, start]`` per idle gap of the window (the
    ``top`` longest only, where given), ``start`` in seconds from the
    window's start, longest first."""
    lo, hi = trace.window()
    namer = GapNamer([s for s in trace.spans
                      if s.end_ns > lo and s.start_ns < hi])
    gaps = sorted(idle_gaps(trace, lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    return [[namer.name(s, e), (e - s) * 1e-9, (s - lo) * 1e-9]
            for s, e in gaps]


def idle_by_span(trace: Trace) -> dict:
    """Idle seconds of the window by the name of each gap, every gap
    counted, largest first."""
    out: dict = {}
    for name, secs, _ in named_gaps(trace):
        out[name] = out.get(name, 0.0) + secs
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def span_seconds(trace: Trace) -> dict:
    """``[count, seconds]`` per span name among the spans wholly inside
    the window (the window itself aside), summed over threads."""
    lo, hi = trace.window()
    out: dict = {}
    for s in events_in(trace.spans, lo, hi):
        if s.name == WINDOW_SPAN:
            continue
        count, secs = out.get(s.name, (0, 0.0))
        out[s.name] = (count + 1, secs + s.seconds)
    return {k: list(v) for k, v in sorted(out.items())}


class GcSpans:
    """``gc.callbacks`` entry: a ``bench.gc`` host span around each
    generation-2 collection (the collector runs on the thread that
    triggered it, so the span opens and closes on that thread)."""

    def __init__(self) -> None:
        import jax

        # bound now: a collection can start in the middle of an import
        self.annotation = jax.profiler.TraceAnnotation
        self.open = None

    def __call__(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self.open = self.annotation(GC_SPAN)
            self.open.__enter__()
        elif self.open is not None:
            self.open.__exit__(None, None, None)
            self.open = None


def time_by_name(events, match=None) -> dict:
    """Summed seconds per event name (optionally only names ``match``
    accepts)."""
    out: dict = {}
    for e in events:
        if match is None or match(e.name):
            out[e.name] = out.get(e.name, 0.0) + e.seconds
    return out


def events_in(events, lo: float, hi: float) -> list:
    return [e for e in events if e.start_ns >= lo and e.end_ns <= hi]


def short_name(hlo: str) -> str:
    """An op's name without its HLO text: ``%while.164 = (...) while(...)``
    gives ``while.164``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


_RUN_ID = re.compile(r"\(\d+\)$")


def op_names(trace: Trace, ops) -> list:
    """``executable/op`` for each op event: the short op name behind the
    name of the executable run (``XLA Modules``) that holds it on its
    device."""
    runs: dict = {}
    for m in trace.modules:
        runs.setdefault(m.device, []).append(m)
    for lst in runs.values():
        lst.sort(key=lambda m: m.start_ns)
    starts = {d: [m.start_ns for m in lst] for d, lst in runs.items()}
    out = []
    for e in ops:
        lst = runs.get(e.device, [])
        i = bisect.bisect_right(starts.get(e.device, []), e.start_ns) - 1
        holder = lst[i] if i >= 0 and lst[i].end_ns >= e.start_ns else None
        name = short_name(e.name)
        out.append(f"{_RUN_ID.sub('', holder.name)}/{name}" if holder
                   else name)
    return out


def summarize(trace: Trace, top: int = 10) -> dict:
    """The numbers every traced run reports: busy and window seconds,
    the top device ops (``executable/op``, summed over their runs) and
    the longest idle gaps, named."""
    lo, hi = trace.window()
    busy = busy_seconds(trace, lo, hi)
    inside = events_in(trace.ops, lo, hi)
    ops: dict = {}
    for name, e in zip(op_names(trace, inside), inside):
        ops[name] = ops.get(name, 0.0) + e.seconds
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    named = [[name, secs] for name, secs, _ in named_gaps(trace, top)]
    return {
        "busy_s": busy,
        "window_s": (hi - lo) * 1e-9,
        "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                      "idle_gaps": named},
    }


#: What names the query kernel's launches in a device trace: the custom
#: call takes the name of its jitted wrapper, ``_spc_query_jit``.
KERNEL_NAME = "spc_query"
_SHAPE = re.compile(r"s32\[(\d+),(\d+)\]")


def kernel_launches(ops) -> list:
    """``(event, (batch, l_cap))`` per launch of the query kernel among
    ``ops``.  The shape is the launch's own ``[L, B]`` int32 operand as
    the event's HLO text gives it (its ``[1, B]`` outputs skipped), None
    where the trace does not carry it."""
    out = []
    for e in ops:
        text = " ".join([e.name] + [str(v) for _, v in e.stats])
        if KERNEL_NAME not in e.name and KERNEL_NAME not in text:
            continue
        shapes = [(int(a), int(b)) for a, b in _SHAPE.findall(text)
                  if int(a) > 1]
        out.append((e, (shapes[0][1], shapes[0][0]) if shapes else None))
    return out
