"""Reduce a profiler trace to the benchmark's device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:TPU:<i>``; their ``XLA Ops`` line holds one event per operation
that ran, their ``XLA Modules`` line one per executable.  Host planes hold
the benchmark's own spans (``jax.profiler.TraceAnnotation``), all named
``bench.<what>``, on the same clock.

The rules, kept here so that every PR computes them the same way:

- device busy time is the union of the op intervals of a device, clipped
  to the traced window (the ``bench.window`` span), averaged over the
  devices that ran anything;
- a kernel's or an executable's time is the sum of its events' durations;
- an idle gap is a stretch of the window in which no op ran; each gap is
  named by the host span that covered most of it.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
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

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclasses.dataclass
class Trace:
    ops: list          # Event per device op, all devices
    modules: list      # Event per executable run, all devices
    spans: list        # Event per bench.* host span

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
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns,
                                           e.start_ns + e.duration_ns))
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


def name_gap(spans, start: float, end: float) -> str:
    """The host span (other than the window) covering most of a gap."""
    best, best_cover = "no span", 0.0
    for sp in spans:
        if sp.name == WINDOW_SPAN:
            continue
        cover = min(end, sp.end_ns) - max(start, sp.start_ns)
        if cover > best_cover:
            best, best_cover = sp.name, cover
    return best


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
    spans = [s for s in trace.spans if s.end_ns > lo and s.start_ns < hi]
    gaps = sorted(idle_gaps(trace, lo, hi), key=lambda g: g[0] - g[1])[:top]
    named = [[name_gap(spans, s, e), (e - s) * 1e-9] for s, e in gaps]
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
