"""The program's spans in a trace and the readers of the program's
counters: the idle gaps of a recorded trace named by the nested
``spc.*`` and ``bench.*`` spans over them (``data/spans_trace.textproto``),
each metric reader of them on a fixture run, and the harness's counters
of a service."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from benchmarks.chip import harness
from benchmarks.chip import trace as tr

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"


def _xplane(tmp_path_factory, name):
    from jax.profiler import ProfileData

    text = "\n".join(line for line in
                     (DATA / f"{name}.textproto").read_text().splitlines()
                     if not line.startswith("#"))
    path = tmp_path_factory.mktemp("trace") / f"{name}.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


@pytest.fixture(scope="module")
def spans_path(tmp_path_factory):
    return _xplane(tmp_path_factory, "spans_trace")


@pytest.fixture(scope="module")
def traced(spans_path):
    """The trace as every traced run keeps it: the benchmark's and the
    program's host spans, each with its thread."""
    return tr.load(spans_path)


def test_the_accepted_loader_still_keeps_only_bench_spans(spans_path):
    """The loader keeps the benchmark's ``bench.*`` spans and the
    program's ``spc.*`` spans, and no other host event."""
    names = {s.name for s in tr.load(spans_path).spans}
    assert {n for n in names if not tr.is_program(n)} == {
        "bench.gc", "bench.reader", "bench.submit", "bench.window"}
    assert all(n.startswith(("bench.", "spc.")) for n in names)
    assert "spc.read" in names


def test_program_spans_load_with_their_threads(traced):
    by_name = {}
    for s in traced.spans:
        by_name.setdefault(s.name, set()).add(s.thread)
    assert len(by_name["spc.read.bound_wait"]) == 2   # two threads
    assert by_name["spc.read"] == by_name["bench.reader"]
    assert "PjitFunction(gather)" not in by_name


def test_gaps_are_named_gc_then_work_then_wait_then_bench(traced):
    assert tr.idle_by_span(traced) == pytest.approx({
        "spc.read.split": 300e-9, "spc.read.ryw_wait": 200e-9,
        "no span": 150e-9, "spc.read.gather": 100e-9,
        "bench.gc": 100e-9, "bench.submit": 100e-9})


def test_every_gap_is_counted_and_the_names_sum_to_the_idle_time(traced):
    lo, hi = traced.window()
    idle = (hi - lo) * 1e-9 - tr.busy_seconds(traced, lo, hi)
    assert sum(tr.idle_by_span(traced).values()) == \
        pytest.approx(idle)


def test_without_program_spans_gaps_are_named_as_before(tmp_path_factory):
    """On the trace the benchmark already had, only bench.* spans: the
    names are those ``trace.summarize`` gives its gaps."""
    trace = tr.load(_xplane(tmp_path_factory, "small_trace"))
    named = tr.summarize(trace)["breakdown"]["idle_gaps"]
    want: dict = {}
    for name, secs in named:
        want[name] = want.get(name, 0.0) + secs
    assert tr.idle_by_span(trace) == pytest.approx(want)


def test_named_gaps_are_longest_first_with_their_start(traced):
    got = tr.named_gaps(traced)
    assert [g[0] for g in got[:2]] == ["spc.read.split", "spc.read.ryw_wait"]
    assert got[0][1:] == pytest.approx([300e-9, 200e-9])
    assert len(got) == 6


def test_a_full_collection_is_a_gc_span(tmp_path):
    import gc

    import jax

    spans = tr.GcSpans()
    gc.callbacks.append(spans)
    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect()
        gc.collect(0)                   # a young collection: no span
    finally:
        jax.profiler.stop_trace()
        gc.callbacks.remove(spans)
    names = [s.name for s in tr.load(str(tmp_path)).spans]
    assert names.count(tr.GC_SPAN) >= 1
    assert set(names) == {tr.GC_SPAN}


def test_span_seconds_count_and_sum_each_name(traced):
    got = tr.span_seconds(traced)
    assert got["spc.read.bound_wait"] == [2, pytest.approx(360e-9)]
    assert got["spc.read"] == [1, pytest.approx(670e-9)]
    assert "bench.window" not in got


def _run(window=None, trace=None):
    return harness.Run(cell=None, seed=1, seconds=50.0, setup_s=1.0,
                       build_s=1.0, l_cap=8, device={}, rehearsal=False,
                       window=window or {}, traced=True, trace=trace)


def test_read_host_ms_per_batch_sums_the_host_steps_per_batch(traced):
    read = harness.reader_for("read_host_ms_per_batch")
    # one spc.read; gather 70 + split 250 + kernel 30 + scatter 100 ns
    assert read(_run(trace=traced)) == pytest.approx(450e-6)
    # a trace with no spc.read span, or no trace: nothing to read
    bench_only = tr.Trace(traced.ops, traced.modules,
                          [s for s in traced.spans
                           if not tr.is_program(s.name)])
    assert read(_run(trace=bench_only)) is None
    assert read(_run()) is None


class _Service:
    """What ``harness.counters`` reads of a service with two serving
    engines."""

    def stats(self):
        import dataclasses
        import types

        @dataclasses.dataclass
        class Update:
            batched_events: int = 0

        views = [types.SimpleNamespace(routes={"pallas": 3},
                                       route_pairs={"pallas": 990}),
                 types.SimpleNamespace(routes={"pallas+merge": 1},
                                       route_pairs={"pallas": 50,
                                                    "merge": 10})]
        return {"serve": views, "queries": 4, "update": Update(),
                "version": 0}


def test_the_window_counters_carry_pairs_per_path():
    out = harness.counters(_Service(), None)
    assert out["routes"] == {"pallas": 3, "pallas+merge": 1}
    assert out["route_pairs"] == {"pallas": 1040, "merge": 10}
    window = harness.delta(out, harness.counters(_Service(), None))
    assert window["route_pairs"] == {"pallas": 0, "merge": 0}
    read = harness.reader_for("pallas_pair_share")
    assert read(_run(out)) == pytest.approx(100 * 1040 / 1050)


def test_pallas_pair_share_reads_pairs_per_path():
    read = harness.reader_for("pallas_pair_share")
    run = _run({"route_pairs": {"pallas": 990, "merge": 10}})
    assert read(run) == pytest.approx(99.0)
    assert read(_run({"route_pairs": {}})) is None
    assert read(_run({"routes": {"pallas+merge": 3}})) is None


@pytest.mark.parametrize("metric,want", [("hub_repairs_per_event", 3.0),
                                         ("relax_rounds_per_event", 12.0)])
def test_update_work_per_event(metric, want):
    read = harness.reader_for(metric)
    update = {"hub_repairs": 30, "relax_rounds": 120, "batched_events": 10}
    assert read(_run({"update": update})) == pytest.approx(want)
    # a program that does not count them, or no event: nothing
    assert read(_run({"update": {"batched_events": 10}})) is None
    assert read(_run({"update": dict(update, batched_events=0)})) is None


def test_update_device_us_per_round():
    read = harness.reader_for("update_device_us_per_round")
    trace = tr.Trace(
        ops=[tr.Event("%while.1", 0.0, 5e8, "/device:TPU:0")],
        modules=[tr.Event("jit__hyb_spc_batch(1)", 0.0, 3e8, "/device:TPU:0"),
                 tr.Event("jit__hyb_spc_batch(1)", 4e8, 6e8, "/device:TPU:0"),
                 tr.Event("jit_other(2)", 6e8, 7e8, "/device:TPU:0")],
        spans=[tr.Event(tr.WINDOW_SPAN, 0.0, 1e9)])
    run = _run({"update": {"relax_rounds": 1000, "batched_events": 2}},
               trace)
    assert read(run) == pytest.approx(500.0)   # 0.5 s over 1,000 rounds
    assert read(_run({"update": {"batched_events": 2}}, trace)) is None
    assert read(_run({"update": {"relax_rounds": 1000}})) is None


def test_breakdown_rehearsal_names_gaps_and_reads_pairs(tmp_path):
    """The breakdown run, rehearsed on the CPU: the result line carries
    the program's counters and spans besides the accepted ones."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "chip" / "breakdown.py"),
         "--workload", "g500-s13-serve.bulk", "--seed", str(2 ** 31 + 9),
         "--seconds", "2", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert 0 < out["metrics"]["pallas_pair_share"]["value"] <= 100
    spans = out["breakdown"]["span_seconds"]
    assert spans["spc.read"][0] >= spans["bench.reader"][0] > 0
    idle = out["breakdown"]["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(2.0, rel=0.05)
