"""The reduction from a profiler trace to the benchmark's numbers, on a
small trace committed with the tests (``data/small_trace.textproto``,
the layout a TPU run records, loaded through the same reader)."""

import pathlib

import pytest

from benchmarks.chip import peaks
from benchmarks.chip import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    from jax.profiler import ProfileData

    text = "\n".join(line for line in
                     (DATA / "small_trace.textproto").read_text().splitlines()
                     if not line.startswith("#"))
    path = tmp_path_factory.mktemp("trace") / "run.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return tr.load(str(path))


def test_the_trace_loads_device_ops_modules_and_bench_spans(trace):
    assert trace.devices == ["/device:TPU:0"]
    assert len(trace.ops) == 4 and len(trace.modules) == 2
    assert [s.name for s in trace.spans] == [
        "bench.window", "bench.reader", "bench.submit"]


def test_busy_is_the_union_clipped_to_the_window(trace):
    lo, hi = trace.window()
    assert (lo, hi) == (1_000_000.0, 1_001_000.0)
    # [100, 400] + [600, 700] + [900, 1000]: overlap counted once, the
    # op running past the window's end clipped
    assert tr.busy_seconds(trace, lo, hi) == pytest.approx(500e-9)


def test_idle_gaps_are_named_by_the_covering_span(trace):
    lo, hi = trace.window()
    gaps = [(s - lo, e - lo) for s, e in tr.idle_gaps(trace, lo, hi)]
    assert gaps == [(0.0, 100.0), (400.0, 600.0), (700.0, 900.0)]
    out = tr.summarize(trace)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_s"] == pytest.approx(500e-9)
    assert [g[0] for g in out["breakdown"]["idle_gaps"]] == [
        "bench.reader", "bench.submit", "no span"]


def test_ops_are_named_by_executable_and_summed(trace):
    ops = dict(tr.summarize(trace)["breakdown"]["device_ops"])
    # both runs of fusion.1 inside jit_gather; the while straddles the
    # window's end and is left out of the sums
    assert ops == pytest.approx({
        "jit_gather/fusion.1": 350e-9,
        "jit__spc_query_jit/_spc_query_jit.1": 100e-9})


def test_kernel_time_sums_its_events_and_reads_its_shape(trace):
    launches = tr.kernel_launches(tr.events_in(trace.ops, *trace.window()))
    assert [shape for _, shape in launches] == [(1024, 1024)]
    assert sum(e.seconds for e, _ in launches) == pytest.approx(100e-9)


def test_roofline_share_from_shapes():
    # 6 operands x 1,024 x 256 x 4 B + 8 B x 256 out, at 819 GB/s
    share = peaks.spc_query_roofline([(256, 1024)], 1e-3, "TPU v5 lite")
    want = 100 * (6 * 1024 * 256 * 4 + 8 * 256) / 819e9 / 1e-3
    assert share == pytest.approx(want)
    assert peaks.spc_query_roofline([], 1e-3, "TPU v5 lite") is None
    assert peaks.spc_query_compares(256, 1024) == 1024 * 1024 * 256
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")
