"""The harness finds what a later change adds as files, and refuses to
run without the chip."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from benchmarks.chip import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
RUN = ROOT / "benchmarks" / "chip" / "run.py"


def test_every_cell_and_metric_of_the_benchmark_has_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        gen = harness.graph_generator(cell.config["graph"]["generator"])
        assert callable(gen)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.reader_for(m["name"]))
    for c in bench["configs"]:
        assert (ROOT / c["file"]).exists()


def test_a_cell_config_and_metric_added_as_files(tmp_path):
    """A new configuration, traffic mix, graph generator and metric are
    found by name from their own files; no module is edited."""
    base = tmp_path / "chip"
    for sub in ("configs", "traffic", "graphs", "metrics"):
        (base / sub).mkdir(parents=True)
    (base / "graphs" / "ring.py").write_text(
        "import numpy as np\n"
        "def generate(params, seed):\n"
        "    n = params['n']\n"
        "    a = np.arange(n)\n"
        "    return n, np.stack([a[:-1], a[1:]], 1)\n")
    (base / "configs" / "ring-8.json").write_text(json.dumps(
        {"name": "ring-8", "graph": {"generator": "ring", "n": 8},
         "service": {}}))
    (base / "traffic" / "tiny.json").write_text(json.dumps(
        {"closed": {"callers": 1, "pairs_per_batch": 8},
         "check": {"sample_sources": 4}}))
    (base / "metrics" / "hops.py").write_text(
        "def read(run):\n    return 3.0\n")
    bench = {"workloads": [{"name": "ring-8.tiny", "config": "ring-8",
                            "traffic": "tiny", "chips": 1}],
             "end_to_end": [{"name": "setup_s"}],
             "per_layer": [{"name": "hops.bulk",
                            "workloads": ["ring-8.tiny"]},
                           {"name": "hops.other", "workloads": ["x"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("ring-8.tiny", base=base,
                             bench_path=tmp_path / "BENCHMARK.json")
    assert cell.traffic["closed"]["pairs_per_batch"] == 8
    assert [m["name"] for m in cell.metrics("per_layer")] == ["hops.bulk"]
    n, edges = harness.graph_generator("ring", base)(cell.config["graph"],
                                                     0)
    assert (n, len(edges)) == (8, 7)
    assert harness.reader_for("hops.bulk", base)(None) == 3.0
    with pytest.raises(SystemExit):
        harness.reader_for("missing", base)


def _run(cwd, *extra, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, str(cwd / "benchmarks" / "chip" / "run.py"),
         "--workload", "g500-s10-stream.ryw", "--seed", "1",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_the_run_exits_non_zero_and_prints_no_result(
        tmp_path):
    proc = _run(ROOT, env_extra={
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_the_benchmark_files_alone_do_not_run(tmp_path):
    """In a directory with only BENCHMARK.json and the benchmark's own
    paths, the program is missing: non-zero exit, no result."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, "--rehearse")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_the_warm_up_insert_joins_two_untouched_vertices():
    import numpy as np

    from benchmarks.chip.generator import EventStream

    stream = EventStream(6, [(0, 1), (1, 2)], {},
                         np.random.default_rng(7))
    op, a, b = stream.isolated_insert()
    assert op == "+" and a < b and {a, b} <= {3, 4, 5}
    assert (a, b) in stream.present
    assert stream.isolated_insert() is None   # one untouched vertex left
    full = EventStream(3, [(0, 1), (1, 2)], {}, np.random.default_rng(7))
    assert full.isolated_insert() is None


class _SplittingService:
    """Serves pairs as an engine that routes a batch holding pair (0, 1)
    off its plain kernel path, and counts routes as the service does."""

    def __init__(self):
        import types

        self.routes = {}
        self.batches = []
        self._view = types.SimpleNamespace(routes=self.routes)

    def reader(self):
        import numpy as np

        def serve(s, t):
            s, t = np.asarray(s), np.asarray(t)
            self.batches.append((s, t))
            heavy = int(np.sum((s == 0) & (t == 1)))
            name = ("pallas" if not heavy else "pallas->merge"
                    if heavy == s.size else "pallas+merge")
            self.routes[name] = self.routes.get(name, 0) + 1
            return np.zeros(s.size), np.zeros(s.size)
        return serve

    def stats(self):
        return {"serve": [self._view]}


def test_warm_split_serves_every_k_per_bucket_through_the_reader():
    import numpy as np

    from benchmarks.chip.generator import PairSampler

    svc = _SplittingService()
    degree = np.ones(64)
    out = harness.warm_split(svc, PairSampler(degree), (8, 64), 5,
                             np.random.default_rng(3), probes=4096)
    assert out["found"] and out["ks"] == {8: 5, 64: 5}
    # one batch of each bucket takes the plain route, before the splits
    plain = svc.batches[-12:-10]
    assert [s.size for s, _ in plain] == [8, 64]
    assert not any(np.any((s == 0) & (t == 1)) for s, t in plain)
    warm = svc.batches[-10:]
    for (s, t), (bucket, k) in zip(warm, [(8, k) for k in range(1, 6)]
                                   + [(64, k) for k in range(1, 6)]):
        assert s.size == bucket
        assert int(np.sum((s == 0) & (t == 1))) == k
    none = harness.warm_split(_SplittingService(), PairSampler(degree[:1]),
                              (8,), 5, np.random.default_rng(3), probes=16)
    assert not none["found"] and none["ks"] == {}
