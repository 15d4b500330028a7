"""Differential tests of the serving engine: every route (eager table,
jit merge, Pallas interpret) against the ``bfs_spc`` oracle on *real*
dynamic indexes -- post-insert, post-delete, disconnected pairs and
isolated vertices -- plus bucketing, routing and overflow-fallback
behavior."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import refimpl as R
from repro.core.dynamic import DynamicSPC
from repro.core.graph import INF
from repro.core.labels import from_ref
from repro.core.query import batched_query
from repro.data import random_graph_edges
from repro.serve import DEFAULT_BUCKETS, QueryEngine, bucket_size

ROUTES = ("merge", "table", "pallas")


def oracle(svc: DynamicSPC):
    """(dist, cnt) lookup tables from BFS on the *current* graph."""
    g = R.RefGraph(svc.n, sorted(svc._edge_set()))
    return {s: R.bfs_spc(g, s) for s in range(svc.n)}


def assert_matches_oracle(svc, eng, s, t, truth):
    d0, c0 = batched_query(svc.index, jnp.asarray(s), jnp.asarray(t))
    for route in ROUTES:
        d, c = eng.query_batch(svc.index, s, t, route=route)
        assert c.dtype == jnp.int64
        # all routes bit-identical with the seed eager path
        np.testing.assert_array_equal(np.asarray(d), np.asarray(d0),
                                      err_msg=route)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(c0),
                                      err_msg=route)
    for k, (sk, tk) in enumerate(zip(s, t)):
        dist, cnt = truth[sk]
        if dist[tk] >= int(INF):
            assert int(c0[k]) == 0 and int(d0[k]) >= int(INF), (sk, tk)
        else:
            assert (int(d0[k]), int(c0[k])) == (int(dist[tk]), int(cnt[tk]))


@pytest.fixture(scope="module")
def dynamic_service():
    """A service that has lived: built, inserted, deleted, with a vertex
    isolated by deletion and a disconnected component."""
    n = 40
    edges = [(a, b) for a, b in random_graph_edges(n, 90, seed=3)
             if max(a, b) < n - 4]  # leave 36..39 out of the initial graph
    svc = DynamicSPC(n, edges, l_cap=64)
    present = set(edges)
    # post-insert: attach 36<->37 to the main component, link 38-39 only
    # to each other (disconnected 2-component)
    ins = [(0, 36), (36, 37), (38, 39)]
    # post-delete: remove real edges, and isolate vertex 37 again via the
    # Section 3.2.3 fast path
    dels = [next(iter(present))] + [(36, 37)]
    svc.apply_events([("+", a, b) for a, b in ins]
                     + [("-", a, b) for a, b in dels])
    return svc


def test_routes_match_oracle_on_dynamic_index(dynamic_service):
    svc = dynamic_service
    eng = QueryEngine()
    truth = oracle(svc)
    rng = np.random.default_rng(0)
    s = [int(x) for x in rng.integers(0, svc.n, 150)]
    t = [int(x) for x in rng.integers(0, svc.n, 150)]
    # force coverage of the interesting pairs
    s += [0, 38, 38, 37, 37, 5]
    t += [36, 39, 0, 37, 4, 5]  # post-insert, 2-comp, disconnected,
    #                             isolated self, isolated-vs-main, self
    assert_matches_oracle(svc, eng, s, t, truth)
    assert set(eng.stats.routes) == set(ROUTES)
    assert eng.stats.queries == len(s) * len(ROUTES)


def test_driver_query_paths_agree(dynamic_service):
    svc = dynamic_service
    rng = np.random.default_rng(1)
    s = rng.integers(0, svc.n, 20)
    t = rng.integers(0, svc.n, 20)
    d, c = svc.query_batch(s, t)
    for k in range(len(s)):
        assert svc.query(int(s[k]), int(t[k])) == (int(d[k]), int(c[k]))
    # both driver entry points route through the one engine
    assert set(svc.engine.stats.routes) == {"merge"}


def test_bucket_padding_static_shapes(dynamic_service):
    svc = dynamic_service
    assert [bucket_size(b) for b in (1, 8, 9, 64, 65, 1024, 1025, 5000)] \
        == [8, 8, 64, 64, 256, 1024, 2048, 5120]
    eng = QueryEngine()
    for b in (1, 3, 5, 8):  # all land in the same bucket -> one compile
        s = list(range(b))
        d, c = eng.query_batch(svc.index, s, s)
        assert d.shape == (b,) and c.shape == (b,)
        # every (k, k) self query answers (0, 1) regardless of where the
        # batch's pad rows start -- padding must not leak into the tail
        for k in range(b):
            assert (int(d[k]), int(c[k])) == (0, 1)
    assert eng.stats.batches == 4


def test_pallas_overflow_falls_back_to_int64(dynamic_service):
    """Counts above 2^24 must not be served from the fp32 kernel."""
    big = 2 ** 24 + 1  # not representable in fp32
    ref = R.RefSPCIndex(2)
    ref.labels[0] = [(0, 0, 1)]
    ref.labels[1] = [(0, 1, big), (1, 0, 1)]
    idx = from_ref(ref, l_cap=4)
    eng = QueryEngine()
    d, c = eng.query_batch(idx, [0], [1], route="pallas")
    assert (int(d[0]), int(c[0])) == (1, big)
    # the kernel reads 2^24 for the row, so the row is patched from int64
    assert eng.stats.routes == {"pallas+merge": 1}
    assert eng.stats.route_pairs == {"merge": 1}
    # a small-count batch on the same engine still takes the kernel
    d, c = eng.query_batch(dynamic_service.index, [0], [1], route="pallas")
    assert "pallas" in eng.stats.routes


def test_mixed_exactness_batch_splits_routes(dynamic_service):
    """A batch mixing exact rows and rows whose kernel count reads 2^23
    or more must keep the kernel's answers for the exact rows and patch
    only the rest from int64, instead of dropping the whole batch to the
    merge (ROADMAP "mixed-exactness batches")."""
    big = 2 ** 24 + 1  # not representable in fp32
    ref = R.RefSPCIndex(3)
    ref.labels[0] = [(0, 0, 1)]
    ref.labels[1] = [(0, 1, big), (1, 0, 1)]
    ref.labels[2] = [(0, 1, 1), (2, 0, 1)]
    idx = from_ref(ref, l_cap=4)
    eng = QueryEngine()
    # rows: (0,2) exact, (0,1) inexact (reads 2^24), (2,2) exact self
    d, c = eng.query_batch(idx, [0, 0, 2], [2, 1, 2], route="pallas")
    assert [int(x) for x in d] == [1, 1, 0]
    assert [int(x) for x in c] == [1, big, 1]  # inexact row still exact int64
    assert eng.stats.routes == {"pallas+merge": 1}
    assert eng.stats.route_pairs == {"pallas": 2, "merge": 1}
    # an all-inexact real batch is a patch of every real row; the
    # bucket's dump-row padding (count 0) is never patched
    d, c = eng.query_batch(idx, [0], [1], route="pallas")
    assert (int(d[0]), int(c[0])) == (1, big)
    assert eng.stats.routes == {"pallas+merge": 2}
    assert eng.stats.route_pairs == {"pallas": 2, "merge": 2}


#: Hub-0 counts of the heavy vertices 1, 3, 5: every pair touching one has
#: a count bound >= 2^24, and their products need int64.
_HEAVY = {1: 2 ** 24 + 1, 3: 2 ** 24 + 3, 5: 3 * 2 ** 24 + 7}


def _heavy_index(l_cap: int = 4):
    """Eight vertices, all reaching hub 0; the heavy ones push every pair
    that touches them over the fp32 bound."""
    ref = R.RefSPCIndex(8)
    ref.labels[0] = [(0, 0, 1)]
    for v in range(1, 8):
        ref.labels[v] = [(0, 1 + v % 3, _HEAVY.get(v, 1 + v % 2)),
                         (v, 0, 1)]
    return ref, from_ref(ref, l_cap=l_cap)


def _mixed_pairs(k: int, b: int, seed: int):
    """``b`` pairs of which exactly ``k`` touch a heavy vertex (never a
    heavy self-pair, whose count is 1), shuffled."""
    rng = np.random.default_rng(seed)
    light = [v for v in range(8) if v not in _HEAVY]
    heavy = list(_HEAVY)
    pairs = [(h, int(rng.choice([v for v in range(8) if v != h])))
             for h in rng.choice(heavy, size=k).tolist()]
    pairs += [(int(rng.choice(light)), int(rng.choice(light)))
              for _ in range(b - k)]
    pairs = [p[::-1] if rng.random() < 0.5 else p for p in pairs]
    rng.shuffle(pairs)
    return [p[0] for p in pairs], [p[1] for p in pairs]


@pytest.mark.parametrize("k,b", [(1, 64), (3, 64), (8, 64), (9, 64),
                                 (63, 64), (5, 50)],
                         ids=["k1", "k3", "k8", "k9", "k_b-1", "bucket_pad"])
def test_mixed_exactness_batch_patches_inexact_rows(k, b, monkeypatch):
    """A mixed batch runs the kernel on every row and patches the k rows
    over the bound with their int64 merge answers in one dispatch: every
    row equals the merge route and the reference, the batch counts as one
    ``pallas+merge`` with k merged pairs, and bucket padding (b = 50 in a
    64 bucket) is never patched and never returned."""
    import repro.kernels.spc_query.ops as ops

    ref, idx = _heavy_index()
    s, t = _mixed_pairs(k, b, seed=k * 100 + b)
    patched = []
    real_patch = ops.merge_patch

    def spy(rows, iex, d, c):
        patched.append(np.asarray(iex))
        return real_patch(rows, iex, d, c)

    monkeypatch.setattr(ops, "merge_patch", spy)
    eng = QueryEngine()
    d, c = eng.query_batch(idx, s, t, route="pallas")
    assert d.shape == c.shape == (b,) and c.dtype == jnp.int64
    assert eng.stats.routes == {"pallas+merge": 1}
    assert eng.stats.route_pairs == {"pallas": b - k, "merge": k}
    (iex,) = patched
    assert len(set(iex.tolist())) == k and iex.max() < b  # no pad row
    dm, cm = QueryEngine().query_batch(idx, s, t, route="merge")
    np.testing.assert_array_equal(np.asarray(d), np.asarray(dm))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cm))
    want = [ref.query(a, z) for a, z in zip(s, t)]
    assert [(int(x), int(y)) for x, y in zip(d, c)] == want
    # every patched count is one fp32 cannot hold, so the patch is what
    # makes it exact
    assert all(int(np.float32(want[i][1])) != want[i][1] for i in iex)


def test_mixed_exactness_compiles_per_pow2_not_per_k():
    """The merge-and-patch program depends on k only through pow2(k):
    k = 3, 5, 7 in one bucket add one merge program and, after the first
    batch, no kernel program; k = 9 adds exactly one more merge program.
    A unique l_cap keeps other tests' programs out of the counts."""
    from repro.kernels.spc_query.kernel import _spc_query_jit
    from repro.kernels.spc_query.ops import merge_patch

    _, idx = _heavy_index(l_cap=7)
    eng = QueryEngine()
    merges0 = merge_patch._cache_size()
    eng.query_batch(idx, *_mixed_pairs(3, 64, seed=3), route="pallas")
    kernels = _spc_query_jit._cache_size()
    for k in (5, 7):
        eng.query_batch(idx, *_mixed_pairs(k, 64, seed=k), route="pallas")
    assert merge_patch._cache_size() == merges0 + 1
    assert _spc_query_jit._cache_size() == kernels
    eng.query_batch(idx, *_mixed_pairs(9, 64, seed=9), route="pallas")
    assert merge_patch._cache_size() == merges0 + 2
    assert _spc_query_jit._cache_size() == kernels
    assert eng.stats.routes == {"pallas+merge": 4}


def test_pallas_route_works_on_cpu_backend(dynamic_service, monkeypatch):
    """Regression: ``route="pallas"`` with ``interpret=None`` must not
    dispatch the compiled Mosaic lowering off-TPU.  The env knob that
    requests compiled mode on the TPU fleet is clamped back to interpret
    mode on backends without a lowering, at dispatch time."""
    from repro.kernels.common import resolve_interpret

    assert jax.default_backend() != "tpu"  # this container
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert resolve_interpret(None) is True   # backend default
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert resolve_interpret(None) is True   # compiled request clamped
    assert resolve_interpret(False) is True  # explicit arg clamped too
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert resolve_interpret(None) is True
    # end-to-end under the poison env: explicit pallas route still answers
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    svc = dynamic_service
    eng = QueryEngine(route="pallas")
    s = list(range(8))
    d, c = eng.query_batch(svc.index, s, s)
    assert [int(x) for x in d] == [0] * 8
    assert [int(x) for x in c] == [1] * 8
    assert "pallas" in eng.stats.routes


def test_pallas_route_compiled_env_subprocess():
    """True end-to-end regression for the interpret default: a process
    *started* with REPRO_PALLAS_INTERPRET=0 on a CPU backend used to
    crash inside ``pallas_call`` on the explicit pallas route."""
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent(
        """
        import numpy as np
        from repro.core.dynamic import DynamicSPC
        from repro.serve import QueryEngine

        svc = DynamicSPC(6, [(0, 1), (1, 2), (2, 3), (3, 4), (0, 4)],
                         l_cap=8)
        eng = QueryEngine(route="pallas")
        d, c = eng.query_batch(svc.index, [0, 1, 5], [3, 4, 5])
        dm, cm = eng.query_batch(svc.index, [0, 1, 5], [3, 4, 5],
                                 route="merge")
        assert [int(x) for x in d] == [int(x) for x in dm]
        assert [int(x) for x in c] == [int(x) for x in cm]
        assert "pallas" in eng.stats.routes
        print("PALLAS_CPU_OK")
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["REPRO_PALLAS_INTERPRET"] = "0"
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        timeout=600,
    )
    assert "PALLAS_CPU_OK" in proc.stdout, proc.stderr[-3000:]


def test_sharded_serving_single_device(dynamic_service):
    import jax
    from jax.sharding import Mesh

    svc = dynamic_service
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    eng = QueryEngine()
    serve = eng.sharded(mesh)
    rng = np.random.default_rng(2)
    s = rng.integers(0, svc.n, 11)  # deliberately not a bucket size
    t = rng.integers(0, svc.n, 11)
    d_sh, c_sh = serve(svc.index, s, t)
    d, c = eng.query_batch(svc.index, s, t, route="merge")
    np.testing.assert_array_equal(np.asarray(d_sh), np.asarray(d))
    np.testing.assert_array_equal(np.asarray(c_sh), np.asarray(c))
    # the executed core is recorded, comparable with single-device "merge"
    assert eng.stats.routes["sharded[data]:merge"] == 1


def test_sharded_serve_validates_route(dynamic_service):
    """Regression: the sharded closure used to skip the route validation
    that query_batch performs and silently ignored the engine's
    configured route."""
    import jax
    from jax.sharding import Mesh

    svc = dynamic_service
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    serve = QueryEngine().sharded(mesh)
    with pytest.raises(ValueError, match="unknown route"):
        serve(svc.index, [0], [1], route="bogus")
    with pytest.raises(ValueError, match="sharded"):
        serve(svc.index, [0], [1], route="pallas")
    # an engine *configured* for a route the sharded path cannot honor
    # must refuse too, instead of silently serving merge
    serve_tbl = QueryEngine(route="table").sharded(mesh)
    with pytest.raises(ValueError, match="sharded"):
        serve_tbl(svc.index, [0], [1])
    eng = QueryEngine(route="merge")
    d, c = eng.sharded(mesh)(svc.index, [0], [0])
    assert (int(d[0]), int(c[0])) == (0, 1)


def test_empty_batch_early_returns(dynamic_service):
    """Regression: B=0 used to pad up to the smallest bucket, dispatch 8
    dump rows, and record a batch of 0 queries in the stats."""
    import jax
    from jax.sharding import Mesh

    svc = dynamic_service
    eng = QueryEngine()
    for route in (None, "merge", "table", "pallas"):
        d, c = eng.query_batch(svc.index, [], [], route=route)
        assert d.shape == (0,) and c.shape == (0,)
        assert d.dtype == jnp.int32 and c.dtype == jnp.int64
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    d, c = eng.sharded(mesh)(svc.index, [], [])
    assert d.shape == (0,) and c.shape == (0,)
    assert eng.stats.batches == 0 and eng.stats.queries == 0
    assert eng.stats.routes == {}
    # a bad route still raises on an empty batch (validated before the
    # early return)
    with pytest.raises(ValueError):
        eng.query_batch(svc.index, [], [], route="bogus")


def test_engine_rejects_unknown_route(dynamic_service):
    with pytest.raises(ValueError):
        QueryEngine(route="bogus")
    eng = QueryEngine()
    with pytest.raises(ValueError):
        eng.query_batch(dynamic_service.index, [0], [1], route="bogus")
    with pytest.raises(ValueError):
        eng.query_batch(dynamic_service.index, [0, 1], [1])  # shape mismatch


def test_stats_dataclass_shape():
    from repro.serve import ServeStats
    st = ServeStats()
    st.count("merge", 5)
    st.count("merge", 3)
    st.count_version(4, 5)
    st.count_version(4, 3)
    assert dataclasses.asdict(st) == {
        "queries": 8, "batches": 2, "routes": {"merge": 2},
        "versions": {4: 8}, "route_pairs": {"merge": 8}}


def test_coalesce_pairs_and_split_rows_round_trip():
    """The front door's assemble/scatter step: heterogeneous per-request
    pair lists concatenate into one flat batch, and answers split back
    in request order."""
    from repro.serve import coalesce_pairs, split_rows
    parts = [([0], [1]), ([2, 3, 4], [5, 6, 7]), ([8, 9], [10, 11])]
    s, t, offsets = coalesce_pairs(parts)
    np.testing.assert_array_equal(s, [0, 2, 3, 4, 8, 9])
    np.testing.assert_array_equal(t, [1, 5, 6, 7, 10, 11])
    np.testing.assert_array_equal(offsets, [0, 1, 4, 6])
    d = np.arange(6, dtype=np.int32)
    c = np.arange(6, dtype=np.int64) * 10
    back = split_rows(d, c, offsets)
    assert len(back) == len(parts)
    for (ps, _), (di, ci) in zip(parts, back):
        assert di.shape == ci.shape == (len(ps),)
    np.testing.assert_array_equal(back[1][0], [1, 2, 3])
    np.testing.assert_array_equal(back[2][1], [40, 50])

    # ids keep their natural dtype -- the engine's host-side bounds
    # check must see un-wrapped values (an eager int32 cast would wrap
    # a huge id into range and silently answer for the wrong vertex)
    big = np.asarray([2**40], np.int64)
    s2, t2, _ = coalesce_pairs([(big, [0])])
    assert s2.dtype == np.int64 and int(s2[0]) == 2**40
    with pytest.raises(ValueError, match="out of range"):
        QueryEngine._validate_ids(100, s2, t2)


def test_coalesce_pairs_edges_and_errors():
    from repro.serve import coalesce_pairs, split_rows
    s, t, offsets = coalesce_pairs([])
    assert s.shape == t.shape == (0,) and list(offsets) == [0]
    assert split_rows(np.empty(0, np.int32), np.empty(0, np.int64),
                      offsets) == []
    # empty parts are legal and produce empty slices in place
    _, _, off = coalesce_pairs([([], []), ([1], [2])])
    np.testing.assert_array_equal(off, [0, 0, 1])
    with pytest.raises(ValueError, match="part 1"):
        coalesce_pairs([([0], [1]), ([0, 1], [2])])
    with pytest.raises(ValueError, match="cover"):
        split_rows(np.zeros(2, np.int32), np.zeros(3, np.int64),
                   np.asarray([0, 3]))
