"""SPCService façade: the consistency contract (pinned /
read-your-writes / at_version), async ingest (bounded queue,
backpressure, drain, updater-failure propagation), RoutePolicy
validation, and service reads differential against the ``bfs_spc``
oracle across a mutation stream in single-device and mesh modes."""

import dataclasses
import queue as queue_lib
import threading
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import refimpl as R
from repro.core.dynamic import DynamicSPC, UpdateStats
from repro.core.graph import INF
from repro.data import graph_stream, random_graph_edges
from repro.serve import (NO_TICKET, QueryEngine, RoutePolicy, ServeStats,
                         SPCService, UpdaterError)

# same (n, m, seed, l_cap) as tests/serve/test_publish.py so the jit
# compile caches stay warm across the serve suites
N, M, SEED = 30, 70, 11


def _service(**kw):
    kw.setdefault("l_cap", 32)
    return SPCService(N, random_graph_edges(N, M, seed=SEED), **kw)


def _stream(svc, n_ins, n_del, seed):
    return graph_stream(sorted(svc.spc._edge_set()), svc.spc.n,
                        n_ins, n_del, seed=seed)


def _oracle(svc):
    g = R.RefGraph(svc.spc.n, sorted(svc.spc._edge_set()))
    return {s: R.bfs_spc(g, s) for s in range(svc.spc.n)}


def _assert_matches_oracle(truth, s, t, d, c):
    for k, (sk, tk) in enumerate(zip(s, t)):
        dist, cnt = truth[sk]
        if dist[tk] >= int(INF):
            assert int(c[k]) == 0 and int(d[k]) >= int(INF), (sk, tk)
        else:
            assert (int(d[k]), int(c[k])) == (int(dist[tk]), int(cnt[tk]))


@pytest.mark.parametrize("update_batch", [None, 0])
def test_update_batch_must_be_positive(update_batch):
    """Every chunk runs through the one update engine: there is no
    per-event mode for ``update_batch=None`` to select, so it is refused
    before anything is built."""
    with pytest.raises(ValueError, match="update_batch"):
        _service(update_batch=update_batch)


# -- routing policies -------------------------------------------------------
def test_route_policy_validation():
    for kind in ("auto", "merge", "table", "pallas"):
        pol = RoutePolicy.coerce(kind)
        assert pol.kind == kind and pol.engine_route == kind
        assert not pol.needs_mesh
    sh = RoutePolicy.sharded(("data", "model"))
    assert sh.needs_mesh and sh.engine_route == "merge"
    assert sh.batch_axes == ("data", "model")
    assert RoutePolicy.coerce(None) == RoutePolicy.auto()
    assert RoutePolicy.coerce(sh) is sh
    with pytest.raises(ValueError, match="unknown route kind"):
        RoutePolicy("palas")
    with pytest.raises(ValueError, match="RoutePolicy"):
        RoutePolicy.coerce(123)
    # kernel knobs only on kernel kinds; axes only on sharded -- all at
    # construction, not at dispatch
    with pytest.raises(ValueError, match="kernel knobs"):
        RoutePolicy("merge", block_b=256)
    with pytest.raises(ValueError, match="kernel knobs"):
        RoutePolicy("table", interpret=True)
    with pytest.raises(ValueError, match="batch_axes"):
        RoutePolicy("merge", batch_axes=("data",))
    with pytest.raises(ValueError, match="axis names"):
        RoutePolicy("sharded", batch_axes=())
    with pytest.raises(ValueError, match="block_b"):
        RoutePolicy.pallas(block_b=0)
    with pytest.raises(ValueError, match="multiple of 128"):
        RoutePolicy.pallas(block_b=64)    # off the lane tiling
    with pytest.raises(dataclasses.FrozenInstanceError):
        RoutePolicy.merge().kind = "table"
    assert RoutePolicy.pallas(block_b=256) == RoutePolicy.pallas(block_b=256)
    assert len({RoutePolicy.merge(), RoutePolicy.merge()}) == 1


def test_route_policy_binds_to_engine():
    pol = RoutePolicy.pallas(block_b=256, interpret=True)
    eng = QueryEngine(route=pol)
    assert (eng.route, eng.block_b, eng.interpret) == ("pallas", 256, True)
    svc = DynamicSPC(N, random_graph_edges(N, M, seed=SEED), l_cap=32)
    eng2 = QueryEngine()
    d, c = eng2.query_batch(svc.index, [0, 1], [2, 3],
                            route=RoutePolicy.table())
    assert eng2.stats.routes == {"table": 1}
    d0, c0 = eng2.query_batch(svc.index, [0, 1], [2, 3], route="table")
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d0))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c0))
    # a per-call policy must bind or raise -- never silently degrade
    with pytest.raises(ValueError, match="single-device"):
        eng2.query_batch(svc.index, [0], [1],
                         route=RoutePolicy.sharded())
    with pytest.raises(ValueError, match="kernel knobs"):
        eng2.query_batch(svc.index, [0], [1],
                         route=RoutePolicy.pallas(block_b=256))


# -- differential: façade reads vs the BFS oracle ---------------------------
@pytest.mark.parametrize("use_mesh", [False, True])
def test_service_differential_vs_oracle(use_mesh):
    """The acceptance test: façade-served answers equal BFS ground truth
    across a mutation stream, in single-device and mesh modes."""
    n, m = (24, 55) if use_mesh else (N, M)
    seed = 7 if use_mesh else SEED
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("model",)) if use_mesh \
        else None
    with SPCService(n, random_graph_edges(n, m, seed=seed), l_cap=32,
                    mesh=mesh, update_batch=4) as svc:
        rng = np.random.default_rng(seed)
        events = _stream(svc, 8, 4, seed=seed + 1)
        for lo in range(0, len(events), 4):
            svc.submit(events[lo:lo + 4])
        svc.drain()
        assert svc.pending == 0 and svc.version == svc.spc.version > 0
        truth = _oracle(svc)
        s = [int(x) for x in rng.integers(0, n, 40)]
        t = [int(x) for x in rng.integers(0, n, 40)]
        d, c = svc.query_batch(s, t)
        _assert_matches_oracle(truth, s, t, d, c)
        # the explicit reader pins the same published snapshot
        serve = svc.reader("read_your_writes")
        d2, c2 = serve(s, t)
        np.testing.assert_array_equal(np.asarray(d2), np.asarray(d))
        np.testing.assert_array_equal(np.asarray(c2), np.asarray(c))
        assert serve.last_version == svc.version
        dp, cp = svc.query_pair(s[0], t[0])
        assert (dp, cp) == (int(d[0]), int(c[0]))


# -- consistency contract ---------------------------------------------------
def test_read_your_writes_under_concurrent_writer():
    """A read-your-writes batch observes a published version covering
    the last accepted submit ticket, while the writer keeps going."""
    with _service(update_batch=3) as svc:
        events = _stream(svc, 10, 5, seed=3)
        stop = threading.Event()

        def writer():
            for lo in range(0, len(events), 3):
                svc.submit(events[lo:lo + 3])
            stop.set()

        th = threading.Thread(target=writer)
        rw = svc.reader("read_your_writes")
        th.start()
        checked = 0
        while not (stop.is_set() and svc.pending == 0):
            want = svc.accepted          # the caller's last accepted ticket
            d, _ = rw([0, 1], [2, 3])
            assert d.shape == (2,)
            assert svc.applied >= want   # the wait actually happened
            if want:
                covering = svc.ticket_version(want)
                assert covering is not None
                assert rw.last_version >= covering
                checked += 1
        th.join()
        svc.drain()
        assert checked > 0               # loop overlapped real ingest
        assert svc.applied == svc.accepted == -(-len(events) // 3)


def test_pinned_never_waits_and_rw_times_out():
    """pinned serves the current published version without touching the
    ingest queue; read_your_writes on a stalled queue raises
    TimeoutError instead of hanging."""
    svc = _service()                     # NOT started: ingest is stalled
    ticket = svc.submit(_stream(svc, 2, 1, seed=4))
    pinned = svc.reader()
    d, c = pinned([0, 1], [2, 3])
    assert pinned.last_version == 0      # still the seed snapshot
    assert svc.pending == 1              # pinned consumed nothing
    rw = svc.reader("read_your_writes", timeout=0.2)
    with pytest.raises(TimeoutError, match="ticket"):
        rw([0], [1])
    svc.start()
    svc.drain()
    rw2 = svc.reader("read_your_writes")
    rw2([0], [1])
    assert rw2.last_version >= svc.ticket_version(ticket) >= 1
    svc.close()


def test_at_version_reader_blocks_until_published():
    with _service(update_batch=2) as svc:
        events = _stream(svc, 4, 2, seed=5)
        # 6 events in chunks of 2 -> 3 committed versions
        target = svc.version + 3
        late = svc.reader(at_version=target)
        svc.submit(events)
        d, _ = late([0], [1])            # blocks until version 3 publishes
        assert late.last_version >= target
        assert svc.version >= target
    with _service() as svc:
        # version 0 (the seed snapshot) is a real published version:
        # at_version=0 must serve immediately, not wait for "something"
        seed_reader = svc.reader(at_version=0, timeout=2)
        seed_reader([0], [1])
        assert seed_reader.last_version == 0
        with pytest.raises(ValueError, match="at_version"):
            svc.reader("read_your_writes", at_version=1)
        with pytest.raises(ValueError, match="consistency"):
            svc.reader("linearizable")


# -- ingest lifecycle -------------------------------------------------------
def test_drain_flushes_queue_and_matches_sequential_replay():
    ref = DynamicSPC(N, random_graph_edges(N, M, seed=SEED), l_cap=32)
    with _service(update_batch=4, queue_size=2) as svc:
        events = _stream(svc, 6, 3, seed=6)
        for lo in range(0, len(events), 3):   # more chunks than queue slots
            svc.submit(events[lo:lo + 3])
        svc.drain()
        assert svc.pending == 0
        assert svc.applied == svc.accepted == -(-len(events) // 3)
        from repro.core.labels import to_ref
        ref.apply_events(events, batch_size=4)
        assert to_ref(svc.spc.index).labels == to_ref(ref.index).labels


def test_bounded_queue_backpressure():
    svc = _service(queue_size=1)         # not started: nothing drains
    events = _stream(svc, 4, 2, seed=7)
    t1 = svc.submit(events[:2])
    assert t1 == 1
    with pytest.raises(queue_lib.Full):  # bounded: the queue pushes back
        svc.submit(events[2:4], timeout=0.05)
    with pytest.raises(RuntimeError, match="not running"):
        svc.submit(events[2:4])          # blocking forever would deadlock
    with pytest.raises(RuntimeError, match="not started"):
        svc.drain()
    svc.start()
    svc.drain()                          # backpressure released
    t2 = svc.submit(events[2:4])
    svc.drain()
    assert (svc.applied, svc.accepted) == (t2, t2) == (2, 2)
    svc.close()


def test_submit_timeout_bounds_the_admission_lock_too():
    """submit(timeout=) must raise queue.Full within the deadline even
    when another submitter holds the admission lock (parked on a full
    queue), not block unboundedly on lock acquisition."""
    svc = _service(queue_size=1)
    events = _stream(svc, 2, 1, seed=13)
    assert svc._submit_lock.acquire()    # another submitter, parked
    try:
        t0 = time.monotonic()
        with pytest.raises(queue_lib.Full, match="admission"):
            svc.submit(events[:1], timeout=0.05)
        assert time.monotonic() - t0 < 5.0
    finally:
        svc._submit_lock.release()
    assert svc.submit(events[:1], timeout=1.0) == 1   # lock free again


def test_pending_never_goes_negative():
    svc = _service()
    with svc._cond:                      # the transient inversion window
        svc._applied = svc._accepted + 1
    assert svc.pending == 0
    assert svc.stats()["ingest"]["pending"] == 0


def test_submitter_blocked_on_full_queue_unblocks_on_updater_death():
    """A submitter parked on a full queue must wake and raise when the
    updater dies mid-wait -- the queue will never drain again, so
    blocking forever would deadlock every later submit too."""
    svc = _service(queue_size=1).start()
    present = svc.spc._edge_set()
    absent = next((a, b) for a in range(N) for b in range(a + 1, N)
                  if (a, b) not in present)
    chunk = [("+",) + absent]            # applies once, dies on repeat
    outcome = []

    def feeder():
        try:
            for _ in range(50):          # enough to park on a full queue
                svc.submit(chunk)
        except UpdaterError as e:
            outcome.append(e)

    th = threading.Thread(target=feeder)
    th.start()
    th.join(timeout=20)
    assert not th.is_alive()             # surfaced, not deadlocked
    assert outcome and isinstance(outcome[0].__cause__, ValueError)
    with pytest.raises(UpdaterError):
        svc.drain()


def test_ticket_version_history_is_bounded():
    with _service(update_batch=2) as svc:
        svc.TICKET_HISTORY = 2           # shrink the retention window
        events = _stream(svc, 4, 2, seed=12)
        tickets = [svc.submit([ev]) for ev in events]
        svc.drain()
        assert len(svc._ticket_versions) == 2
        assert svc.ticket_version(tickets[0]) is None   # aged out
        assert svc.ticket_version(tickets[-1]) == svc.version


def test_updater_failure_surfaces_on_next_call():
    """A poisoned stream kills the updater thread; the failure is raised
    (chained) on the next submit/drain/read/close instead of the thread
    dying silently."""
    svc = _service().start()
    present = sorted(svc.spc._edge_set())
    svc.submit([("+",) + present[0]])    # already present: fails at apply
    with pytest.raises(UpdaterError) as ei:
        svc.drain()
    assert isinstance(ei.value.__cause__, ValueError)
    with pytest.raises(UpdaterError):
        svc.submit([("-",) + present[0]])
    reader = svc.reader()
    with pytest.raises(UpdaterError):
        reader([0], [1])
    with pytest.raises(UpdaterError):
        svc.close()
    # bad tags never reach the queue at all (validated at submit)
    svc2 = _service()
    with pytest.raises(ValueError, match="unknown event op"):
        svc2.submit([("insert", 0, 1)])
    assert svc2.pending == 0


def test_close_is_idempotent_and_blocks_further_ingest():
    # a never-started service with accepted submits refuses to close
    # (the tickets would be silently discarded) and stays open
    stalled = _service()
    stalled.submit(_stream(stalled, 2, 1, seed=8))
    with pytest.raises(RuntimeError, match="not started"):
        stalled.close()
    stalled.start()
    stalled.close()                      # now drains, then closes
    assert stalled.pending == 0

    svc = _service().start()
    svc.submit(_stream(svc, 2, 1, seed=8))
    svc.close()
    svc.close()
    assert svc.pending == 0              # close drained first
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit([("+", 0, 1)])
    with pytest.raises(RuntimeError, match="closed"):
        svc.start()
    svc.reader()([0], [1])               # reads outlive the lifecycle


# -- session scoping / ticket sentinels -------------------------------------
def test_read_your_writes_is_session_scoped():
    """THE bug this PR fixes: read-your-writes used to wait on the
    globally last accepted ticket, so any foreign in-flight write gated
    every RYW reader.  Now each Session tracks its own last submit
    ticket, and a session that wrote nothing never waits."""
    svc = _service().start()
    gate = threading.Event()
    orig = svc.spc.apply_events

    def gated(events, **kw):
        assert gate.wait(30)
        return orig(events, **kw)

    svc.spc.apply_events = gated
    try:
        foreign = svc.session()
        mine = svc.session()
        ticket = foreign.submit(_stream(svc, 2, 1, seed=20))
        assert ticket == 1 and svc.applied == 0   # parked behind the gate
        # my session wrote nothing: its RYW reader must not wait on the
        # foreign ticket (pre-fix this timed out)
        rw_mine = svc.reader("read_your_writes", session=mine, timeout=0.5)
        d, _ = rw_mine([0], [1])
        assert d.shape == (1,)
        # the writing session itself DOES wait -- that is its write
        rw_foreign = foreign.reader(timeout=0.2)
        with pytest.raises(TimeoutError, match="ticket"):
            rw_foreign([0], [1])
    finally:
        gate.set()
    svc.drain()
    rw_foreign([0], [1])                          # now covered
    assert rw_foreign.last_version >= svc.ticket_version(ticket) >= 1
    foreign.wait_applied()
    assert foreign.last_ticket == ticket
    svc.close()


def test_empty_submit_returns_no_ticket_sentinel():
    """submit([]) gates nothing: it returns NO_TICKET (0), and an RYW
    wait keyed on it serves immediately -- pre-fix it returned the
    global last accepted ticket, blocking the caller on FOREIGN ingest
    it never performed."""
    svc = _service()                     # not started: ingest is stalled
    other = svc.session()
    other.submit(_stream(svc, 2, 1, seed=21))     # foreign pending write
    sess = svc.session()
    assert sess.submit([]) == NO_TICKET == 0
    assert sess.last_ticket == NO_TICKET
    assert svc.ticket_version(NO_TICKET) is None
    # the sentinel never aliases the foreign ticket: this RYW read
    # serves the seed snapshot instead of timing out on stalled ingest
    rw = svc.reader("read_your_writes", session=sess, timeout=0.3)
    d, _ = rw([0], [1])
    assert rw.last_version == 0
    svc.start()
    svc.close()


def test_default_reader_built_once_under_race():
    """Two concurrent FIRST query_batch callers must share one lazily
    built default reader -- pre-fix both constructed one, leaking a
    round-robin slot and skewing per-replica stats."""
    with _service(replicas=2) as svc:
        builds = []
        barrier = threading.Barrier(4)
        orig = svc.reader

        def slow_reader(*a, **kw):
            builds.append(threading.get_ident())
            time.sleep(0.05)             # hold the race window open
            return orig(*a, **kw)

        svc.reader = slow_reader
        errs = []

        def caller():
            barrier.wait()
            try:
                svc.query_batch([0], [1])
            except BaseException as e:   # surfaced after join
                errs.append(e)

        threads = [threading.Thread(target=caller) for _ in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errs, errs
        assert len(builds) == 1          # exactly one construction
        assert svc._rr == 1              # exactly one round-robin claim


def test_close_detects_stuck_updater_thread():
    """A join that times out at shutdown means the updater is STILL
    applying; close() must raise instead of silently marking the
    service closed over a thread that keeps mutating the index."""
    svc = _service(wait_timeout=0.3).start()
    gate = threading.Event()
    orig = svc.spc.apply_events

    def stuck(events, **kw):
        assert gate.wait(30)
        return orig(events, **kw)

    svc.spc.apply_events = stuck
    svc.submit(_stream(svc, 2, 1, seed=22))
    with pytest.raises(TimeoutError, match="updater thread"):
        svc.close(timeout=0.1)
    assert svc._closed                   # closed to NEW work regardless
    gate.set()                           # let the thread finish cleanly
    svc._thread.join(timeout=20)
    assert not svc._thread.is_alive()


def test_route_policy_coerces_mappings():
    """Configs and front-door knobs carry the route as plain data."""
    assert RoutePolicy.coerce({"kind": "pallas", "block_b": 256}) == \
        RoutePolicy.pallas(block_b=256)
    assert RoutePolicy.coerce({}) == RoutePolicy.auto()
    sh = RoutePolicy.coerce({"kind": "sharded", "batch_axes": ["x", "y"]})
    assert sh.batch_axes == ("x", "y") and sh.needs_mesh
    with pytest.raises(ValueError, match="unknown keys"):
        RoutePolicy.coerce({"kind": "merge", "blocksize": 9})
    with pytest.raises(ValueError, match="kernel knobs"):
        RoutePolicy.coerce({"kind": "merge", "block_b": 256})


# -- routing through the service -------------------------------------------
def test_sharded_policy_reader_matches_routed_path():
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    with _service(serve_mesh=mesh) as svc:
        svc.submit(_stream(svc, 2, 1, seed=9))
        svc.drain()
        serve = svc.reader(route=RoutePolicy.sharded())
        rng = np.random.default_rng(9)
        s = rng.integers(0, N, 13)
        t = rng.integers(0, N, 13)
        d, c = serve(s, t)
        d0, c0 = QueryEngine().query_batch(svc.spc.index, s, t,
                                           route="merge")
        np.testing.assert_array_equal(np.asarray(d), np.asarray(d0))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(c0))
        view = serve.engine.stats.snapshot()
        assert view.routes == {"sharded[data]:merge": 1}
    with pytest.raises(ValueError, match="serve_mesh"):
        _service(route=RoutePolicy.sharded())
    with _service() as svc:
        with pytest.raises(ValueError, match="serve_mesh"):
            svc.reader(route="sharded")


def test_sharded_route_respects_service_axes_and_default_route():
    """The string route \"sharded\" binds the service's batch_axes; a
    policy naming an axis the mesh lacks fails at reader construction;
    and a sharded reader over replicas defaulting to a non-mergeable
    route still serves (the POLICY's route wins, not the engine's)."""
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("x",))
    with _service(serve_mesh=mesh, batch_axes=("x",),
                  route="table") as svc:
        serve = svc.reader(route="sharded")   # service axes: ("x",)
        d, c = serve([0, 1], [2, 3])          # table default must not leak
        assert d.shape == (2,)
        view = serve.engine.stats.snapshot()
        assert view.routes == {"sharded[x]:merge": 1}
        with pytest.raises(ValueError, match="batch axes"):
            svc.reader(route=RoutePolicy.sharded(("data",)))


def test_replicas_round_robin_and_aggregate_stats():
    with _service(replicas=2) as svc:
        r1, r2, r3 = svc.reader(), svc.reader(), svc.reader()
        assert r1.engine is not r2.engine
        assert r3.engine is r1.engine    # wrapped around
        r1([0], [1])
        r2([0, 1], [2, 3])
        st = svc.stats()
        assert st["queries"] == 3
        assert [v.queries for v in st["serve"]] == [1, 2]
        assert st["ingest"]["pending"] == 0
        assert st["version"] == 0


def test_dedicated_policy_engines_are_cached():
    """Readers whose policy carries its own kernel knobs get a
    dedicated engine -- ONE per knob pair, however many readers -- and
    the round-robin pool never serves foreign knobs."""
    with _service() as svc:
        pol = RoutePolicy.pallas(block_b=256)
        rs = [svc.reader(route=pol) for _ in range(3)]
        assert rs[0].engine is rs[1].engine is rs[2].engine
        assert rs[0].engine.block_b == 256
        assert len(svc._engines) == 1    # pool: default-knob replicas only
        assert len(svc._dedicated) == 1
        assert svc.reader().engine is svc._engines[0]  # shared path
        rs[0]([0], [1])
        st = svc.stats()                 # both engines visible in stats
        assert len(st["serve"]) == 2 and st["queries"] == 1


# -- stats snapshots --------------------------------------------------------
def test_stats_snapshots_are_frozen_copies():
    stats = ServeStats()
    stats.count("merge", 5)
    stats.count_version(2, 5)
    view = stats.snapshot()
    assert (view.queries, view.batches) == (5, 1)
    with pytest.raises(dataclasses.FrozenInstanceError):
        view.queries = 0
    with pytest.raises(TypeError):
        view.routes["merge"] = 99        # read-only mapping proxy
    stats.count("merge", 1)              # live object moved on ...
    assert view.queries == 5             # ... the view did not
    ustats = UpdateStats()
    ustats.bump(batches=2, batched_events=10)
    uview = ustats.snapshot()
    assert uview.events_per_batch == 5.0
    with pytest.raises(dataclasses.FrozenInstanceError):
        uview.batches = 0


def test_stats_snapshot_safe_against_concurrent_counting():
    """Iterating a snapshot while another thread inserts new dict keys
    must never raise (live-dict iteration would).  The counter is
    bounded (not stop-flag driven): a tight count loop can starve
    ``snapshot()``'s lock acquisition indefinitely (lock convoy), so an
    unbounded counter turned scheduler-dependent snapshot slowness into
    a test hang."""
    stats = ServeStats()
    n_counts = 20_000
    done = threading.Event()

    def counter():
        try:
            for i in range(n_counts):
                stats.count(f"route{i}", 1)  # new key every call
                stats.count_version(i, 1)
        finally:
            done.set()

    th = threading.Thread(target=counter)
    th.start()
    try:
        while not done.is_set():
            view = stats.snapshot()
            assert sum(view.routes.values()) == view.batches
            list(view.versions.items())
    finally:
        th.join()
    view = stats.snapshot()             # final state is fully consistent
    assert view.batches == n_counts
    assert sum(view.routes.values()) == n_counts
    assert len(view.versions) == n_counts


# -- state round trip -------------------------------------------------------
def test_service_state_dict_round_trip_serves_identically():
    with _service(update_batch=4) as svc:
        svc.submit(_stream(svc, 4, 2, seed=10))
        svc.drain()
        state = {k: np.asarray(v) for k, v in svc.state_dict().items()}
        restored = SPCService.from_state_dict(N, state)
        rng = np.random.default_rng(10)
        s = rng.integers(0, N, 20)
        t = rng.integers(0, N, 20)
        d0, c0 = svc.query_batch(s, t)
        d1, c1 = restored.query_batch(s, t)
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c0))
        assert restored.version == svc.version
        restored.close()
