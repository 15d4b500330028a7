"""Regression: interpret-mode resolution happens at *dispatch time* in
every Pallas kernel -- never snapshotted at import, never baked into a
cached jit trace (the CHANGES.md PR 3 INTERPRET class, and its subtler
recurrence where ``resolve_interpret`` ran inside the jitted entry so
the first call's env read was frozen into the trace cache)."""

import ast
import os

import jax.numpy as jnp
import numpy as np

from repro.analysis.rules import (check_env_import_snapshot,
                                  check_jit_nondeterminism)

KERNELS_ROOT = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "..", "src", "repro", "kernels"))

ENTRY_MODULES = [
    os.path.join(KERNELS_ROOT, name, "kernel.py")
    for name in ("spc_query", "segment_matmul", "embedding_bag",
                 "flash_decode")
]


def _kernel_sources():
    for root, dirs, files in os.walk(KERNELS_ROOT):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                yield path, ast.parse(open(path).read(), filename=path)


def test_no_import_time_env_snapshot_anywhere_under_kernels():
    findings = [f for path, tree in _kernel_sources()
                for f in check_env_import_snapshot(path, tree)]
    assert not findings, [f.format() for f in findings]


def test_no_env_resolution_inside_any_jitted_kernel_entry():
    # the lint rule that encodes the bug: resolve_interpret (or any env
    # read) inside a jit-decorated function is trace-time, not
    # dispatch-time
    findings = [f for path, tree in _kernel_sources()
                for f in check_jit_nondeterminism(path, tree)]
    assert not findings, [f.format() for f in findings]


def test_all_four_entries_resolve_through_common(monkeypatch):
    # each public entry must call kernels.common.resolve_interpret on
    # EVERY dispatch: a trace-cached resolution would call it once for
    # the first (tracing) call and never again
    import repro.kernels.embedding_bag.kernel as eb
    import repro.kernels.flash_decode.kernel as fd
    import repro.kernels.segment_matmul.kernel as sm
    import repro.kernels.spc_query.kernel as sq

    calls = []

    def make_recorder(mod):
        real = mod.resolve_interpret

        def recorder(flag=None):
            calls.append(mod.__name__)
            return real(flag)

        monkeypatch.setattr(mod, "resolve_interpret", recorder)

    for mod in (eb, fd, sm, sq):
        make_recorder(mod)

    ids = jnp.asarray(np.zeros((2, 2), np.int32))
    table = jnp.asarray(np.zeros((4, 4), np.float32))
    q = jnp.asarray(np.zeros((2, 4), np.float32))
    kv = jnp.asarray(np.zeros((2, 8, 4), np.float32))
    lengths = jnp.asarray(np.full((2,), 8, np.int32))
    vals = jnp.asarray(np.ones((4, 4), np.float32))
    dst = jnp.asarray(np.zeros((4,), np.int32))
    hub = jnp.asarray(np.zeros((2, 2), np.int32))
    dist = jnp.asarray(np.zeros((2, 2), np.int32))
    cnt = jnp.asarray(np.ones((2, 2), np.float32))

    for _ in range(2):  # second round hits the jit cache
        eb.embedding_bag_pallas(ids, table, interpret=True)
        fd.flash_decode_pallas(q, kv, kv, lengths, block_bh=2,
                               block_s=8, interpret=True)
        sm.segment_matmul_pallas(vals, dst, 2, block_e=4, block_n=2,
                                 interpret=True)
        sq.spc_query_pallas(hub, dist, cnt, hub, dist, cnt, block_b=128,
                            interpret=True)

    for mod in (eb, fd, sm, sq):
        assert calls.count(mod.__name__) == 2, (
            f"{mod.__name__}: resolve_interpret ran "
            f"{calls.count(mod.__name__)}x over 2 dispatches -- "
            f"resolution is being cached with the trace")


def test_env_flip_respected_between_dispatches(monkeypatch):
    # the user-visible symptom of the bug: flipping the env var between
    # two identical calls had no effect.  Off-TPU the compiled request
    # is clamped back to interpret (documented), so pin the backend to
    # TPU to make the flip observable.
    import jax

    from repro.kernels import common

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert common.resolve_interpret() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert common.resolve_interpret() is True
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    assert common.resolve_interpret() is False  # TPU default: compiled
