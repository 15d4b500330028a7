"""Query-path equivalences: table vs merge-join vs reference, and the
serving (jit/shard) wrappers."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_index, from_edges
from repro.core.labels import to_ref
from repro.core.query import (batched_query, batched_query_jit,
                              batched_query_merge, one_to_all,
                              one_to_all_dists)
from repro.data import random_graph_edges


@pytest.mark.parametrize("seed", range(4))
def test_merge_equals_table_and_ref(seed):
    n = 50
    edges = random_graph_edges(n, 120, seed=seed)
    g = from_edges(n, edges)
    idx = build_index(g, l_cap=n + 2)
    assert int(idx.overflow) == 0
    ref = to_ref(idx)
    rng = np.random.default_rng(seed)
    s = rng.integers(0, n, 300)
    t = rng.integers(0, n, 300)
    d1, c1 = batched_query(idx, jnp.asarray(s), jnp.asarray(t))
    d2, c2 = batched_query_merge(idx, jnp.asarray(s), jnp.asarray(t))
    d3, c3 = batched_query_jit(idx, jnp.asarray(s), jnp.asarray(t))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d3))
    for k in range(0, 300, 37):
        dr, cr = ref.query(int(s[k]), int(t[k]))
        if cr == 0:  # disconnected: sentinel values differ by module
            assert int(c1[k]) == 0 and int(d1[k]) >= (1 << 28)
        else:
            assert (int(d1[k]), int(c1[k])) == (dr, cr)


def test_merge_handles_disconnected_and_identity():
    g = from_edges(6, [(0, 1), (2, 3)])
    idx = build_index(g, l_cap=8)
    d, c = batched_query_merge(idx, jnp.asarray([0, 0, 4]),
                               jnp.asarray([1, 2, 4]))
    assert (int(d[0]), int(c[0])) == (1, 1)
    assert int(c[1]) == 0 and int(d[1]) >= (1 << 28)
    assert (int(d[2]), int(c[2])) == (0, 1)


@pytest.mark.parametrize("limit,cols", [(0, 64), (9, 5), (30, 1), (51, 64)])
def test_one_to_all_dists_equals_one_to_all(limit, cols):
    """All roots at once, any column chunk (one that does not divide
    l_cap included), equals ``one_to_all`` root by root."""
    n = 50
    g = from_edges(n, random_graph_edges(n, 120, seed=5))
    idx = build_index(g, l_cap=n + 2)
    roots = jnp.asarray([0, 1, 7, 30, 49, n], jnp.int32)   # n: dump row
    got = one_to_all_dists(idx, roots, jnp.int32(limit), cols=cols)
    for k, r in enumerate(roots):
        want, _ = one_to_all(idx, r, limit=jnp.int32(limit))
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want))
