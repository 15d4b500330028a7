"""The Graph500 Kronecker generator copied into the benchmark."""

import numpy as np
import pytest

from benchmarks.chip.graphs import kronecker


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 11])
def test_same_seed_same_graph(seed):
    params = {"scale": 8, "edgefactor": 16}
    n1, e1 = kronecker.generate(params, seed)
    n2, e2 = kronecker.generate(params, seed)
    assert n1 == n2 == 256
    np.testing.assert_array_equal(e1, e2)
    _, e3 = kronecker.generate(params, seed + 1)
    assert not np.array_equal(e1, e3)


def test_tuple_count_is_edgefactor_times_vertices():
    rng = np.random.default_rng(3)
    tuples = kronecker.kronecker_tuples(9, 16, rng)
    assert tuples.shape == (16 * 512, 2)
    assert tuples.min() >= 0 and tuples.max() < 512


def test_simple_undirected_edges():
    n, e = kronecker.generate({"scale": 10, "edgefactor": 16}, 5)
    assert (e[:, 0] < e[:, 1]).all(), "self-loops kept or not oriented"
    assert len(np.unique(e, axis=0)) == len(e), "duplicates kept"
    # duplicates and self-loops drop a share of the 16,384 tuples
    assert 8_000 < len(e) < 16 * n


def test_degree_skew_of_the_initiator():
    """A = 0.57 concentrates edges: the top 1% of vertices hold a large
    share of the endpoints, and the largest degree is far above the mean
    (a uniform graph of the same size would give about 1% and ~2x)."""
    n, e = kronecker.generate({"scale": 12, "edgefactor": 16}, 7)
    deg = np.bincount(e.ravel(), minlength=n)
    top = np.sort(deg)[::-1][: n // 100].sum() / deg.sum()
    assert top > 0.15
    assert deg.max() > 20 * deg.mean()

