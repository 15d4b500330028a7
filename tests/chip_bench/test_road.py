"""The road-network generator: a seeded perturbed planar grid."""

import numpy as np
import pytest

from benchmarks.chip.graphs import road


def _generate(side, seed, drop=0.30):
    return road.generate({"side": side, "drop": drop},
                         np.random.default_rng(seed))


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_same_seed_same_graph(seed):
    n1, e1 = _generate(32, seed)
    n2, e2 = _generate(32, seed)
    assert n1 == n2
    np.testing.assert_array_equal(e1, e2)
    _, e3 = _generate(32, seed + 1)
    assert not np.array_equal(e1, e3)


def test_edges_are_sorted_unique_and_oriented():
    n, e = _generate(24, 3)
    assert e.dtype == np.int64 and e.shape[1] == 2
    assert (e[:, 0] < e[:, 1]).all()
    assert len(np.unique(e, axis=0)) == len(e)
    np.testing.assert_array_equal(e, e[np.lexsort((e[:, 1], e[:, 0]))])
    assert e.min() >= 0 and e.max() == n - 1


def test_edges_join_lattice_neighbours_only():
    """Without drops or permutation the graph is the lattice itself; with
    them, every edge still joins two lattice neighbours: recover each
    label's cell from the lattice through the seeded permutation."""
    side = 20
    n, e = _generate(side, 5, drop=0.0)
    assert n == side * side and len(e) == 2 * side * (side - 1)
    deg = np.bincount(e.ravel(), minlength=n)
    assert sorted(np.unique(deg)) == [2, 3, 4]
    # the drops of seed 5 remove only edges: every kept edge is a lattice
    # edge of the same labels, re-drawn with the same permutation
    rng = np.random.default_rng(5)
    lat = road.lattice_edges(side)
    kept = lat[rng.random(len(lat)) >= 0.30]
    keep = road.largest_component(side * side, kept)
    label = np.full(side * side, -1)
    label[keep] = rng.permutation(keep.size)
    cell = np.empty(keep.size, np.int64)
    cell[label[keep]] = keep
    _, e = _generate(side, 5)
    r, c = np.divmod(cell[e], side)
    assert (np.abs(r[:, 0] - r[:, 1]) + np.abs(c[:, 0] - c[:, 1]) == 1).all()


@pytest.mark.parametrize("seed", [7, 11])
def test_connected(seed):
    n, e = _generate(40, seed)
    assert road.largest_component(n, e).size == n


@pytest.mark.parametrize("seed", [7, 3160000001])
def test_mean_degree_is_new_yorks_at_side_96(seed):
    """USA-road-d.NY: 733,846 arcs over 264,346 nodes, mean degree 2.78."""
    n, e = _generate(96, seed)
    assert 8_800 < n < 9_216
    assert abs(2 * len(e) / n - 2.78) <= 0.08
