"""The bulk graph generators draw exactly what a per-try loop draws."""

import numpy as np
import pytest

from repro.data import graph_stream, random_graph_edges


def _per_try_edges(n, m, seed=0, power_law=True):
    """Reference: one ``rng.choice`` / ``rng.integers`` call per try."""
    rng = np.random.default_rng(seed)
    edges = set()
    if power_law:
        w = 1.0 / (np.arange(1, n + 1) ** 0.8)
        w /= w.sum()
    tries = 0
    while len(edges) < m and tries < 50 * m:
        tries += 1
        if power_law:
            a, b = rng.choice(n, size=2, p=w)
        else:
            a, b = rng.integers(0, n, size=2)
        if a == b:
            continue
        edges.add((min(int(a), int(b)), max(int(a), int(b))))
    return sorted(edges)


def _per_delete_sort_stream(edges, n, n_insert, n_delete, seed=0):
    """Reference: re-sort the present edges at every delete."""
    rng = np.random.default_rng(seed)
    present = set(edges)
    events = []
    ops = ["+"] * n_insert + ["-"] * n_delete
    rng.shuffle(ops)
    for op in ops:
        if op == "+":
            while True:
                a, b = rng.integers(0, n, size=2)
                key = (min(int(a), int(b)), max(int(a), int(b)))
                if a != b and key not in present:
                    present.add(key)
                    events.append(("+", key[0], key[1]))
                    break
        else:
            if not present:
                continue
            key = sorted(present)[rng.integers(0, len(present))]
            present.discard(key)
            events.append(("-", key[0], key[1]))
    return events


@pytest.mark.parametrize("n,m,seed,power_law", [
    (500, 2000, 3, True),
    (64, 160, 0, True),
    (30, 60, 11, False),
    (5, 10, 1, True),       # complete graph: m distinct pairs exist
    (4, 10, 2, True),       # only 6 pairs: gives up after 50 m tries
    (2000, 9000, 7, True),
])
def test_random_graph_edges_match_per_try_draws(n, m, seed, power_law):
    got = random_graph_edges(n, m, seed=seed, power_law=power_law)
    assert got == _per_try_edges(n, m, seed=seed, power_law=power_law)


@pytest.mark.parametrize("n,m,ins,dels,seed", [
    (120, 300, 30, 10, 0),
    (64, 160, 12, 40, 5),
    (10, 12, 3, 20, 2),     # deletes outrun the graph: some are skipped
])
def test_graph_stream_matches_per_delete_sort(n, m, ins, dels, seed):
    edges = random_graph_edges(n, m, seed=seed)
    assert graph_stream(edges, n, ins, dels, seed=seed) == \
        _per_delete_sort_stream(edges, n, ins, dels, seed=seed)
