"""The benchmark's Graph500 generator (bench/data/graph500.py)."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.data.graph500 import (EDGEFACTOR, INITIATOR, graph500,  # noqa
                                 kronecker_edges)

SCALE = 10


@pytest.fixture(scope="module")
def graph():
    return graph500(SCALE, 7)


def test_same_seed_same_graph(graph):
    again = graph500(SCALE, 7)
    for a, b in zip(graph, again):
        np.testing.assert_array_equal(a, b)
    other = graph500(SCALE, 8)
    assert not np.array_equal(graph[1], other[1])


def test_csr_is_symmetric_without_loops_or_duplicates(graph):
    row_ptr, col_idx, out_deg = graph
    n = len(out_deg)
    assert row_ptr[0] == 0 and row_ptr[-1] == len(col_idx)
    np.testing.assert_array_equal(np.diff(row_ptr), out_deg)
    src = np.repeat(np.arange(n), out_deg)
    keys = src * n + col_idx
    assert np.all(np.diff(keys) > 0), "rows sorted, no duplicate edge"
    assert not np.any(src == col_idx), "no self-loop"
    rev = np.sort(col_idx.astype(np.int64) * n + src)
    np.testing.assert_array_equal(rev, keys)


def test_edge_count_near_edgefactor(graph):
    """2 * 16 * 2^S directed entries before duplicates and self-loops are
    dropped; at this scale the skew merges about a quarter of them."""
    m = len(graph[1])
    full = 2 * EDGEFACTOR << SCALE
    assert 0.6 * full < m < full
    assert (graph[2] > 0).all(), "Graphalytics drops isolated vertices"
    assert len(graph[2]) < 1 << SCALE


def test_same_graph_as_a_plain_build():
    """The generator's draws, cleaned up one edge at a time: relabel,
    drop self-loops, make undirected, drop duplicates, keep the vertices
    that have an edge and number them in order."""
    scale, seed = 8, 3
    rng = np.random.default_rng(seed)
    src, dst = kronecker_edges(scale, EDGEFACTOR, INITIATOR, rng)
    perm = rng.permutation(1 << scale)
    edges = set()
    for s, d in zip(perm[src].tolist(), perm[dst].tolist()):
        if s != d:
            edges.update([(s, d), (d, s)])
    ids = {v: i for i, v in enumerate(sorted({s for s, _ in edges}))}
    rows = {}
    for s, d in edges:
        rows.setdefault(ids[s], []).append(ids[d])
    row_ptr, col_idx, out_deg = graph500(scale, seed)
    assert len(out_deg) == len(ids)
    for v in range(len(ids)):
        np.testing.assert_array_equal(col_idx[row_ptr[v]:row_ptr[v + 1]],
                                      sorted(rows[v]))


def test_quadrant_frequencies_follow_initiator():
    """One level: each draw lands in quadrant (i, j) with the initiator's
    probability A, B, C or 1 - A - B - C."""
    rng = np.random.default_rng(0)
    src, dst = kronecker_edges(1, 1 << 17, INITIATOR, rng)
    a, b, c = INITIATOR
    freq = np.bincount(2 * src + dst, minlength=4) / len(src)
    np.testing.assert_allclose(freq, [a, b, c, 1 - a - b - c], atol=0.005)
