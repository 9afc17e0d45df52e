"""End-to-end correctness properties of the paper's algorithms (estimator
bias, Monte-Carlo scaling, round complexity, coupon accounting).

Engine-vs-power-iteration equivalence checks live in ONE place now — the
cross-engine gate in `test_engine_conformance.py` — not per-engine here."""
import jax
import numpy as np
import pytest

from repro.core import (exact_pagerank, improved_pagerank, l1_error,
                        normalized, power_iteration, simple_pagerank,
                        walks_per_node_for)

EPS = 0.2


def test_power_iteration_matches_eigenvector(small_graphs):
    for name, g in small_graphs.items():
        pi, err, iters = power_iteration(g, EPS)
        pi_exact = exact_pagerank(g, EPS)
        assert l1_error(pi, pi_exact) < 1e-4, name
        assert iters < 200


def _dense_pagerank(g, eps):
    """PageRank by one dense float64 linear solve of
    pi = eps/n + (1 - eps) (Q^T pi + dangling mass / n), Q the
    row-stochastic out-edge matrix: the fixed point power iteration
    converges to, found without iterating."""
    n = g.n
    row_ptr, col = np.asarray(g.row_ptr), np.asarray(g.col_idx)
    deg = np.diff(row_ptr)
    A = np.zeros((n, n))               # A[u, v] = P(step v -> u)
    for v in range(n):
        if deg[v]:
            np.add.at(A[:, v], col[row_ptr[v]:row_ptr[v + 1]], 1.0 / deg[v])
        else:
            A[:, v] = 1.0 / n
    return np.linalg.solve(np.eye(n) - (1 - eps) * A, np.full(n, eps / n))


@pytest.mark.parametrize("name", ["ring", "grid", "er", "ba", "ba_hub",
                                  "dweb"])
def test_power_iteration_matches_dense_solve(small_graphs, name):
    """The segment-sum push converges to the dense solve's fixed point,
    dangling vertices (the directed web graph has them) included."""
    g = small_graphs[name]
    pi, err, _ = power_iteration(g, EPS, tol=1e-7)
    want = _dense_pagerank(g, EPS)
    assert err <= 1e-7
    np.testing.assert_allclose(np.asarray(pi, np.float64), want,
                               rtol=1e-5, atol=1e-8)


def test_simple_pagerank_unbiased_total_mass(small_graphs):
    """E[sum zeta] = nK/eps; empirical total within 5%."""
    g = small_graphs["ring"]
    K = 200
    res = simple_pagerank(g, EPS, walks_per_node=K, key=jax.random.PRNGKey(5))
    expected = g.n * K / EPS
    assert abs(int(res.zeta.sum()) - expected) / expected < 0.05


def test_error_decreases_with_K(small_graphs):
    g = small_graphs["ba"]
    pi_ref, _, _ = power_iteration(g, EPS)
    errs = []
    for K in (20, 80, 320):
        res = simple_pagerank(g, EPS, walks_per_node=K,
                              key=jax.random.PRNGKey(7))
        errs.append(l1_error(normalized(res.pi), pi_ref))
    assert errs[2] < errs[0], errs  # Monte Carlo error shrinks ~ 1/sqrt(K)


def test_improved_pagerank_coupon_accounting(small_graphs):
    g = small_graphs["er"]
    res = improved_pagerank(g, EPS, walks_per_node=150,
                            key=jax.random.PRNGKey(11))
    assert res.coupons_used <= res.coupons_created
    assert res.exhausted_walks == 0  # auto-eta sized generously


def test_improved_faster_than_simple_in_congest_rounds(small_graphs):
    """Theorem 2 vs Theorem 1: stitched walks need fewer CONGEST rounds."""
    g = small_graphs["er"]
    simple = simple_pagerank(g, EPS, walks_per_node=60,
                             key=jax.random.PRNGKey(13), traced=True)
    improved = improved_pagerank(g, EPS, walks_per_node=60,
                                 key=jax.random.PRNGKey(13))
    assert improved.report.congest_rounds < simple.report.congest_rounds


def test_default_K_accuracy(small_graphs):
    """K = c log n (Sec 3.2) gives whp-accurate PageRank (Avrachenkov)."""
    g = small_graphs["grid"]
    K = walks_per_node_for(g.n, EPS)
    pi_ref, _, _ = power_iteration(g, EPS)
    res = simple_pagerank(g, EPS, walks_per_node=K, key=jax.random.PRNGKey(19))
    assert l1_error(normalized(res.pi), pi_ref) < 0.10
