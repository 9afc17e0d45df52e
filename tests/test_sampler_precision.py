"""Regression tests for the aggregate sampler's integer exactness.

The bug class (same as the PR-6 estimator fix, one layer down): the old
per-round draws ran `jax.random.binomial(k, counts.astype(float32), p)`.
float32 is integer-exact only up to 2**24, so a hub row whose aggregate
coupon count passed ~16.7M silently truncated — coupons created or
destroyed before the draw even happened. The shared sampler
(`core/binomial_tree`) keeps counts in int32 end to end: the
Binomial endpoints p == 0 and p == 1 are computed in integer arithmetic
and every split hands its halves r and c - r of an integer count c, so
conservation (T.sum() == counts) holds bit-exactly at ANY count
magnitude. Only the *marginal means* of the normal branch run through
float32 (a ~1e-7 relative statistical error, never a leak).

The split over a row's out-edges is a binomial tree over dyadic slot
intervals; the tests below also check that it draws the multinomial it
stands for, that a row's draws do not depend on the width it is padded
to, and that the degree-bucketed sampler (`aggregate_sampler`) draws
what the tree draws over the whole row vector at once, in a bucket of
any capacity.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregate_sampler import BucketLayout, sample_buckets
from repro.core.binomial_tree import (_BINV_MEAN_MAX, key_words,
                                      sample_rows_math)

KW = (np.uint32(0x12345678), np.uint32(0x9ABCDEF0))


@functools.partial(jax.jit, static_argnames=("eps", "width"))
def multinomial_rows_ref(counts, deg, rid, key_words, *, eps: float,
                         width: int):
    """The oracle: T [R, width+1] int32, column 0 the terminations and
    1+j the count on out-edge slot j, from the tree's math over the whole
    row vector at once (one block, no buckets)."""
    return sample_rows_math(counts, deg, rid, key_words[0], key_words[1],
                            eps=eps, width=width)


def _sample(counts, deg, *, eps=0.2, width=None):
    counts = jnp.asarray(counts, jnp.int32)
    deg = jnp.asarray(deg, jnp.int32)
    width = width or max(int(deg.max()), 1)
    rid = jnp.arange(counts.shape[0], dtype=jnp.int32)
    return sample_rows_math(counts, deg, rid, KW[0], KW[1],
                            eps=float(eps), width=width)


def test_float32_would_truncate_but_sampler_conserves():
    # the motivating rounding: 2**24 and 2**24 + 1 collide in float32 —
    # the old astype(f32) draw path could not tell these rows apart
    assert np.float32(2 ** 24) == np.float32(2 ** 24 + 1)
    counts = [2 ** 24, 2 ** 24 + 1, 2 ** 30, 2 ** 31 - 1]
    T = np.asarray(_sample(counts, [3, 3, 5, 2], width=8))
    # bit-exact conservation per row, far beyond float32 integer range
    np.testing.assert_array_equal(T.sum(axis=1), np.asarray(counts))
    # and the two f32-colliding rows stay distinct in total
    assert T[1].sum() - T[0].sum() == 1


def test_endpoint_probabilities_are_integer_exact():
    big = 2 ** 26 + 13
    # eps = 1: every coupon terminates, none leak to edges
    T1 = np.asarray(_sample([big], [4], eps=1.0, width=4))
    assert T1[0, 0] == big and T1[0, 1:].sum() == 0
    # deg = 1: the single out-edge draws p == 1 -> exactly the survivors
    T2 = np.asarray(_sample([big], [1], eps=0.25, width=1))
    assert T2[0, 0] + T2[0, 1] == big


def test_dangling_rows_terminate_whole():
    big = 2 ** 28 + 5
    T = np.asarray(_sample([big, 7, 0], [0, 0, 0], width=3))
    np.testing.assert_array_equal(T[:, 0], [big, 7, 0])
    assert T[:, 1:].sum() == 0


def test_ref_kernel_conserves_across_magnitudes():
    rng = np.random.default_rng(0)
    counts = np.concatenate([
        rng.integers(0, 2000, size=64),
        np.array([2 ** 24, 2 ** 24 + 1, 2 ** 27 + 3, 2 ** 30])],
    ).astype(np.int32)
    deg = rng.integers(0, 9, size=counts.shape[0]).astype(np.int32)
    rid = np.arange(counts.shape[0], dtype=np.int32)
    T = np.asarray(multinomial_rows_ref(
        jnp.asarray(counts), jnp.asarray(deg), jnp.asarray(rid),
        jnp.asarray(np.stack(KW)), eps=0.2, width=8))
    np.testing.assert_array_equal(T.sum(axis=1), counts)
    # nothing lands beyond a row's degree
    for j in range(8):
        assert np.all(T[deg <= j, 1 + j] == 0)


DRAW_KEYS = 400
MAX_Z = 6.0     # standard errors allowed for every mean and variance


def _draws_over_keys(n, deg, width):
    """[DRAW_KEYS, width] slot counts of one row (count n, degree deg,
    eps = 0 so all n survive) under DRAW_KEYS independent round keys."""
    kws = jnp.asarray(np.random.default_rng(deg).integers(
        0, 2 ** 32, size=(DRAW_KEYS, 2), dtype=np.uint32))
    one = lambda x: jnp.asarray([x], jnp.int32)
    T = jax.vmap(lambda kw: multinomial_rows_ref(
        one(n), one(deg), one(7), kw, eps=0.0, width=width))(kws)
    return np.asarray(T)[:, 0, 1:].astype(np.float64)


@pytest.mark.parametrize("n,deg,width,regime", [
    (20, 3, 4, "binv"), (5000, 3, 4, "normal"),
    (30, 5, 8, "binv"), (5000, 5, 8, "normal"),
    (500, 100, 128, "binv"), (100_000, 100, 128, "normal"),
    (20_000, 15_752, 16_384, "binv")])
def test_tree_split_is_uniform_multinomial(n, deg, width, regime):
    """Per slot, and per block of adjacent slots, the counts have the
    mean and variance of Binomial(n, block / deg): the marginals of a
    multinomial uniform over the row's deg live slots. `binv` cases put
    the last level's splits (mean n / deg) in the inverse-CDF regime of
    `binomial_counter`, `normal` cases every split in its normal one."""
    assert (n / deg <= _BINV_MEAN_MAX) == (regime == "binv")
    T = _draws_over_keys(n, deg, width)
    np.testing.assert_array_equal(T.sum(axis=1), n)
    assert not T[:, deg:].any()
    for block in (b for b in (1, 8, 64, 1024) if b < deg):
        starts = np.arange(0, deg, block)
        sums = np.add.reduceat(T[:, :deg], starts, axis=1)
        p = np.minimum(block, deg - starts) / deg
        var = n * p * (1 - p)
        mu4 = var * (1 + 3 * (n - 2) * p * (1 - p))
        N = DRAW_KEYS
        z_mean = (sums.mean(axis=0) - n * p) / np.sqrt(var / N)
        z_var = (sums.var(axis=0, ddof=1) - var) / np.sqrt(
            (mu4 - var ** 2 * (N - 3) / (N - 1)) / N)
        assert np.abs(z_mean).max() < MAX_Z, (block, z_mean)
        assert np.abs(z_var).max() < MAX_Z, (block, z_var)


@pytest.mark.parametrize("deg", [1, 3, 5, 33])
def test_draws_do_not_depend_on_the_width(deg):
    """A row's termination and slot counts are bit-identical whether its
    slots are padded to its own power of two, to 64, or to 100."""
    counts = jnp.asarray([0, 1, 9, 4000, 2 ** 24 + 3], jnp.int32)
    degs = jnp.full(counts.shape, deg, jnp.int32)
    rid = jnp.arange(counts.shape[0], dtype=jnp.int32) * 5 + 3
    own = 1 << max(deg - 1, 0).bit_length()
    Ts = [np.asarray(multinomial_rows_ref(counts, degs, rid,
                                          jnp.asarray(np.stack(KW)),
                                          eps=0.2, width=w))
          for w in (own, 64, 100)]
    for T in Ts[1:]:
        np.testing.assert_array_equal(T[:, :deg + 1], Ts[0][:, :deg + 1])
        assert not T[:, deg + 1:].any()
    np.testing.assert_array_equal(Ts[0].sum(axis=1), np.asarray(counts))


def _one_bucket(counts, deg, rid, kw, perm, *, cap, width, eps):
    """`sample_buckets` over one bucket of `cap` rows: its T block."""
    layout = BucketLayout(widths=(width,), caps=(cap,),
                          n_rows=counts.shape[0])
    run = jax.jit(functools.partial(sample_buckets, layout=layout, eps=eps))
    samples, occ, residual = run(counts, deg, rid, kw,
                                 jnp.asarray(perm, jnp.int32))
    assert int(residual) == 0
    ((rows, T),) = samples
    np.testing.assert_array_equal(np.asarray(rows), perm)
    return np.asarray(T)


@pytest.mark.parametrize("R,width,eps", [(64, 4, 0.2), (1000, 8, 0.1),
                                         (4096, 16, 0.5), (257, 1, 0.3),
                                         (1, 32, 0.2)])
def test_sample_buckets_matches_ref(R, width, eps, key):
    """The bucketed sampler on one bucket draws what the oracle draws
    over the same rows (counter RNG: the same draws in any grouping)."""
    k1, k2 = jax.random.split(key)
    counts = jax.random.randint(k1, (R,), 0, 5000)
    deg = jax.random.randint(k2, (R,), 0, width + 1)
    rid = jnp.arange(R, dtype=jnp.int32) * 3 + 11
    kw = jnp.asarray(np.array([0xDEADBEEF, 0x12345678], np.uint32))
    got = _one_bucket(counts, deg, rid, kw, np.arange(R), cap=R,
                      width=width, eps=eps)
    want = multinomial_rows_ref(counts, deg, rid, kw, eps=eps, width=width)
    np.testing.assert_array_equal(got, np.asarray(want))
    # conservation: rows whose degree fits the width leak nothing
    np.testing.assert_array_equal(got.sum(axis=1), np.asarray(counts))


@pytest.mark.parametrize("cap", [256, 1024])
def test_draws_do_not_depend_on_bucket_capacity(cap, key):
    """A row draws the same counts whichever bucket holds it and however
    many rows (and -1 padding slots) that bucket has."""
    R, width = 3000, 8
    counts = jax.random.randint(key, (R,), 0, 300)
    deg = jnp.full((R,), 5, jnp.int32)
    rid = jnp.arange(R, dtype=jnp.int32)
    kw = jnp.asarray(np.array([1, 2], np.uint32))
    want = np.asarray(multinomial_rows_ref(counts, deg, rid, kw, eps=0.2,
                                           width=width))
    rows = np.random.default_rng(cap).choice(R, cap // 2, replace=False)
    perm = np.full(cap, -1, np.int32)
    perm[::2] = rows                        # every other slot is padding
    got = _one_bucket(counts, deg, rid, kw, perm, cap=cap, width=width,
                      eps=0.2)
    np.testing.assert_array_equal(got[::2], want[rows])
    assert not got[1::2].any()
