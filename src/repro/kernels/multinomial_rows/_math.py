"""Shared sampling math for the fused aggregate-multinomial kernel.

Everything here is plain jnp on arrays, so the SAME functions run inside
the Pallas kernel body and in the pure-jnp oracle — `use_pallas` switches
only the execution path, never the draws, which keeps the engines
bit-identical across the flag (the repo-wide kernel contract, see
`tests/test_kernels.py::test_engine_pallas_bit_parity`).

RNG contract — counter-based, per row:
  u(row, t) = u01(fmix32(fmix32((rid * C1) ^ k0) + ((t * C2) ^ k1)))
where `rid` is the caller-supplied globally-unique row id, `t` the draw
index within the row, and (k0, k1) the two uint32 words of a per-round
PRNG key. t = 0 is the eps-termination draw; the split of the slot
interval [i * 2^k, (i + 1) * 2^k) draws t = 32 * i + k (k >= 1), a
function of the interval alone. Draws are pure functions of
(k0, k1, rid, t): no split-chain threading, so rows sample independently
in any blocking/order — exactly what a row-blocked kernel needs — and
replay/checkpoint-recovery stays bit-exact.

Survivors split over a row's out-edge slots by a binomial tree: the
width is padded to a power of two and every dyadic interval sends
Binomial(c, R / (L + R)) of its count c to its right half, with L and R
the live slots (below the row's degree) in its left and right halves.
That is an exact multinomial, uniform over the live slots, in
ceil(log2 width) sequential levels. An interval with no live slot in its
right half draws p == 0 exactly, so nothing lands beyond a row's degree,
and the draws of a row do not depend on the width it is padded to.

Binomial(n, p) from ONE uniform (hybrid, complement-flipped so pp <= 1/2):
  * n*pp <= 10 — BINV inverse-CDF walk (exact CDF inversion, truncated at
    `_BINV_ITERS`; the neglected tail mass is < 1e-15 at mean 10);
  * n*pp  > 10 — normal approximation with the Acklam inverse-normal.
The endpoints are EXACT in integer arithmetic: p == 0 returns 0 and
p == 1 returns n itself (never n routed through float32). Each split
hands its right half r in [0, c] and its left half c - r, so the tree
conserves mass bit-exactly at any count magnitude, fixing the former
`jax.random.binomial(k, c.astype(f32))` truncation for counts above
2**24 (see tests/test_sampler_precision.py).
The normal branch evaluates means in float32, so marginals for counts
beyond 2**24 carry a ~1e-7 relative mean error — statistical, never a
conservation leak.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_BINV_ITERS = 48
_BINV_MEAN_MAX = 10.0


def _u32(x):
    if isinstance(x, int):
        return jnp.uint32(np.uint32(x))
    return jnp.asarray(x).astype(jnp.uint32)


def _fmix32(x):
    """murmur3 finalizer: full-avalanche 32-bit hash."""
    x = x ^ (x >> _u32(16))
    x = x * _u32(0x85EBCA6B)
    x = x ^ (x >> _u32(13))
    x = x * _u32(0xC2B2AE35)
    x = x ^ (x >> _u32(16))
    return x


def counter_u01(rid, t, k0, k1):
    """Uniform in (0, 1), a pure function of (k0, k1, rid, t)."""
    h = _fmix32((_u32(rid) * _u32(0x9E3779B1)) ^ _u32(k0))
    h = _fmix32(h + ((_u32(t) * _u32(0x85EBCA77)) ^ _u32(k1)))
    # 24 mantissa bits, offset half a ulp: strictly inside (0, 1)
    # via int32 (exact below 2**24): Mosaic has no uint32 -> float32 cast
    return (((h >> _u32(8)).astype(jnp.int32).astype(jnp.float32) + 0.5)
            * jnp.float32(2.0 ** -24))


def _ndtri(u):
    """Acklam's rational approximation to the inverse normal CDF."""
    u = jnp.clip(u, 1e-7, 1.0 - 1e-7)
    a = (-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00)
    plow = 0.02425
    # central region
    q = u - 0.5
    r = q * q
    num = ((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r + a[5]
    den = ((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r + b[4]) * r + 1.0
    x_mid = q * num / den
    # lower tail (upper tail by symmetry)
    ul = jnp.minimum(u, 1.0 - u)
    ql = jnp.sqrt(-2.0 * jnp.log(ul))
    numt = ((((c[0] * ql + c[1]) * ql + c[2]) * ql + c[3]) * ql + c[4]) * ql \
        + c[5]
    dent = (((d[0] * ql + d[1]) * ql + d[2]) * ql + d[3]) * ql + 1.0
    x_tail = numt / dent
    x_tail = jnp.where(u < 0.5, x_tail, -x_tail)
    tail = (u < plow) | (u > 1.0 - plow)
    return jnp.where(tail, x_tail, x_mid).astype(jnp.float32)


def binomial_counter(n, p, u):
    """X ~ Binomial(n, p) from one uniform. n int32 >= 0, p float32.

    Endpoint-exact (p==0 -> 0, p==1 -> n, in int arithmetic); hybrid
    BINV / normal elsewhere — see the module docstring.
    """
    n = n.astype(jnp.int32)
    n_f = n.astype(jnp.float32)
    p = jnp.asarray(p, jnp.float32)
    u = jnp.asarray(u, jnp.float32)
    flip = p > 0.5
    pp = jnp.where(flip, 1.0 - p, p)
    mean = n_f * pp

    # --- BINV: count how many prefix-CDF values u clears ---
    q = pp / jnp.maximum(1.0 - pp, 0.5)       # pp <= 0.5 so 1-pp >= 0.5
    pdf0 = jnp.exp(n_f * jnp.log1p(-pp))
    x0 = jnp.zeros_like(n)

    def body(k, carry):
        pdf, cdf, x = carry
        kf = k.astype(jnp.float32)
        x = x + (u > cdf).astype(jnp.int32)
        pdf = pdf * ((n_f - kf + 1.0) / kf) * q
        cdf = cdf + pdf
        return pdf, cdf, x

    # unrolled: one fusion per call, not _BINV_ITERS passes over the carries
    _, _, x_small = jax.lax.fori_loop(1, _BINV_ITERS + 1, body,
                                      (pdf0, pdf0, x0), unroll=True)

    # --- normal approximation with continuity correction ---
    sd = jnp.sqrt(jnp.maximum(mean * (1.0 - pp), 1e-12))
    x_norm = jnp.floor(mean + sd * _ndtri(u) + 0.5).astype(jnp.int32)

    x = jnp.where(mean <= _BINV_MEAN_MAX, x_small, x_norm)
    x = jnp.clip(x, 0, n)
    return jnp.where(flip, n - x, x)


def termination(counts, deg, rid, k0, k1, *, eps: float):
    """Column 0 of the fused sampler: (term, rem) with `term` the
    Binomial(counts, eps) termination count (a dangling row — deg == 0 —
    terminates whole) and `rem = counts - term` the survivors."""
    counts = counts.astype(jnp.int32)
    u_t = counter_u01(rid, 0, k0, k1)
    term = jnp.where(deg > 0,
                     binomial_counter(counts, jnp.float32(eps), u_t),
                     counts)
    return term, counts - term


def tree_depth(width: int) -> int:
    """Split levels of a row padded to `width` slots: ceil(log2 width)."""
    return max(int(width) - 1, 0).bit_length()


def split_level(c, deg, rid, node, k, k0, k1):
    """Split the count `c` of slot interval [node * 2^k, (node + 1) * 2^k)
    between its halves, uniformly over the live slots (those below
    `deg`): (left, right) counts, left + right == c exactly."""
    s = 1 << (k - 1)
    lo = node * (2 * s)
    live_l = jnp.clip(deg - lo, 0, s)
    live_r = jnp.clip(deg - lo - s, 0, s)
    p = (live_r.astype(jnp.float32)
         / jnp.maximum(live_l + live_r, 1).astype(jnp.float32))
    r = binomial_counter(c, p, counter_u01(rid, 32 * node + k, k0, k1))
    return c - r, r


def split_tree(rems, degs, rids, depths, k0, k1):
    """Split each group's survivors over its 2^depth slots by the binomial
    tree, running each level ONCE for every group deep enough to have it
    (level k splits the intervals of size 2^k), so the sequential depth
    is the deepest group's, not the sum over groups.

    rems/degs/rids: per group [R_g] int32. Returns per group the slot
    counts [2^depth, R_g] int32, slot-major.
    """
    nodes = [r[None, :] for r in rems]
    flat = lambda xs: jnp.concatenate([x.reshape(-1) for x in xs])
    grid = lambda x, g: jnp.broadcast_to(x, nodes[g].shape)
    for k in range(max(depths, default=0), 0, -1):
        live = [g for g, d in enumerate(depths) if d >= k]
        index = lambda g: jnp.arange(nodes[g].shape[0], dtype=jnp.int32)
        left, right = split_level(
            flat([nodes[g] for g in live]),
            flat([grid(degs[g], g) for g in live]),
            flat([grid(rids[g], g) for g in live]),
            flat([grid(index(g)[:, None], g) for g in live]), k, k0, k1)
        off = 0
        for g in live:
            n, rows = nodes[g].shape
            halves = [x[off:off + n * rows].reshape(n, rows)
                      for x in (left, right)]
            nodes[g] = jnp.stack(halves, axis=1).reshape(2 * n, rows)
            off += n * rows
    return nodes


def sample_rows_math(counts, deg, rid, k0, k1, *, eps: float, width: int):
    """Fused termination + binomial-tree split for a block of rows.

    counts/deg/rid: [R] int32. Returns T [R, width+1] int32 where column 0
    is the termination count and column 1+j the count sent down out-edge
    slot j. Rows with deg <= width conserve mass exactly: T.sum(1) ==
    counts, and nothing lands in a slot at or beyond the row's degree.
    """
    term, rem = termination(counts, deg, rid, k0, k1, eps=eps)
    (slots,) = split_tree([rem], [deg], [rid], [tree_depth(width)], k0, k1)
    return jnp.concatenate([term[None, :], slots[:width]], axis=0).T


def key_words(key):
    """(k0, k1) uint32 words of a legacy PRNGKey array."""
    kw = jnp.asarray(key).astype(jnp.uint32).reshape(-1)
    return kw[:2]
