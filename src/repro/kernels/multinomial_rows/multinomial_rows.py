"""Fused aggregate-multinomial Pallas kernel.

One degree bucket per call: every row draws its Binomial(eps) termination
and splits the survivors over `width` out-edge slots with the binomial
tree of `_math` (the same draws as the jnp oracle), fused in VMEM. The
engines call it once per power-of-two degree bucket (see
`core/aggregate_sampler.py`), so the tree spans the bucket width — at
most 2x the row's degree — instead of the global max degree.

Rows are independent by construction (counter-based RNG keyed on the
caller's row id, see `_math`), so the grid streams row blocks with no
cross-block state; the only whole-mapped input is the 2-word PRNG key.
The kernel writes its block slot-major ([2^depth + 1, rows]: one slot
per sublane row, rows on lanes) because Mosaic indexes a dynamic slot
only along that axis, and splits the tree in place: an interval's count
sits in the row of its first slot, and a split leaves the left half's
count there and writes the right half's in the row of the right half's
first slot. The wrapper drops the padding slots and transposes back to
row-major.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANE_TILE, cdiv, round_up
from repro.kernels.multinomial_rows._math import (split_level, termination,
                                                  tree_depth)

DEFAULT_BLOCK_R = 2048


def _mn_kernel(c_ref, deg_ref, rid_ref, kw_ref, out_ref, *, eps: float,
               depth: int):
    kw = kw_ref[...]
    k0, k1 = kw[0], kw[1]
    deg, rid = deg_ref[...], rid_ref[...]
    term, rem = termination(c_ref[...], deg, rid, k0, k1, eps=eps)
    out_ref[pl.ds(0, 1), :] = term[None, :]
    out_ref[pl.ds(1, 1), :] = rem[None, :]

    def body(h, carry):
        # h walks the internal nodes in heap order (root 1, children of h
        # at 2h and 2h + 1); the level holding h starts at `top`, and its
        # nodes split intervals of 2^k slots
        top, k = carry
        deeper = h == 2 * top
        top = jnp.where(deeper, h, top)
        k = jnp.where(deeper, k - 1, k)
        node = h - top
        lo = 1 + node * (1 << k)
        left, right = split_level(out_ref[pl.ds(lo, 1), :][0], deg, rid,
                                  node, k, k0, k1)
        out_ref[pl.ds(lo, 1), :] = left[None, :]
        out_ref[pl.ds(lo + (1 << (k - 1)), 1), :] = right[None, :]
        return top, k

    jax.lax.fori_loop(1, 1 << depth, body, (jnp.int32(1), jnp.int32(depth)))


@functools.partial(jax.jit,
                   static_argnames=("eps", "width", "block_r", "interpret"))
def multinomial_rows_pallas(counts, deg, rid, key_words, *, eps: float,
                            width: int, block_r: int = DEFAULT_BLOCK_R,
                            interpret: bool = True):
    """T [R, width+1] int32; column 0 = terminations, 1+j = out-edge j."""
    R = counts.shape[0]
    block_r = min(block_r, round_up(max(R, 1), LANE_TILE))
    r_pad = cdiv(max(R, 1), block_r) * block_r
    pad = lambda x: jnp.zeros((r_pad,), jnp.int32).at[:R].set(
        x.astype(jnp.int32))
    grid = (r_pad // block_r,)
    depth = tree_depth(width)
    slots = 1 << depth
    out = pl.pallas_call(
        functools.partial(_mn_kernel, eps=eps, depth=depth),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r,), lambda i: (i,)),   # counts
            pl.BlockSpec((block_r,), lambda i: (i,)),   # deg
            pl.BlockSpec((block_r,), lambda i: (i,)),   # rid
            pl.BlockSpec((2,), lambda i: (0,)),         # key words (whole)
        ],
        out_specs=pl.BlockSpec((slots + 1, block_r), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((slots + 1, r_pad), jnp.int32),
        interpret=interpret,
    )(pad(counts), pad(deg), pad(rid), key_words.astype(jnp.uint32))
    return out[:width + 1, :R].T
