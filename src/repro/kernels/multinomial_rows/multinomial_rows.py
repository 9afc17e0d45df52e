"""Fused aggregate-multinomial Pallas kernel.

One degree bucket per call: every row draws its Binomial(eps) termination
and splits the survivors over `width` out-edge slots with the
conditional-binomial chain, fused in VMEM. The engines call it once per
power-of-two degree bucket (see `core/aggregate_sampler.py`), so the chain
scans the bucket width — at most 2x the row's degree — instead of the
global max degree.

Rows are independent by construction (counter-based RNG keyed on the
caller's row id, see `_math`), so the grid streams row blocks with no
cross-block state; the only whole-mapped input is the 2-word PRNG key.
The kernel writes its block slot-major ([width+1, rows]: one chain slot
per sublane row, rows on lanes) because Mosaic stores a dynamic slot
index only along that axis; the wrapper transposes back to row-major.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANE_TILE, cdiv, round_up
from repro.kernels.multinomial_rows._math import chain_slot, termination

DEFAULT_BLOCK_R = 2048


def _mn_kernel(c_ref, deg_ref, rid_ref, kw_ref, out_ref, *, eps: float,
               width: int):
    kw = kw_ref[...]
    k0, k1 = kw[0], kw[1]
    deg, rid = deg_ref[...], rid_ref[...]
    term, rem = termination(c_ref[...], deg, rid, k0, k1, eps=eps)
    out_ref[pl.ds(0, 1), :] = term[None, :]

    def body(j, rem):
        rem, t = chain_slot(rem, j, deg, rid, k0, k1)
        out_ref[pl.ds(j + 1, 1), :] = t[None, :]
        return rem

    jax.lax.fori_loop(0, width, body, rem)


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
    out = pl.pallas_call(
        functools.partial(_mn_kernel, eps=eps, width=width),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r,), lambda i: (i,)),   # counts
            pl.BlockSpec((block_r,), lambda i: (i,)),   # deg
            pl.BlockSpec((block_r,), lambda i: (i,)),   # rid
            pl.BlockSpec((2,), lambda i: (0,)),         # key words (whole)
        ],
        out_specs=pl.BlockSpec((width + 1, block_r), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((width + 1, r_pad), jnp.int32),
        interpret=interpret,
    )(pad(counts), pad(deg), pad(rid), key_words.astype(jnp.uint32))
    return out[:, :R].T
