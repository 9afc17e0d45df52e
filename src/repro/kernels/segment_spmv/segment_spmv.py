"""Segment-sum SpMV Pallas kernel — the power-iteration push.

Power iteration (the baseline the paper compares against) is dominated by
the CSR push  y[dst_e] += val_e. On TPU the scatter becomes a blocked
one-hot *matmul* so the reduction runs on the MXU:

    partial[j] = sum_e val_e * 1[dst_e == base + j]
               = val_block  @ onehot(dst_block)        # [1,bm] @ [bm,bn]

Grid: (vertex_blocks, edge_blocks) with edge blocks minormost, accumulating
into the resident output tile. Edge values/ids are padded with dst = -1
(never matches). fp32 accumulation regardless of input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANE_TILE, cdiv, round_up


DEFAULT_BLOCK_E = 2048
DEFAULT_BLOCK_N = 1024


def _spmv_kernel(val_ref, dst_ref, out_ref, *, block_n: int):
    ni = pl.program_id(0)
    ei = pl.program_id(1)
    val = val_ref[...].astype(jnp.float32)      # [be]
    dst = dst_ref[...]                          # [be]
    base = ni * block_n
    local = dst - base
    iota = jax.lax.broadcasted_iota(jnp.int32, (dst.shape[0], block_n), 1)
    onehot = (local[:, None] == iota).astype(jnp.float32)   # [be, bn]
    partial = jnp.dot(val[None, :], onehot,
                      preferred_element_type=jnp.float32)[0]  # MXU

    @pl.when(ei == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(ei != 0)
    def _acc():
        out_ref[...] = out_ref[...] + partial


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "block_e", "block_n",
                                    "interpret"))
def segment_spmv_pallas(values: jnp.ndarray, dst: jnp.ndarray,
                        num_segments: int, *,
                        block_e: int = DEFAULT_BLOCK_E,
                        block_n: int = DEFAULT_BLOCK_N,
                        interpret: bool = True) -> jnp.ndarray:
    """y[v] = sum over edges e with dst[e]==v of values[e]  (fp32)."""
    E = values.shape[0]
    block_e = min(block_e, round_up(max(E, 1), LANE_TILE))
    n_pad = cdiv(num_segments, block_n) * block_n
    e_pad = cdiv(max(E, 1), block_e) * block_e
    val_p = jnp.zeros((e_pad,), values.dtype).at[:E].set(values)
    dst_p = jnp.full((e_pad,), -1, jnp.int32).at[:E].set(dst.astype(jnp.int32))
    grid = (n_pad // block_n, e_pad // block_e)
    out = pl.pallas_call(
        functools.partial(_spmv_kernel, block_n=block_n),
        grid=grid,
        in_specs=[pl.BlockSpec((block_e,), lambda ni, ei: (ei,)),
                  pl.BlockSpec((block_e,), lambda ni, ei: (ei,))],
        out_specs=pl.BlockSpec((block_n,), lambda ni, ei: (ni,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.float32),
        interpret=interpret,
    )(val_p, dst_p)
    return out[:num_segments]
