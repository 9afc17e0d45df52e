"""Visit-count histogram Pallas kernel (TPU one-hot reduction).

The PageRank engines increment per-vertex visit counters with a histogram of
walk arrival positions every super-step. A data-dependent scatter is hostile
to the TPU's vector/matrix units, so the TPU-native formulation is a blocked
one-hot reduction:

    counts[v] = sum_w 1[ids_w == v]

Grid: (vertex_blocks, id_blocks); for a fixed vertex block the id blocks
iterate minormost and accumulate into the same VMEM output tile, so each
output tile is written once. ids == -1 (dead/masked walks) never match and
are naturally dropped. Both 1-D blocks are multiples of the 1024-element
tile XLA gives a 1-D int32 array on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANE_TILE, cdiv, round_up


DEFAULT_BLOCK_IDS = 2048
DEFAULT_BLOCK_N = 1024


def _hist_kernel(ids_ref, out_ref, *, block_n: int):
    ni = pl.program_id(0)
    wi = pl.program_id(1)
    ids = ids_ref[...]                      # [block_ids] int32
    base = ni * block_n
    local = ids - base                      # [-inf..) ; matches only in-range
    iota = jax.lax.broadcasted_iota(jnp.int32, (ids.shape[0], block_n), 1)
    onehot = (local[:, None] == iota).astype(jnp.int32)
    partial = jnp.sum(onehot, axis=0)       # [block_n]

    @pl.when(wi == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(wi != 0)
    def _acc():
        out_ref[...] = out_ref[...] + partial


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "block_ids", "block_n",
                                    "interpret"))
def histogram_pallas(ids: jnp.ndarray, num_segments: int, *,
                     block_ids: int = DEFAULT_BLOCK_IDS,
                     block_n: int = DEFAULT_BLOCK_N,
                     interpret: bool = True) -> jnp.ndarray:
    """counts[v] = |{w : ids[w] == v}| for v in [0, num_segments).

    ids entries outside [0, num_segments) are ignored (use -1 to mask).
    """
    W = ids.shape[0]
    block_ids = min(block_ids, round_up(max(W, 1), LANE_TILE))
    n_pad = cdiv(num_segments, block_n) * block_n
    w_pad = cdiv(max(W, 1), block_ids) * block_ids
    ids_p = jnp.full((w_pad,), -1, jnp.int32).at[:W].set(ids.astype(jnp.int32))
    grid = (n_pad // block_n, w_pad // block_ids)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, block_n=block_n),
        grid=grid,
        in_specs=[pl.BlockSpec((block_ids,), lambda ni, wi: (wi,))],
        out_specs=pl.BlockSpec((block_n,), lambda ni, wi: (ni,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        interpret=interpret,
    )(ids_p)
    return out[:num_segments]
