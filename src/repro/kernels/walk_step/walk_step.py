"""Fused PageRank walk-step Pallas kernel.

One engine super-step per walk block, fused in VMEM:
    terminate?  u_term < eps  (or dangling)          — VPU compare
    edge pick   j = floor(u_edge * deg[pos])          — gather + VPU
    advance     dst = col[row_ptr[pos] + j]           — two gathers

The graph tables (row_ptr, col_idx, out_deg) are mapped whole into VMEM
(BlockSpec with a constant index_map); walk arrays stream through in blocks.
This is the right TPU shape for per-shard graphs up to a few tens of MB of
CSR — beyond that, the distributed engine shards vertices across chips
before the kernel ever sees them (see core/distributed.py).

Randomness enters as precomputed uniforms so the kernel is a deterministic
function (replay/restart stay bit-exact, and the ref oracle is trivially
comparable).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANE_TILE, cdiv, round_up


DEFAULT_BLOCK_W = 4096


def _walk_kernel(pos_ref, alive_ref, uterm_ref, uedge_ref,
                 row_ptr_ref, col_ref, deg_ref,
                 newpos_ref, newalive_ref, *, eps: float):
    pos = pos_ref[...]                       # [bw] int32
    alive = alive_ref[...] != 0
    u_term = uterm_ref[...]
    u_edge = uedge_ref[...]
    deg_tab = deg_ref[...]
    rp_tab = row_ptr_ref[...]
    col_tab = col_ref[...]

    safe_pos = jnp.clip(pos, 0, deg_tab.shape[0] - 1)
    deg = jnp.take(deg_tab, safe_pos)
    survive = alive & (u_term >= eps) & (deg > 0)
    j = jnp.minimum((u_edge * jnp.maximum(deg, 1).astype(u_edge.dtype))
                    .astype(jnp.int32),
                    jnp.maximum(deg - 1, 0))
    eid = jnp.clip(jnp.take(rp_tab, safe_pos) + j, 0, col_tab.shape[0] - 1)
    dst = jnp.take(col_tab, eid)
    newpos_ref[...] = jnp.where(survive, dst, pos)
    newalive_ref[...] = survive.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("eps", "block_w", "interpret"))
def walk_step_pallas(pos: jnp.ndarray, alive: jnp.ndarray,
                     u_term: jnp.ndarray, u_edge: jnp.ndarray,
                     row_ptr: jnp.ndarray, col_idx: jnp.ndarray,
                     out_deg: jnp.ndarray, *, eps: float,
                     block_w: int = DEFAULT_BLOCK_W,
                     interpret: bool = True):
    """Returns (new_pos [W] int32, new_alive [W] int32/bool-ish)."""
    W = pos.shape[0]
    block_w = min(block_w, round_up(max(W, 1), LANE_TILE))
    w_pad = cdiv(max(W, 1), block_w) * block_w
    pad = lambda x, fill: jnp.full((w_pad,), fill, x.dtype).at[:W].set(x)
    grid = (w_pad // block_w,)
    whole = lambda arr: pl.BlockSpec(arr.shape, lambda wi: (0,) * arr.ndim)
    new_pos, new_alive = pl.pallas_call(
        functools.partial(_walk_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_w,), lambda wi: (wi,)),  # pos
            pl.BlockSpec((block_w,), lambda wi: (wi,)),  # alive
            pl.BlockSpec((block_w,), lambda wi: (wi,)),  # u_term
            pl.BlockSpec((block_w,), lambda wi: (wi,)),  # u_edge
            whole(row_ptr), whole(col_idx), whole(out_deg),
        ],
        out_specs=(pl.BlockSpec((block_w,), lambda wi: (wi,)),
                   pl.BlockSpec((block_w,), lambda wi: (wi,))),
        out_shape=(jax.ShapeDtypeStruct((w_pad,), jnp.int32),
                   jax.ShapeDtypeStruct((w_pad,), jnp.int32)),
        interpret=interpret,
    )(pad(pos.astype(jnp.int32), 0), pad(alive.astype(jnp.int32), 0),
      pad(u_term, 1.0), pad(u_edge, 0.0), row_ptr, col_idx, out_deg)
    return new_pos[:W], new_alive[:W]
