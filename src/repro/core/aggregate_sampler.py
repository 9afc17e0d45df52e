"""Degree-bucketed aggregate multinomial sampler — the shared compute core
of every count-moving engine.

Problem: the split of an aggregate coupon count over a vertex's out-edges
used to span the GLOBAL max degree, so on power-law graphs one hub made
every low-degree vertex pay hub cost: per-round sampler FLOPs were
n * max_deg.

Fix: group rows by power-of-two degree buckets. Bucket b holds rows with
degree in (2^(b-1), 2^b] (bucket 0: degree 0 and 1) and splits over
width min(2^b, max_deg) <= 2 * degree slots, so the per-round FLOPs drop
to sum_v O(deg(v)) — per-node work proportional to local degree, the
property the paper's CONGEST model assumes. The grouping is a STATIC
permutation computed on the host at shard/build time and memoized (like
the engines' step makers).

The split is the binomial tree of `core/binomial_tree`, each level run
once for all buckets (`binomial_tree.split_tree`), so a round is
`BucketLayout.depth` = ceil(log2 max_deg) sequential levels.

Sharded engines run ONE traced program on every shard, so bucket
capacities must be shard-uniform: `build_layout_sharded` takes the max
row count per bucket over shards and pads each shard's permutation with
-1 sentinels (gathered as count 0 — they never sample, never ship).

`bucketed=False` (the pre-PR shape, kept for benchmarking and as the
degenerate fallback) is the SAME machinery with a single bucket of width
max_deg — one code path, two layouts, the same draws.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import List, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.binomial_tree import split_tree, termination, tree_depth


@dataclasses.dataclass(frozen=True)
class BucketLayout:
    """Static (hashable) shape of a bucketed row grouping.

    widths[b]: out-edge slots of bucket b (min(2^b, max_deg)).
    caps[b]:   row slots in bucket b (shard-uniform max; >= real rows).
    n_rows:    number of real rows the permutation indexes into.
    """

    widths: Tuple[int, ...]
    caps: Tuple[int, ...]
    n_rows: int

    @property
    def total_rows(self) -> int:
        return sum(self.caps)

    @property
    def total_edges(self) -> int:
        """Flat bucketed-adjacency length: sum of caps[b] * widths[b]."""
        return sum(c * w for c, w in zip(self.caps, self.widths))

    @property
    def row_starts(self) -> Tuple[int, ...]:
        out, s = [], 0
        for c in self.caps:
            out.append(s)
            s += c
        return tuple(out)

    @property
    def depth(self) -> int:
        """Sequential split levels a round: the deepest bucket's tree,
        ceil(log2 max width)."""
        return tree_depth(max(self.widths))

    def tile(self, copies: int) -> "BucketLayout":
        """Layout for `copies` stacked replicas of the same row set (the
        Phase-1 home-major (home, vertex) row matrix)."""
        return BucketLayout(widths=self.widths,
                            caps=tuple(c * copies for c in self.caps),
                            n_rows=self.n_rows * copies)


def bucket_of(deg: np.ndarray) -> np.ndarray:
    """Power-of-two bucket index per degree: 0 for deg <= 1, else
    ceil(log2(deg))."""
    d = np.maximum(np.asarray(deg, np.int64), 1)
    return np.ceil(np.log2(d)).astype(np.int64)


@lru_cache(maxsize=256)
def _layout_cached(deg_bytes: bytes, rows_per_shard: int, shards: int,
                   max_deg: int, bucketed: bool):
    deg = np.frombuffer(deg_bytes, dtype=np.int32).reshape(shards,
                                                           rows_per_shard)
    if not bucketed or max_deg <= 1:
        perm = np.tile(np.arange(rows_per_shard, dtype=np.int32),
                       (shards, 1))
        layout = BucketLayout(widths=(max(max_deg, 1),),
                              caps=(rows_per_shard,),
                              n_rows=rows_per_shard)
        return layout, perm
    n_b = int(np.ceil(np.log2(max_deg))) + 1
    widths = tuple(min(1 << b, max_deg) for b in range(n_b))
    b_of = bucket_of(deg)
    counts = np.stack([np.bincount(b, minlength=n_b) for b in b_of])
    caps = tuple(int(c) for c in counts.max(axis=0))
    starts = np.concatenate([[0], np.cumsum(caps)[:-1]])
    perm = np.full((shards, int(sum(caps))), -1, np.int32)
    for p in range(shards):
        # rows grouped by bucket, ascending row id within a bucket
        order = np.argsort(b_of[p], kind="stable")
        b_sorted = b_of[p][order]
        first = np.concatenate([[0], np.cumsum(counts[p])[:-1]])
        rank = np.arange(rows_per_shard) - first[b_sorted]
        perm[p, starts[b_sorted] + rank] = order
    layout = BucketLayout(widths=widths, caps=caps, n_rows=rows_per_shard)
    return layout, perm


def build_layout(deg: np.ndarray, max_deg: int, *,
                 bucketed: bool = True) -> Tuple[BucketLayout, np.ndarray]:
    """Single-shard layout: (layout, perm [total_rows] int32, -1 = pad)."""
    deg = np.ascontiguousarray(np.asarray(deg, np.int32))
    layout, perm = _layout_cached(deg.tobytes(), len(deg), 1, int(max_deg),
                                  bool(bucketed))
    return layout, perm[0]


def build_layout_sharded(deg: np.ndarray, max_deg: int, *,
                         bucketed: bool = True
                         ) -> Tuple[BucketLayout, np.ndarray]:
    """Shard-uniform layout from a [shards, n_loc] degree matrix:
    (layout with caps = max over shards, perm [shards, total_rows])."""
    deg = np.ascontiguousarray(np.asarray(deg, np.int32))
    shards, n_loc = deg.shape
    return _layout_cached(deg.tobytes(), n_loc, shards, int(max_deg),
                          bool(bucketed))


def bucketize_csr(row_ptr: np.ndarray, col_idx: np.ndarray,
                  deg: np.ndarray, perm: np.ndarray, layout: BucketLayout, *,
                  pad_dst: int = 0) -> np.ndarray:
    """Flat bucketed neighbor table [*, total_edges], built straight from
    the CSR: bucket b contributes a [widths[b], caps[b]] block (slot-major)
    whose column i holds the first widths[b] out-neighbours of row
    perm[i]. Slot-major matches `flatten_moves`: on TPU, flattening a
    [caps[b], widths[b]] block row-major makes the compiler relayout a
    narrow minor dimension, which takes minutes to compile at n = 2^22.

    `deg` is the [shards, rows] (or [rows]) degree matrix the layout was
    built from; row r of shard p is global vertex p * rows + r, and rows
    past the CSR's last vertex have degree 0. Slots past a row's degree,
    and every slot of a padding row (perm == -1), point at `pad_dst` —
    they only ever carry zero counts, because a row's degree is <= its
    bucket width (tests/test_property.py).
    """
    row_ptr = np.asarray(row_ptr, np.int64)
    n = len(row_ptr) - 1
    col = np.concatenate([np.asarray(col_idx, np.int32),
                          np.asarray([pad_dst], np.int32)])
    m = len(col) - 1
    perm2 = np.atleast_2d(perm)
    deg2 = np.asarray(deg).reshape(perm2.shape[0], -1)
    rows_per_shard = deg2.shape[1]
    base = (np.arange(perm2.shape[0]) * rows_per_shard)[:, None]
    flat = np.empty((perm2.shape[0], layout.total_edges), np.int32)
    s_rows, s_edges = 0, 0
    for cap, w in zip(layout.caps, layout.widths):
        rows = perm2[:, s_rows:s_rows + cap]
        ok_row = rows >= 0
        v = np.minimum(np.where(ok_row, rows + base, n), n)
        d = np.where(ok_row, np.take_along_axis(deg2, np.maximum(rows, 0),
                                                axis=1), 0)
        j = np.arange(w)
        idx = np.where(j < d[..., None], row_ptr[v][..., None] + j, m)
        flat[:, s_edges:s_edges + cap * w] = col[idx].transpose(
            0, 2, 1).reshape(len(perm2), cap * w)
        s_rows += cap
        s_edges += cap * w
    return flat.reshape(np.shape(perm)[:-1] + (layout.total_edges,))


def sample_buckets(counts, deg, rid, key_words, perm, layout: BucketLayout,
                   *, eps: float
                   ) -> Tuple[List[Tuple[jnp.ndarray, jnp.ndarray]],
                              jnp.ndarray, jnp.ndarray]:
    """Run the fused sampler over every bucket of `layout`.

    counts/deg/rid: [n_rows] int32 vectors in ORIGINAL row order;
    perm: [total_rows] int32 bucket-grouped row indices (-1 = padding).

    Returns (samples, occupancy, residual):
      samples   — per bucket (rows_b [caps[b]], T_b [caps[b], widths[b]+1])
                  with T_b column 0 the termination count;
      occupancy — [n_buckets] int32, rows with a nonzero count per bucket;
      residual  — scalar int32, sum over rows of (count - T.sum()): 0 by
                  construction (endpoint-exact splits), kept as a tripwire.
    """
    rows = jnp.asarray(perm)
    ok = rows >= 0
    safe = jnp.clip(rows, 0, counts.shape[0] - 1)
    c, d, r = (jnp.where(ok, x[safe], 0) for x in (counts, deg, rid))
    cut = lambda x: [x[s:s + cap] for s, cap in zip(layout.row_starts,
                                                     layout.caps)]
    k0, k1 = key_words[0], key_words[1]
    term, rem = termination(c, d, r, k0, k1, eps=eps)
    slots = split_tree(cut(rem), cut(d), cut(r),
                       [tree_depth(w) for w in layout.widths], k0, k1)
    Ts = [jnp.concatenate([t[None, :], s[:w]], axis=0).T
          for t, s, w in zip(cut(term), slots, layout.widths)]
    occ = jnp.stack([jnp.sum(c_b > 0) for c_b in cut(c)]).astype(jnp.int32)
    residual = jnp.sum(c) - sum(jnp.sum(T) for T in Ts)
    return list(zip(cut(rows), Ts)), occ, residual


def flatten_moves(samples) -> jnp.ndarray:
    """Per-edge counts [total_edges] aligned with `bucketize_csr`
    (termination column dropped, each bucket's block slot-major)."""
    return jnp.concatenate([T[:, 1:].T.reshape(-1) for _, T in samples])

