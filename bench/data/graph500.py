"""Graph500 Kronecker graphs, built the way LDBC Graphalytics builds its
`graph500-S` datasets.

Generator (Graph500 specification, `kronecker_generator`): scale S gives
N = 2^S vertices and M = edgefactor * N directed draws. Each draw picks
one quadrant of the adjacency matrix per level, with initiator
probabilities A, B, C and 1 - A - B - C, and the vertex ids are then
relabelled by a random permutation. Graphalytics then makes the edge set
undirected and drops self-loops and duplicate edges. Its datasets list
only vertices that have an edge (graph500-22 has about 2.4M vertices, not
2^22), so isolated vertices are dropped too and the others renumbered in
order: every vertex of the result has an edge.

Everything is vectorized numpy: one pass over the M draws per level and
one sort of the 2M directed keys. No loop runs per vertex or per edge.
The result depends only on (scale, edgefactor, initiator, seed).
"""
from __future__ import annotations

import numpy as np

# Graph500 specification, section "Kernel 0 -- Generation"
INITIATOR = (0.57, 0.19, 0.19)
EDGEFACTOR = 16

_U16 = 1 << 16


def kronecker_edges(scale: int, edgefactor: int, initiator, rng):
    """The generator's M = edgefactor * 2^scale directed draws (src, dst),
    as int64, before relabelling. One 16-bit uniform per draw and level
    picks the quadrant: [0, A) -> (0, 0), [A, A+B) -> (0, 1),
    [A+B, A+B+C) -> (1, 0), the rest -> (1, 1)."""
    a, b, c = initiator
    t_a, t_ab, t_abc = (round(x * _U16) for x in (a, a + b, a + b + c))
    m = edgefactor << scale
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for level in range(scale):
        u = np.frombuffer(rng.bytes(2 * m), np.uint16)
        ii = u >= t_ab
        jj = (u >= t_a) ^ ii ^ (u >= t_abc)
        src |= ii.astype(np.int64) << level
        dst |= jj.astype(np.int64) << level
    return src, dst


def graph500(scale: int, seed: int, *, edgefactor: int = EDGEFACTOR,
             initiator=INITIATOR):
    """Symmetric CSR of a Graph500 graph with Graphalytics' clean-up.

    Returns (row_ptr [n+1] int32, col_idx [m] int32, out_deg [n] int32)
    with n <= 2^scale: every undirected edge appears once in each
    direction, rows are sorted, there are no self-loops, duplicates or
    isolated vertices."""
    n = 1 << scale
    rng = np.random.default_rng(seed)
    src, dst = kronecker_edges(scale, edgefactor, initiator, rng)
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    keep = src != dst
    src, dst = src[keep], dst[keep]
    # both directions of every edge, deduplicated by one sort of the keys
    keys = np.unique(np.concatenate([(src << scale) | dst,
                                     (dst << scale) | src]))
    del src, dst
    deg = np.bincount(keys >> scale, minlength=n)
    # renumber the vertices that have an edge 0..n'-1, in order
    new_id = np.cumsum(deg > 0) - 1
    col_idx = new_id[keys & (n - 1)].astype(np.int32)
    out_deg = deg[deg > 0].astype(np.int32)
    row_ptr = np.zeros(len(out_deg) + 1, np.int32)
    np.cumsum(out_deg, out=row_ptr[1:])
    return row_ptr, col_idx, out_deg
