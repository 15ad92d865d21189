"""Pallas TPU kernel: predicated (branch-free) forest traversal.

Paper Fig. 2(c) / Nvidia FIL adapted to the TPU (DESIGN.md Sec. 3): a tile of
samples [BB, F] and a tile of trees (node arrays [BT, Ip]) are staged in
VMEM; for each tree of the tile all node predicates are evaluated densely on
the MXU (gather-free, ``common.node_predicates``), then the fixed-depth
descent

    idx_{d+1} = 2*idx_d + 1 + (1 - s[b, idx_d])

runs as ``depth`` unrolled VPU steps, where the data-dependent fetch
``s[b, idx]`` is an iota compare + masked sum — the FIL predication trick
with the pointer arithmetic replaced by vector arithmetic.  The exit-leaf
fetch is one more masked sum over the leaves.

Two walks, picked by the static row tile ``BB`` alone:

  * row-major (``BB`` not a multiple of 128: the serve plane's 8- and
    32-row buckets, tiles ``block_heuristics`` shrank).  Rows on sublanes,
    nodes on lanes, as ``s`` arrives: every level compares all ``Ip``
    nodes of the tile and sums across lanes.
  * node-major (``BB`` a multiple of 128: the rows fill the lanes).  ``s``
    is transposed once to [Ip, BB], nodes on sublanes and rows on lanes,
    and the index is a [1, BB] lane vector.  Level d reads only the
    8-sublane groups holding its own nodes [2^d - 1, 2^(d+1) - 1) and sums
    over sublanes, so the descent does no cross-lane reduction and touches
    39 of the 8 x 32 vregs the row-major walk touches at depth 8.  The
    leaves are transposed the same way, the exit leaf is fetched by one
    more sublane sum, and only the [1, BB] result goes back to [BB, 1].

Below 128 rows the node-major layout would fill 8 or 32 of the 128 lanes
and cost about as much as a full tile, so the row-major walk stays there.
Both walks select exactly one node or leaf per row and add zeros to it, so
they return the same bits.  ``kernel.predicated.node_major_traces`` and
``kernel.predicated.row_major_traces`` (``obs.METRICS``) count the kernel
traces that took each walk.

Grid: (ceil(B/BB), ceil(T/BT)).  The unfused kernel writes one [BB, BT]
tile of raw per-tree scores.  Tree tiles are independent => the tree axis
can be sharded across the mesh 'model' axis (relation-centric plan) with
this same kernel.

FUSED variant (``common.forest_call(fused=True)``): phase-2 aggregation
moves INTO the kernel.  The tree grid axis j revisits one [BB, 1] output block per
sample tile (initialized at j == 0), accumulating each tree tile's partial
sum in VMEM — the [B, T] per-tree score matrix never exists in HBM, which is
the data-movement term the paper's stage-materialization analysis charges
the unfused path with (Sec. 3.3).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import LANES
from repro.obs import METRICS

__all__ = ["predicated_tree_score"]

#: rows of one f32 vreg: the node-major walk slices the node axis in
#: whole groups of this many sublanes
SUBLANES = 8


def _row_major(s, leaves, depth):
    BB, Ip = s.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (BB, Ip), 1)
    idx = jnp.zeros((BB, 1), jnp.int32)
    for _ in range(depth):                          # unrolled descent
        go_left = jnp.sum(jnp.where(lane == idx, s, 0.0), axis=1,
                          keepdims=True)
        idx = 2 * idx + 1 + (1 - go_left.astype(jnp.int32))
    leaf = idx - ((1 << depth) - 1)                 # [BB, 1] in [0, L)
    leaf_lane = jax.lax.broadcasted_iota(jnp.int32, (BB, leaves.shape[1]), 1)
    return jnp.sum(jnp.where(leaf_lane == leaf, leaves, 0.0), axis=1,
                   keepdims=True)


def _node_major(s, leaves, depth):
    BB, Ip = s.shape
    Lp = leaves.shape[1]
    s_t = s.T                                       # [Ip, BB]
    idx = jnp.zeros((1, BB), jnp.int32)
    for d in range(depth):                          # unrolled descent
        lo = ((1 << d) - 1) // SUBLANES * SUBLANES
        hi = min(Ip, -(-((1 << (d + 1)) - 1) // SUBLANES) * SUBLANES)
        node = jax.lax.broadcasted_iota(jnp.int32, (hi - lo, BB), 0) + lo
        go_left = jnp.sum(jnp.where(node == idx, s_t[lo:hi], 0.0), axis=0,
                          keepdims=True)
        idx = jnp.where(go_left > 0.5, 2 * idx + 1, 2 * idx + 2)
    leaf = idx - ((1 << depth) - 1)                 # [1, BB] in [0, L)
    slot = jax.lax.broadcasted_iota(jnp.int32, (Lp, BB), 0)
    leaves_t = jnp.broadcast_to(leaves, (BB, Lp)).T            # [Lp, BB]
    value = jnp.sum(jnp.where(slot == leaf, leaves_t, 0.0), axis=0,
                    keepdims=True)                  # [1, BB]
    return jnp.broadcast_to(value, (SUBLANES, BB)).T[:, :1]


def predicated_tree_score(s, leaves, *, depth):
    """s [BB, Ip] f32 0/1, leaves [1, Lp] f32 -> exit-leaf values [BB, 1].

    The walk follows from ``BB``: node-major where the rows fill whole
    lane widths, row-major below (module docstring)."""
    if s.shape[0] % LANES:
        METRICS.counter("kernel.predicated.row_major_traces").inc()
        return _row_major(s, leaves, depth)
    METRICS.counter("kernel.predicated.node_major_traces").inc()
    return _node_major(s, leaves, depth)
