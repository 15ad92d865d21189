"""Pallas TPU kernel: predicated (branch-free) forest traversal.

Paper Fig. 2(c) / Nvidia FIL adapted to the TPU (DESIGN.md Sec. 3): a tile of
samples [BB, F] and a tile of trees (node arrays [BT, Ip]) are staged in
VMEM; for each tree of the tile all node predicates are evaluated densely on
the MXU (gather-free, ``common.node_predicates``), then the fixed-depth
descent

    idx_{d+1} = 2*idx_d + 1 + (1 - s[b, idx_d])

runs as ``depth`` unrolled VPU steps, where the data-dependent fetch
``s[b, idx]`` is a lane-iota compare + masked lane sum — the FIL predication
trick with the pointer arithmetic replaced by lane arithmetic.  The
exit-leaf fetch is one more masked lane sum over the leaves.

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

__all__ = ["predicated_tree_score"]


def predicated_tree_score(s, leaves, *, depth):
    """s [BB, Ip] f32 0/1, leaves [1, Lp] f32 -> exit-leaf values [BB, 1]."""
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
