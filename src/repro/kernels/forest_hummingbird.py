"""Pallas TPU kernel: fused HummingBird (GEMM) forest inference.

Paper Fig. 1(b): tree traversal as tensor algebra.  HummingBird materializes
the predicate tensor S [B, T, I] and path tensor P [B, T, L] in device
memory between GEMMs; those intermediates are the largest tensors in the
whole computation (B*T*(I+L) words vs B*F + T*(I+L) for the inputs).

TPU adaptation (DESIGN.md Sec. 3): fuse all three stages into one kernel so S
and P live only in VMEM, one tree at a time per (sample-tile x tree-tile):

  1. S  = dense predicate eval                 (MXU, gather-free, common.py)
  2. P  = S @ C                                (MXU; C is the [Ip, Lp]
          structure-only path matrix shared by ALL trees of a depth - a
          consequence of the dense complete-tree layout, so it is loaded
          once, not per tree)
  3. out = sum_l (P == D[l]) * leaf_value[t,l] (VPU compare + lane sum)

S is 0/1 and C is -1/0/1, so the path GEMM runs on bf16 operands and is
exact.  HBM traffic per tile drops from (read S + write S + read P + write
P) to zero.

FUSED variant (``common.forest_call(fused=True)``): the remaining [B, T]
score write is folded away too — the tree grid axis accumulates each tile's
per-sample partial sum into one revisited [BB, 1] output block (init at
j == 0), so phase 2 never touches HBM.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["hummingbird_tree_score"]


def hummingbird_tree_score(s, leaves, C, D):
    """s [BB, Ip] f32 0/1, leaves [1, Lp] -> exit-leaf values [BB, 1].

    C [Ip, Lp] bf16 and D [1, Lp] f32 are the lane-padded structure-only
    tensors from ``core.forest.hb_path_matrix`` (``ops._hb_tensors``)."""
    P = jnp.dot(s.astype(jnp.bfloat16), C,
                preferred_element_type=jnp.float32)          # [BB, Lp]
    return jnp.sum(jnp.where(P == D, leaves, 0.0), axis=1, keepdims=True)
