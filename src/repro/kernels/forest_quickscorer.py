"""Pallas TPU kernel: QuickScorer (bit-vector) forest inference, dense form.

Paper Fig. 1(c) / Lucchese et al. SIGIR'15: every internal node carries a
bit-vector with zeros on the leaves its FALSE outcome makes unreachable;
AND-ing the vectors of all FALSE nodes leaves the exit leaf as the lowest
surviving bit.  The CPU algorithm avoids evaluating every node via
per-feature sorted node lists + binary search — branchy, irregular,
unbalanced (the reason the paper rejects it for relation-centric netsDB,
Sec. 3.1, and the reason TFDF caps depth at 6 / 64-bit masks).

TPU adaptation (DESIGN.md Sec. 3/6.2): evaluate ALL predicates densely on the
MXU (the evaluation QuickScorer works to avoid is nearly free on a systolic
array), then per 32-bit word

    surviving[b, w] = AND_i ( s_false[b,i] ? bv[w, i] : 0xFFFFFFFF )

as a log-depth butterfly of lane rotations (``pltpu.roll``) on the VPU,
which leaves the AND replicated on every lane.  The ≤64-leaf limit
disappears: depth-8 trees use W = 8 words.  Each leaf lane then reads its
own bit of its own word, and the exit leaf is the lowest lane whose bit is
set (a lane min, no argmax), fused with the leaf-value select.  The words
are int32 bit patterns: the TPU has no unsigned-to-float conversion, and
none is needed.

The bit-vectors are STRUCTURE-ONLY for the dense complete layout (identical
for every tree of a depth) => loaded once per kernel, not per tree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

__all__ = ["quickscorer_tree_score"]


def _and_all_lanes(m):
    """[BB, n] int32 -> AND over the lane axis, replicated on every lane
    (n a power of two)."""
    shift = m.shape[1] // 2
    while shift:
        m = jnp.bitwise_and(m, pltpu.roll(m, shift, 1))
        shift //= 2
    return m


def quickscorer_tree_score(s, leaves, bv):
    """s [BB, Ip] f32 (1.0 = go left), leaves [1, Lp], bv [W, Ip] int32
    -> exit-leaf values [BB, 1].

    ``bv`` is ``core.forest.qs_bitvectors`` transposed, bit-cast and
    lane-padded with all-ones columns (``ops._qs_tensors``)."""
    BB, Lp = s.shape[0], leaves.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (BB, Lp), 1)
    word_of_lane = jax.lax.shift_right_logical(lane, 5)          # lane // 32
    words = jnp.zeros((BB, Lp), jnp.int32)
    for w in range(bv.shape[0]):                     # static unroll
        # a TRUE (left) node clears nothing: it contributes all ones
        m = _and_all_lanes(jnp.where(s > 0.5, jnp.int32(-1),
                                     bv[w:w + 1, :]))
        words = jnp.where(word_of_lane == w, m, words)
    bits = jnp.bitwise_and(
        jax.lax.shift_right_logical(words, jnp.bitwise_and(lane, 31)), 1)
    # lanes past the real leaves are phantom (their bits are never
    # cleared, or belong to no word) — but the real exit leaf always
    # survives and is lower, so the lowest set lane is exact
    lane_f = lane.astype(jnp.float32)
    exit_leaf = jnp.min(jnp.where(bits == 1, lane_f, float(Lp)), axis=1,
                        keepdims=True)
    return jnp.sum(jnp.where(lane_f == exit_leaf, leaves, 0.0), axis=1,
                   keepdims=True)
