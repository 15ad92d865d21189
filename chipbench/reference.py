"""The plain reference: xgboost scoring written out, in f32.

For every row and every tree: start at the root, look up the node's
feature and threshold, go left when the value is below the threshold
(a missing value, NaN, goes the node's default way), and stop after
``depth`` levels at a leaf.  The prediction is the sigmoid of the sum of
the exit leaves.  Nothing here comes from the engine under test: the
traversal is gathers over the seeded arrays of ``chipbench.data``.

``narrow=True`` is the control: the same scoring with the table, the
thresholds and the leaves rounded to bfloat16, the next precision below
the f32 the deployments state.  The comparison has to reject it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["predict", "BLOCK_ROWS"]

#: rows scored per jitted call, so that the [rows, trees] index arrays
#: of a large table or forest fit beside the table
BLOCK_ROWS = 1 << 16


def _round_bf16(a):
    # an explicit rounding: XLA may drop an f32 -> bf16 -> f32 convert
    # pair (it allows excess precision), which would leave the control
    # in f32
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


@partial(jax.jit, static_argnames=("depth", "narrow"))
def _score(x, feature, threshold, default_left, leaf_value, *, depth,
           narrow):
    if narrow:
        x, threshold, leaf_value = (_round_bf16(a) for a in
                                    (x, threshold, leaf_value))
    rows = x.shape[0]
    trees, internal = feature.shape
    tree = jnp.arange(trees)[None, :]
    node = jnp.zeros((rows, trees), jnp.int32)
    for _ in range(depth):
        value = jnp.take_along_axis(x, feature[tree, node], axis=1)
        left = jnp.where(jnp.isnan(value), default_left[tree, node],
                         value < threshold[tree, node])
        node = 2 * node + jnp.where(left, 1, 2)
    margin = jnp.sum(leaf_value[tree, node - internal], axis=1)
    return jax.nn.sigmoid(margin)


def predict(forest: dict, x: jax.Array, *, narrow: bool = False,
            block_rows: int = BLOCK_ROWS) -> jax.Array:
    """[N] probabilities of ``forest`` (``chipbench.data.forest_arrays``)
    on ``x`` [N, F], scored in blocks of ``block_rows`` (the tail block
    padded with zero rows, so that one program scores every block)."""
    depth = int(np.log2(forest["feature"].shape[1] + 1))
    arrays = (forest["feature"], forest["threshold"],
              forest["default_left"], forest["leaf_value"])
    n = x.shape[0]
    block = min(block_rows, n)
    pad = (-n) % block
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = [_score(x[lo:lo + block], *arrays, depth=depth, narrow=narrow)
           for lo in range(0, n + pad, block)]
    return jnp.concatenate(out)[:n]
