"""Dense complete-tree tensor encoding of a decision forest.

The paper's workloads use max_depth=8 trees (Sec. 4).  We adopt a *dense
complete binary tree* layout: every tree is embedded in a perfect binary
tree of depth ``depth`` using the classic heap indexing

    root = 0, children(i) = (2i+1, 2i+2)
    internal nodes: positions [0, 2^depth - 1)
    leaves:         positions [2^depth - 1, 2^(depth+1) - 1)

Trees whose real shape is smaller are *completed*: a premature leaf becomes
a pass-through internal node (threshold = +inf, default_left = True, so every
sample — including NaN — goes left) and its value is propagated to every
dense leaf below it.  This makes the traversal fixed-length and branch-free,
which is what the TPU VPU wants, and makes the HummingBird path matrix and
the QuickScorer bitvectors *structure-only* (identical for all trees of the
same depth) — see ``hb_path_matrix`` / ``qs_bitvectors``.

All per-tree arrays carry the tree dimension T in front, so the paper's
relation-centric *model parallelism* is literally "shard dim 0".
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Forest",
    "num_internal",
    "num_leaves",
    "make_forest",
    "complete_from_nodes",
    "hb_path_matrix",
    "qs_bitvectors",
    "pad_trees",
    "tree_slice",
    "used_feature_counts",
    "compact_forest",
]


def num_internal(depth: int) -> int:
    return (1 << depth) - 1


def num_leaves(depth: int) -> int:
    return 1 << depth


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Forest:
    """A forest of T depth-``depth`` complete binary trees.

    feature      int32  [T, I]  feature tested at each internal node
    threshold    f32    [T, I]  split threshold; x < t goes left
    default_left bool   [T, I]  where NaN inputs go
    leaf_value   f32    [T, L]  per-leaf raw score / class-1 probability
    node_is_leaf bool   [T, I]  True where the original tree had a leaf
    node_value   f32    [T, I]  value of that premature leaf (naive early exit)
    """

    feature: jax.Array
    threshold: jax.Array
    default_left: jax.Array
    leaf_value: jax.Array
    node_is_leaf: jax.Array
    node_value: jax.Array
    # --- static metadata -------------------------------------------------
    depth: int = dataclasses.field(metadata=dict(static=True), default=8)
    n_features: int = dataclasses.field(metadata=dict(static=True), default=0)
    model_type: str = dataclasses.field(metadata=dict(static=True), default="xgboost")
    task: str = dataclasses.field(metadata=dict(static=True), default="classification")
    base_score: float = dataclasses.field(metadata=dict(static=True), default=0.0)

    @property
    def num_trees(self) -> int:
        return self.feature.shape[0]

    @property
    def num_internal(self) -> int:
        return num_internal(self.depth)

    @property
    def num_leaves(self) -> int:
        return num_leaves(self.depth)

    def astype(self, dtype) -> "Forest":
        return dataclasses.replace(
            self,
            threshold=self.threshold.astype(dtype),
            leaf_value=self.leaf_value.astype(dtype),
            node_value=self.node_value.astype(dtype),
        )

    def arrays(self) -> dict[str, jax.Array]:
        return {
            f.name: getattr(self, f.name)
            for f in dataclasses.fields(self)
            if not f.metadata.get("static", False)
        }


def make_forest(
    feature: np.ndarray,
    threshold: np.ndarray,
    leaf_value: np.ndarray,
    *,
    default_left: np.ndarray | None = None,
    node_is_leaf: np.ndarray | None = None,
    node_value: np.ndarray | None = None,
    n_features: int,
    model_type: str = "xgboost",
    task: str = "classification",
    base_score: float = 0.0,
) -> Forest:
    """Build a Forest from already-dense arrays (e.g. the in-JAX trainer)."""
    T, I = feature.shape
    depth = int(np.log2(I + 1))
    assert (1 << depth) - 1 == I, f"I={I} is not 2^d - 1"
    L = leaf_value.shape[1]
    assert L == 1 << depth
    if default_left is None:
        default_left = np.ones((T, I), dtype=bool)
    if node_is_leaf is None:
        node_is_leaf = np.zeros((T, I), dtype=bool)
    if node_value is None:
        node_value = np.zeros((T, I), dtype=np.float32)
    return Forest(
        feature=jnp.asarray(feature, jnp.int32),
        threshold=jnp.asarray(threshold, jnp.float32),
        default_left=jnp.asarray(default_left, bool),
        leaf_value=jnp.asarray(leaf_value, jnp.float32),
        node_is_leaf=jnp.asarray(node_is_leaf, bool),
        node_value=jnp.asarray(node_value, jnp.float32),
        depth=depth,
        n_features=int(n_features),
        model_type=model_type,
        task=task,
        base_score=float(base_score),
    )


# ---------------------------------------------------------------------------
# Conversion from a generic node-list model (the "external model import" path;
# this is what the paper's model-conversion benchmark, Fig. 8, measures).
# ---------------------------------------------------------------------------


def complete_from_nodes(
    trees: list[dict[str, np.ndarray]],
    *,
    depth: int,
    n_features: int,
    model_type: str = "xgboost",
    task: str = "classification",
    base_score: float = 0.0,
) -> Forest:
    """Convert sklearn-style node lists into the dense complete layout.

    Each tree dict has arrays ``children_left``, ``children_right``,
    ``feature``, ``threshold``, ``value`` (leaf score; ignored at internals),
    optionally ``default_left``; -1 children mean leaf.  Trees deeper than
    ``depth`` are rejected (the dense layout is the paper's depth-8 regime;
    deeper models use the jnp sparse path, see algorithms.naive_predict).
    """
    T = len(trees)
    I, L = num_internal(depth), num_leaves(depth)
    feature = np.zeros((T, I), np.int32)
    threshold = np.full((T, I), np.inf, np.float32)
    default_left = np.ones((T, I), bool)
    node_is_leaf = np.zeros((T, I), bool)
    node_value = np.zeros((T, I), np.float32)
    leaf_value = np.zeros((T, L), np.float32)

    for t, tr in enumerate(trees):
        cl, cr = tr["children_left"], tr["children_right"]
        feat, thr, val = tr["feature"], tr["threshold"], tr["value"]
        dl = tr.get("default_left")
        # BFS: (orig_node, dense_pos). A leaf reached at dense depth d < depth
        # turns into a pass-through chain; we propagate its value to all dense
        # leaves underneath in one go.
        stack = [(0, 0)]
        while stack:
            node, pos = stack.pop()
            d = int(np.floor(np.log2(pos + 1)))
            is_leaf = cl[node] < 0
            if is_leaf:
                if pos < I:
                    node_is_leaf[t, pos] = True
                    node_value[t, pos] = val[node]
                # all dense leaves under `pos`: leftmost descendant chain.
                lo = pos
                for _ in range(depth - d):
                    lo = 2 * lo + 1
                span = 1 << (depth - d)
                leaf_value[t, lo - I : lo - I + span] = val[node]
            else:
                if d >= depth:
                    raise ValueError(
                        f"tree {t} deeper than dense depth {depth}"
                    )
                feature[t, pos] = feat[node]
                threshold[t, pos] = thr[node]
                if dl is not None:
                    default_left[t, pos] = dl[node]
                stack.append((int(cl[node]), 2 * pos + 1))
                stack.append((int(cr[node]), 2 * pos + 2))

    return make_forest(
        feature,
        threshold,
        leaf_value,
        default_left=default_left,
        node_is_leaf=node_is_leaf,
        node_value=node_value,
        n_features=n_features,
        model_type=model_type,
        task=task,
        base_score=base_score,
    )


# ---------------------------------------------------------------------------
# Structure-only auxiliary tensors (shared across all trees of a depth).
# ---------------------------------------------------------------------------


def _leaf_ancestry(depth: int) -> tuple[np.ndarray, np.ndarray]:
    """For each leaf l: (ancestor internal positions [L, depth],
    went_left flags [L, depth])."""
    I, L = num_internal(depth), num_leaves(depth)
    anc = np.zeros((L, depth), np.int64)
    left = np.zeros((L, depth), bool)
    for l in range(L):
        pos = I + l
        for d in range(depth - 1, -1, -1):
            parent = (pos - 1) // 2
            anc[l, d] = parent
            left[l, d] = pos == 2 * parent + 1
            pos = parent
    return anc, left


def hb_path_matrix(depth: int) -> tuple[np.ndarray, np.ndarray]:
    """HummingBird tensors, structure-only for the complete layout.

    Returns (C [I, L] int8, D_count [L] int32) with the property: given the
    per-node predicate vector s (1 = x<t goes left), the exit leaf is the
    unique l with  (s @ C)[l] == D_count[l].
    """
    I, L = num_internal(depth), num_leaves(depth)
    anc, left = _leaf_ancestry(depth)
    C = np.zeros((I, L), np.int8)
    for l in range(L):
        for d in range(depth):
            C[anc[l, d], l] = 1 if left[l, d] else -1
    D_count = left.sum(axis=1).astype(np.int32)
    return C, D_count


def qs_bitvectors(depth: int) -> np.ndarray:
    """QuickScorer leaf bitvectors, structure-only for the complete layout.

    bv [I, W] uint32, W = ceil(L/32); leaf l maps to word l//32, bit l%32
    (LSB-first).  bv[i] has zeros exactly on the leaves of i's *left*
    subtree: AND-ing the bitvectors of all FALSE nodes (x >= t, i.e. the
    sample goes right) leaves the exit leaf as the lowest surviving bit
    (Lucchese et al., SIGIR'15).
    """
    I, L = num_internal(depth), num_leaves(depth)
    W = (L + 31) // 32
    anc, left = _leaf_ancestry(depth)
    bv = np.full((I, W), 0xFFFFFFFF, np.uint32)
    for l in range(L):
        for d in range(depth):
            if left[l, d]:
                i = anc[l, d]
                bv[i, l // 32] &= ~np.uint32(1 << (l % 32))
    return bv


# ---------------------------------------------------------------------------
# Used-feature compaction (the wide-sparse data plane's model half).
# ---------------------------------------------------------------------------
#
# A depth-d tree tests at most 2^d - 1 distinct features, so a forest over
# criteo-scale F touches only a tiny slice of the feature space (Yggdrasil
# DF's per-tree "used feature" compaction is the same observation).  We
# compact at FOREST granularity: remap every split's feature id into the
# sorted union of features the forest actually tests, and publish that
# union as a gather index table.  The inference contract is then
#
#     predict(forest, x)  ==  predict(compact, x[:, gather_idx])
#
# for every backend, because node n reads x_compact[inv[f_n]] =
# x[gather_idx[inv[f_n]]] = x[f_n].  The feature-gather prepass
# (kernels/gather.py) produces x_compact directly from CSR pages, so the
# kernels' in-VMEM one-hot shrinks from [BT, I, F] to [BT, I, F_used] —
# the difference between criteo-scale F being modeled and being real.
#
# Invariants (asserted by tests/test_sparse.py):
#   * gather_idx is sorted and duplicate-free over its first F_used slots
#     (padding slots repeat gather_idx[0] and are never referenced by any
#     remapped split);
#   * completed pass-through nodes (threshold == +inf) are excluded from
#     the used set — their feature slot is never read;
#   * per-tree used counts never exceed num_internal(depth).


def used_feature_counts(forest: Forest) -> np.ndarray:
    """[T] number of DISTINCT features each tree really tests.

    Pass-through completion nodes (threshold +inf) don't count: their
    predicate is constant.  This is the per-tree compaction stat.
    """
    feat = np.asarray(jax.device_get(forest.feature))
    real = np.isfinite(np.asarray(jax.device_get(forest.threshold)))
    return np.array([np.unique(feat[t][real[t]]).size
                     for t in range(feat.shape[0])], np.int64)


def compact_forest(forest: Forest, *, pad_to: int = 8
                   ) -> tuple[Forest, np.ndarray]:
    """Remap split features into the forest's used-feature union.

    Returns (compact forest with n_features = F_used padded to ``pad_to``,
    gather_idx [F_used_padded] int32).  ``x[:, gather_idx]`` (or the CSR
    gather prepass) produces the matching compact sample block.  Padding
    slots repeat gather_idx[0] so the index table stays valid for a plain
    column gather; no remapped split ever points at them.
    """
    feat = np.asarray(jax.device_get(forest.feature))
    real = np.isfinite(np.asarray(jax.device_get(forest.threshold)))
    used = np.unique(feat[real])
    if used.size == 0:
        used = np.zeros(1, feat.dtype)          # degenerate all-pass forest
    f_used = used.size
    pad = (-f_used) % max(pad_to, 1)
    gather_idx = np.concatenate(
        [used, np.full(pad, used[0], used.dtype)]).astype(np.int32)
    inv = np.zeros(forest.n_features, np.int32)
    inv[used] = np.arange(f_used, dtype=np.int32)
    # pass-through nodes keep whatever slot their (ignored) feature maps to
    remapped = inv[np.clip(feat, 0, forest.n_features - 1)]
    compact = dataclasses.replace(
        forest,
        feature=jnp.asarray(remapped, jnp.int32),
        n_features=int(gather_idx.size),
    )
    return compact, gather_idx


# ---------------------------------------------------------------------------
# Tree-dimension utilities (model parallelism / padding).
# ---------------------------------------------------------------------------


def pad_trees(forest: Forest, multiple: int) -> tuple[Forest, int]:
    """Pad the tree dimension to a multiple (identity trees: value 0).

    Padding trees are pass-through with all-zero leaves so SUM aggregation is
    unaffected; MEAN aggregation must divide by the *original* count, which
    the caller keeps (returned here).
    """
    T = forest.num_trees
    pad = (-T) % multiple
    if pad == 0:
        return forest, T

    def _pad(x, fill):
        widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, widths, constant_values=fill)

    return (
        dataclasses.replace(
            forest,
            feature=_pad(forest.feature, 0),
            threshold=_pad(forest.threshold, jnp.inf),
            default_left=_pad(forest.default_left, True),
            leaf_value=_pad(forest.leaf_value, 0.0),
            node_is_leaf=_pad(forest.node_is_leaf, True),
            node_value=_pad(forest.node_value, 0.0),
        ),
        T,
    )


def tree_slice(forest: Forest, start: int, size: int) -> Forest:
    """A contiguous tree partition (the relation-centric model partitioner)."""
    changes = {
        k: jax.lax.dynamic_slice_in_dim(v, start, size, axis=0)
        for k, v in forest.arrays().items()
    }
    return dataclasses.replace(forest, **changes)
