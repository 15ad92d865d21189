"""Public jit'd wrappers for the forest Pallas kernels.

Handles everything the raw kernels assume away:
  * padding the sample axis (zeros) and tree axis (pass-through zero-leaf
    trees) to block multiples, and un-padding the output;
  * padding the node and leaf axes to the 128-lane width;
  * block-size selection against the VMEM budget (``common.block_heuristics``)
    and, for compiled kernels, a clear error for a block the TPU compiler
    would refuse;
  * the structure-only side tensors (HummingBird C/D, QuickScorer
    bit-vectors) — built once per depth and LRU-cached;
  * ``interpret=``: the Pallas interpreter off the TPU (CPU tests), the
    compiled kernel on it — ``interpret=True`` on a TPU is an error.

Two backend families:

  ``KERNEL_ALGORITHMS`` (unfused) return RAW per-tree scores [B, T] like
  ``core.algorithms``; phase-2 aggregation stays in ``core.postprocess``.

  ``FUSED_KERNEL_ALGORITHMS`` (``*_pallas_fused``) return the phase-2 SUM
  [B] directly: aggregation happens in-kernel across the tree grid axis, so
  the [B, T] score matrix never round-trips HBM (the materialization cost
  the paper charges stage boundaries with, Sec. 3.3).  Tree padding is
  correct for both SUM and MEAN: padding trees carry zero leaves (add 0.0
  to the sum) and MEAN divides by the TRUE tree count downstream
  (``core.postprocess.postprocess(num_trees=...)``).

All wrappers are shape-driven, so they compose with ``shard_map``: inside
a manual-sharding region the forest argument is the device-LOCAL tree
shard and ``x`` the local sample shard — block selection, tree padding and
the in-kernel sum all operate on local counts, and because per-shard
padding trees still sum to exactly 0.0, a cross-device ``psum`` of the
per-shard fused sums equals the global SUM (MEAN again divides by the true
GLOBAL tree count downstream).  ``default_tree_block`` exposes the
heuristic tree-block size as the mesh-less tree-partition granularity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.forest import Forest, hb_path_matrix, qs_bitvectors
from repro.kernels.common import (VMEM_LIMIT_BYTES, block_heuristics,
                                  forest_call, lane_pad, vmem_words)
from repro.kernels.forest_hummingbird import hummingbird_tree_score
from repro.kernels.forest_predicated import predicated_tree_score
from repro.kernels.forest_quickscorer import quickscorer_tree_score

__all__ = [
    "predicated_pallas",
    "hummingbird_pallas",
    "quickscorer_pallas",
    "predicated_pallas_fused",
    "hummingbird_pallas_fused",
    "quickscorer_pallas_fused",
    "KERNEL_ALGORITHMS",
    "FUSED_KERNEL_ALGORITHMS",
    "predict_raw_pallas",
    "predict_sum_pallas",
    "default_tree_block",
]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve_interpret(interpret: bool | None) -> bool:
    """Interpret off the TPU (CPU tests), compile on it — never the
    interpreter on a chip, where it would hide the compiled kernel."""
    if interpret is None:
        return not _on_tpu()
    if interpret and _on_tpu():
        raise ValueError(
            "interpret=True on a TPU backend: the forest kernels run "
            "compiled on the chip")
    return bool(interpret)


def _pad_axis(x, axis, multiple, fill=0.0):
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def _pad_to(x, axis, size, fill=0.0):
    return _pad_axis(x, axis, size, fill) if x.shape[axis] < size else x


def _pad_forest_arrays(feature, threshold, default_left, leaf_value, block_t):
    """Tree-axis padding with pass-through zero-leaf trees, and node/leaf
    padding to the lane width (the padded node is never visited and its
    leaf is zero).  ``default_left`` becomes int32, a 32-bit TPU tile."""
    ip = lane_pad(feature.shape[1])
    feature = _pad_to(_pad_axis(feature, 0, block_t, 0), 1, ip, 0)
    threshold = _pad_to(_pad_axis(threshold, 0, block_t, np.float32(np.inf)),
                        1, ip, np.float32(np.inf))
    default_left = _pad_to(
        _pad_axis(default_left.astype(jnp.int32), 0, block_t, 1), 1, ip, 1)
    leaf_value = _pad_to(_pad_axis(leaf_value, 0, block_t, 0.0), 1, ip, 0.0)
    return feature, threshold, default_left, leaf_value


# The structure-tensor caches hold HOST numpy arrays: the first call can
# happen inside a jit trace, and memoizing the jnp conversion there would
# leak a DynamicJaxprTracer into later traces.  jnp.asarray at the use site
# is a free constant embed under trace and a cached transfer in eager mode.
@functools.lru_cache(maxsize=16)
def _hb_tensors_np(depth: int):
    """Path matrix C [Ip, Ip] (zero rows/columns past the real nodes and
    leaves) and left-turn counts D [1, Ip] (-1 past the real leaves, which
    no path count can equal)."""
    C, D = hb_path_matrix(depth)
    ip = lane_pad(C.shape[0])
    Cp = np.zeros((ip, ip), np.float32)
    Cp[: C.shape[0], : C.shape[1]] = C
    Dp = np.full((1, ip), -1.0, np.float32)
    Dp[0, : D.shape[0]] = D
    return Cp, Dp


def _hb_tensors(depth: int):
    C, D = _hb_tensors_np(depth)
    return jnp.asarray(C, jnp.bfloat16), jnp.asarray(D)


@functools.lru_cache(maxsize=16)
def _qs_tensors_np(depth: int):
    """Bit-vectors as [W, Ip] int32 bit patterns, node-padded with
    all-ones (the AND identity)."""
    bv = qs_bitvectors(depth).view(np.int32).T           # [W, I]
    ip = lane_pad(bv.shape[1])
    out = np.full((bv.shape[0], ip), -1, np.int32)
    out[:, : bv.shape[1]] = bv
    return out


def _qs_tensors(depth: int):
    return jnp.asarray(_qs_tensors_np(depth))


def _blocks(forest: Forest, B, block_b, block_t, *, fused=False):
    """Block selection.  Fused kernels get a higher tree-block cap: their
    output tile is [BB, 1] regardless of BT (in-kernel aggregation), so
    enlarging the tree tile costs no output bandwidth and cuts the number
    of accumulator-block passes (``block_heuristics`` still shrinks on
    overflow).
    """
    T, I = forest.feature.shape
    if block_b is None or block_t is None:
        hb, ht = block_heuristics(B, T, I, forest.n_features, fused=fused)
        block_b = block_b or hb
        block_t = block_t or ht
    return block_b, block_t


def _check_compiled_blocks(forest: Forest, B, block_b, block_t, *, fused):
    """What the TPU compiler accepts: a block dim is a multiple of 8 rows
    or the whole padded dim, and one grid step fits the VMEM budget.  A
    shape outside that raises here, before Mosaic would."""
    T, I = forest.feature.shape
    Bp, Tp = -(-B // block_b) * block_b, -(-T // block_t) * block_t
    for name, blk, full in (("block_b", block_b, Bp),
                            ("block_t", block_t, Tp)):
        if blk % 8 and blk != full:
            raise ValueError(
                f"{name}={blk} is neither a multiple of 8 nor the whole "
                f"padded axis ({full}): not a TPU block")
    need = vmem_words(block_b, block_t, I, forest.n_features,
                      fused=fused) * 4
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"forest kernel tile (block_b={block_b}, block_t={block_t}, "
            f"F={forest.n_features}, I={I}) needs {need} B of VMEM, over "
            f"the {VMEM_LIMIT_BYTES} B a kernel may use")


def default_tree_block(forest: Forest, batch_rows: int = 128, *,
                       fused: bool = True) -> int:
    """The tree-block size ``block_heuristics`` would pick for this forest.

    This is the natural tree-PARTITION granularity for the mesh-less
    relation-centric plan: one partition per kernel tree block means the
    unrolled cross-product loop launches exactly the passes the fused
    kernel would make anyway (``db.query`` derives its ``n_parts``
    default from it, replacing the old magic ``4``).  ``batch_rows`` only
    matters when the VMEM budget forces a shrink; the tree block is
    batch-independent in the common case — which is also what keeps the
    per-shard kernel calls under ``shard_map`` (local tree counts)
    bit-compatible with the mesh-less unrolled template.
    """
    _, bt = block_heuristics(batch_rows, forest.num_trees,
                             forest.num_internal, forest.n_features,
                             fused=fused)
    return bt


def _prepared(forest: Forest, x: jax.Array, block_b, block_t, interpret,
              *, fused=False, tree_dtype=None):
    """Shared padding + block selection for both backend families.

    ``tree_dtype`` (e.g. bf16) narrows the per-tree tiles — thresholds and
    leaves — AFTER padding (inf/0 fills survive the cast exactly); the
    kernels upcast on load and accumulate in f32 (InTreeger-style tree
    shrink: half the tree-tile VMEM footprint and HBM bandwidth).
    """
    interpret = _resolve_interpret(interpret)
    B = x.shape[0]
    block_b, block_t = _blocks(forest, B, block_b, block_t, fused=fused)
    if not interpret:
        _check_compiled_blocks(forest, B, block_b, block_t, fused=fused)
    xp = _pad_axis(x, 0, block_b)
    fe, th, dl, lv = _pad_forest_arrays(
        forest.feature, forest.threshold, forest.default_left,
        forest.leaf_value, block_t)
    if tree_dtype is not None:
        th = th.astype(tree_dtype)
        lv = lv.astype(tree_dtype)
    return xp, fe, th, dl, lv, block_b, block_t, interpret


def _tree_scorer(kind: str, depth: int):
    """``kind``'s per-tree scoring body and its structure-only tensors."""
    if kind == "predicated":
        return functools.partial(predicated_tree_score, depth=depth), ()
    if kind == "hummingbird":
        return hummingbird_tree_score, _hb_tensors(depth)
    if kind == "quickscorer":
        return quickscorer_tree_score, (_qs_tensors(depth),)
    raise ValueError(f"unknown kernel {kind!r}")


def _run(kind: str, forest: Forest, x: jax.Array, *, fused: bool,
         block_b=None, block_t=None, interpret=None,
         tree_dtype=None) -> jax.Array:
    """Unfused: RAW per-tree scores [B, T].  Fused: predict + SUM, the [B]
    raw-margin sums with no [B, T] materialization (padding trees sum to
    0.0, so only the sample axis needs un-padding).

    ``tree_dtype=jnp.bfloat16`` stages the tree tiles (thresholds/leaves)
    at half width; accumulation stays f32.
    """
    B, T = x.shape[0], forest.num_trees
    score, aux = _tree_scorer(kind, forest.depth)
    xp, fe, th, dl, lv, block_b, block_t, interpret = _prepared(
        forest, x, block_b, block_t, interpret, fused=fused,
        tree_dtype=tree_dtype)
    out = forest_call(score, xp, fe, th, dl, lv, aux,
                      name=f"forest_{kind}" + ("_fused" if fused else ""),
                      block_b=block_b, block_t=block_t, interpret=interpret,
                      fused=fused)
    if fused:
        return out[:B, 0]
    # [T/BT, Bp, BT] tiles -> [Bp, Tp] per-tree scores
    out = out.transpose(1, 0, 2).reshape(xp.shape[0], fe.shape[0])
    return out[:B, :T]


predicated_pallas = functools.partial(_run, "predicated", fused=False)
hummingbird_pallas = functools.partial(_run, "hummingbird", fused=False)
quickscorer_pallas = functools.partial(_run, "quickscorer", fused=False)

predicated_pallas_fused = functools.partial(_run, "predicated", fused=True)
hummingbird_pallas_fused = functools.partial(_run, "hummingbird", fused=True)
quickscorer_pallas_fused = functools.partial(_run, "quickscorer", fused=True)

KERNEL_ALGORITHMS = {
    "predicated_pallas": predicated_pallas,
    "hummingbird_pallas": hummingbird_pallas,
    "quickscorer_pallas": quickscorer_pallas,
}

FUSED_KERNEL_ALGORITHMS = {
    "predicated_pallas_fused": predicated_pallas_fused,
    "hummingbird_pallas_fused": hummingbird_pallas_fused,
    "quickscorer_pallas_fused": quickscorer_pallas_fused,
}


def predict_raw_pallas(forest: Forest, x: jax.Array,
                       algorithm: str = "hummingbird_pallas", **kw) -> jax.Array:
    try:
        fn = KERNEL_ALGORITHMS[algorithm]
    except KeyError:
        raise ValueError(
            f"unknown kernel algorithm {algorithm!r}; "
            f"options {sorted(KERNEL_ALGORITHMS)}")
    return fn(forest, x, **kw)


def predict_sum_pallas(forest: Forest, x: jax.Array,
                       algorithm: str = "hummingbird_pallas_fused",
                       **kw) -> jax.Array:
    """[B] summed raw margins via a fused backend (no [B, T] round-trip)."""
    try:
        fn = FUSED_KERNEL_ALGORITHMS[algorithm]
    except KeyError:
        raise ValueError(
            f"unknown fused kernel algorithm {algorithm!r}; "
            f"options {sorted(FUSED_KERNEL_ALGORITHMS)}")
    return fn(forest, x, **kw)
