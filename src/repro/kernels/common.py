"""Shared in-kernel building blocks for the forest Pallas kernels.

TPU-native design notes (DESIGN.md Sec. 3): the MXU wants 128-aligned
matmuls and the VPU wants dense 8x128 vector ops; data-dependent gathers are
the enemy.  All three kernels therefore share one gather-free primitive and
one loop:

  dense predicate evaluation, one tree at a time
      s[b, i] = "sample b at internal node i goes LEFT"
  computed as a one-hot MXU contraction:
      onehot[f, i] = (feature[i] == f)             (built from iota compares)
      xv = x @ onehot                              (MXU matmul, [BB, Ip])
      s  = where(isnan(xv'), default_left, xv < threshold)

Every in-kernel value is 2-D with the node (or leaf) axis on the 128-wide
lanes: the node axis is padded from I = 2^d - 1 to ``Ip = lane_pad(I)``
(255 -> 256 at depth 8) and leaves are padded to the same width, so the
compiler never has to split or merge a lane dimension (Mosaic refuses such
shape casts).  ``forest_call`` walks the trees of a tile with a
``fori_loop``, reading one row of each tree array per step, so the working
set is one tree's [BB, Ip] temporaries whatever the tree tile size.

NaN note: the matmul contraction would turn a NaN feature into NaN only when
the one-hot row selects it — but 0 * NaN = NaN would poison the row, so NaN
inputs are pre-masked to 0 and a parallel "is-nan" indicator column is
contracted with the same one-hot to recover per-node missingness exactly.
The value contraction runs at full f32 precision (``Precision.HIGHEST``):
a one-hot product must return the feature value bit for bit, or threshold
comparisons would flip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["LANES", "VMEM_LIMIT_BYTES", "VMEM_BUDGET_BYTES", "lane_pad",
           "node_predicates", "forest_call", "vmem_words",
           "block_heuristics"]

#: TPU vector lane width: the minor dimension of every in-kernel value
LANES = 128

#: scoped VMEM a TPU v5e kernel may use by default
VMEM_LIMIT_BYTES = 16 * 1024 * 1024

#: what ``block_heuristics`` fits a grid step into: headroom under the
#: limit for what the compiler adds
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

#: feature-axis chunk of the one-hot contraction: bounds the [F, Ip]
#: one-hot to [F_CHUNK, Ip] however wide the sample tile is
F_CHUNK = 512

#: live [BB, Ip] f32 temporaries per tree step (predicates, their float
#: copy, the lane iota, the select mask, quickscorer's word planes), with
#: headroom for what the compiler keeps alive between them
TREE_TEMPS = 8


def lane_pad(n: int) -> int:
    """``n`` rounded up to a whole number of lanes (at least one)."""
    return max(LANES, -(-n // LANES) * LANES)


def node_predicates(x_safe, x_nan, feature, threshold, default_left):
    """Predicates of ONE tree for a sample tile.

    x_safe       [BB, F]  f32 samples with NaN replaced by 0
    x_nan        [BB, F]  f32 1.0 where the sample value is NaN
    feature      [1, Ip]  int32 feature tested at each node
    threshold    [1, Ip]  f32
    default_left [1, Ip]  int32, nonzero where NaN goes left
    returns s    [BB, Ip] f32, 1.0 = go left (a float, not a bool: Mosaic
                 cannot select between boolean vectors)
    """
    BB, F = x_safe.shape
    Ip = feature.shape[1]
    xv = nanv = None
    for lo in range(0, F, F_CHUNK):               # static: F is a shape
        hi = min(F, lo + F_CHUNK)
        f_iota = jax.lax.broadcasted_iota(jnp.int32, (hi - lo, Ip), 0) + lo
        onehot = (f_iota == feature).astype(jnp.float32)          # [Fc, Ip]
        v = jnp.dot(x_safe[:, lo:hi], onehot,
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
        n = jnp.dot(x_nan[:, lo:hi], onehot,
                    preferred_element_type=jnp.float32)
        # each node's feature lies in exactly one chunk: every other chunk
        # adds an exact 0.0
        xv = v if xv is None else xv + v
        nanv = n if nanv is None else nanv + n
    return jnp.where(nanv > 0.5, default_left.astype(jnp.float32),
                     jnp.where(xv < threshold, 1.0, 0.0))


def _accumulate_trees(tree_score, x_ref, feat_ref, thr_ref, dl_ref,
                      leaf_ref, out_ref, *, fused: bool):
    """Run ``tree_score`` over every tree of the tile and write the output.

    ``tree_score(s [BB, Ip] f32 0/1, leaves [1, Lp] f32) -> [BB, 1]``
    scores one tree; the loop reads one row of each tree array per step.

    fused:   the tree grid axis (program_id 1) revisits one [BB, 1] output
             block; it is zeroed at j == 0 and each tile adds its sum.
    unfused: the output block is the [BB, BT] per-tree score tile.
    """
    x = x_ref[...].astype(jnp.float32)             # [BB, F]
    missing = jnp.isnan(x)
    x_safe = jnp.where(missing, 0.0, x)
    x_nan = missing.astype(jnp.float32)
    BB = x.shape[0]
    BT = feat_ref.shape[0]

    def score(t):
        row = pl.ds(t, 1)
        s = node_predicates(x_safe, x_nan, feat_ref[row, :],
                            thr_ref[row, :], dl_ref[row, :])
        return tree_score(s, leaf_ref[row, :])

    if fused:
        total = jax.lax.fori_loop(
            0, BT, lambda t, acc: acc + score(t),
            jnp.zeros((BB, 1), jnp.float32))

        @pl.when(pl.program_id(1) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        out_ref[...] += total
    else:
        col = jax.lax.broadcasted_iota(jnp.int32, (BB, BT), 1)
        out_ref[...] = jax.lax.fori_loop(
            0, BT, lambda t, tile: jnp.where(col == t, score(t), tile),
            jnp.zeros((BB, BT), jnp.float32))


def forest_call(tree_score, x, feature, threshold, default_left, leaf_value,
                aux=(), *, block_b, block_t, interpret, fused):
    """The one ``pallas_call`` behind every forest kernel.

    x [B, F] and the tree arrays [T, Ip] (``default_left`` int32) must
    already be padded to block multiples and the lane width
    (``ops._prepared``).  ``aux`` are structure-only tensors shared by all
    trees (whole-array blocks); ``tree_score(s, leaves, *aux_values)``
    scores one tree.

    Returns [B, 1] per-sample sums (fused) or [T/BT, B, BT] per-tree
    score tiles (unfused: the tile's minor dim is the whole array dim, a
    legal TPU block for any BT; the caller restores [B, T]).

    Narrow tree tiles (bf16 thresholds/leaves) are upcast once per grid
    step into f32 VMEM scratch: the tree loop reads single rows, and a
    packed bf16 tile cannot be read at an arbitrary row.
    """
    B, F = x.shape
    T, Ip = feature.shape
    assert B % block_b == 0 and T % block_t == 0
    narrow = [a.dtype.itemsize < 4 for a in (threshold, leaf_value)]
    n_aux = len(aux)

    def kernel(x_ref, feat_ref, thr_ref, dl_ref, leaf_ref, *rest):
        aux_vals = [r[...] for r in rest[:n_aux]]
        out_ref, scratch = rest[n_aux], list(rest[n_aux + 1:])
        tiles = []
        for ref, is_narrow in ((thr_ref, narrow[0]), (leaf_ref, narrow[1])):
            if is_narrow:
                wide = scratch.pop(0)
                wide[...] = ref[...].astype(jnp.float32)
                ref = wide
            tiles.append(ref)
        _accumulate_trees(lambda s, lv: tree_score(s, lv, *aux_vals),
                          x_ref, feat_ref, tiles[0], dl_ref, tiles[1],
                          out_ref, fused=fused)

    tree = pl.BlockSpec((block_t, Ip), lambda i, j: (j, 0))
    in_specs = [pl.BlockSpec((block_b, F), lambda i, j: (i, 0)),
                tree, tree, tree, tree]
    in_specs += [pl.BlockSpec(a.shape, lambda i, j: (0, 0)) for a in aux]
    if fused:
        out_spec = pl.BlockSpec((block_b, 1), lambda i, j: (i, 0))
        out_shape = jax.ShapeDtypeStruct((B, 1), jnp.float32)
    else:
        out_spec = pl.BlockSpec((None, block_b, block_t),
                                lambda i, j: (j, i, 0))
        out_shape = jax.ShapeDtypeStruct((T // block_t, B, block_t),
                                         jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=(B // block_b, T // block_t),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_t, Ip), jnp.float32)
                        for n in narrow if n],
        interpret=interpret,
    )(x, feature, threshold, default_left, leaf_value, *aux)


def vmem_words(bb: int, bt: int, I: int, F: int, *, fused: bool) -> int:
    """f32 words of VMEM one grid step of a forest kernel allocates.

    Pallas double-buffers every block: the sample tile [bb, F], the three
    node arrays and the leaves [bt, Ip], the structure tensor (at most the
    hummingbird path matrix, [Ip, Ip]) and the output block ([bb, 1] fused,
    [bb, bt] unfused; both lane-padded).  On top: the NaN-masked and
    indicator copies of the sample tile, the f32 scratch of narrow tree
    tiles, one [F_CHUNK, Ip] one-hot, and ``TREE_TEMPS`` [bb, Ip]
    temporaries of the tree step.  The tree loop
    keeps only one tree's temporaries alive, so ``bt`` enters through the
    tree blocks alone.
    """
    ip = lane_pad(I)
    f_lanes = lane_pad(F)
    out = bb * (LANES if fused else lane_pad(bt))
    blocks = bb * f_lanes + 4 * bt * ip + ip * ip + out
    step = (2 * bb * f_lanes + 2 * bt * ip + min(F, F_CHUNK) * ip
            + TREE_TEMPS * bb * ip)
    return 2 * blocks + step


def block_heuristics(B, T, I, F, *, fused):
    """Pick (BB, BT) so the kernel working set fits ``VMEM_BUDGET_BYTES``.

    ``vmem_words`` is the working set the kernel really allocates.  BB is
    a multiple of 8 (sublanes) and at most 128; BT is the tree-tile cap or
    all of T when T is smaller.  Both shapes are legal TPU blocks: a block
    dimension is a multiple of the tile or the whole (padded) array
    dimension.  Shrinking halves BB down to 8, then BT down to 8.

    The tree-tile cap is 8 for the unfused kernels (their [BB, BT] output
    tile pays bandwidth per extra tree) and 32 for the fused ones — their
    output tile is [BB, 1] regardless of BT, so more trees per pass cuts
    the grid steps.
    """
    bb = min(128, -(-B // 8) * 8)
    bt = min(32 if fused else 8, T)

    def fits(bb, bt):
        return vmem_words(bb, bt, I, F, fused=fused) * 4 <= VMEM_BUDGET_BYTES

    while not fits(bb, bt) and bb > 8:
        bb = max(8, bb // 2 // 8 * 8)
    while not fits(bb, bt) and bt > 8:
        bt = max(8, bt // 2 // 8 * 8)
    return bb, bt
