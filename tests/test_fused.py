"""Fused (in-kernel aggregation) Pallas backends vs the unfused reference.

The contract: ``*_pallas_fused(forest, x)`` == ``aggregate_raw(
predict_raw_pallas(forest, x))`` for every algorithm, with tree/sample
padding never perturbing SUM or MEAN, and with NO [B, T] score matrix in
the traced program (checked on the jaxpr, not narrated).
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.forest import make_forest, pad_trees
from repro.core.postprocess import aggregate_raw, postprocess, predict_proba
from repro.kernels.ops import (FUSED_KERNEL_ALGORITHMS, KERNEL_ALGORITHMS,
                               predict_sum_pallas)
from repro.kernels.ref import ref_predicated
from repro.obs import METRICS, METRIC_NAMES

from conftest import (NODE_MAJOR_GRID, NODE_MAJOR_IDS, node_major_case,
                      random_forest_arrays)

BASES = ("predicated", "hummingbird", "quickscorer")

SHAPE_GRID = [
    # (B, T, depth, F, block_b, block_t)
    (8, 4, 3, 8, 8, 4),
    (16, 5, 4, 11, 8, 2),        # tree padding (5 -> 6)
    (7, 3, 2, 5, 4, 2),          # padding on both axes
    (24, 10, 8, 30, 8, 2),       # paper's depth-8 regime
    (9, 13, 5, 7, 8, 8),         # B and T both non-multiples
]


def _forest_and_x(rng, B, T, depth, F, seed, *, nan_frac=0.0,
                  integer_leaves=False):
    fe, th, dl, lv = random_forest_arrays(rng, T=T, depth=depth, F=F,
                                          seed=seed)
    if integer_leaves:
        r = np.random.default_rng(seed)
        lv = r.integers(-8, 9, lv.shape).astype(np.float32)
    forest = make_forest(fe, th, lv, default_left=dl, n_features=F)
    r = np.random.default_rng(seed + 1)
    x = r.normal(size=(B, F)).astype(np.float32)
    if nan_frac:
        x[r.random(x.shape) < nan_frac] = np.nan
    return forest, jnp.asarray(x)


@pytest.mark.parametrize("base", BASES)
@pytest.mark.parametrize("shape", SHAPE_GRID,
                         ids=[f"B{b}T{t}d{d}F{f}" for b, t, d, f, _, _
                              in SHAPE_GRID])
def test_fused_matches_unfused(rng, base, shape):
    B, T, depth, F, bb, bt = shape
    # crc32, not hash(): str hashing is PYTHONHASHSEED-randomized, and a
    # per-process seed would make any tolerance-marginal failure
    # unreproducible
    forest, x = _forest_and_x(rng, B, T, depth, F,
                              seed=zlib.crc32(f"{base}{shape}".encode())
                              % 9973)
    want = aggregate_raw(KERNEL_ALGORITHMS[base + "_pallas"](
        forest, x, block_b=bb, block_t=bt, interpret=True))
    got = FUSED_KERNEL_ALGORITHMS[base + "_pallas_fused"](
        forest, x, block_b=bb, block_t=bt, interpret=True)
    assert got.shape == (B,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("base", BASES)
def test_fused_bit_identical_on_exact_sums(rng, base):
    """Small-integer leaf values make every partial sum exact in f32, so
    the fused accumulation order must reproduce the unfused reduction
    BIT-identically (padding trees included: 5 trees -> block_t 4)."""
    forest, x = _forest_and_x(rng, 16, 5, 4, 9, seed=123,
                              integer_leaves=True)
    want = aggregate_raw(KERNEL_ALGORITHMS[base + "_pallas"](
        forest, x, block_b=8, block_t=4, interpret=True))
    got = FUSED_KERNEL_ALGORITHMS[base + "_pallas_fused"](
        forest, x, block_b=8, block_t=4, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(want)), (
        np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("base", BASES)
def test_fused_nan_features(rng, base):
    forest, x = _forest_and_x(rng, 12, 4, 4, 9, seed=31, nan_frac=0.25)
    want = aggregate_raw(KERNEL_ALGORITHMS[base + "_pallas"](
        forest, x, block_b=4, block_t=2, interpret=True))
    got = FUSED_KERNEL_ALGORITHMS[base + "_pallas_fused"](
        forest, x, block_b=4, block_t=2, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("base", BASES)
def test_fused_tree_padding_preserves_mean(rng, base):
    """MEAN semantics: padding 5 trees to a block multiple must not change
    the randomforest mean (zero-leaf pads + division by the TRUE count)."""
    fe, th, dl, lv = random_forest_arrays(rng, T=5, depth=3, F=7, seed=77)
    lv = np.abs(lv) / (np.abs(lv).max() + 1.0)   # valid probabilities
    forest = make_forest(fe, th, lv, default_left=dl, n_features=7,
                         model_type="randomforest")
    r = np.random.default_rng(7)
    x = jnp.asarray(r.normal(size=(6, 7)).astype(np.float32))
    summed = FUSED_KERNEL_ALGORITHMS[base + "_pallas_fused"](
        forest, x, block_b=8, block_t=4, interpret=True)
    got = postprocess(summed, model_type="randomforest",
                      task="classification", num_trees=5)
    want = predict_proba(forest, x, algorithm="predicated")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_fused_trace_has_no_bt_matrix(rng):
    """The fused program must not contain ANY [B_padded, T_padded]
    intermediate, while the unfused one does — asserted on the jaxpr."""
    B, T, bb, bt = 16, 8, 8, 4
    forest, x = _forest_and_x(rng, B, T, 4, 9, seed=5)
    Bp, Tp = B, T                      # already block multiples

    def shapes(fn):
        jaxpr = jax.make_jaxpr(fn)(x)
        out = set()
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                out.add(tuple(getattr(v.aval, "shape", ())))
        return out

    unfused = shapes(lambda xx: KERNEL_ALGORITHMS["hummingbird_pallas"](
        forest, xx, block_b=bb, block_t=bt, interpret=True))
    fused = shapes(lambda xx: FUSED_KERNEL_ALGORITHMS[
        "hummingbird_pallas_fused"](forest, xx, block_b=bb, block_t=bt,
                                    interpret=True))
    assert (Bp, Tp) in unfused          # sanity: the reference materializes
    assert (Bp, Tp) not in fused
    assert (Bp, 1) in fused


def test_predict_sum_pallas_dispatch(rng):
    forest, x = _forest_and_x(rng, 8, 4, 3, 6, seed=11)
    got = predict_sum_pallas(forest, x, "quickscorer_pallas_fused",
                             block_b=8, block_t=4, interpret=True)
    want = aggregate_raw(KERNEL_ALGORITHMS["quickscorer_pallas"](
        forest, x, block_b=8, block_t=4, interpret=True))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    with pytest.raises(ValueError):
        predict_sum_pallas(forest, x, "nope")


@pytest.mark.parametrize("base", BASES)
def test_fused_default_blocks_and_padding(rng, base):
    """No explicit blocks: the heuristics pick them, padding both axes."""
    forest, x = _forest_and_x(rng, 11, 6, 4, 13, seed=900)
    want = aggregate_raw(KERNEL_ALGORITHMS[base + "_pallas"](
        forest, x, interpret=True))
    got = FUSED_KERNEL_ALGORITHMS[base + "_pallas_fused"](
        forest, x, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("case", NODE_MAJOR_GRID, ids=NODE_MAJOR_IDS)
def test_fused_predicated_node_major_walk(case):
    """128-row tiles walk node-major; the fused sums are the row-major
    walk's (``block_b=64``) bit for bit and match the reference."""
    forest, x, kw = node_major_case(*case, seed=sum(case[:4]) + 1)
    fused = FUSED_KERNEL_ALGORITHMS["predicated_pallas_fused"]
    node_major = fused(forest, x, block_b=128, block_t=4, interpret=True,
                       **kw)
    row_major = fused(forest, x, block_b=64, block_t=4, interpret=True,
                      **kw)
    assert np.array_equal(np.asarray(node_major), np.asarray(row_major))
    want = aggregate_raw(ref_predicated(forest, x))
    np.testing.assert_allclose(np.asarray(node_major), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


WALKS = ("kernel.predicated.node_major_traces",
         "kernel.predicated.row_major_traces")


def test_walk_counters_follow_the_row_tile():
    """Default blocks: a 1,024-row scan batch traces the node-major walk;
    the serve plane's 8- and 32-row buckets trace only the row-major one."""
    assert set(WALKS) <= set(METRIC_NAMES)
    forest, x, _ = node_major_case(1024, 5, 8, 28, None, 0.1, seed=3)

    def traced(B):
        before = [METRICS.counter(n).value for n in WALKS]
        jax.block_until_ready(FUSED_KERNEL_ALGORITHMS[
            "predicated_pallas_fused"](forest, x[:B], interpret=True))
        return [METRICS.counter(n).value - b for n, b in zip(WALKS, before)]

    node_major, row_major = traced(1024)
    assert node_major > 0 and row_major == 0
    for bucket in (8, 32):
        node_major, row_major = traced(bucket)
        assert node_major == 0 and row_major > 0
