"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, allclose.

Kernels run in interpret mode on CPU (the kernel body executes in Python);
on a real TPU the same ``pallas_call`` compiles.  Each kernel is checked
against its matching jnp algorithm (ref.py), which is itself checked
against the naive while_loop in test_forest_algorithms.py.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.forest import make_forest
from repro.kernels.ops import KERNEL_ALGORITHMS, predict_raw_pallas
from repro.kernels.ref import REFERENCES

from conftest import (NODE_MAJOR_GRID, NODE_MAJOR_IDS, node_major_case,
                      random_forest_arrays)

KERNELS = sorted(KERNEL_ALGORITHMS)

SHAPE_GRID = [
    # (B, T, depth, F, block_b, block_t)
    (8, 4, 3, 8, 8, 4),
    (16, 5, 4, 11, 8, 2),        # padding on both axes
    (32, 8, 6, 16, 16, 4),
    (7, 3, 2, 5, 4, 2),          # tiny, non-aligned
    (24, 10, 8, 30, 8, 2),       # paper's depth-8 regime
]


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("shape", SHAPE_GRID,
                         ids=[f"B{b}T{t}d{d}F{f}" for b, t, d, f, _, _
                              in SHAPE_GRID])
def test_kernel_matches_ref(rng, kernel, shape):
    B, T, depth, F, bb, bt = shape
    fe, th, dl, lv = random_forest_arrays(rng, T=T, depth=depth, F=F,
                                          seed=hash((kernel, shape)) % 9973)
    forest = make_forest(fe, th, lv, default_left=dl, n_features=F)
    x = rng.normal(size=(B, F)).astype(np.float32)
    want = REFERENCES[kernel](forest, jnp.asarray(x))
    got = KERNEL_ALGORITHMS[kernel](forest, jnp.asarray(x),
                                    block_b=bb, block_t=bt, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_nan_inputs(rng, kernel):
    fe, th, dl, lv = random_forest_arrays(rng, T=4, depth=4, F=9, seed=31)
    forest = make_forest(fe, th, lv, default_left=dl, n_features=9)
    x = rng.normal(size=(12, 9)).astype(np.float32)
    x[rng.random(x.shape) < 0.25] = np.nan
    want = REFERENCES[kernel](forest, jnp.asarray(x))
    got = KERNEL_ALGORITHMS[kernel](forest, jnp.asarray(x),
                                    block_b=4, block_t=2, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtype_sweep(rng, dtype):
    """bf16 thresholds/leaves: kernels must stay allclose to the jnp ref
    evaluated at the same precision."""
    fe, th, dl, lv = random_forest_arrays(rng, T=4, depth=4, F=8, seed=77)
    forest = make_forest(fe, th, lv, default_left=dl, n_features=8)
    forest = forest.astype(dtype).astype(jnp.float32)  # quantize once
    x = rng.normal(size=(8, 8)).astype(np.float32)
    want = REFERENCES["predicated_pallas"](forest, jnp.asarray(x))
    got = predict_raw_pallas(forest, jnp.asarray(x),
                             "predicated_pallas", block_b=8, block_t=2,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", NODE_MAJOR_GRID, ids=NODE_MAJOR_IDS)
def test_predicated_node_major_walk(case):
    """Per-tree scores of the node-major walk are the row-major walk's
    bit for bit, and match the jnp reference."""
    forest, x, kw = node_major_case(*case, seed=sum(case[:4]))
    node_major = predict_raw_pallas(forest, x, "predicated_pallas",
                                    block_b=128, block_t=4, interpret=True,
                                    **kw)
    row_major = predict_raw_pallas(forest, x, "predicated_pallas",
                                   block_b=64, block_t=4, interpret=True,
                                   **kw)
    assert np.array_equal(np.asarray(node_major), np.asarray(row_major))
    want = REFERENCES["predicated_pallas"](forest, x)
    np.testing.assert_allclose(np.asarray(node_major), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_block_heuristics_fit_budget():
    from repro.kernels.common import block_heuristics, vmem_words
    for fused in (False, True):
        bb, bt = block_heuristics(4096, 1600, 255, 2000, fused=fused)
        # legal TPU blocks (multiples of 8) that fit the VMEM budget as
        # the kernel really allocates it (dense [bb, F] tile, F = 2000)
        assert bb % 8 == 0 and bt % 8 == 0, (bb, bt)
        assert vmem_words(bb, bt, 255, 2000, fused=fused) * 4 \
            <= 12 * 1024 * 1024


def test_block_heuristics_wide_sparse_sane():
    """criteo scale (F = 10k) dense: the sample tile [bb, F] is the real
    operand, so bb shrinks until it fits — but the one-hot is chunked over
    F (never [F, Ip] whole), so sample blocks stay MXU-sized and the tree
    block keeps its cap."""
    from repro.kernels.common import block_heuristics, vmem_words
    bb, bt = block_heuristics(4096, 1600, 255, 10_000, fused=True)
    assert bb >= 64, (bb, bt)
    assert bt == 32, (bb, bt)
    assert vmem_words(bb, bt, 255, 10_000, fused=True) * 4 \
        <= 12 * 1024 * 1024
    # the compact CSR tile (F_used = 255) keeps the full 128-row block
    assert block_heuristics(4096, 1600, 255, 255, fused=True) == (128, 32)
