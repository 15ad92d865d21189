"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches must
see 1 device; only launch/dryrun.py forces 512 placeholder devices."""

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def random_forest_arrays(rng, *, T=7, depth=4, F=11, seed=None):
    """Random dense complete forest arrays (valid for every backend)."""
    r = np.random.default_rng(seed) if seed is not None else rng
    I, L = (1 << depth) - 1, 1 << depth
    feature = r.integers(0, F, (T, I)).astype(np.int32)
    threshold = r.normal(size=(T, I)).astype(np.float32)
    default_left = r.random((T, I)) < 0.5
    leaf_value = r.normal(size=(T, L)).astype(np.float32)
    return feature, threshold, default_left, leaf_value


#: row tiles of 128, where ``predicated_tree_score`` walks node-major;
#: scoring the same rows with ``block_b=64`` sends them down the
#: row-major walk by the same shape rule
NODE_MAJOR_GRID = [
    # (B, T, depth, F, tree_dtype, nan_frac); T is never a multiple of 4
    (128, 5, 8, 28, None, 0.0),
    (129, 7, 3, 255, "bfloat16", 0.2),
    (1024, 6, 8, 255, None, 0.1),
    (1024, 9, 3, 28, "bfloat16", 0.0),
    (128, 11, 8, 255, "bfloat16", 0.2),
    (129, 5, 8, 28, None, 0.2),
]
NODE_MAJOR_IDS = [f"B{b}T{t}d{d}F{f}-{dt or 'f32'}-nan{n}"
                  for b, t, d, f, dt, n in NODE_MAJOR_GRID]


def node_major_case(B, T, depth, F, tree_dtype, nan_frac, seed):
    """Forest (quantized to ``tree_dtype`` once, so the f32 reference
    sees the values the kernel stages), rows with NaNs, kernel kwargs."""
    import jax.numpy as jnp
    from repro.core.forest import make_forest

    fe, th, dl, lv = random_forest_arrays(None, T=T, depth=depth, F=F,
                                          seed=seed)
    forest = make_forest(fe, th, lv, default_left=dl, n_features=F)
    kw = {}
    if tree_dtype is not None:
        forest = forest.astype(tree_dtype).astype(jnp.float32)
        kw["tree_dtype"] = jnp.dtype(tree_dtype)
    r = np.random.default_rng(seed + 1)
    x = r.normal(size=(B, F)).astype(np.float32)
    x[r.random(x.shape) < nan_frac] = np.nan
    return forest, jnp.asarray(x), kw


@pytest.fixture
def random_forest(rng):
    from repro.core.forest import make_forest

    feature, threshold, default_left, leaf_value = \
        random_forest_arrays(rng, seed=42)
    return make_forest(feature, threshold, leaf_value,
                       default_left=default_left, n_features=11,
                       model_type="xgboost")
