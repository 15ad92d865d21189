"""The plain reference against hand-worked trees and against the engine
at a small size, and the bfloat16 control against the limits."""

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, data, reference

FUSED = "predicated_pallas_fused"


def _stump(threshold, default_left, left, right):
    """One tree of depth 1 on feature 0."""
    return {"feature": jnp.array([[0]], jnp.int32),
            "threshold": jnp.array([[threshold]], jnp.float32),
            "default_left": jnp.array([[default_left]]),
            "leaf_value": jnp.array([[left, right]], jnp.float32)}


@pytest.mark.parametrize("default_left", [True, False])
def test_stump_directions(default_left):
    x = jnp.array([[0.2], [0.7], [0.5], [np.nan]], jnp.float32)
    got = np.asarray(reference.predict(_stump(0.5, default_left, -1.0, 2.0),
                                       x))
    nan_leaf = -1.0 if default_left else 2.0
    # below the threshold goes left, at or above it right, NaN its default
    want = 1 / (1 + np.exp(-np.array([-1.0, 2.0, 2.0, nan_leaf])))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_depth_two_path_and_sum_over_trees():
    # tree 0: root on feature 1 (< 0), left child on feature 0 (< 1);
    # tree 1: every node on feature 0 at threshold 0
    forest = {
        "feature": jnp.array([[1, 0, 0], [0, 0, 0]], jnp.int32),
        "threshold": jnp.array([[0.0, 1.0, 5.0], [0.0, 0.0, 0.0]],
                               jnp.float32),
        "default_left": jnp.array([[True, False, True]] * 2),
        "leaf_value": jnp.array([[0.01, 0.02, 0.03, 0.04],
                                 [0.1, 0.2, 0.3, 0.4]], jnp.float32)}
    x = jnp.array([[0.5, -1.0],        # t0: left, left;   t1: right, right
                   [2.0, -1.0],        # t0: left, right;  t1: right, right
                   [2.0, 3.0],         # t0: right, left;  t1: right, right
                   [np.nan, np.nan]],  # both: default left, then default
                  jnp.float32)         # right
    margin = np.array([0.41, 0.42, 0.43, 0.22])
    np.testing.assert_allclose(np.asarray(reference.predict(forest, x)),
                               1 / (1 + np.exp(-margin)), rtol=1e-6)


def test_blocks_give_the_same_answer():
    forest = data.forest_arrays(3, 7, 4, 11)
    x = data.table(3, 1000, 11)
    whole = reference.predict(forest, x, block_rows=4096)
    np.testing.assert_array_equal(
        np.asarray(reference.predict(forest, x, block_rows=128)),
        np.asarray(whole))


@pytest.fixture(scope="module")
def engine_case():
    """A small table with missing values, a random depth-8 forest, and
    the reference's predictions."""
    from repro.core.forest import make_forest
    rows, features, trees = 2000, 28, 16
    x = np.array(data.table(5, rows, features))
    x[np.random.default_rng(5).random(x.shape) < 0.2] = np.nan
    arrays = data.forest_arrays(5, trees, 8, features)
    forest = make_forest(arrays["feature"], arrays["threshold"],
                         arrays["leaf_value"],
                         default_left=arrays["default_left"],
                         n_features=features, model_type="xgboost")
    return x, arrays, forest, reference.predict(arrays, jnp.asarray(x))


@pytest.mark.parametrize("plan", ["udf", "rel+reuse"])
def test_engine_agrees_with_the_reference(engine_case, plan):
    from repro.core.reuse import ModelReuseCache
    from repro.db.query import ForestQueryEngine
    from repro.db.store import TensorBlockStore
    x, _, forest, want = engine_case
    store = TensorBlockStore()
    store.put("t", x, tier="device")
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                               plan_cache=ModelReuseCache())
    res = engine.infer("t", forest, algorithm=FUSED, plan=plan, n_parts=2)
    gap = float(jnp.max(jnp.abs(res.predictions - want)))
    assert gap <= check.LIMITS["max_prob_gap"]


def test_bf16_control_falls_outside_the_limit(engine_case):
    x, arrays, _, want = engine_case
    control = reference.predict(arrays, jnp.asarray(x), narrow=True)
    gap = float(jnp.max(jnp.abs(control - want)))
    assert gap > check.LIMITS["max_prob_gap"]
    correct, _ = check.verdict({"max_prob_gap": gap, "nonfinite": 0,
                                "missing": 0})
    assert not correct
