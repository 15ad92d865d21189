"""In-database layer: store, plans (udf / rel / rel+reuse), loaders.

The paper's core systems claims, as testable invariants:
  * all three physical plans produce identical predictions;
  * udf compiles to ONE pipeline stage, rel to multiple (Sec. 3.2/3.3);
  * model-reuse skips the partition stage on the second query (netsDB-OPT);
  * external loaders (CSV / LIBSVM / array-rows) round-trip exactly and
    report the split load/convert/transfer timings the benchmarks plot.
"""

import os

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.postprocess import predict_proba
from repro.core.reuse import ModelReuseCache
from repro.core.train import TrainConfig, train_forest
from repro.db.loader import (load_array_rows_external, load_csv_external,
                             load_libsvm_external, synth_dataset,
                             write_array_rows, write_csv, write_libsvm)
from repro.obs import METRICS
from repro.db.query import ForestQueryEngine
from repro.db.store import TensorBlockStore


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(300, 8)).astype(np.float32)
    w = rng.normal(size=8).astype(np.float32)
    y = (x @ w > 0).astype(np.float32)
    forest = train_forest(x, y, TrainConfig(model_type="xgboost",
                                            num_trees=12, max_depth=4))
    store = TensorBlockStore(default_page_rows=64)
    store.put("test", x, labels=y)
    return store, forest, x


PLANS = ["udf", "rel", "rel+reuse"]


@pytest.mark.parametrize("plan", PLANS)
@pytest.mark.parametrize("algorithm", ["predicated", "hummingbird",
                                       "quickscorer"])
def test_plans_agree_with_direct(setup, plan, algorithm):
    store, forest, x = setup
    engine = ForestQueryEngine(store,
                               reuse_cache=ModelReuseCache())
    res = engine.infer("test", forest, algorithm=algorithm, plan=plan)
    direct = predict_proba(forest, jnp.asarray(x), algorithm=algorithm)
    np.testing.assert_allclose(np.asarray(res.predictions),
                               np.asarray(direct), rtol=1e-5, atol=1e-6)


def test_stage_counts(setup):
    store, forest, _ = setup
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache())
    udf = engine.infer("test", forest, plan="udf")
    rel = engine.infer("test", forest, plan="rel")
    assert udf.num_stages == 1
    assert rel.num_stages >= 4      # partition, cross-product, agg, write


def test_model_reuse_skips_partition(setup):
    store, forest, _ = setup
    cache = ModelReuseCache()
    engine = ForestQueryEngine(store, reuse_cache=cache)
    r1 = engine.infer("test", forest, plan="rel+reuse", model_id="m1")
    r2 = engine.infer("test", forest, plan="rel+reuse", model_id="m1")
    assert not r1.reuse_hit and r2.reuse_hit
    assert cache.stats.hits == 1 and cache.stats.misses == 1
    assert r2.partition_s == 0.0
    np.testing.assert_allclose(np.asarray(r1.predictions),
                               np.asarray(r2.predictions))


FUSED = ["predicated_pallas_fused", "hummingbird_pallas_fused",
         "quickscorer_pallas_fused"]


@pytest.mark.parametrize("plan", PLANS)
@pytest.mark.parametrize("algorithm", FUSED)
def test_fused_plans_agree_with_direct(setup, plan, algorithm):
    """Fused in-kernel aggregation backends through every physical plan."""
    store, forest, x = setup
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                               plan_cache=ModelReuseCache())
    res = engine.infer("test", forest, algorithm=algorithm, plan=plan)
    base = algorithm.replace("_pallas_fused", "")
    direct = predict_proba(forest, jnp.asarray(x), algorithm=base)
    np.testing.assert_allclose(np.asarray(res.predictions),
                               np.asarray(direct), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("plan", ["udf", "rel+reuse"])
def test_compiled_plan_cache_no_retrace(setup, plan):
    """Second identical query: reuse_hit, zero partition time, and ZERO
    re-traces of any stage function (the compile counter must not move)."""
    store, forest, x = setup
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                               plan_cache=ModelReuseCache())
    kw = dict(algorithm="hummingbird_pallas_fused", plan=plan,
              model_id="plan-cache-m1")
    r1 = engine.infer("test", forest, **kw)
    assert not r1.plan_reuse_hit
    traces_after_first = METRICS.counter("plan.traces").value
    assert traces_after_first > 0

    r2 = engine.infer("test", forest, **kw)
    assert r2.reuse_hit and r2.plan_reuse_hit
    assert r2.partition_s == 0.0
    assert METRICS.counter("plan.traces").value == traces_after_first, \
        "stage re-traced"
    np.testing.assert_allclose(np.asarray(r1.predictions),
                               np.asarray(r2.predictions))


def test_plan_cache_distinguishes_batch_shape(setup):
    """A different page batching is a different executable: no false hit."""
    store, forest, _ = setup
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                               plan_cache=ModelReuseCache())
    r1 = engine.infer("test", forest, plan="udf", model_id="m-bs")
    r2 = engine.infer("test", forest, plan="udf", model_id="m-bs",
                      batch_pages=2)
    assert not r2.plan_reuse_hit
    np.testing.assert_allclose(np.asarray(r1.predictions),
                               np.asarray(r2.predictions), rtol=1e-6)


def test_plan_cache_not_stale_after_model_eviction(setup):
    """If the model cache evicts and rebuilds a materialization, the plan
    cache must MISS (its stages close over the old mat) — partition cost
    is honestly reported and no stale executable is served."""
    store, forest, x = setup
    rng = np.random.default_rng(3)
    y2 = (x @ rng.normal(size=x.shape[1]).astype(np.float32) > 0)
    forest2 = train_forest(x, y2.astype(np.float32),
                           TrainConfig(model_type="xgboost", num_trees=8,
                                       max_depth=3))
    engine = ForestQueryEngine(store,
                               reuse_cache=ModelReuseCache(max_entries=1),
                               plan_cache=ModelReuseCache())
    kw = dict(algorithm="predicated", plan="rel+reuse")
    r1 = engine.infer("test", forest, model_id="mA", **kw)
    engine.infer("test", forest2, model_id="mB", **kw)   # evicts mA's mat
    r3 = engine.infer("test", forest, model_id="mA", **kw)
    assert not r3.reuse_hit and not r3.plan_reuse_hit
    assert r3.partition_s > 0.0
    np.testing.assert_allclose(np.asarray(r3.predictions),
                               np.asarray(r1.predictions))


def test_engine_invalidate_sweeps_both_caches(setup):
    """Regression: plan keys lead with a kind tag ('udf-plan'/'rel-plan'),
    so ModelReuseCache.invalidate's key[0] == model_id match silently
    misses every compiled plan.  The engine-level invalidate must sweep
    BOTH the partition cache and the plan cache."""
    store, forest, _ = setup
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                               plan_cache=ModelReuseCache())
    engine.infer("test", forest, plan="udf", model_id="mX")
    engine.infer("test", forest, plan="rel+reuse", model_id="mX")
    assert len(engine.cache) == 1 and len(engine.plan_cache) == 2
    # the raw cache-level sweep is exactly the silent miss being fixed
    assert engine.plan_cache.invalidate("mX") == 0
    n = engine.invalidate("mX")
    assert n == 3
    assert len(engine.cache) == 0 and len(engine.plan_cache) == 0
    # next queries rebuild from scratch: no stale hit either way
    r = engine.infer("test", forest, plan="rel+reuse", model_id="mX")
    assert not r.reuse_hit and not r.plan_reuse_hit
    # other models' entries survive a targeted sweep
    engine.infer("test", forest, plan="udf", model_id="mY")
    engine.invalidate("mX")
    assert len(engine.plan_cache) == 1


def test_reuse_cache_is_lru_not_fifo():
    """A hit must refresh recency: with capacity 2, touching A before
    inserting C must evict B (FIFO would evict the hot A)."""
    import dataclasses as _dc

    @_dc.dataclass
    class E:
        v: int
        build_time_s: float = 0.0

    cache = ModelReuseCache(max_entries=2)
    cache.get_or_build(("A",), lambda: E(1))
    cache.get_or_build(("B",), lambda: E(2))
    cache.get_or_build(("A",), lambda: E(-1))      # hit: refresh A
    cache.get_or_build(("C",), lambda: E(3))       # evicts B, not A
    assert cache.get_or_build(("A",), lambda: E(-2)).v == 1, "hot A evicted"
    assert cache.get_or_build(("B",), lambda: E(4)).v == 4, "B survived"


def test_rel_n_parts_default_derived_from_tree_block(setup):
    """Mesh-less rel partitioning of kernel-backed algorithms derives
    from the kernel tree-block heuristic (ceil(T / tree_block)), not the
    old magic 4; jnp backends (no tree blocks) keep the small default."""
    store, forest, x = setup
    from repro.core.forest import make_forest
    from repro.kernels.ops import default_tree_block
    from conftest import random_forest_arrays
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache())
    # jnp backend: thread-count-like default, clamped to the tree count
    r = engine.infer("test", forest, plan="rel", algorithm="predicated")
    assert r.n_parts == 4
    # fused kernel backend, 12 trees <= one 32-tree block -> 1 partition
    rf = engine.infer("test", forest, plan="rel",
                      algorithm="predicated_pallas_fused")
    assert rf.n_parts == 1
    # a forest wider than one tree block really splits: 100 trees / 32
    fe, th, dl, lv = random_forest_arrays(np.random.default_rng(5),
                                          T=100, depth=3, F=8, seed=5)
    wide = make_forest(fe, th, lv, default_left=dl, n_features=8)
    bt = default_tree_block(wide, fused=True)
    assert engine._resolve_n_parts(wide, "predicated_pallas_fused", None) \
        == -(-100 // bt) == 4
    direct = predict_proba(forest, jnp.asarray(x), algorithm="predicated")
    np.testing.assert_allclose(np.asarray(r.predictions),
                               np.asarray(direct), rtol=1e-5, atol=1e-6)


def test_rel_n_parts_override(setup):
    """infer(n_parts=...) overrides the mesh-less partition count; the
    partition count is part of both rel cache keys (no false sharing)."""
    store, forest, x = setup
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                               plan_cache=ModelReuseCache())
    kw = dict(algorithm="predicated_pallas_fused", plan="rel+reuse",
              model_id="np-m1")
    r3 = engine.infer("test", forest, n_parts=3, **kw)
    assert r3.n_parts == 3
    r4 = engine.infer("test", forest, n_parts=4, **kw)
    assert r4.n_parts == 4 and not r4.reuse_hit, \
        "different n_parts must be a different materialization + plan"
    again = engine.infer("test", forest, n_parts=3, **kw)
    assert again.reuse_hit and again.n_parts == 3
    direct = predict_proba(forest, jnp.asarray(x), algorithm="predicated")
    for r in (r3, r4):
        np.testing.assert_allclose(np.asarray(r.predictions),
                                   np.asarray(direct), rtol=1e-5, atol=1e-6)


def test_batching_equivalence(setup):
    """F3: page-batched execution must equal single-batch execution."""
    store, forest, x = setup
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache())
    whole = engine.infer("test", forest, plan="udf")
    batched = engine.infer("test", forest, plan="udf", batch_pages=2)
    np.testing.assert_allclose(np.asarray(batched.predictions),
                               np.asarray(whole.predictions), rtol=1e-6)


def test_device_tier_batch_bounded_by_predictor(setup, monkeypatch):
    """A resident table is not scored in one batch when the predictor's
    intermediates for all of its rows would pass ``PREDICT_BATCH_BYTES``:
    the default batch shrinks to what fits, with the one-batch answer."""
    from repro.db import query
    store, forest, x = setup
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache())
    whole = engine.infer("test", forest, plan="udf")
    assert whole.scan.batches == 1
    page_cost = 64 * query.predict_row_bytes("predicated", forest,
                                             x.shape[1])
    monkeypatch.setattr(query, "PREDICT_BATCH_BYTES", 2 * page_cost)
    cut = engine.infer("test", forest, plan="udf")
    assert cut.scan.batch_pages == 2 and cut.scan.batches == 3
    np.testing.assert_allclose(np.asarray(cut.predictions),
                               np.asarray(whole.predictions), rtol=1e-6)


def test_write_operator(setup):
    store, forest, _ = setup
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache())
    res = engine.infer("test", forest, plan="udf", write_as="preds_out")
    assert "preds_out" in store
    out = store.get("preds_out")
    assert out.num_rows == 300
    assert res.write_s >= 0.0


def test_store_page_padding():
    store = TensorBlockStore(default_page_rows=64)
    ds = store.put("odd", np.ones((100, 4), np.float32))
    assert ds.num_rows == 100
    assert ds.data.shape[0] % 64 == 0
    # padded rows are NaN (never counted in results)
    tail = np.asarray(ds.data)[100:]
    assert np.isnan(tail).all()


def test_synth_dataset_same_in_every_process():
    """Seeded data does not depend on the per-process str-hash salt: these
    values are pinned, so every process draws them."""
    x, y = synth_dataset("higgs", seed=0, max_rows=4)
    np.testing.assert_array_equal(
        x[0, :3], np.array([-1.316493, -1.6562703, 1.0178546], np.float32))
    np.testing.assert_array_equal(y, np.array([0, 0, 1, 1], np.float32))


def test_compile_cache_dir_env_or_checkout(monkeypatch, tmp_path):
    """The persistent compile cache lives where the environment says, or
    at the fixed ``.jax_cache`` of the checkout — nowhere else."""
    import jax
    from repro import compile_cache
    saved = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == saved   # JAX reads env
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CHECKOUT / ".jax_cache")
        assert (compile_cache.CHECKOUT / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


# ---------------------------------------------------------------------------
# external loaders (the data-loading cost the paper measures)
# ---------------------------------------------------------------------------


def test_csv_roundtrip(tmp_path):
    x, _ = synth_dataset("fraud", max_rows=50)
    p = str(tmp_path / "d.csv")
    write_csv(p, x)
    dev, timing = load_csv_external(p)
    np.testing.assert_allclose(np.asarray(dev), x, rtol=1e-4, atol=1e-5)
    assert timing.total_s > 0 and timing.parse_s > 0


def test_libsvm_roundtrip(tmp_path):
    x, y = synth_dataset("bosch", max_rows=40)
    p = str(tmp_path / "d.svm")
    write_libsvm(p, x, y)
    dev, labels, timing = load_libsvm_external(p, x.shape[1])
    got = np.asarray(dev)
    mask = ~np.isnan(x) & (x != 0.0)
    np.testing.assert_allclose(got[mask], x[mask], rtol=1e-4, atol=1e-5)
    assert np.isnan(got[~mask]).all()
    np.testing.assert_allclose(labels, y)


def test_array_rows_roundtrip(tmp_path):
    x, _ = synth_dataset("epsilon", max_rows=10)
    p = str(tmp_path / "d.arr")
    write_array_rows(p, x)
    dev, timing = load_array_rows_external(p)
    np.testing.assert_allclose(np.asarray(dev), x, rtol=1e-4, atol=1e-5)
    assert timing.convert_s >= 0
