"""Cost-based optimizer: decisions, persistence, invalidation, routing.

The optimizer's contract, as testable invariants:
  * ``infer(plan="auto", algorithm="auto")`` resolves to a concrete
    feasible cell and its predictions are BIT-identical to running that
    cell statically;
  * the first decision per (model, dataset signature, mesh) pays one
    bounded autotune pass; every repeat is a persisted-decision lookup
    (``optimizer.decision_cache_hits``; ZERO ``autotune_runs`` deltas);
  * decisions are swept exactly like compiled plans —
    ``engine.invalidate(model_id)``, ``store.drop``,
    ``invalidate_dataset``, and a re-``put`` of the dataset
    (stale-decision regression) all remove them;
  * the analytic cost model ranks the paper's asymptotics correctly
    (hummingbird's GEMM grows with 2^{2·depth}; everything grows with
    rows × trees) and the calibrated-peaks table is measured once;
  * the serving plane resolves ``algorithm="auto"`` once at
    registration;
  * importing ``launch.hillclimb`` is side-effect-free (regression for
    the XLA_FLAGS-above-docstring bug).
"""

import os

import numpy as np
import pytest

from repro.core.reuse import ModelReuseCache
from repro.core.train import TrainConfig, train_forest
from repro.db.optimizer import (DEFAULT_ALGORITHMS, CostBasedOptimizer,
                                Decision, _forest_flop_bytes,
                                dataset_signature)
from repro.db.query import ForestQueryEngine
from repro.db.store import TensorBlockStore
from repro.obs import METRICS


def _counter(name: str) -> int:
    return METRICS.counter_values().get(name, 0)


def _tight(engine) -> CostBasedOptimizer:
    """Test-sized budgets: tiny probes, no minutes-long autotunes."""
    opt = CostBasedOptimizer(engine, measure_budget_s=2.0,
                             max_measurements=6, probe_iters=1)
    engine.optimizer = opt
    return opt


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(300, 8)).astype(np.float32)
    w = rng.normal(size=8).astype(np.float32)
    y = (x @ w > 0).astype(np.float32)
    forest = train_forest(x, y, TrainConfig(model_type="xgboost",
                                            num_trees=12, max_depth=4))
    return forest, x


def _store(x) -> TensorBlockStore:
    store = TensorBlockStore(default_page_rows=64)
    store.put("ds", x)
    return store


# ---------------------------------------------------------------------------
# auto routing + decision persistence
# ---------------------------------------------------------------------------

def test_auto_matches_static_bit_identically(setup):
    forest, x = setup
    engine = ForestQueryEngine(_store(x), reuse_cache=ModelReuseCache())
    _tight(engine)
    res = engine.infer("ds", forest, plan="auto", algorithm="auto")
    assert res.decision is not None
    assert res.algorithm in DEFAULT_ALGORITHMS
    assert res.plan in ("udf", "rel+reuse")
    static = engine.infer("ds", forest, plan=res.plan,
                          algorithm=res.algorithm, n_parts=res.n_parts)
    assert np.array_equal(np.asarray(res.predictions),
                          np.asarray(static.predictions), equal_nan=True)


def test_repeat_auto_is_a_lookup_not_an_autotune(setup):
    forest, x = setup
    engine = ForestQueryEngine(_store(x), reuse_cache=ModelReuseCache())
    _tight(engine)
    first = engine.infer("ds", forest, plan="auto", algorithm="auto")
    runs0, hits0 = _counter("optimizer.autotune_runs"), \
        _counter("optimizer.decision_cache_hits")
    again = engine.infer("ds", forest, plan="auto", algorithm="auto")
    assert _counter("optimizer.autotune_runs") == runs0     # ZERO re-runs
    assert _counter("optimizer.decision_cache_hits") == hits0 + 1
    assert again.decision == first.decision
    assert (again.algorithm, again.plan) == (first.algorithm, first.plan)


def test_pinned_axis_constrains_the_decision(setup):
    forest, x = setup
    engine = ForestQueryEngine(_store(x), reuse_cache=ModelReuseCache())
    _tight(engine)
    res = engine.infer("ds", forest, plan="rel+reuse", algorithm="auto")
    assert res.plan == "rel+reuse"
    assert res.algorithm in DEFAULT_ALGORITHMS
    res2 = engine.infer("ds", forest, plan="auto",
                        algorithm="hummingbird")
    assert res2.algorithm == "hummingbird"
    assert res2.plan in ("udf", "rel+reuse")


def test_decision_persists_in_store_catalog(setup):
    forest, x = setup
    store = _store(x)
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache())
    _tight(engine)
    engine.infer("ds", forest, plan="auto", algorithm="auto")
    cat = store.decision_catalog()
    assert len(cat) == 1
    (key, entry), = cat.items()
    assert key[0] == engine._model_key(forest, None)     # fingerprint
    assert key[1] == "ds"                                # dataset name
    assert key[2] == dataset_signature(store.get("ds"))
    assert entry["source"] in ("measured", "model")
    assert entry["plan"] in ("udf", "rel+reuse")


def test_host_tier_auto_never_exceeds_predictor_cap(setup, monkeypatch):
    """Regression: the hillclimb passes ``batch_pages`` explicitly, which
    skips the engine's default cap — doubling from the default must stop
    at ``max_batch_pages``, and so must the decision it persists."""
    from repro.db import query
    forest, x = setup
    store = TensorBlockStore(default_page_rows=64)
    ds = store.put("ds", x, tier="host")
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache())
    engine.optimizer = CostBasedOptimizer(
        engine, measure_budget_s=30.0, max_measurements=8, probe_iters=1,
        uncertainty_band=1e9)
    page_cost = 64 * query.predict_row_bytes("predicated", forest,
                                             x.shape[1])
    monkeypatch.setattr(query, "PREDICT_BATCH_BYTES", 2 * page_cost)
    cap = engine.max_batch_pages(ds, forest, "predicated")
    assert cap == 2 < ds.num_pages
    assert engine.default_batch_pages(ds, forest, "predicated") == cap
    ran = []
    infer = engine._infer

    def spy(*a, **kw):
        res = infer(*a, **kw)
        ran.append((kw.get("batch_pages"), res.scan.batch_pages))
        return res

    monkeypatch.setattr(engine, "_infer", spy)
    res = engine.infer("ds", forest, plan="auto", algorithm="predicated")
    assert any(asked is not None for asked, _ in ran), \
        "the hillclimb never probed a batch size"
    assert max(got for _, got in ran) <= cap, ran
    assert res.decision.batch_pages is None \
        or res.decision.batch_pages <= cap
    assert res.scan.batch_pages <= cap


# ---------------------------------------------------------------------------
# invalidation: decisions are swept exactly like compiled plans
# ---------------------------------------------------------------------------

def test_invalidate_model_sweeps_decisions(setup):
    forest, x = setup
    store = _store(x)
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache())
    _tight(engine)
    engine.infer("ds", forest, plan="auto", algorithm="auto")
    assert len(store.decision_catalog()) == 1
    mid = engine._model_key(forest, None)
    swept = engine.invalidate(mid)
    assert swept >= 1                       # plans + the decision
    assert store.decision_catalog() == {}
    # next auto query re-decides (miss), not a stale hit
    misses0 = _counter("optimizer.decision_cache_misses")
    engine.infer("ds", forest, plan="auto", algorithm="auto")
    assert _counter("optimizer.decision_cache_misses") == misses0 + 1


def test_store_drop_sweeps_decisions(setup):
    forest, x = setup
    store = _store(x)
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache())
    _tight(engine)
    engine.infer("ds", forest, plan="auto", algorithm="auto")
    assert len(store.decision_catalog()) == 1
    swept = store.drop("ds")
    assert swept >= 1
    assert store.decision_catalog() == {}


def test_invalidate_dataset_sweeps_decisions(setup):
    forest, x = setup
    store = _store(x)
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache())
    _tight(engine)
    engine.infer("ds", forest, plan="auto", algorithm="auto")
    assert engine.invalidate_dataset("ds") >= 1
    assert store.decision_catalog() == {}


def test_stale_decision_swept_after_re_put(setup):
    """Regression: re-putting a dataset must not leave the old decision
    resident — even though the new SIGNATURE would miss anyway, a stale
    entry would resurface if the old shape ever came back."""
    forest, x = setup
    store = _store(x)
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache())
    _tight(engine)
    engine.infer("ds", forest, plan="auto", algorithm="auto")
    assert len(store.decision_catalog()) == 1
    store.put("ds", x[:128])                 # reshaped re-put
    assert store.decision_catalog() == {}
    misses0 = _counter("optimizer.decision_cache_misses")
    engine.infer("ds", forest, plan="auto", algorithm="auto")
    assert _counter("optimizer.decision_cache_misses") == misses0 + 1


def test_signature_conditions_on_tier_and_shape(setup):
    forest, x = setup
    store = _store(x)
    ds = store.get("ds")
    sig = dataset_signature(ds)
    assert sig[3] == "device"
    store.move("ds", "host")
    assert dataset_signature(store.get("ds"))[3] == "host"
    assert dataset_signature(store.get("ds")) != sig


# ---------------------------------------------------------------------------
# analytic cost model + calibrated peaks
# ---------------------------------------------------------------------------

def test_cost_model_ranks_the_paper_asymptotics():
    kw = dict(rows=4096, trees=100, depth=8, f_used=32)
    hb_flops = _forest_flop_bytes("hummingbird", **kw)[0]
    qs_flops = _forest_flop_bytes("quickscorer", **kw)[0]
    pr_flops = _forest_flop_bytes("predicated", **kw)[0]
    # hummingbird's GEMM term is 2·B·T·L·I ≫ quickscorer's bit ops ≫
    # predicated's per-level selects (the flip the optimizer exploits)
    assert hb_flops > qs_flops > pr_flops
    # everything scales ~linearly in rows and trees
    f2 = _forest_flop_bytes("predicated", rows=8192, trees=100, depth=8,
                            f_used=32)[0]
    assert f2 == pytest.approx(2 * pr_flops, rel=0.01)


def test_score_cell_orders_by_work(setup):
    forest, x = setup
    engine = ForestQueryEngine(_store(x), reuse_cache=ModelReuseCache())
    opt = _tight(engine)
    from repro.db.optimizer import _Cell
    from repro.launch.roofline import resolve_peaks
    peaks = resolve_peaks()
    kw = dict(trees=100, depth=8, f_used=32, data_nbytes=1 << 20,
              num_pages=16, page_rows=256, batch_pages=16, peaks=peaks)
    small = opt.score_cell(_Cell("predicated", "udf", "device"),
                           rows=1024, **kw)
    big = opt.score_cell(_Cell("predicated", "udf", "device"),
                         rows=65536, **kw)
    assert 0 < small < big
    # off-device tiers pay the transfer term
    host = opt.score_cell(_Cell("predicated", "udf", "host"),
                          rows=1024, **kw)
    assert host > small


def test_tpu_peaks_keyed_by_device_kind(monkeypatch):
    """A TPU gets the published table of its own device kind; a kind not
    in the table is an error, never the v5e numbers by default."""
    import jax
    from repro.launch import roofline

    class Dev:
        device_kind = "TPU of an unknown kind"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [Dev()])
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.resolve_peaks("tpu")
    Dev.device_kind = "TPU v5 lite"
    peaks = roofline.resolve_peaks("tpu")
    assert peaks["device_kind"] == "TPU v5 lite"
    assert peaks["hbm_bandwidth"] == 819e9
    assert peaks["measured"] is False        # gather/h2d/dispatch assumed


def test_calibrated_peaks_measured_once_and_positive():
    from repro.launch import roofline
    p1 = roofline.calibrate_peaks()
    assert p1["measured"] is True
    for k in ("peak_flops_bf16", "hbm_bandwidth", "ici_bandwidth",
              "gather_bandwidth", "h2d_bandwidth", "dispatch_s"):
        assert p1[k] > 0
    assert roofline.calibrate_peaks() is p1          # cached
    assert roofline.resolve_peaks() is p1            # non-TPU backend
    # the production-mesh dryrun keeps modeling v5e explicitly
    from repro.launch.mesh import V5E
    assert roofline.roofline_terms(
        flops_per_chip=V5E["peak_flops_bf16"], bytes_per_chip=1.0,
        coll_bytes_per_chip=0.0)["compute_s"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# serving plane
# ---------------------------------------------------------------------------

def test_serve_register_model_resolves_auto(setup):
    from repro.serve.forest import ForestServeEngine
    forest, x = setup
    se = ForestServeEngine(buckets=(8, 32))
    _tight(se.qe)
    m = se.register_model("m", forest, algorithm="auto", plan="auto")
    assert m.algorithm in DEFAULT_ALGORITHMS
    assert m.plan in ("udf", "rel+reuse")
    # the row decision persisted under the #rows sentinel: dataset
    # sweeps never touch it, model invalidation does
    cat = se.store.decision_catalog()
    assert len(cat) == 1
    (key, _), = cat.items()
    assert key[1] == "#rows"
    assert se.store.drop_decisions(dataset="ds") == 0
    assert se.qe.invalidate(m.model_id) >= 1
    assert se.store.decision_catalog() == {}


def test_infer_rows_auto_routes_through_row_decision(setup):
    forest, x = setup
    engine = ForestQueryEngine(_store(x), reuse_cache=ModelReuseCache())
    _tight(engine)
    batch = np.zeros((16, forest.n_features), np.float32)
    res = engine.infer_rows(forest, batch, algorithm="auto", plan="auto")
    assert res.algorithm in DEFAULT_ALGORITHMS
    assert res.plan in ("udf", "rel+reuse")
    runs0 = _counter("optimizer.autotune_runs")
    engine.infer_rows(forest, batch, algorithm="auto", plan="auto")
    assert _counter("optimizer.autotune_runs") == runs0


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------

def test_hillclimb_import_is_side_effect_free():
    import repro.launch.hillclimb as hc
    assert hc.__doc__ and "hillclimb" in hc.__doc__
    assert "--xla_force_host_platform_device_count=512" not in \
        os.environ.get("XLA_FLAGS", "")


def test_decision_overrides_round_trip():
    d = Decision(algorithm="quickscorer", plan="rel+reuse", tier="device",
                 n_parts=3, batch_pages=None, predicted_s=1e-3,
                 measured_s=None, source="model")
    assert d.overrides() == dict(algorithm="quickscorer",
                                 plan="rel+reuse", n_parts=3,
                                 batch_pages=None)
