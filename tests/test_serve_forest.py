"""Forest serving plane: coalescer correctness, tenancy, LRU eviction.

The load-bearing claims, each tested against ground truth rather than
the engine's own bookkeeping:

  * coalescing is INVISIBLE to callers — row order preserved across
    arbitrary interleavings, padding rows never leak, predictions match
    the direct ``predict_proba`` kernel;
  * the bucket ladder keeps the steady state on the compiled-plan
    cache — zero plan misses after registration warmup;
  * tenancy is real — per-model plan keys never collide under
    interleaved multi-model traffic, and an LRU-evicted model
    re-registers and re-serves BIT-identically;
  * the deadline ladder fires — a lone interactive request flushes at
    the interactive deadline, batch-tier work waits for a full bucket
    but is bounded by the batch deadline, lapsed admission timeouts
    shed to the batch tier (PR 6 contract).
"""

import time

import numpy as np
import pytest

from repro.core.postprocess import predict_proba
from repro.core.train import TrainConfig, train_forest
from repro.obs import METRICS
from repro.serve.forest import ForestServeEngine
from repro.serve.router import (QUEUE_DEPTH_METRIC, TIER_BATCH,
                                TIER_INTERACTIVE, ForestRouter,
                                live_queue_depth, request_features)

F = 6


def _forest(seed: int, trees: int = 6, depth: int = 3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(256, F)).astype(np.float32)
    y = (x[:, seed % F] + x[:, (seed + 1) % F] > 0).astype(np.float32)
    return train_forest(x, y, TrainConfig(model_type="randomforest",
                                          num_trees=trees, max_depth=depth,
                                          seed=seed))


def _rows(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(100 + seed).normal(
        size=(n, F)).astype(np.float32)


@pytest.fixture(scope="module")
def engine():
    eng = ForestServeEngine(buckets=(8,), interactive_deadline_s=0.001,
                            batch_deadline_s=0.02)
    eng.register_model("m0", _forest(0))
    return eng


def _ref(eng, model, x):
    return np.asarray(predict_proba(eng._get(model).forest, x,
                                    algorithm="predicated"))


# ---------------------------------------------------------------------------
# coalescer correctness
# ---------------------------------------------------------------------------

def test_row_order_preserved_across_coalesce(engine):
    """Mixed-size requests coalesced into one padded tick come back in
    request-row order, matching the direct kernel bitwise."""
    x = _rows(0, 7)
    sizes = [1, 3, 1, 2]
    reqs, off = [], 0
    for k in sizes:
        reqs.append(engine.submit("m0", x[off:off + k]))
        off += k
    engine.drain()
    got = np.concatenate([r.wait(5.0) for r in reqs])
    assert np.array_equal(got, _ref(engine, "m0", x))


def test_padding_never_leaks(engine):
    """3 rows into an 8-bucket: exactly 3 predictions, none NaN (the
    engine NaNs padding rows internally — a leak would surface here)."""
    x = _rows(1, 3)
    req = engine.submit("m0", x)
    engine.drain()
    out = req.wait(5.0)
    assert out.shape == (3,)
    assert not np.isnan(out).any()
    assert np.array_equal(out, _ref(engine, "m0", x))
    # and the flush really padded: 8-bucket, 3 live rows
    assert engine.stats("m0")["padding_rows"] >= 5


def test_steady_state_zero_plan_misses(engine):
    """After registration warmup, every tick hits a resident compiled
    plan — the zero-retrace property the bucket ladder buys."""
    st0 = engine.stats("m0")
    misses0 = METRICS.counter("plan.cache_misses").value
    for i in range(6):
        engine.submit("m0", _rows(2 + i, 1 + i % 4))
    engine.drain()
    st1 = engine.stats("m0")
    assert st1["plan_misses"] == st0["plan_misses"]
    assert st1["plan_hits"] > st0["plan_hits"]
    assert METRICS.counter("plan.cache_misses").value == misses0


def test_oversized_request_rejected(engine):
    with pytest.raises(ValueError, match="largest"):
        engine.submit("m0", _rows(9, 16))   # largest bucket is 8
    with pytest.raises(ValueError, match="features"):
        engine.submit("m0", np.zeros((1, F + 2), np.float32))


def test_deadline_flush_fires_on_lone_request(engine):
    """A single interactive request must not wait for a full bucket:
    the ticker flushes it at the interactive deadline."""
    engine.start()
    try:
        req = engine.submit("m0", _rows(10, 1),
                            priority=TIER_INTERACTIVE)
        out = req.wait(5.0)
    finally:
        engine.stop()
    assert out.shape == (1,)
    # flushed by deadline, not by a full bucket
    assert req.finished_at - req.submitted_at < 1.0


def test_batch_tier_waits_for_deadline():
    """TIER_BATCH work waits for a full bucket; the batch deadline
    bounds the wait for a queue that never fills one."""
    eng = ForestServeEngine(buckets=(8,), batch_deadline_s=0.05)
    eng.register_model("m", _forest(3))
    now = time.perf_counter()
    req = eng.submit("m", _rows(11, 2), priority=TIER_BATCH)
    assert eng.tick(now=now) == 0                  # not due yet
    assert not req.done.is_set()
    assert eng.tick(now=now + 0.051) == 2          # batch deadline lapsed
    assert req.done.is_set()
    # a FULL bucket flushes immediately, no deadline needed
    reqs = [eng.submit("m", _rows(12 + i, 2), priority=TIER_BATCH)
            for i in range(4)]
    assert eng.tick(now=time.perf_counter()) == 8
    assert all(r.done.is_set() for r in reqs)


def test_each_flush_stamps_its_own_admission_time():
    """A tick loop under a backlog flushes several batches: each request
    is admitted when its own flush takes it, so the second flush of one
    ``tick()`` stamps a later ``admitted_at`` than the first, after the
    first flush's answers were handed back."""
    eng = ForestServeEngine(buckets=(8,))
    eng.register_model("m", _forest(5))
    reqs = [eng.submit("m", _rows(40 + i, 1), priority=TIER_BATCH)
            for i in range(16)]
    now = time.perf_counter()
    assert eng.tick(now=now) == 16                 # two full buckets
    first, second = reqs[:8], reqs[8:]             # FIFO for batch tier
    assert len({r.admitted_at for r in first}) == 1
    assert len({r.admitted_at for r in second}) == 1
    assert now <= first[0].admitted_at <= first[0].finished_at
    assert second[0].admitted_at > first[0].admitted_at
    assert second[0].admitted_at >= first[0].finished_at


def test_admission_timeout_sheds_to_batch_tier():
    """PR 6 degradation ladder, coalescer edition: an interactive
    request queued past its timeout is demoted to the batch tier
    (flagged + counted) instead of forcing an early flush."""
    eng = ForestServeEngine(buckets=(8,), interactive_deadline_s=0.001,
                            batch_deadline_s=0.05)
    eng.register_model("m", _forest(4))
    req = eng.submit("m", _rows(13, 1), priority=TIER_INTERACTIVE,
                     timeout_s=0.0)
    now = time.perf_counter()
    eng.tick(now=now + 0.002)      # past the interactive deadline ...
    assert not req.done.is_set()   # ... but it was shed first
    assert req.shed and req.priority == TIER_BATCH
    assert eng.stats("m")["shed"] == 1
    eng.tick(now=now + 0.06)       # batch deadline still bounds it
    assert np.array_equal(req.wait(1.0), _ref(eng, "m", req.rows))


def test_queue_depth_counter_roundtrip(engine):
    """The process-global arrival-load gauge: +1 per submit, -1 per
    coalesced admission, back to baseline after a drain."""
    base = METRICS.counter(QUEUE_DEPTH_METRIC).value
    reqs = [engine.submit("m0", _rows(20 + i, 1)) for i in range(5)]
    assert METRICS.counter(QUEUE_DEPTH_METRIC).value == base + 5
    engine.drain()
    for r in reqs:
        r.wait(5.0)
    assert METRICS.counter(QUEUE_DEPTH_METRIC).value == base


def test_predict_blocks_without_ticker(engine):
    x = _rows(30, 2)
    assert np.array_equal(engine.predict("m0", x),
                          _ref(engine, "m0", x))


# ---------------------------------------------------------------------------
# tenancy + LRU eviction
# ---------------------------------------------------------------------------

def test_multi_model_interleaved_traffic_never_collides():
    """Interleaved traffic over 3 tenants: every request's predictions
    match ITS model's direct kernel — a plan-key collision would serve
    one model's executable for another's rows."""
    eng = ForestServeEngine(buckets=(8,))
    for i in range(3):
        eng.register_model(f"t{i}", _forest(10 + i))
    x = _rows(40, 12)
    reqs = [(f"t{i % 3}", eng.submit(f"t{i % 3}", x[i:i + 1]))
            for i in range(12)]
    eng.drain()
    for i, (name, req) in enumerate(reqs):
        assert np.array_equal(req.wait(5.0), _ref(eng, name, x[i:i + 1])), \
            f"request {i} served with the wrong tenant's plan"
    # the tenants are genuinely different models (the check above would
    # pass vacuously otherwise)
    assert not np.array_equal(_ref(eng, "t0", x), _ref(eng, "t1", x))


def test_lru_eviction_and_bit_identical_reserve():
    """More tenants than the plan cache holds: the coldest model's
    executable ages out (a plan MISS on its next request), but the
    model catalog pin keeps it servable — and the recompiled plan
    serves BIT-identical predictions."""
    eng = ForestServeEngine(buckets=(8,), max_plans=3)
    x = _rows(50, 4)
    eng.register_model("a", _forest(20))
    first = eng.predict("a", x)
    a_misses0 = eng.stats("a")["plan_misses"]
    # 3 more tenants x 1 bucket each: "a"'s plan is the LRU victim
    for i in range(3):
        eng.register_model(f"b{i}", _forest(21 + i))
    miss0 = METRICS.counter("plan.cache_misses").value
    again = eng.predict("a", x)
    assert METRICS.counter("plan.cache_misses").value > miss0, \
        "expected an eviction-driven plan miss"
    assert eng.stats("a")["plan_misses"] > a_misses0
    assert np.array_equal(first, again)
    # warm again -> steady state restored (next serve is a hit)
    h0 = eng.stats("a")["plan_hits"]
    assert np.array_equal(eng.predict("a", x), first)
    assert eng.stats("a")["plan_hits"] > h0


def test_unregister_then_reregister_serves_identically():
    eng = ForestServeEngine(buckets=(8,))
    f = _forest(30)
    eng.register_model("m", f)
    x = _rows(60, 3)
    first = eng.predict("m", x)
    assert eng.unregister_model("m") > 0          # plans swept
    with pytest.raises(KeyError):
        eng.submit("m", x)
    with pytest.raises(KeyError):
        eng.store.get_model("m")
    eng.register_model("m", f)
    assert np.array_equal(eng.predict("m", x), first)


def test_store_model_catalog_roundtrip():
    eng = ForestServeEngine(buckets=(8,))
    f = _forest(31)
    eng.register_model("cat", f, warmup=False)
    assert eng.store.get_model("cat") is f
    cat = eng.store.model_catalog()
    assert "cat" in cat and "forest" not in cat["cat"]
    assert cat["cat"]["trees"] == f.num_trees
    assert eng.models()["cat"]["algorithm"] == "predicated"


# ---------------------------------------------------------------------------
# router: live arrival-load feature + named tier defaults (satellites)
# ---------------------------------------------------------------------------

def test_live_queue_depth_reads_metric_and_clamps():
    c = METRICS.counter(QUEUE_DEPTH_METRIC)
    old = c.value
    try:
        c.set(7)
        assert live_queue_depth() == 7.0
        assert request_features(4, 2)[2] == 7.0
        c.set(-3)          # transient mid-reset skew must not go negative
        assert live_queue_depth() == 0.0
    finally:
        c.set(old)


def test_routing_shifts_with_live_load():
    """The regression the live-load feature exists for: the SAME
    request routes interactive when the process is idle and batch when
    the queue metric reports load — without the caller passing depth."""
    router = ForestRouter(seed=0)
    flip = None
    for plen in range(40, 520, 40):
        for mnt in range(10, 260, 25):
            idle = router.route(request_features(plen, mnt, 0.0))
            busy = router.route(request_features(plen, mnt, 60.0))
            if idle == TIER_INTERACTIVE and busy == TIER_BATCH:
                flip = (plen, mnt)
                break
        if flip:
            break
    assert flip is not None, "no load-sensitive request in the grid"
    plen, mnt = flip
    c = METRICS.counter(QUEUE_DEPTH_METRIC)
    old = c.value
    try:
        c.set(0)
        assert router.route(request_features(plen, mnt)) \
            == TIER_INTERACTIVE
        c.set(60)
        assert router.route(request_features(plen, mnt)) == TIER_BATCH
    finally:
        c.set(old)


def test_default_priority_is_named_batch_tier():
    """Satellite: the request default is the named TIER_BATCH constant
    (not a magic int), in both serve engines' request types."""
    from repro.serve.engine import Request
    import dataclasses as dc
    assert Request(uid=1, prompt=np.zeros(1, np.int32)).priority \
        == TIER_BATCH
    from repro.serve.forest import ForestRequest
    f = dc.fields(ForestRequest)
    assert next(fl for fl in f if fl.name == "priority").default \
        == TIER_BATCH
