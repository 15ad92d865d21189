"""Out-of-core streaming scan: host-tier pages + double-buffered DMA.

The contracts under test:
  * host-tier and device-tier executions are BIT-identical in f32, for
    dense and CSR storage, through udf and rel plans, mesh-less and (in
    the multi-device section, which skips without 8 forced CPU devices)
    on a (data x model) mesh;
  * ``device_budget_bytes`` auto-spills oversized ingests to the host
    tier, with per-tier nbytes accounting and catalog tiers;
  * the streaming executor keeps AT MOST 2 device page buffers in flight
    (the double-buffer invariant, asserted inside the executor and
    reported via ``ScanStats.max_in_flight``) — since the drain moved to
    a dedicated worker thread the probe here exercises the async path by
    default; the disk-tier grid and the drain-accounting contracts live
    in ``tests/test_disk_tier.py``;
  * tier migration (``store.move`` — eviction and promotion) and
    drop + re-page (different ``page_rows``) preserve predictions;
  * ``TensorBlockStore.drop`` sweeps dependent compiled-plan entries in
    registered engines (the stale-plan-after-re-put regression);
  * ``load_libsvm_csr_external(tier="host")`` parses into host pages
    with ``transfer_s == 0`` and no device round-trip.
"""

import os
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.reuse import ModelReuseCache
from repro.core.train import TrainConfig, train_forest
from repro.db import loader as ld
from repro.db.executor import (MAX_IN_FLIGHT, ScanSource,
                               StreamingScanExecutor)
from repro.db.query import ForestQueryEngine
from repro.db.store import TensorBlockStore

N, F, T, PAGE = 384, 16, 24, 32
FUSED = "predicated_pallas_fused"
SPARSE_ALGO = "hummingbird_pallas_fused"


@pytest.fixture(scope="module")
def data_and_forest():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(N, F)).astype(np.float32)
    w = rng.normal(size=F).astype(np.float32)
    y = (x @ w > 0).astype(np.float32)
    forest = train_forest(x, y, TrainConfig(model_type="xgboost",
                                            num_trees=T, max_depth=4))
    xs = x.copy()
    xs[rng.random(x.shape) < 0.7] = np.nan
    return x, xs, forest


def _engine(store):
    return ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                             plan_cache=ModelReuseCache())


def _put_tiered(x, xs, *, mesh=None, page_rows=PAGE):
    """One store holding every (format, tier) combination of the data."""
    store = TensorBlockStore(mesh, default_page_rows=page_rows)
    store.put("dense@dev", x)
    store.put("dense@host", x, tier="host")
    store.put_sparse("csr@dev", xs)
    store.put_sparse("csr@host", xs, tier="host")
    return store


# ---------------------------------------------------------------------------
# tiering: auto-spill, accounting, protocol
# ---------------------------------------------------------------------------


def test_device_budget_auto_spills_to_host():
    """tier="auto" (the default): an ingest that would push the device-
    resident total past device_budget_bytes lands on the host tier."""
    x = np.ones((256, 8), np.float32)
    store = TensorBlockStore(default_page_rows=32,
                             device_budget_bytes=int(x.nbytes * 1.5))
    a = store.put("a", x)                      # fits: device
    b = store.put("b", x)                      # would exceed: spills
    assert a.tier == "device" and b.tier == "host"
    assert isinstance(b.data, np.ndarray)
    assert store.device_nbytes == a.nbytes
    assert store.host_nbytes == b.nbytes
    cat = store.catalog()
    assert cat["a"]["tier"] == "device" and cat["b"]["tier"] == "host"
    # explicit tier overrides the budget in both directions
    assert store.put("c", x, tier="device").tier == "device"
    store2 = TensorBlockStore(default_page_rows=32)   # no budget
    assert store2.put("d", x).tier == "device"
    assert store2.put("e", x, tier="host").tier == "host"
    with pytest.raises(ValueError):
        store2.put("f", x, tier="hbm")


def test_sparse_budget_spill(data_and_forest):
    _, xs, _ = data_and_forest
    store = TensorBlockStore(default_page_rows=PAGE, device_budget_bytes=1)
    ds = store.put_sparse("s", xs)
    assert ds.tier == "host"
    assert isinstance(ds.pages.indptr, np.ndarray)
    assert ds.pages.tier == "host"
    assert store.host_nbytes == ds.nbytes and store.device_nbytes == 0
    assert store.catalog()["s"]["tier"] == "host"


def test_datasets_implement_scan_source(data_and_forest):
    """Both dataset classes satisfy the executor's ScanSource protocol on
    both tiers — callers never branch on where pages live."""
    x, xs, _ = data_and_forest
    store = _put_tiered(x, xs)
    for name in ("dense@dev", "dense@host", "csr@dev", "csr@host"):
        ds = store.get(name)
        assert isinstance(ds, ScanSource), name
        blk = ds.page_slice(0, 2)
        dev = ds.to_device(blk, None)
        for leaf in jax.tree_util.tree_leaves(dev):
            assert isinstance(leaf, jax.Array), (name, type(leaf))


# ---------------------------------------------------------------------------
# bit-identical host-tier vs device-tier predictions (mesh-less half; the
# mesh half of the grid is in the multi-device section below)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("plan", ["udf", "rel"])
@pytest.mark.parametrize("fmt,algo", [("dense", FUSED),
                                      ("csr", SPARSE_ALGO)])
def test_host_tier_bitwise_parity(data_and_forest, plan, fmt, algo):
    x, xs, forest = data_and_forest
    engine = _engine(_put_tiered(x, xs))
    kw = dict(algorithm=algo, plan=plan, batch_pages=3)
    rd = engine.infer(f"{fmt}@dev", forest, **kw)
    rh = engine.infer(f"{fmt}@host", forest, **kw)
    assert rd.tier == "device" and rh.tier == "host"
    assert rh.storage_format == fmt
    assert rh.scan.batches > 1 and rh.scan.bytes_streamed > 0
    assert rd.scan.bytes_streamed == 0          # no-op transfer stage
    assert np.array_equal(np.asarray(rh.predictions),
                          np.asarray(rd.predictions)), "f32 bitwise parity"


def test_unfused_jnp_backend_streams_too(data_and_forest):
    """The executor is algorithm-agnostic: jnp backends stream the same."""
    x, xs, forest = data_and_forest
    engine = _engine(_put_tiered(x, xs))
    rd = engine.infer("dense@dev", forest, algorithm="predicated",
                      plan="udf", batch_pages=2)
    rh = engine.infer("dense@host", forest, algorithm="predicated",
                      plan="udf", batch_pages=2)
    assert np.array_equal(np.asarray(rh.predictions),
                          np.asarray(rd.predictions))


def test_budget_default_batch_pages_runs_out_of_core(data_and_forest):
    """End-to-end acceptance shape: budget below nbytes -> host tier,
    infer() derives a batch size so 2 in-flight buffers fit the budget,
    and streamed predictions match the all-device-resident run."""
    x, xs, forest = data_and_forest
    dev = _engine(_put_tiered(x, xs))
    store = TensorBlockStore(default_page_rows=PAGE,
                             device_budget_bytes=x.nbytes // 4)
    ds = store.put("big", x)
    assert ds.tier == "host" and ds.nbytes >= 4 * (x.nbytes // 4)
    engine = _engine(store)
    for plan in ("udf", "rel"):
        res = engine.infer("big", forest, algorithm=FUSED, plan=plan)
        ref = dev.infer("dense@dev", forest, algorithm=FUSED, plan=plan,
                        batch_pages=res.scan.batch_pages)
        assert res.scan.batches > 1
        # two in-flight page batches fit the budget
        assert 2 * res.scan.batch_pages * ds.page_nbytes \
            <= store.device_budget_bytes
        assert np.array_equal(np.asarray(res.predictions),
                              np.asarray(ref.predictions))


def test_host_tier_without_budget_still_streams(data_and_forest,
                                                monkeypatch):
    """An EXPLICIT host ingest on a budget-less store must not fall back
    to a whole-dataset device_put: the default batch is capped at the
    fixed streaming footprint instead."""
    import repro.db.query as q
    x, xs, forest = data_and_forest
    store = TensorBlockStore(default_page_rows=PAGE)   # no budget
    ds = store.put("h", x, tier="host")
    monkeypatch.setattr(q, "DEFAULT_STREAM_BATCH_BYTES",
                        3 * ds.page_nbytes)
    engine = _engine(store)
    res = engine.infer("h", forest, algorithm=FUSED, plan="udf")
    assert res.scan.batches > 1 and res.scan.batch_pages == 3
    ref = _engine(_put_tiered(x, xs)).infer(
        "dense@dev", forest, algorithm=FUSED, plan="udf", batch_pages=3)
    assert np.array_equal(np.asarray(res.predictions),
                          np.asarray(ref.predictions))


def test_device_pages_handoff_stays_on_device(data_and_forest):
    """put_sparse(pages=<device CSRPages>) must hand the arrays over
    as-is — no device->host->device round-trip on the in-database ingest
    boundary the paper measures."""
    _, xs, _ = data_and_forest
    from repro.db.sparse import csr_pages_from_dense
    pages = csr_pages_from_dense(xs, page_rows=PAGE)
    store = TensorBlockStore(default_page_rows=PAGE)
    ds = store.put_sparse("s", pages=pages, num_rows=xs.shape[0])
    assert ds.tier == "device"
    assert ds.pages.indptr is pages.indptr        # zero-copy handoff
    assert ds.pages.values is pages.values


# ---------------------------------------------------------------------------
# the double-buffer invariant: at most 2 device page buffers in flight
# ---------------------------------------------------------------------------


def test_at_most_two_buffers_in_flight(data_and_forest):
    x, xs, forest = data_and_forest
    engine = _engine(_put_tiered(x, xs))
    res = engine.infer("dense@host", forest, algorithm=FUSED, plan="udf",
                       batch_pages=2)
    assert res.scan.batches >= 3                 # a real pipeline
    assert res.scan.max_in_flight == MAX_IN_FLIGHT == 2
    assert res.scan.prefetch_depth == 2
    # synchronous reference pipeline: one buffer, same predictions
    ser = engine.infer("dense@host", forest, algorithm=FUSED, plan="udf",
                       batch_pages=2, prefetch_depth=1)
    assert ser.scan.max_in_flight == 1
    assert np.array_equal(np.asarray(ser.predictions),
                          np.asarray(res.predictions))


def test_live_device_buffer_count_during_stream():
    """The REAL buffer-count assertion (not just the executor's own
    counter): an unjitted probe stage counts live device arrays of the
    page-block shape mid-stream.  At most 2 ever exist — the block being
    computed plus the one in DMA flight — including for plans that
    thread "x" through to the stage output (the executor must drop the
    whole state, not just its own handle, to keep this true)."""
    from repro.db.operators import Operator, split_into_stages
    F_odd = 17                       # unique shape: nothing else matches
    x = np.arange(256 * F_odd, dtype=np.float32).reshape(256, F_odd)
    store = TensorBlockStore(default_page_rows=16)
    ds = store.put("probe", x, tier="host")
    batch_pages = 2
    block_shape = (batch_pages * ds.page_rows, F_odd)
    seen = []

    def probe(state):
        seen.append(sum(1 for a in jax.live_arrays()
                        if tuple(a.shape) == block_shape
                        and not a.is_deleted()))
        return state

    def udf(state):
        state = dict(state)
        state["pred"] = jnp.sum(state["x"], axis=1)   # keeps "x" threaded
        return state

    stages = split_into_stages(
        [Operator("probe", probe), Operator("udf", udf),
         Operator("write", lambda s: s, breaker=True)], jit=False)
    out, _, stats = StreamingScanExecutor(stages).execute(ds, batch_pages)
    assert stats.batches == len(seen) == 8
    assert max(seen) == 2, f"3+ page buffers were live: {seen}"
    assert seen[-1] == 1             # no prefetch past the last batch
    np.testing.assert_allclose(out, x.sum(axis=1), rtol=1e-6)


def test_pinned_host_probe_never_silent_on_accelerator():
    """Pinned D2H staging never switches off in silence: a device other
    than the CPU that lacks the memory kind raises; only the CPU yields
    None (the plain path)."""
    from repro.db.executor import _pinned_host_sharding

    class NoPinnedHost:
        def __init__(self, platform):
            self.platform = platform

        def memory(self, kind):
            raise ValueError(f"no memory kind {kind!r}")

    with pytest.raises(ValueError, match="no memory kind"):
        _pinned_host_sharding(NoPinnedHost("tpu"))
    assert _pinned_host_sharding(NoPinnedHost("cpu")) is None


def test_executor_rejects_deeper_prefetch():
    """The <=2 invariant is a constructor-level contract, not a tuning
    knob: depths that would put 3+ page buffers in flight are refused."""
    with pytest.raises(ValueError):
        StreamingScanExecutor([], prefetch_depth=3)
    with pytest.raises(ValueError):
        StreamingScanExecutor([], prefetch_depth=0)


def test_single_batch_single_buffer(data_and_forest):
    """Whole-dataset batch: the pipeline degenerates to one buffer."""
    x, xs, forest = data_and_forest
    engine = _engine(_put_tiered(x, xs))
    res = engine.infer("dense@host", forest, algorithm=FUSED, plan="udf")
    assert res.scan.batches == 1 and res.scan.max_in_flight == 1


# ---------------------------------------------------------------------------
# eviction + re-page correctness
# ---------------------------------------------------------------------------


def test_eviction_and_promotion_preserve_predictions(data_and_forest):
    """move() device->host (eviction) and back (promotion): page layout —
    and therefore every prediction — is unchanged, bitwise."""
    x, xs, forest = data_and_forest
    store = _put_tiered(x, xs)
    engine = _engine(store)
    kw = dict(algorithm=FUSED, plan="udf", batch_pages=2)
    ref = engine.infer("dense@dev", forest, **kw)
    evicted = store.move("dense@dev", "host")
    assert evicted.tier == "host" and isinstance(evicted.data, np.ndarray)
    r_h = engine.infer("dense@dev", forest, **kw)
    assert r_h.tier == "host"
    promoted = store.move("dense@dev", "device")
    assert promoted.tier == "device"
    r_d = engine.infer("dense@dev", forest, **kw)
    for r in (r_h, r_d):
        assert np.array_equal(np.asarray(r.predictions),
                              np.asarray(ref.predictions))
    # CSR eviction too
    ref_s = engine.infer("csr@dev", forest, algorithm=SPARSE_ALGO,
                         plan="udf", batch_pages=2)
    store.move("csr@dev", "host")
    r_s = engine.infer("csr@dev", forest, algorithm=SPARSE_ALGO,
                       plan="udf", batch_pages=2)
    assert r_s.tier == "host"
    assert np.array_equal(np.asarray(r_s.predictions),
                          np.asarray(ref_s.predictions))


def test_repage_after_drop(data_and_forest):
    """Drop + re-put with a DIFFERENT page_rows (re-page): the new page
    layout batches differently but predictions are unchanged."""
    x, xs, forest = data_and_forest
    store = TensorBlockStore(default_page_rows=PAGE)
    store.put("d", x)
    engine = _engine(store)
    ref = engine.infer("d", forest, algorithm=FUSED, plan="udf")
    store.drop("d")
    store.put("d", x, page_rows=PAGE // 2, tier="host")
    res = engine.infer("d", forest, algorithm=FUSED, plan="udf",
                       batch_pages=3)
    assert res.tier == "host" and res.scan.batches > 1
    assert np.array_equal(np.asarray(res.predictions),
                          np.asarray(ref.predictions))


# ---------------------------------------------------------------------------
# drop -> dependent plan invalidation (stale-plan-after-re-put regression)
# ---------------------------------------------------------------------------


def test_drop_invalidates_dependent_plans(data_and_forest):
    """Regression: drop used to only delete the catalog reference — the
    compiled plans keyed on the dataset's batch signature stayed resident
    (pinning their device buffers) and a re-put with the same shape
    silently served the old executable as a "reuse hit".  drop must sweep
    dependent plan entries in every registered engine, so the first query
    after re-put honestly rebuilds."""
    x, xs, forest = data_and_forest
    store = _put_tiered(x, xs)
    engine = _engine(store)
    kw = dict(algorithm=FUSED, model_id="m-drop")
    engine.infer("dense@dev", forest, plan="udf", **kw)
    engine.infer("dense@dev", forest, plan="rel+reuse", **kw)
    engine.infer("dense@host", forest, plan="udf", **kw)
    assert len(engine.plan_cache) == 3
    n = store.drop("dense@dev")
    assert n == 2, "both of the dropped dataset's plans must be swept"
    assert len(engine.plan_cache) == 1           # dense@host survives
    # model materializations are dataset-independent: they survive
    assert len(engine.cache) == 1
    # re-put (same shape): NOT a stale plan hit — a fresh executable
    store.put("dense@dev", x)
    r = engine.infer("dense@dev", forest, plan="udf", **kw)
    assert not r.plan_reuse_hit
    # steady state re-established
    assert engine.infer("dense@dev", forest, plan="udf",
                        **kw).plan_reuse_hit
    # dead engines unregister themselves (weak hooks): no error on drop
    del engine
    assert store.drop("dense@host") == 0


# ---------------------------------------------------------------------------
# host-tier external ingest (the criteo-scale path)
# ---------------------------------------------------------------------------


def test_libsvm_host_tier_ingest(tmp_path, data_and_forest):
    _, xs, forest = data_and_forest
    y = np.zeros(xs.shape[0], np.float32)
    p = str(tmp_path / "d.svm")
    ld.write_libsvm(p, xs, y)
    pages_h, labels, t_h = ld.load_libsvm_csr_external(
        p, xs.shape[1], page_rows=PAGE, tier="host")
    assert t_h.transfer_s == 0.0, "host-tier ingest must not transfer"
    assert t_h.parse_s > 0 and t_h.total_s > 0
    assert isinstance(pages_h.indptr, np.ndarray)
    assert pages_h.tier == "host"
    # registers with zero device work and streams bit-identically to the
    # device-tier load of the same file
    pages_d, _, t_d = ld.load_libsvm_csr_external(p, xs.shape[1],
                                                  page_rows=PAGE)
    assert t_d.transfer_s > 0.0
    store = TensorBlockStore(default_page_rows=PAGE)
    store.put_sparse("h", pages=pages_h, num_rows=len(labels), tier="host")
    store.put_sparse("d", pages=pages_d, num_rows=len(labels))
    assert store.get("h").tier == "host" and store.get("d").tier == "device"
    engine = _engine(store)
    rh = engine.infer("h", forest, algorithm=SPARSE_ALGO, plan="udf",
                      batch_pages=2)
    rd = engine.infer("d", forest, algorithm=SPARSE_ALGO, plan="udf",
                      batch_pages=2)
    assert rh.tier == "host" and rh.storage_format == "csr"
    assert np.array_equal(np.asarray(rh.predictions),
                          np.asarray(rd.predictions))


# ---------------------------------------------------------------------------
# multi-device half of the parity grid
# ---------------------------------------------------------------------------

NDEV = len(jax.devices())
needs_mesh = pytest.mark.skipif(
    NDEV < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _mesh(n_data, n_model):
    devs = np.array(jax.devices()[: n_data * n_model])
    from jax.sharding import Mesh
    return Mesh(devs.reshape(n_data, n_model), ("data", "model"))


@needs_mesh
@pytest.mark.parametrize("plan", ["udf", "rel"])
@pytest.mark.parametrize("fmt,algo", [("dense", FUSED),
                                      ("csr", SPARSE_ALGO)])
def test_mesh_host_tier_bitwise_parity(data_and_forest, plan, fmt, algo):
    """Host-tier pages DMA'd under data_sharding through the shard_map
    plans: bit-identical to the device-resident mesh run."""
    x, xs, forest = data_and_forest
    mesh = _mesh(2, 4)
    engine = _engine(_put_tiered(x, xs, mesh=mesh))
    kw = dict(algorithm=algo, plan=plan, batch_pages=4)
    rd = engine.infer(f"{fmt}@dev", forest, **kw)
    rh = engine.infer(f"{fmt}@host", forest, **kw)
    assert rh.tier == "host" and rh.mesh_devices == 8
    assert rh.scan.batches > 1 and rh.scan.max_in_flight == 2
    assert np.array_equal(np.asarray(rh.predictions),
                          np.asarray(rd.predictions)), "f32 bitwise parity"


@needs_mesh
def test_mesh_budget_batch_respects_budget(data_and_forest):
    """Data-axis divisibility must not inflate the budget-derived batch:
    the default is sized in data-axis units rounding DOWN, so the two
    in-flight buffers stay within the budget whenever it has room for
    at least one page per device."""
    x, _, forest = data_and_forest
    mesh = _mesh(2, 4)                           # n_data = 2
    budget = x.nbytes // 2
    store = TensorBlockStore(mesh, default_page_rows=PAGE,
                             device_budget_bytes=budget)
    ds = store.put("d", x)
    assert ds.tier == "host"
    res = _engine(store).infer("d", forest, algorithm=FUSED, plan="udf")
    assert res.scan.batch_pages % 2 == 0         # data-axis divisible
    assert 2 * res.scan.batch_pages * ds.page_nbytes <= budget


@needs_mesh
def test_mesh_multibatch_device_tier_needs_no_workaround(data_and_forest):
    """Multi-batch device-tier output on a (data, model) mesh: the
    executor's host result buffer (per-shard copy + stitch) assembles it
    bit-identically to one whole-table batch, without replicating
    anything first."""
    x, xs, forest = data_and_forest
    engine = _engine(_put_tiered(x, xs, mesh=_mesh(2, 4)))
    whole = engine.infer("dense@dev", forest, algorithm=FUSED, plan="rel")
    multi = engine.infer("dense@dev", forest, algorithm=FUSED, plan="rel",
                         batch_pages=2)
    assert multi.scan.batches > 1
    assert np.array_equal(np.asarray(multi.predictions),
                          np.asarray(whole.predictions))
