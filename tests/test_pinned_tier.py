"""The ``pinned`` tier: dense rows page-locked in host memory in the
device's layout, streamed to the device batch by batch.

The contracts under test:
  * ``put`` / ``move`` / ``drop`` / the catalog keep the rung's rows in
    the ``pinned_host`` memory kind, and its bytes count as host RAM
    (``pinned_nbytes`` and ``host_nbytes``, the host budget);
  * a pinned scan is BIT-identical to the device-tier scan of the same
    rows at page-aligned, ragged-tail and one-batch sizes, and matches
    the jnp reference on seeded random forests;
  * the ``page_dma_in`` halving ladder splits a faulted pinned batch
    bit-identically;
  * ``scan.host_relayout_bytes`` counts the numpy pages PJRT relays out
    on the host (0 on a pinned scan, ``scan.bytes_streamed`` on a host
    scan) and ``scan.pinned_bytes`` the copies out of pinned memory;
  * a CSR dataset cannot live on the rung.
"""

import numpy as np
import pytest

import jax

from repro.core.forest import make_forest
from repro.core.postprocess import aggregate_raw
from repro.db.executor import ScanSource
from repro.db.faults import FaultInjector, RetryPolicy
from repro.db.query import ForestQueryEngine
from repro.db.store import PinnedSpan, TensorBlockStore
from repro.kernels.ref import ref_predicated
from repro.obs import METRICS, TRACER

F, T, DEPTH, PAGE = 28, 12, 6, 64
FUSED = "predicated_pallas_fused"
FAST = RetryPolicy(backoff_base_s=0.0, max_backoff_s=0.0)


def _forest(seed: int):
    """A complete random xgboost forest, as the benchmark draws one."""
    rng = np.random.default_rng(seed)
    internal, leaves = 2 ** DEPTH - 1, 2 ** DEPTH
    return make_forest(
        rng.integers(0, F, (T, internal)).astype(np.int32),
        rng.standard_normal((T, internal)).astype(np.float32),
        (0.1 * rng.standard_normal((T, leaves))).astype(np.float32),
        default_left=rng.random((T, internal)) < 0.5,
        n_features=F, model_type="xgboost", task="classification")


def _rows(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((n, F),
                                                       dtype=np.float32)


def _engine(store):
    return ForestQueryEngine(store)


def _assert_pinned(ds):
    """The rows of ``ds`` lie in page-locked host memory."""
    assert isinstance(ds.data, jax.Array)
    assert ds.data.sharding.memory_kind == "pinned_host"


def _counters(fn):
    """``fn()`` and the METRICS counters it moved."""
    before = METRICS.counter_values()
    out = fn()
    now = METRICS.counter_values()
    return out, {k: now[k] - before.get(k, 0) for k in now}


# ---------------------------------------------------------------------------
# the rung: put / move / drop / catalog / accounting
# ---------------------------------------------------------------------------


def test_put_places_rows_in_pinned_memory():
    x = _rows(5 * PAGE + 3)
    store = TensorBlockStore(default_page_rows=PAGE)
    ds = store.put("t", x, tier="pinned")
    assert ds.tier == "pinned" and store.catalog()["t"]["tier"] == "pinned"
    _assert_pinned(ds)
    assert ds.num_pages == 6 and ds.data.shape == (6 * PAGE, F)
    got = np.asarray(ds.data)
    assert np.array_equal(got[: len(x)], x)
    assert np.isnan(got[len(x):]).all()          # page padding
    assert store.pinned_nbytes == ds.nbytes == store.host_nbytes
    assert store.device_nbytes == 0 and store.disk_nbytes == 0
    assert isinstance(ds, ScanSource)
    span = ds.page_slice(2, 3)
    assert isinstance(span, PinnedSpan)
    assert (span.lo, span.rows) == (2 * PAGE, 3 * PAGE)
    block = ds.to_device(span)
    assert block.sharding.memory_kind == "device"
    assert np.array_equal(np.asarray(block), got[2 * PAGE: 5 * PAGE])


@pytest.mark.parametrize("other", ["device", "host", "disk"])
def test_move_to_and_from_pinned_keeps_the_pages(tmp_path, other):
    x = _rows(4 * PAGE + 9, seed=1)
    store = TensorBlockStore(default_page_rows=PAGE, spill_dir=str(tmp_path))
    want = np.asarray(store.put("t", x, tier=other).data)
    ds = store.move("t", "pinned")
    assert ds.tier == "pinned"
    _assert_pinned(ds)
    assert np.array_equal(np.asarray(ds.data), want, equal_nan=True)
    assert store.pinned_nbytes == ds.nbytes
    back = store.move("t", other)
    assert back.tier == other and store.pinned_nbytes == 0
    assert np.array_equal(np.asarray(back.data), want, equal_nan=True)


def test_drop_frees_the_pinned_table():
    store = TensorBlockStore(default_page_rows=PAGE)
    store.put("t", _rows(3 * PAGE), tier="pinned")
    store.drop("t")
    assert "t" not in store and store.pinned_nbytes == store.host_nbytes == 0


def test_pinned_bytes_fill_the_host_budget_and_auto_skips_the_rung():
    x = _rows(4 * PAGE)
    store = TensorBlockStore(default_page_rows=PAGE, device_budget_bytes=0,
                             host_budget_bytes=x.nbytes + x.nbytes // 2)
    store.put("pinned", x, tier="pinned")
    # past the device budget, and the pinned table already holds most of
    # the host budget: the cascade goes device -> host -> disk
    assert store.put("auto", x).tier == "disk"
    assert store.host_nbytes == x.nbytes


def test_stream_writer_lands_on_pinned():
    x = _rows(2 * PAGE + 7, seed=2)
    store = TensorBlockStore(default_page_rows=PAGE)
    ds = store.put_stream("t", iter(np.array_split(x, 3)),
                          num_rows=len(x), num_features=F, tier="pinned")
    assert ds.tier == "pinned"
    _assert_pinned(ds)
    assert np.array_equal(np.asarray(ds.data)[: len(x)], x)


def test_csr_cannot_live_on_the_pinned_tier():
    x = _rows(2 * PAGE)
    store = TensorBlockStore(default_page_rows=PAGE)
    with pytest.raises(ValueError, match="pinned tier holds dense rows"):
        store.put_sparse("csr", x, tier="pinned")
    assert "csr" not in store
    store.put_sparse("csr", x, tier="host")
    with pytest.raises(ValueError, match="pinned tier holds dense rows"):
        store.move("csr", "pinned")
    assert store.get("csr").tier == "host"


def test_unknown_tier_is_refused():
    with pytest.raises(ValueError, match="unknown tier 'cache'"):
        TensorBlockStore().put("t", _rows(8), tier="cache")


def test_ingest_records_the_pin_span():
    x = _rows(3 * PAGE)
    TRACER.reset()
    TRACER.enable()
    try:
        TensorBlockStore(default_page_rows=PAGE).put("t", x, tier="pinned")
    finally:
        TRACER.disable()
    (pin,) = [s for s in TRACER.finished() if s.name == "store.pin"]
    assert pin.attrs["bytes"] == x.nbytes


# ---------------------------------------------------------------------------
# the scan: parity, reference, halving ladder, counters
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows,batch_pages", [
    (8 * PAGE, 3),            # page-aligned, several batches, ragged last
    (8 * PAGE + 5, 2),        # ragged tail page
    (5 * PAGE + 1, 64),       # one batch holds the table
])
@pytest.mark.parametrize("plan", ["udf", "rel"])
def test_pinned_scan_is_bit_identical_to_the_device_tier(rows, batch_pages,
                                                         plan):
    x = _rows(rows, seed=rows)
    forest = _forest(seed=batch_pages)
    store = TensorBlockStore(default_page_rows=PAGE)
    store.put("dev", x, tier="device")
    store.put("pin", x, tier="pinned")
    engine = _engine(store)
    kw = dict(algorithm=FUSED, plan=plan, batch_pages=batch_pages)
    rd = engine.infer("dev", forest, **kw)
    rp = engine.infer("pin", forest, **kw)
    assert rp.tier == "pinned"
    assert rp.scan.batches == -(-store.get("pin").num_pages // batch_pages)
    assert rp.scan.max_in_flight == min(2, rp.scan.batches)
    assert np.array_equal(np.asarray(rp.predictions),
                          np.asarray(rd.predictions))
    want = jax.nn.sigmoid(aggregate_raw(ref_predicated(forest, x)))
    np.testing.assert_allclose(np.asarray(rp.predictions), np.asarray(want),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("batch_pages", [None, 2, 4, 9])
def test_pinned_batches_of_any_size_match_the_device_tier(batch_pages):
    """The default batch (half the device budget here: 3 pages) and any
    other size are each one slice of the one pinned table, bit-identical
    to the device-resident scan."""
    x = _rows(8 * PAGE + 5, seed=7)
    page_bytes = PAGE * F * 4
    store = TensorBlockStore(default_page_rows=PAGE,
                             device_budget_bytes=6 * page_bytes)
    store.put("dev", x, tier="device")
    ds = store.put("pin", x, tier="pinned")
    engine = _engine(store)
    forest = _forest(seed=7)
    kw = dict(algorithm=FUSED, batch_pages=batch_pages)
    rd = engine.infer("dev", forest, **kw)
    rp = engine.infer("pin", forest, **kw)
    if batch_pages is None:
        assert rp.scan.batch_pages == 3 and rp.scan.batches == 3
    assert rp.scan.pinned_bytes == ds.nbytes
    assert np.array_equal(np.asarray(rp.predictions),
                          np.asarray(rd.predictions))


@pytest.mark.parametrize("first,num", [(0, 1), (2, 3), (5, 1)])
def test_pinned_copy_ships_no_host_argument(first, num):
    """Once a start row has been copied from, copying from it again moves
    nothing from host to device: the start is a device scalar, and the
    rows go pinned -> device inside one program."""
    x = _rows(6 * PAGE, seed=8)
    ds = TensorBlockStore(default_page_rows=PAGE).put("t", x, tier="pinned")
    span = ds.page_slice(first, num)
    ds.to_device(span)
    with jax.transfer_guard("disallow"):
        block = ds.to_device(span)
    assert block.sharding.memory_kind == "device"
    assert np.array_equal(np.asarray(block),
                          x[first * PAGE: (first + num) * PAGE])


def test_dma_halving_ladder_on_a_pinned_source():
    x = _rows(6 * PAGE, seed=3)
    forest = _forest(seed=3)
    store = TensorBlockStore(default_page_rows=PAGE)
    store.put("dev", x, tier="device")
    store.put("pin", x, tier="pinned")
    engine = _engine(store)
    clean = engine.infer("dev", forest, algorithm=FUSED, batch_pages=4)
    inj = FaultInjector().inject("page_dma_in", fail_at=1,
                                 times=FAST.max_attempts)
    res = engine.infer("pin", forest, algorithm=FUSED, batch_pages=4,
                       injector=inj, retry_policy=FAST)
    assert res.scan.batch_resubmits == 1       # the 4-page batch split
    assert res.scan.batches == 3               # 2 + 2 and the last 2
    assert res.scan.pinned_bytes == x.nbytes
    assert np.array_equal(np.asarray(res.predictions),
                          np.asarray(clean.predictions))


@pytest.mark.parametrize("tier", ["pinned", "host", "disk", "device"])
def test_relayout_and_pinned_byte_counters(tmp_path, tier):
    x = _rows(4 * PAGE, seed=4)
    store = TensorBlockStore(default_page_rows=PAGE, spill_dir=str(tmp_path))
    store.put("t", x, tier=tier)
    engine = _engine(store)
    forest = _forest(seed=4)
    engine.infer("t", forest, algorithm=FUSED, batch_pages=2)   # warm
    res, moved = _counters(
        lambda: engine.infer("t", forest, algorithm=FUSED, batch_pages=2))
    sc = res.scan
    streamed = 0 if tier == "device" else x.nbytes
    assert sc.bytes_streamed == moved["scan.bytes_streamed"] == streamed
    pinned = streamed if tier == "pinned" else 0
    assert sc.pinned_bytes == moved["scan.pinned_bytes"] == pinned
    relaid = streamed - pinned
    assert sc.host_relayout_bytes == moved["scan.host_relayout_bytes"] \
        == relaid


def test_pinned_scan_records_a_page_copy_in_each_dma_in():
    x = _rows(5 * PAGE, seed=5)
    store = TensorBlockStore(default_page_rows=PAGE)
    store.put("pin", x, tier="pinned")
    engine = _engine(store)
    forest = _forest(seed=5)
    engine.infer("pin", forest, algorithm=FUSED, batch_pages=2)
    TRACER.reset()
    TRACER.enable()
    try:
        engine.infer("pin", forest, algorithm=FUSED, batch_pages=2)
    finally:
        TRACER.disable()
    spans = TRACER.finished()
    dma = {s.span_id for s in spans if s.name == "scan.dma_in"}
    copies = [s for s in spans if s.name == "scan.page_copy"]
    assert len(copies) == len(dma) == 3
    assert all(s.parent_id in dma for s in copies)
    assert sorted(s.attrs["rows"] for s in copies) == [PAGE, 2 * PAGE,
                                                       2 * PAGE]


NDEV = len(jax.devices())


@pytest.mark.skipif(
    NDEV < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
@pytest.mark.parametrize("batch_pages", [4, 6])
@pytest.mark.parametrize("plan", ["udf", "rel"])
def test_mesh_pinned_scan_is_bit_identical_to_the_device_tier(plan,
                                                              batch_pages):
    """On a (data x model) mesh the pinned table keeps the data sharding
    in the pinned_host memory kind, and its batches feed the shard_map
    plans bit-identically to the device-resident run."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                ("data", "model"))
    x = _rows(9 * PAGE + 3, seed=6)
    forest = _forest(seed=6)
    store = TensorBlockStore(mesh, default_page_rows=PAGE,
                             device_budget_bytes=8 * PAGE * F * 4)
    store.put("dev", x, tier="device")
    ds = store.put("pin", x, tier="pinned")
    _assert_pinned(ds)
    assert ds.data.sharding.spec == jax.sharding.PartitionSpec("data", None)
    engine = _engine(store)
    kw = dict(algorithm=FUSED, plan=plan, batch_pages=batch_pages)
    rd = engine.infer("dev", forest, **kw)
    rp = engine.infer("pin", forest, **kw)
    assert rp.mesh_devices == 8
    assert rp.scan.batches == -(-ds.num_pages // batch_pages)
    assert np.array_equal(np.asarray(rp.predictions),
                          np.asarray(rd.predictions))
