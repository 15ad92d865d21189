"""Sparse data plane: CSR page store, gather prepass, compaction, plans.

The subsystem's contract, as testable invariants:
  * CSR pages round-trip losslessly (NaN = missing, explicit zeros kept)
    and keep the dense store's page<->batch determinism;
  * used-feature compaction preserves predictions exactly
    (predict(forest, x) == predict(compact, x[:, gather_idx]));
  * the LIBSVM->CSR loader reports the split parse/convert/transfer
    timings without ever densifying;
  * CSR and dense plans agree on predictions for every physical plan,
    and their compiled-plan cache entries never collide;
  * at criteo-scale F the sparse path's traced program contains NO
    intermediate with a full-F trailing axis — no full-F sample tile
    reaches the kernel (checked on the jaxpr, recursively through the
    pallas kernel's sub-jaxpr).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.forest import (compact_forest, make_forest,
                               used_feature_counts)
from repro.core.postprocess import predict_proba
from repro.core.reuse import ModelReuseCache
from repro.core.train import TrainConfig, train_forest
from repro.db.loader import (load_libsvm_csr_external, synth_dataset,
                             write_libsvm)
from repro.db.query import ForestQueryEngine
from repro.db.sparse import csr_pages_from_dense, densify_csr
from repro.db.store import TensorBlockStore
from repro.kernels.common import lane_pad
from repro.kernels.gather import (csr_block_to_dense, gather_columns,
                                  gather_inverse_map)
from repro.kernels.ops import FUSED_KERNEL_ALGORITHMS, KERNEL_ALGORITHMS

from conftest import random_forest_arrays


def _nan_heavy(n=300, F=24, nan_frac=0.7, seed=0):
    """Bosch-like block: wide-ish, mostly missing, some exact zeros."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, F)).astype(np.float32)
    x[rng.random((n, F)) < 0.05] = 0.0          # explicit zeros are data
    x[rng.random((n, F)) < nan_frac] = np.nan
    return x


@pytest.fixture(scope="module")
def sparse_setup():
    x = _nan_heavy()
    rng = np.random.default_rng(1)
    y = (np.nan_to_num(x) @ rng.normal(size=x.shape[1]).astype(np.float32)
         > 0).astype(np.float32)
    forest = train_forest(np.nan_to_num(x), y,
                          TrainConfig(model_type="xgboost", num_trees=10,
                                      max_depth=4))
    store = TensorBlockStore(default_page_rows=64)
    store.put("d", x)
    store.put_sparse("s", x)
    return store, forest, x


# ---------------------------------------------------------------------------
# storage: CSR pages
# ---------------------------------------------------------------------------


def test_csr_pages_roundtrip(sparse_setup):
    store, _, x = sparse_setup
    ds = store.get("s")
    assert ds.storage_format == "csr"
    dense = densify_csr(np.asarray(ds.pages.indptr),
                        np.asarray(ds.pages.indices),
                        np.asarray(ds.pages.values), x.shape[1])
    got = dense[: x.shape[0]]
    # missing stays missing, present values (zeros included) exact
    assert np.array_equal(np.isnan(got), np.isnan(x))
    m = ~np.isnan(x)
    np.testing.assert_array_equal(got[m], x[m])
    # padding rows are fully missing (the dense plane's NaN rows)
    assert np.isnan(dense[x.shape[0]:]).all()


def test_csr_page_batch_determinism(sparse_setup):
    store, _, _ = sparse_setup
    ds = store.get("s")
    blocks = list(ds.batches(2))
    assert len(blocks) == -(-ds.num_pages // 2)
    # every block has the SAME array shapes (one jit signature per batching)
    shapes = {(b.indptr.shape, b.indices.shape, b.values.shape)
              for _, b in blocks[:-1]}
    assert len(shapes) == 1
    # batch k always covers the same pages: re-iteration is bit-identical
    again = list(ds.batches(2))
    for (_, a), (_, b) in zip(blocks, again):
        np.testing.assert_array_equal(np.asarray(a.indices),
                                      np.asarray(b.indices))


def test_catalog_tags_format(sparse_setup):
    store, _, _ = sparse_setup
    cat = store.catalog()
    assert cat["d"]["format"] == "dense"
    assert cat["s"]["format"] == "csr"
    assert cat["s"]["nnz"] > 0
    # CSR pages genuinely compress the 70%-missing block
    assert cat["s"]["bytes"] < cat["d"]["bytes"]


# ---------------------------------------------------------------------------
# model half: used-feature compaction
# ---------------------------------------------------------------------------


def test_compact_forest_invariants(rng):
    F = 10_000
    fe, th, dl, lv = random_forest_arrays(rng, T=6, depth=4, F=F, seed=7)
    forest = make_forest(fe, th, lv, default_left=dl, n_features=F)
    counts = used_feature_counts(forest)
    assert (counts <= forest.num_internal).all()
    compact, gidx = compact_forest(forest)
    f_used = np.unique(gidx).size
    # sorted, duplicate-free over the real slots; padding repeats gidx[0]
    real = gidx[:f_used]
    assert np.array_equal(real, np.unique(real))
    assert (gidx[f_used:] == gidx[0]).all()
    assert compact.n_features == gidx.size
    assert compact.n_features % 8 == 0
    # every remapped split points inside the compact space
    assert int(np.asarray(compact.feature).max()) < compact.n_features


def test_compact_forest_prediction_parity(rng):
    F = 2_000
    fe, th, dl, lv = random_forest_arrays(rng, T=5, depth=4, F=F, seed=11)
    forest = make_forest(fe, th, lv, default_left=dl, n_features=F)
    compact, gidx = compact_forest(forest)
    r = np.random.default_rng(2)
    x = r.normal(size=(32, F)).astype(np.float32)
    x[r.random(x.shape) < 0.5] = np.nan
    for algo in ("predicated", "hummingbird", "quickscorer"):
        want = predict_proba(forest, jnp.asarray(x), algorithm=algo)
        got = predict_proba(compact, gather_columns(jnp.asarray(x), gidx),
                            algorithm=algo)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# loader: LIBSVM -> CSR, no densify
# ---------------------------------------------------------------------------


def test_libsvm_csr_loader(tmp_path):
    x, y = synth_dataset("bosch", max_rows=40)
    p = str(tmp_path / "d.svm")
    write_libsvm(p, x, y)
    pages, labels, timing = load_libsvm_csr_external(p, x.shape[1],
                                                     page_rows=16)
    # the full LoadTiming breakdown is populated (same contract as every
    # other external loader)
    assert timing.parse_s > 0 and timing.convert_s > 0
    assert timing.transfer_s > 0
    assert timing.total_s >= (timing.parse_s + timing.convert_s
                              + timing.transfer_s) * 0.99
    np.testing.assert_allclose(labels, y)
    dense = densify_csr(np.asarray(pages.indptr), np.asarray(pages.indices),
                        np.asarray(pages.values), x.shape[1])[:40]
    mask = ~np.isnan(x) & (x != 0.0)      # libsvm files drop zeros
    np.testing.assert_allclose(dense[mask], x[mask], rtol=1e-4, atol=1e-5)
    assert np.isnan(dense[~mask]).all()


def test_libsvm_csr_loader_feeds_store(tmp_path):
    x, y = synth_dataset("bosch", max_rows=30)
    p = str(tmp_path / "d.svm")
    write_libsvm(p, x, y)
    pages, labels, _ = load_libsvm_csr_external(p, x.shape[1], page_rows=16)
    store = TensorBlockStore(default_page_rows=16)
    ds = store.put_sparse("ext", pages=pages, num_rows=len(labels),
                          labels=labels)
    assert ds.storage_format == "csr" and ds.num_rows == 30


# ---------------------------------------------------------------------------
# query plans: CSR <-> dense parity, cache key separation
# ---------------------------------------------------------------------------


PLANS = ("udf", "rel", "rel+reuse")


@pytest.mark.parametrize("plan", PLANS)
def test_csr_dense_plan_parity(sparse_setup, plan):
    """Same model, same rows: the CSR plane (compaction + gather prepass)
    must reproduce the dense plane bit-for-allclose on NaN-heavy data."""
    store, forest, _ = sparse_setup
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                               plan_cache=ModelReuseCache())
    algo = "hummingbird_pallas_fused"
    rd = engine.infer("d", forest, algorithm=algo, plan=plan)
    rs = engine.infer("s", forest, algorithm=algo, plan=plan)
    assert rd.storage_format == "dense" and rs.storage_format == "csr"
    assert rd.num_stages == rs.num_stages
    np.testing.assert_allclose(np.asarray(rs.predictions),
                               np.asarray(rd.predictions),
                               rtol=1e-5, atol=1e-6)


def test_plan_cache_separates_formats(sparse_setup):
    """Dense and CSR plans over the SAME model are different executables:
    neither may serve the other's cache entry."""
    store, forest, _ = sparse_setup
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                               plan_cache=ModelReuseCache())
    kw = dict(algorithm="predicated", plan="udf", model_id="fmt-sep")
    r_d1 = engine.infer("d", forest, **kw)
    r_s1 = engine.infer("s", forest, **kw)
    assert not r_d1.plan_reuse_hit and not r_s1.plan_reuse_hit
    # steady state: each format hits its OWN entry
    r_d2 = engine.infer("d", forest, **kw)
    r_s2 = engine.infer("s", forest, **kw)
    assert r_d2.plan_reuse_hit and r_s2.plan_reuse_hit
    assert len(engine.plan_cache) == 2


def test_rel_reuse_model_cache_separates_formats(sparse_setup):
    """The partition-model cache keys on format too: the CSR plane's
    materialization is the COMPACTED forest, not the full-F one."""
    store, forest, _ = sparse_setup
    cache = ModelReuseCache()
    engine = ForestQueryEngine(store, reuse_cache=cache,
                               plan_cache=ModelReuseCache())
    kw = dict(algorithm="predicated", plan="rel+reuse", model_id="m-fmt")
    engine.infer("d", forest, **kw)
    r2 = engine.infer("s", forest, **kw)
    assert not r2.reuse_hit          # csr materialization is distinct
    assert cache.stats.misses == 2
    r3 = engine.infer("s", forest, **kw)
    assert r3.reuse_hit


# ---------------------------------------------------------------------------
# the acceptance check: no [BT, I, F] one-hot at criteo-scale F
# ---------------------------------------------------------------------------


def _all_shapes(jaxpr):
    """Every intermediate's shape, recursing through sub-jaxprs (the
    pallas kernel body lives in the call's params)."""
    out = set()
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out.add(tuple(getattr(v.aval, "shape", ())))
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", p)
            if hasattr(inner, "eqns"):
                out |= _all_shapes(inner)
    return out


def test_sparse_path_has_no_full_f_onehot(rng):
    """F=10k: the dense kernel path stages full-F [BB, F] sample tiles
    in-kernel; the sparse path (gather prepass + compact forest) must
    contain NO >=2-D intermediate with a full-F trailing axis anywhere in
    its traced program."""
    F, B, page_rows = 10_000, 16, 8
    fe, th, dl, lv = random_forest_arrays(rng, T=4, depth=3, F=F, seed=13)
    forest = make_forest(fe, th, lv, default_left=dl, n_features=F)
    compact, gidx = compact_forest(forest)
    inv = jnp.asarray(gather_inverse_map(gidx, F))
    f_used = int(gidx.size)
    kfn = FUSED_KERNEL_ALGORITHMS["predicated_pallas_fused"]

    x = _nan_heavy(B, F, nan_frac=0.9, seed=3)
    pages = csr_pages_from_dense(x, page_rows=page_rows)

    def sparse_path(pg):
        xc = csr_block_to_dense(pg, inv, f_used)
        return kfn(compact, xc, block_b=8, block_t=4, interpret=True)

    def dense_path(xx):
        return kfn(forest, xx, block_b=8, block_t=4, interpret=True)

    sparse_shapes = _all_shapes(jax.make_jaxpr(sparse_path)(pages).jaxpr)
    dense_shapes = _all_shapes(
        jax.make_jaxpr(dense_path)(jnp.asarray(x)).jaxpr)

    wide = [s for s in sparse_shapes if len(s) >= 2 and s[-1] == F]
    assert not wide, f"full-F intermediates on the sparse path: {wide}"
    # sanity: the dense path DOES carry full-F tiles in-kernel
    assert any(len(s) == 2 and s[-1] == F for s in dense_shapes)
    # and the compact one-hot is the expected [F_used, Ip] (one tree's
    # nodes on the lanes)
    assert (f_used, lane_pad(compact.num_internal)) in sparse_shapes

    # parity on the same rows, so the jaxpr claim is about a CORRECT path
    want = np.asarray(dense_path(jnp.asarray(x)))
    got = np.asarray(sparse_path(pages))[:B]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_criteo_scale_end_to_end(rng):
    """The acceptance run: F=10k, used features per tree <= 64, end to
    end through the CSR store + gather prepass, with CSR/dense parity."""
    F = 10_000
    fe, th, dl, lv = random_forest_arrays(rng, T=8, depth=6, F=F, seed=17)
    forest = make_forest(fe, th, lv, default_left=dl, n_features=F)
    assert used_feature_counts(forest).max() <= 64
    x = _nan_heavy(96, F, nan_frac=0.96, seed=5)      # criteo density
    store = TensorBlockStore(default_page_rows=32)
    store.put("wide-d", x)
    store.put_sparse("wide-s", x)
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                               plan_cache=ModelReuseCache())
    algo = "predicated_pallas_fused"
    rd = engine.infer("wide-d", forest, algorithm=algo, plan="udf")
    rs = engine.infer("wide-s", forest, algorithm=algo, plan="udf")
    assert rs.storage_format == "csr"
    np.testing.assert_allclose(np.asarray(rs.predictions),
                               np.asarray(rd.predictions),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# bf16 tree tiles (satellite: kernel-side acc_dtype plumb)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("base", ["predicated", "hummingbird",
                                  "quickscorer"])
def test_fused_bf16_tree_tiles(rng, base):
    """bf16-staged thresholds/leaves with f32 accumulation must match the
    unfused reference over a bf16-quantized forest, and keep f32 output."""
    fe, th, dl, lv = random_forest_arrays(rng, T=5, depth=4, F=11, seed=21)
    forest = make_forest(fe, th, lv, default_left=dl, n_features=11)
    x = jnp.asarray(np.random.default_rng(4).normal(
        size=(16, 11)).astype(np.float32))
    got = FUSED_KERNEL_ALGORITHMS[base + "_pallas_fused"](
        forest, x, block_b=8, block_t=2, interpret=True,
        tree_dtype=jnp.bfloat16)
    assert got.dtype == jnp.float32
    qf = forest.astype(jnp.bfloat16).astype(jnp.float32)
    want = np.sum(np.asarray(KERNEL_ALGORITHMS[base + "_pallas"](
        qf, x, block_b=8, block_t=2, interpret=True)), axis=1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
