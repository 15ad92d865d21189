"""Paper Tab. 7–9: WIDE and/or SPARSE datasets (Bosch NaN-dense-wide,
Epsilon array-typed-wide, Criteo LIBSVM-sparse).  Claims: the expensive
load/convert path (array-column parse, LIBSVM densify) makes in-database
inference win by the largest factors; sparse storage (criteo) shrinks the
transfer bottleneck and with it the in-DB advantage.

SPARSE section (``run_sparse`` / BENCH_sparse.json): the CSR data plane
vs the dense fallback, end to end — same model, same rows, one dataset
stored ``[N, F]`` dense and once as CSR pages.  The CSR run goes through
used-feature compaction + the feature-gather prepass (no ``[BT, I, F]``
one-hot at full F), and the record includes the external-load comparison
(LIBSVM -> densify -> transfer vs LIBSVM -> CSR pages -> transfer).  Each
run asserts the query really executed on the CSR plane
(``QueryResult.storage_format``) and that predictions match the dense
plane — the smoke job in CI runs this with ``--fast`` on synthetic
criteo (F=10k) so the sparse plane cannot silently regress to the dense
fallback.
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from benchmarks import common as C
from repro.core.reuse import ModelReuseCache
from repro.db import loader as ld
from repro.db.query import ForestQueryEngine
from repro.db.store import TensorBlockStore

ALGO = "predicated"
SPARSE_ALGO = "hummingbird_pallas_fused"
FILE_KIND = {"bosch": "csv", "epsilon": "array", "criteo": "libsvm"}
BENCH_SPARSE_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_sparse.json")


def run(datasets=("bosch", "epsilon", "criteo"), trees=C.TREE_GRID,
        scale=1.0):
    rows = []
    for ds in datasets:
        x, y = C.bench_data(ds, scale=scale)
        kind = FILE_KIND[ds]
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, f"{ds}.dat")
            if kind == "csv":
                ld.write_csv(path, x)
            elif kind == "array":
                ld.write_array_rows(path, x)
            else:
                ld.write_libsvm(path, x, y)
            store = TensorBlockStore(default_page_rows=512)
            store.put(ds, x)
            engine = ForestQueryEngine(store,
                                       reuse_cache=ModelReuseCache())
            for T in trees:
                forest = C.get_forest(ds, "xgboost", T)
                base = dict(dataset=ds, model="xgboost", trees=T,
                            file_kind=kind)
                rows.append({**base,
                             **C.run_standalone(forest, path, kind, ALGO,
                                                n_features=x.shape[1])})
                for plan in ("udf", "rel"):
                    rows.append({**base,
                                 **C.run_netsdb(forest, store, ds, plan,
                                                ALGO, engine=engine)})
                C.run_netsdb(forest, store, ds, "rel+reuse", ALGO,
                             engine=engine)
                rows.append({**base,
                             **C.run_netsdb(forest, store, ds, "rel+reuse",
                                            ALGO, engine=engine)})
    return rows


def run_sparse(datasets=("bosch", "criteo"), trees=C.FAST_TREE_GRID,
               scale=1.0, algo=SPARSE_ALGO, page_rows=512):
    """CSR data plane vs dense fallback, end to end.

    Returns (rows, records).  Raises if the CSR run fell back to the
    dense plane or disagrees with it — this doubles as the CI smoke.
    """
    rows, records = [], []
    for ds in datasets:
        x, y = C.bench_data(ds, scale=scale)
        n, F = x.shape
        store = TensorBlockStore(default_page_rows=page_rows)
        store.put(ds, x)
        sp = store.put_sparse(ds + "@csr", x)
        density = sp.nnz / float(n * F)
        # external-load comparison on the same LIBSVM file: densify path
        # vs CSR-pages path (the transfer-shrink claim)
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, f"{ds}.svm")
            ld.write_libsvm(path, x, y)
            _, _, t_dense = ld.load_libsvm_external(path, F)
            _, _, t_csr = ld.load_libsvm_csr_external(path, F,
                                                      page_rows=page_rows)
        engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                                   plan_cache=ModelReuseCache())
        for T in trees:
            forest = C.get_forest(ds, "xgboost", T)
            base = dict(dataset=ds, model="xgboost", trees=T)
            res_d = engine.infer(ds, forest, algorithm=algo, plan="udf",
                                 write_as="preds_dense")
            res_s = engine.infer(ds + "@csr", forest, algorithm=algo,
                                 plan="udf", write_as="preds_csr")
            # regression guards: the sparse plane must actually execute,
            # and must agree with the dense plane
            if res_s.storage_format != "csr":
                raise RuntimeError(
                    f"{ds}: sparse query fell back to "
                    f"{res_s.storage_format!r} — CSR plane regressed")
            if not np.allclose(np.asarray(res_s.predictions),
                               np.asarray(res_d.predictions),
                               rtol=1e-5, atol=1e-6):
                raise RuntimeError(f"{ds}: CSR/dense prediction mismatch")
            for fmt, res in (("dense", res_d), ("csr", res_s)):
                rows.append({**base, "platform": f"netsdb-udf-{fmt}",
                             "load_s": 0.0,
                             "infer_s": round(res.infer_s, 4),
                             "write_s": round(res.write_s
                                              + res.aggregate_s, 4),
                             "total_s": round(res.total_s, 4),
                             "checksum": float(np.sum(np.asarray(
                                 res.predictions))),
                             "file_kind": fmt})
            records.append(dict(
                dataset=ds, trees=T, algorithm=algo, rows=n, features=F,
                density=round(density, 5),
                stored_dense_bytes=store.get(ds).nbytes,
                stored_csr_bytes=sp.nbytes,
                load_libsvm_densify_s=round(t_dense.total_s, 5),
                load_libsvm_csr_s=round(t_csr.total_s, 5),
                dense_total_s=round(res_d.total_s, 5),
                csr_total_s=round(res_s.total_s, 5),
                csr_vs_dense=round(res_d.total_s
                                   / max(res_s.total_s, 1e-9), 3),
                **C.env_info(engine.mesh)))
    return rows, records


def write_sparse_json(records, path=BENCH_SPARSE_JSON):
    payload = {"bench": "csr_vs_dense", "created_at": time.time(),
               "env": C.env_info(), "records": records}
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--fast", action="store_true",
                    help="wide-sparse smoke: criteo-scale synthetic "
                         "(F=10k) through the CSR plane only, small grid")
    ap.add_argument("--sparse-out", default=BENCH_SPARSE_JSON)
    args = ap.parse_args()
    if args.fast:
        # the CI smoke: F=10k criteo synthetic end to end through the CSR
        # store + gather prepass; raises inside run_sparse on any dense
        # fallback or parity break
        rows, records = run_sparse(datasets=("criteo",), trees=(10, 50),
                                   scale=min(args.scale, 0.25))
        C.print_rows(rows, extra_cols=("file_kind",))
        path = write_sparse_json(records, args.sparse_out)
        print(f"# sparse trajectory -> {path}  (smoke OK: CSR plane "
              f"executed, parity held)")
        return
    trees = C.TREE_GRID
    rows = run(trees=trees, scale=args.scale)
    C.print_rows(rows, extra_cols=("file_kind",))
    srows, records = run_sparse(trees=C.FAST_TREE_GRID, scale=args.scale)
    C.print_rows(srows, header=False, extra_cols=("file_kind",))
    path = write_sparse_json(records, args.sparse_out)
    print(f"# sparse trajectory -> {path}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
