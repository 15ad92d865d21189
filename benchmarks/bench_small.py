"""Paper Tab. 2–3: end-to-end latency on SMALL dense datasets (Fraud,
Year).  Claim under test: data loading dominates, so in-database
inference wins at every model size; netsdb-udf best for small models,
netsdb-opt best for large (reuse repairs rel's fixed stage overheads)."""

from __future__ import annotations

import argparse
import os
import tempfile

import numpy as np

from repro.compile_cache import enable_compile_cache
from benchmarks import common as C
from repro.db import loader as ld
from repro.db.query import ForestQueryEngine
from repro.core.reuse import ModelReuseCache
from repro.db.store import TensorBlockStore

ALGO = "predicated"
STANDALONE_ALGOS = ("predicated", "hummingbird", "quickscorer")


def run(datasets=("fraud", "year"), trees=C.TREE_GRID,
        model_types=("xgboost",), scale=1.0):
    rows = []
    for ds in datasets:
        x, y = C.bench_data(ds, scale=scale)
        with tempfile.TemporaryDirectory() as td:
            csv = os.path.join(td, f"{ds}.csv")
            ld.write_csv(csv, x)
            store = TensorBlockStore(default_page_rows=1024)
            store.put(ds, x)
            engine = ForestQueryEngine(store,
                                       reuse_cache=ModelReuseCache())
            for mt in model_types:
                for T in trees:
                    forest = C.get_forest(ds, mt, T)
                    base = dict(dataset=ds, model=mt, trees=T)
                    for algo in STANDALONE_ALGOS:
                        r = C.run_standalone(forest, csv, "csv", algo,
                                             n_features=x.shape[1])
                        rows.append({**base, **r})
                    for plan in ("udf", "rel"):
                        r = C.run_netsdb(forest, store, ds, plan,
                                         ALGO, engine=engine)
                        rows.append({**base, **r})
                    # netsdb-opt: steady state = 2nd query on same model
                    C.run_netsdb(forest, store, ds, "rel+reuse", ALGO,
                                 engine=engine)
                    r = C.run_netsdb(forest, store, ds, "rel+reuse", ALGO,
                                     engine=engine)
                    rows.append({**base, **r})
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    trees = C.FAST_TREE_GRID if args.fast else C.TREE_GRID
    C.print_rows(run(trees=trees, scale=args.scale))


if __name__ == "__main__":
    enable_compile_cache()
    main()
