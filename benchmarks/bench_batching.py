"""Paper Sec. 7 'Batching'/'Vectorization': latency vs batch size (pages
per batch) and vs vectorization granularity (rows per page / per block).

Claims: latency improves with batch size until the working set exceeds
memory-level resources; the rows-per-block granularity (vectorizing the
UDF itself) matters more than the blocks-per-batch granularity."""

from __future__ import annotations

import argparse

from repro.compile_cache import enable_compile_cache
from benchmarks import common as C
from repro.core.reuse import ModelReuseCache
from repro.db.query import ForestQueryEngine
from repro.db.store import TensorBlockStore

ALGO = "predicated"


def run(dataset="higgs", trees=500, scale=1.0,
        page_rows_grid=(128, 512, 2048, 8192),
        batch_pages_grid=(1, 4, 16, 64)):
    rows = []
    x, _ = C.bench_data(dataset, scale=scale)
    forest = C.get_forest(dataset, "xgboost", trees)
    # vectorization granularity: rows per page (block height)
    for pr in page_rows_grid:
        store = TensorBlockStore(default_page_rows=pr)
        store.put(dataset, x)
        engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache())
        r = C.run_netsdb(forest, store, dataset, "udf", ALGO,
                         engine=engine)
        rows.append(dict(dataset=dataset, model="xgboost", trees=trees,
                         platform=f"udf-pagerows-{pr}", **{
                             k: r[k] for k in ("load_s", "infer_s",
                                               "write_s", "total_s")}))
    # batching granularity: pages per batch at fixed page size
    store = TensorBlockStore(default_page_rows=512)
    store.put(dataset, x)
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache())
    for bp in batch_pages_grid:
        r = C.run_netsdb(forest, store, dataset, "udf", ALGO,
                         engine=engine, batch_pages=bp)
        rows.append(dict(dataset=dataset, model="xgboost", trees=trees,
                         platform=f"udf-batchpages-{bp}", **{
                             k: r[k] for k in ("load_s", "infer_s",
                                               "write_s", "total_s")}))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--trees", type=int, default=500)
    args = ap.parse_args()
    C.print_rows(run(trees=args.trees, scale=args.scale))


if __name__ == "__main__":
    enable_compile_cache()
    main()
