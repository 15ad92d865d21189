"""Paper Tab. 4–6: MEDIUM/LARGE dense datasets (Higgs, Airline, TPCx-AI,
row-scaled).  Claims: netsdb-udf wins small models by avoiding transfer;
netsdb-rel (model parallelism) overtakes udf as trees grow; the netsDB
advantage shrinks as inference compute starts to dominate.

STREAMING section (``run_stream`` / BENCH_stream.json): the paper's
"large-scale datasets" scenario class — datasets that do NOT fit device
memory.  Two tier sections per dataset:

  * HOST: a dataset ≥ 4x ``device_budget_bytes`` is ingested (auto-spills
    to the host tier) and streamed through the double-buffered scan
    executor (``repro.db.executor``), for both udf and rel plans;
  * DISK: the same dataset under a host budget it also exceeds by ≥ 4x,
    so the auto cascade lands it on page-aligned mmap files and the scan
    reads memmap page views — the bottom rung of the tier ladder.

Each record reports the transfer/compute overlap fraction: the
synchronous reference pipeline (``prefetch_depth=1``) exposes the full
page-DMA wait, the double-buffered run (``prefetch_depth=2``) hides what
it can, and

    overlap_fraction = 1 - wait_streamed / wait_serial

is the hidden share.  Records also carry the ASYNC DRAIN accounting
(``drain_s`` worker write time, ``drain_wait_s`` what the compute thread
actually paid, ``drain_overlap_s`` the hidden difference — see
docs/benchmarks.md for every field and the honest XLA:CPU ≈ 0 caveats).
``run_stream`` RAISES if the budgeted ingest missed its expected tier or
if streamed predictions diverge from the all-device-resident run — the
CI ``streaming-smoke`` job runs it with ``--fast`` and deliberately tiny
device AND host budgets so out-of-core paging down to the disk tier
cannot silently regress.
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
STREAM_ALGO = "predicated_pallas_fused"
BENCH_STREAM_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_stream.json")


def run(datasets=("higgs", "airline", "tpcxai"), trees=C.TREE_GRID,
        model_types=("xgboost",), scale=1.0):
    rows = []
    for ds in datasets:
        x, y = C.bench_data(ds, scale=scale)
        with tempfile.TemporaryDirectory() as td:
            csv = os.path.join(td, f"{ds}.csv")
            ld.write_csv(csv, x)
            store = TensorBlockStore(default_page_rows=2048)
            store.put(ds, x)
            engine = ForestQueryEngine(store,
                                       reuse_cache=ModelReuseCache())
            for mt in model_types:
                for T in trees:
                    forest = C.get_forest(ds, mt, T)
                    base = dict(dataset=ds, model=mt, trees=T)
                    rows.append({**base,
                                 **C.run_standalone(forest, csv, "csv",
                                                    ALGO,
                                                    n_features=x.shape[1])})
                    for plan in ("udf", "rel"):
                        rows.append({**base,
                                     **C.run_netsdb(forest, store, ds,
                                                    plan, ALGO,
                                                    engine=engine)})
    return rows


def run_stream(datasets=("higgs",), trees=C.FAST_TREE_GRID, scale=1.0,
               device_budget_bytes=None, host_budget_bytes=None,
               algo=STREAM_ALGO, page_rows=512, tiers=("host", "disk"),
               inject_drain_death=False):
    """Out-of-core streaming scan vs the all-device-resident run, per
    off-device tier (host pages, then disk mmap pages).

    Returns (rows, records).  Raises if a budgeted ingest failed to land
    on its section's tier (host section: past the device budget; disk
    section: past device AND host budgets, each exceeded >= 4x) or if
    streamed predictions diverge from the device-resident reference —
    this doubles as the CI smoke.

    ``inject_drain_death=True`` is the fault smoke (docs/reliability.md):
    each streamed run gets a ``FaultInjector`` that kills the async drain
    worker on its first item, and the run RAISES if the scan did not
    report the mid-flight fallback (``degraded_to_sync``) — parity is
    already checked, so a silent or unreported degradation cannot pass.
    """
    rows, records = [], []
    for ds in datasets:
        x, y = C.bench_data(ds, scale=scale)
        # out-of-core by construction: the dataset is >= 4x each budget
        budget = device_budget_bytes or max(x.nbytes // 4, 1)
        hbudget = host_budget_bytes or max(x.nbytes // 4, 1)
        store_dev = TensorBlockStore(default_page_rows=page_rows)
        store_dev.put(ds, x)
        engine_dev = ForestQueryEngine(store_dev,
                                       reuse_cache=ModelReuseCache(),
                                       plan_cache=ModelReuseCache())
        for tier in tiers:
            budgets = dict(device_budget_bytes=budget)
            if tier == "disk":
                budgets["host_budget_bytes"] = hbudget
            store = TensorBlockStore(default_page_rows=page_rows, **budgets)
            stored = store.put(ds, x)
            if stored.tier != tier:
                raise RuntimeError(
                    f"{ds}: ingest of {stored.nbytes} B under budgets "
                    f"{budgets} landed on tier {stored.tier!r}, expected "
                    f"{tier!r} — out-of-core spill cascade regressed")
            engine = ForestQueryEngine(store,
                                       reuse_cache=ModelReuseCache(),
                                       plan_cache=ModelReuseCache())
            for T in trees:
                forest = C.get_forest(ds, "xgboost", T)
                base = dict(dataset=ds, model="xgboost", trees=T)
                for plan in ("udf", "rel"):
                    kw = dict(algorithm=algo, plan=plan)
                    # synchronous reference first (cold compile lands
                    # here), then the double-buffered run, then the
                    # device-resident parity reference at SAME batching
                    serial = engine.infer(ds, forest, prefetch_depth=1,
                                          **kw)
                    skw = {}
                    if inject_drain_death:
                        from repro.db.faults import FaultInjector
                        skw["injector"] = FaultInjector().inject(
                            "drain_worker", fail_at=1)
                    stream = engine.infer(ds, forest, prefetch_depth=2,
                                          **kw, **skw)
                    if inject_drain_death and not (
                            stream.scan.degraded_to_sync
                            and stream.scan.faults_injected == 1):
                        raise RuntimeError(
                            f"{ds}/{plan}@{tier}: drain worker was killed "
                            f"but the scan did not report degraded_to_sync"
                            f" — unreported degradation")
                    ref = engine_dev.infer(
                        ds, forest, batch_pages=stream.scan.batch_pages,
                        **kw)
                    if not np.array_equal(np.asarray(stream.predictions),
                                          np.asarray(ref.predictions)):
                        raise RuntimeError(
                            f"{ds}/{plan}@{tier}: streamed predictions "
                            f"diverge from the device-resident run — "
                            f"parity broke")
                    sc, ss = stream.scan, serial.scan
                    overlap = max(0.0, 1.0 - sc.transfer_wait_s
                                  / max(ss.transfer_wait_s, 1e-9))
                    rows.append({**base,
                                 "platform": f"netsdb-{plan}-{tier}-stream",
                                 "load_s": 0.0,
                                 "infer_s": round(stream.infer_s
                                                  + stream.partition_s, 4),
                                 "write_s": round(stream.write_s
                                                  + stream.aggregate_s, 4),
                                 "total_s": round(stream.total_s, 4),
                                 "checksum": float(np.sum(np.asarray(
                                     stream.predictions)))})
                    records.append(dict(
                        dataset=ds, trees=T, algorithm=algo, plan=plan,
                        rows=x.shape[0], features=x.shape[1],
                        dataset_bytes=stored.nbytes,
                        device_budget_bytes=budget,
                        host_budget_bytes=(hbudget if tier == "disk"
                                           else None),
                        tier=stream.tier, out_of_core=True,
                        batch_pages=sc.batch_pages, batches=sc.batches,
                        max_in_flight=sc.max_in_flight,
                        bytes_streamed=sc.bytes_streamed,
                        transfer_wait_serial_s=round(ss.transfer_wait_s, 5),
                        transfer_wait_stream_s=round(sc.transfer_wait_s, 5),
                        overlap_fraction=round(overlap, 4),
                        compute_s=round(sc.compute_s, 5),
                        drain_s=round(sc.drain_s, 5),
                        drain_wait_s=round(sc.drain_wait_s, 5),
                        drain_overlap_s=round(sc.drain_overlap_s, 5),
                        drain_async=sc.drain_async,
                        pinned_staging=sc.pinned_staging,
                        serial_wall_s=round(ss.wall_s, 5),
                        stream_wall_s=round(sc.wall_s, 5),
                        device_wall_s=round(ref.scan.wall_s, 5),
                        **C.env_info(engine.mesh)))
    return rows, records


def write_stream_json(records, path=BENCH_STREAM_JSON):
    payload = {"bench": "out_of_core_streaming", "created_at": time.time(),
               "env": C.env_info(), "records": records}
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--datasets", default="higgs")
    ap.add_argument("--device-budget-bytes", type=int, default=None,
                    help="force this device budget for the streaming "
                         "section (default: dataset_bytes // 4)")
    ap.add_argument("--host-budget-bytes", type=int, default=None,
                    help="force this host budget for the streaming "
                         "section's DISK tier (default: "
                         "dataset_bytes // 4)")
    ap.add_argument("--stream-only", action="store_true",
                    help="skip the classic section (the CI smoke)")
    ap.add_argument("--inject-drain-death", action="store_true",
                    help="fault smoke: kill the async drain worker on "
                         "its first item in every streamed run; raise "
                         "unless the scan reports the sync fallback AND "
                         "keeps bitwise parity")
    ap.add_argument("--stream-out", default=BENCH_STREAM_JSON)
    args = ap.parse_args()
    trees = C.FAST_TREE_GRID if args.fast else C.TREE_GRID
    datasets = tuple(args.datasets.split(","))
    if not args.stream_only:
        C.print_rows(run(datasets=datasets, trees=trees, scale=args.scale))
    srows, records = run_stream(
        datasets=datasets, trees=trees,
        scale=min(args.scale, 0.25) if args.fast else args.scale,
        device_budget_bytes=args.device_budget_bytes,
        host_budget_bytes=args.host_budget_bytes,
        inject_drain_death=args.inject_drain_death)
    C.print_rows(srows, header=args.stream_only)
    if args.inject_drain_death:
        # fault smoke: don't overwrite the clean trajectory file with
        # degraded-path numbers
        print("# fault smoke OK: drain worker killed mid-scan in every "
              "streamed run; sync fallback reported, parity held")
        return
    path = write_stream_json(records, args.stream_out)
    print(f"# streaming trajectory -> {path}  (smoke OK: host AND disk "
          f"tiers executed out-of-core, parity held)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
