"""Fault-plane trajectory (BENCH_faults.json): what reliability costs.

Two claims, measured (docs/reliability.md):

  * ZERO-FAULT OVERHEAD — the instrumented scan (an ARMED FaultInjector
    on every site that never fires, plus the full RetryPolicy wrappers)
    vs the seed path with no injector at all.  The guards live in Python
    driver code strictly off the jitted hot path, so the measured
    overhead must stay within ``OVERHEAD_BOUND`` (5%) — ``run`` RAISES
    past it, which makes the bench double as the regression smoke.
  * RECOVERY LATENCY — wall time of a scan that takes a degradation
    ladder mid-flight, vs the clean run: drain-worker death -> mid-scan
    sync-drain fallback, and device-transfer retry exhaustion -> halved
    ``batch_pages`` resubmit.  Bitwise parity with the clean run is
    asserted on every recovered scan; the interesting number is how much
    wall the ladder costs, not whether the answer survives (tests pin
    that).

Timing protocol: warm once (compile), then min-of-``iters`` of the
scan's own ``wall_s`` — same shape as the rest of the trajectory
benches.  The fault runs re-arm a fresh injector every iteration so each
measured scan actually takes the ladder.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from benchmarks import common as C
from repro.core.reuse import ModelReuseCache
from repro.db.faults import FAULT_SITES, FaultInjector, RetryPolicy
from repro.db.query import ForestQueryEngine
from repro.db.store import TensorBlockStore

ALGO = "predicated_pallas_fused"
OVERHEAD_BOUND = 0.05
BENCH_FAULTS_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_faults.json")


def _armed_silent_injector() -> FaultInjector:
    """Every site armed, none ever firing: the full instrumented path."""
    inj = FaultInjector()
    for site in FAULT_SITES:
        inj.inject(site, fail_at=10**9)
    return inj


def run(dataset="higgs", trees=100, scale=0.25, iters=5, plan="udf",
        batch_pages=4, page_rows=512, strict=True):
    """Returns (rows, records).  Raises (``strict``) if the zero-fault
    overhead breaches ``OVERHEAD_BOUND`` or any recovered scan loses
    bitwise parity with the clean run."""
    x, _ = C.bench_data(dataset, scale=scale)
    budget = max(x.nbytes // 4, 1)          # host tier by construction
    store = TensorBlockStore(default_page_rows=page_rows,
                             device_budget_bytes=budget)
    stored = store.put(dataset, x)
    assert stored.tier == "host", stored.tier
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                               plan_cache=ModelReuseCache())
    forest = C.get_forest(dataset, "xgboost", trees)
    policy = RetryPolicy()
    kw = dict(algorithm=ALGO, plan=plan, batch_pages=batch_pages)
    base = dict(dataset=dataset, model="xgboost", trees=trees,
                algorithm=ALGO, plan=plan, tier=stored.tier,
                rows=x.shape[0], features=x.shape[1],
                batch_pages=batch_pages, iters=iters)

    def best(make_extra):
        walls, last = [], None
        for _ in range(iters):
            last = engine.infer(dataset, forest, **kw, **make_extra())
            walls.append(last.scan.wall_s)
        return min(walls), last

    engine.infer(dataset, forest, **kw)      # warm: compile lands here
    base_s, clean = best(dict)
    ref = np.asarray(clean.predictions)

    inst_s, inst = best(lambda: dict(injector=_armed_silent_injector(),
                                     retry_policy=policy))
    overhead = inst_s / max(base_s, 1e-9) - 1.0
    if not np.array_equal(np.asarray(inst.predictions), ref):
        raise RuntimeError("armed-but-silent injector changed predictions")
    if inst.scan.faults_injected or inst.scan.retries:
        raise RuntimeError("silent injector reported fault activity")
    if strict and overhead > OVERHEAD_BOUND:
        raise RuntimeError(
            f"zero-fault overhead {overhead:.1%} breaches the "
            f"{OVERHEAD_BOUND:.0%} bound — retry wrappers leaked onto "
            f"the hot path")
    records = [dict(scenario="zero_fault_overhead", fault_site=None,
                    baseline_wall_s=round(base_s, 5),
                    instrumented_wall_s=round(inst_s, 5),
                    recovery_wall_s=None,
                    overhead_fraction=round(overhead, 4),
                    overhead_bound=OVERHEAD_BOUND,
                    within_bound=bool(overhead <= OVERHEAD_BOUND),
                    faults_injected=0, retries=0,
                    degraded_to_sync=False, batch_resubmits=0,
                    parity=True, **base, **C.env_info(engine.mesh))]
    rows = [{**base, "platform": "faults-baseline", "load_s": 0.0,
             "infer_s": round(base_s, 4), "write_s": 0.0,
             "total_s": round(base_s, 4)},
            {**base, "platform": "faults-instrumented", "load_s": 0.0,
             "infer_s": round(inst_s, 4), "write_s": 0.0,
             "total_s": round(inst_s, 4)}]

    ladders = [
        ("recovery_drain_fallback", "drain_worker",
         lambda: FaultInjector().inject("drain_worker", fail_at=1)),
        ("recovery_batch_resubmit", "page_dma_in",
         lambda: FaultInjector().inject("page_dma_in", fail_at=1,
                                        times=policy.max_attempts)),
    ]
    for scenario, site, make_inj in ladders:
        rec_s, rec = best(lambda: dict(injector=make_inj(),
                                       retry_policy=policy))
        if not np.array_equal(np.asarray(rec.predictions), ref):
            raise RuntimeError(f"{scenario}: recovered predictions "
                               f"diverge from the clean run")
        sc = rec.scan
        if scenario == "recovery_drain_fallback" and not sc.degraded_to_sync:
            raise RuntimeError(f"{scenario}: fallback not reported")
        if scenario == "recovery_batch_resubmit" and not sc.batch_resubmits:
            raise RuntimeError(f"{scenario}: resubmit not reported")
        records.append(dict(
            scenario=scenario, fault_site=site,
            baseline_wall_s=round(base_s, 5), instrumented_wall_s=None,
            recovery_wall_s=round(rec_s, 5),
            overhead_fraction=round(rec_s / max(base_s, 1e-9) - 1.0, 4),
            overhead_bound=None, within_bound=None,
            faults_injected=sc.faults_injected, retries=sc.retries,
            degraded_to_sync=sc.degraded_to_sync,
            batch_resubmits=sc.batch_resubmits, parity=True,
            **base, **C.env_info(engine.mesh)))
        rows.append({**base, "platform": f"faults-{site}", "load_s": 0.0,
                     "infer_s": round(rec_s, 4), "write_s": 0.0,
                     "total_s": round(rec_s, 4)})
    return rows, records


def write_faults_json(records, path=BENCH_FAULTS_JSON):
    payload = {"bench": "fault_tolerance", "created_at": time.time(),
               "env": C.env_info(), "records": records}
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--trees", type=int, default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--out", default=BENCH_FAULTS_JSON)
    args = ap.parse_args()
    rows, records = run(
        trees=args.trees or (10 if args.fast else 100),
        scale=args.scale or (0.1 if args.fast else 0.25),
        iters=args.iters or (3 if args.fast else 5))
    C.print_rows(rows)
    path = write_faults_json(records, args.out)
    ov = records[0]
    print(f"# fault trajectory -> {path}  (zero-fault overhead "
          f"{ov['overhead_fraction']:+.1%}, bound {OVERHEAD_BOUND:.0%})")


if __name__ == "__main__":
    enable_compile_cache()
    main()
