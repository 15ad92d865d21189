"""Observability-plane trajectory (BENCH_obs.json): what tracing costs.

Two jobs, same gate discipline as ``bench_faults.py``:

  * ARMED-TRACING OVERHEAD — the fused streamed scan with ``obs.TRACER``
    fully enabled (every span and event of docs/observability.md
    recording) vs the same scan with the tracer disabled (the default:
    every trace point returns the shared ``NULL_SPAN``, allocating
    nothing).  Spans live in Python driver code strictly off the jitted
    hot path, so the measured overhead must stay within
    ``OVERHEAD_BOUND`` (5%) — ``run`` RAISES past it, making the bench
    double as the regression smoke for the whole instrumentation layer.
  * TRACE VALIDITY (``--smoke``, the CI obs-smoke job) — a streamed
    scan under a forced tiny tier ladder with tracing enabled, whose
    exported Chrome trace is validated structurally: JSON round-trip,
    non-empty, every span event carrying ``ph``/``ts``/``dur``/``tid``,
    spans NESTED (parent_id chains resolve, the cross-thread
    ``scan.drain_write`` -> ``scan.batch`` edge included).

Timing protocol: warm once (compile), then min-of-``iters`` of the
scan's own ``wall_s`` — the shared trajectory protocol.  The traced
iterations re-arm (enable + reset) the tracer each pass so every
measured scan records a full span tree, not an amortized tail.

Every record field and exported name is documented in
``docs/observability.md`` (enforced by ``benchmarks/check_docs.py``).
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
from repro.db.query import ForestQueryEngine
from repro.db.store import TensorBlockStore
from repro.obs import TRACER

ALGO = "predicated_pallas_fused"
OVERHEAD_BOUND = 0.05
BENCH_OBS_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_obs.json")


def validate_chrome_trace(payload: dict) -> dict:
    """Structural validation of an exported Chrome trace (used by the
    CI smoke and tests/test_obs.py).  Round-trips through json, checks
    the trace-event contract on every row, and resolves parent chains.
    Returns summary counts; raises on any violation."""
    data = json.loads(json.dumps(payload))        # serializability
    events = data["traceEvents"]
    if not events:
        raise RuntimeError("exported trace is empty")
    spans = {}
    for ev in events:
        if not isinstance(ev.get("name"), str) or "ph" not in ev:
            raise RuntimeError(f"malformed trace event: {ev}")
        if ev["ph"] == "X":
            for field in ("ts", "dur", "tid", "pid"):
                if not isinstance(ev.get(field), (int, float)):
                    raise RuntimeError(
                        f"span {ev['name']!r} missing numeric {field}")
            spans[ev["args"]["span_id"]] = ev
    nested = cross_thread = 0
    for ev in spans.values():
        pid = ev["args"].get("parent_id")
        if pid is None:
            continue
        parent = spans.get(pid)
        if parent is None:
            raise RuntimeError(
                f"span {ev['name']!r} parent_id {pid} unresolved")
        nested += 1
        if parent["tid"] != ev["tid"]:
            cross_thread += 1
    if nested == 0:
        raise RuntimeError("no nested spans in exported trace")
    threads = {ev["tid"] for ev in events if ev["ph"] == "X"}
    return {"events": len(events), "spans": len(spans), "nested": nested,
            "cross_thread": cross_thread, "threads": len(threads)}


def run(dataset="higgs", trees=100, scale=0.25, iters=5, plan="udf",
        batch_pages=4, page_rows=512, strict=True):
    """Returns (rows, records).  Raises (``strict``) if the armed-tracing
    overhead breaches ``OVERHEAD_BOUND``, tracing changes predictions,
    or the traced run's exported trace fails structural validation."""
    x, _ = C.bench_data(dataset, scale=scale)
    budget = max(x.nbytes // 4, 1)          # host tier by construction
    store = TensorBlockStore(default_page_rows=page_rows,
                             device_budget_bytes=budget)
    stored = store.put(dataset, x)
    assert stored.tier == "host", stored.tier
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                               plan_cache=ModelReuseCache())
    forest = C.get_forest(dataset, "xgboost", trees)
    kw = dict(algorithm=ALGO, plan=plan, batch_pages=batch_pages)
    base = dict(dataset=dataset, model="xgboost", trees=trees,
                algorithm=ALGO, plan=plan, tier=stored.tier,
                rows=x.shape[0], features=x.shape[1],
                batch_pages=batch_pages, iters=iters)

    def one(traced: bool):
        if traced:
            TRACER.reset()
            TRACER.enable()
        try:
            return engine.infer(dataset, forest, **kw)
        finally:
            TRACER.disable()

    engine.infer(dataset, forest, **kw)      # warm: compile lands here
    # INTERLEAVED pairs, not two separately-timed groups: machine drift
    # (thermal, co-tenant load) between group A and group B otherwise
    # reads as tracing overhead — on shared CI runners the drift alone
    # exceeds the 5% bound.  Alternating per round exposes both sides
    # to the same drift; min-of-iters then compares best-case to
    # best-case as usual.
    base_walls, traced_walls = [], []
    clean = traced = None
    for _ in range(iters):
        clean = one(False)
        base_walls.append(clean.scan.wall_s)
        traced = one(True)
        traced_walls.append(traced.scan.wall_s)
    base_s, traced_s = min(base_walls), min(traced_walls)
    ref = np.asarray(clean.predictions)
    overhead = traced_s / max(base_s, 1e-9) - 1.0
    if not np.array_equal(np.asarray(traced.predictions), ref):
        raise RuntimeError("enabling the tracer changed predictions")
    if traced.trace is None or not traced.trace.num_spans:
        raise RuntimeError("traced run produced no TraceSummary spans")
    shape = validate_chrome_trace(TRACER.export_chrome())
    if strict and overhead > OVERHEAD_BOUND:
        raise RuntimeError(
            f"armed-tracing overhead {overhead:.1%} breaches the "
            f"{OVERHEAD_BOUND:.0%} bound — span bookkeeping leaked onto "
            f"the hot path")
    records = [dict(scenario="tracing_overhead",
                    baseline_wall_s=round(base_s, 5),
                    traced_wall_s=round(traced_s, 5),
                    overhead_fraction=round(overhead, 4),
                    overhead_bound=OVERHEAD_BOUND,
                    within_bound=bool(overhead <= OVERHEAD_BOUND),
                    spans_recorded=traced.trace.num_spans,
                    batches=traced.scan.batches,
                    trace_events=shape["events"],
                    nested_spans=shape["nested"],
                    cross_thread_spans=shape["cross_thread"],
                    threads=shape["threads"],
                    parity=True, **base, **C.env_info(engine.mesh))]
    rows = [{**base, "platform": "obs-disabled", "load_s": 0.0,
             "infer_s": round(base_s, 4), "write_s": 0.0,
             "total_s": round(base_s, 4)},
            {**base, "platform": "obs-traced", "load_s": 0.0,
             "infer_s": round(traced_s, 4), "write_s": 0.0,
             "total_s": round(traced_s, 4)}]
    return rows, records


def smoke(device_budget_bytes=262144, host_budget_bytes=262144,
          out=None, page_rows=64):
    """The CI obs-smoke job: stream a scan down the forced tier ladder
    (budgets default to 256 KiB, so the dataset lands on DISK) with
    tracing enabled, then validate the exported Chrome trace.  Raises
    on any structural violation; prints the trace shape on success."""
    x, _ = C.bench_data("fraud", scale=0.5)   # [6000, 28] f32 ≈ 656 KiB
    store = TensorBlockStore(default_page_rows=page_rows,
                             device_budget_bytes=device_budget_bytes,
                             host_budget_bytes=host_budget_bytes)
    stored = store.put("obs-smoke", x)
    if x.nbytes > host_budget_bytes:
        assert stored.tier == "disk", stored.tier
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                               plan_cache=ModelReuseCache())
    forest = C.get_forest("fraud", "xgboost", 10, depth=4)
    TRACER.reset()
    TRACER.enable()
    try:
        res = engine.infer("obs-smoke", forest, algorithm=ALGO)
    finally:
        TRACER.disable()
    if res.trace is None or not res.trace.span_counts.get("scan.batch"):
        raise RuntimeError("smoke scan recorded no batch spans")
    payload = TRACER.export_chrome(out)
    shape = validate_chrome_trace(payload)
    print(f"# obs-smoke ok: tier={stored.tier} "
          f"batches={res.scan.batches} spans={shape['spans']} "
          f"nested={shape['nested']} cross_thread={shape['cross_thread']} "
          f"threads={shape['threads']}"
          + (f" -> {out}" if out else ""))
    return shape


def write_obs_json(records, path=BENCH_OBS_JSON):
    payload = {"bench": "observability", "created_at": time.time(),
               "env": C.env_info(), "records": records}
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: traced streamed scan + trace validation"
                         " only (no BENCH_obs.json)")
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--trees", type=int, default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--device-budget-bytes", type=int, default=262144)
    ap.add_argument("--host-budget-bytes", type=int, default=262144)
    ap.add_argument("--trace-out", default=None,
                    help="--smoke: also write the exported trace here")
    ap.add_argument("--out", default=BENCH_OBS_JSON)
    args = ap.parse_args()
    if args.smoke:
        smoke(device_budget_bytes=args.device_budget_bytes,
              host_budget_bytes=args.host_budget_bytes,
              out=args.trace_out)
        return
    rows, records = run(
        trees=args.trees or (10 if args.fast else 100),
        scale=args.scale or (0.1 if args.fast else 0.25),
        iters=args.iters or (3 if args.fast else 5))
    C.print_rows(rows)
    path = write_obs_json(records, args.out)
    ov = records[0]
    print(f"# obs trajectory -> {path}  (armed-tracing overhead "
          f"{ov['overhead_fraction']:+.1%}, bound {OVERHEAD_BOUND:.0%})")


if __name__ == "__main__":
    enable_compile_cache()
    main()
