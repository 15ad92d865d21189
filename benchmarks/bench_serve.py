"""Online-serving trajectory (BENCH_serve.json): what coalescing buys.

The serving plane (``serve/forest.py``, docs/serving.md) exists for one
measurable reason: under REQUEST traffic, per-request ``infer()`` loops
pay the full store-roundtrip + plan-lookup + dispatch cost per row,
capping throughput near 1/service-time — while micro-batch coalescing
onto the compiled-plan cache amortizes that cost across every request
in a tick WITHOUT ever re-tracing.  This bench measures both sides
honestly, open-loop:

  * OPEN-LOOP ARRIVALS — requests arrive on a fixed schedule
    (``rate_hz``), NOT as fast as the server finishes (closed loop
    hides queueing collapse: a saturated closed-loop server just slows
    its own clients).  Latency is measured from the SCHEDULED arrival
    instant, so a submitter that falls behind cannot flatter the
    server.
  * PER-REQUEST BASELINE — the decoupled-platform discipline from the
    paper's standalone lane, one request at a time: ship the row into
    the store (``store.put``), run ``engine.infer`` over it, read the
    prediction back.  The plan cache still helps it (constant [1, F]
    batch signature — we do NOT strawman the baseline with per-request
    retraces); what it cannot amortize is the per-request overhead.
    Above its capacity (~1/service-time) the open-loop queue grows and
    its percentiles collapse — that collapse is the phenomenon, not an
    artifact.
  * ZERO-RETRACE GATE — around every coalesced traffic window the
    bench snapshots the process-global ``plan.traces`` /
    ``plan.cache_misses`` counters; after ``register_model``'s bucket
    warmup BOTH deltas must be exactly 0 (every tick hits a resident
    ``CompiledQueryPlan``).  ``strict`` runs RAISE otherwise, and the
    CI serve-smoke job (``--smoke``) repeats the check plus a tail
    gate: smoke p99 must stay within ``SMOKE_P99_MULT`` of the p50
    floor — a coalescer that flushes erratically fails even when its
    median looks fine.

The acceptance line for the plane: coalesced p50 beats the
per-request baseline by >= ``MIN_MID_RATE_SPEEDUP`` at the MID arrival
rate (above baseline capacity, below coalesced capacity), with zero
retraces.  Every record field is documented in ``docs/serving.md``
(enforced by ``benchmarks/check_docs.py``).
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
from repro.obs import METRICS
from repro.serve.forest import ForestServeEngine
from repro.serve.router import TIER_INTERACTIVE

ALGO = "predicated"                 # jitted jnp kernel: ~0.1-1 ms/tick at
#                                     bench scale (the Pallas interpret-
#                                     mode kernels are scan-grade, not
#                                     serving-grade, on CPU)
DATASET = "fraud"                   # 28 dense features
RATES_HZ = (200, 800, 3000)         # below / above / far above the
#                                     per-request baseline's capacity
MODEL_TREES = (10, 100)             # tenant scales (both registered in
#                                     ONE engine: the runs are multi-
#                                     tenant by construction)
MIN_MID_RATE_SPEEDUP = 2.0          # acceptance: coalesced p50 wins by
#                                     >= this at the mid rate
SMOKE_P50_FLOOR_S = 2e-3            # smoke tail gate: p99 must stay
SMOKE_P99_MULT = 25.0               # within MULT x max(p50, floor)
BENCH_SERVE_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_serve.json")


def _pcts(lats_s: list[float]) -> tuple[float, float]:
    a = np.asarray(lats_s)
    return float(np.percentile(a, 50)), float(np.percentile(a, 99))


def _arrivals(rate_hz: float, n: int, t0: float) -> np.ndarray:
    """Deterministic open-loop schedule: request i is DUE at
    ``t0 + i/rate`` on the perf_counter timeline."""
    return t0 + np.arange(n) / float(rate_hz)


def run_coalesced(eng: ForestServeEngine, model: str, x: np.ndarray,
                  rate_hz: float, n: int) -> dict:
    """Drive ``n`` single-row requests at ``rate_hz`` into a RUNNING
    engine; returns latency percentiles + per-window counter deltas
    (plan.traces / plan.cache_misses deltas are THE zero-retrace
    evidence)."""
    m = eng._get(model)
    snap = {k: m.metrics.counter(k).value
            for k in ("serve.ticks", "serve.padding_rows",
                      "serve.plan_hits", "serve.plan_misses",
                      "serve.shed")}
    wh = m.metrics.histogram("serve.coalesce_width")
    w_sum, w_cnt = wh.sum, wh.count
    traces0 = METRICS.counter("plan.traces").value
    misses0 = METRICS.counter("plan.cache_misses").value

    t0 = time.perf_counter() + 0.01
    due = _arrivals(rate_hz, n, t0)
    reqs = []
    for i in range(n):
        now = time.perf_counter()
        if now < due[i]:
            time.sleep(due[i] - now)
        reqs.append(eng.submit(model, x[i % len(x)],
                               priority=TIER_INTERACTIVE))
    for r in reqs:
        r.wait(30.0)
    lats = [r.finished_at - due[i] for i, r in enumerate(reqs)]
    p50, p99 = _pcts(lats)
    span = max(r.finished_at for r in reqs) - t0
    d = {k: m.metrics.counter(k).value - v for k, v in snap.items()}
    return {
        "p50_ms": round(p50 * 1e3, 4), "p99_ms": round(p99 * 1e3, 4),
        "throughput_rps": round(n / max(span, 1e-9), 1),
        "ticks": d["serve.ticks"],
        "mean_coalesce_width": round(
            (wh.sum - w_sum) / max(wh.count - w_cnt, 1), 2),
        "padding_rows": d["serve.padding_rows"],
        "plan_hits": d["serve.plan_hits"],
        "plan_misses": d["serve.plan_misses"],
        "shed": d["serve.shed"],
        "traces_delta": METRICS.counter("plan.traces").value - traces0,
        "cache_misses_delta":
            METRICS.counter("plan.cache_misses").value - misses0,
    }


def run_baseline(forest, x: np.ndarray, rate_hz: float, n: int) -> dict:
    """Per-request ``store.put`` + ``infer`` loop on the same open-loop
    schedule (single server, FIFO — each request is served no earlier
    than its due instant, latency measured from the due instant)."""
    store = TensorBlockStore()
    eng = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                            plan_cache=ModelReuseCache())
    store.put("req", x[:1])
    eng.infer("req", forest, algorithm=ALGO)        # warm: plan + trace
    t0 = time.perf_counter() + 0.01
    due = _arrivals(rate_hz, n, t0)
    lats = []
    for i in range(n):
        now = time.perf_counter()
        if now < due[i]:
            time.sleep(due[i] - now)
        store.put("req", np.ascontiguousarray(x[i % len(x)][None]))
        res = eng.infer("req", forest, algorithm=ALGO)
        np.asarray(res.predictions)
        lats.append(time.perf_counter() - due[i])
    p50, p99 = _pcts(lats)
    span = time.perf_counter() - t0
    return {"base_p50_ms": round(p50 * 1e3, 4),
            "base_p99_ms": round(p99 * 1e3, 4),
            "base_throughput_rps": round(n / max(span, 1e-9), 1)}


def build_engine(trees_grid=MODEL_TREES, *, buckets=(8, 32, 128),
                 interactive_deadline_s=0.002):
    """One multi-tenant engine, one registered model per tree scale
    (``forest<T>``), bucket plans warmed at registration."""
    eng = ForestServeEngine(buckets=buckets, algorithm=ALGO,
                            interactive_deadline_s=interactive_deadline_s)
    for T in trees_grid:
        eng.register_model(f"forest{T}",
                           C.get_forest(DATASET, "xgboost", T, depth=6))
    return eng


def run(rates=RATES_HZ, trees_grid=MODEL_TREES, duration_s=1.0,
        max_requests=1200, strict=True):
    """Returns (rows, records): the rate x model-scale grid, coalesced
    vs per-request, with the zero-retrace and mid-rate-speedup gates
    applied when ``strict``."""
    x, _ = C.bench_data(DATASET, scale=0.25)
    x = np.ascontiguousarray(x[:2048])
    eng = build_engine(trees_grid)
    rows, records = [], []
    mid_rate = sorted(rates)[len(rates) // 2]
    with eng:
        for T in trees_grid:
            model = f"forest{T}"
            forest = eng._get(model).forest
            for rate in rates:
                n = min(int(rate * duration_s), max_requests)
                co = run_coalesced(eng, model, x, rate, n)
                base = run_baseline(forest, x, rate, n)
                speedup = base["base_p50_ms"] / max(co["p50_ms"], 1e-9)
                rec = dict(scenario="serve", model=model, trees=T,
                           algorithm=ALGO, rate_hz=rate, requests=n,
                           duration_s=duration_s,
                           buckets=list(eng.buckets),
                           interactive_deadline_ms=round(
                               eng.interactive_deadline_s * 1e3, 3),
                           zero_retrace=bool(co["traces_delta"] == 0
                                             and co["cache_misses_delta"]
                                             == 0),
                           speedup_p50=round(speedup, 2),
                           **co, **base, **C.env_info(eng.qe.mesh))
                records.append(rec)
                rows.append({
                    "platform": f"serve-coalesced", "dataset": DATASET,
                    "model": model, "trees": T, "rate_hz": rate,
                    "load_s": 0.0, "infer_s": co["p50_ms"] / 1e3,
                    "write_s": 0.0, "total_s": co["p50_ms"] / 1e3})
                rows.append({
                    "platform": "serve-per-request", "dataset": DATASET,
                    "model": model, "trees": T, "rate_hz": rate,
                    "load_s": 0.0, "infer_s": base["base_p50_ms"] / 1e3,
                    "write_s": 0.0,
                    "total_s": base["base_p50_ms"] / 1e3})
                if strict and not rec["zero_retrace"]:
                    raise RuntimeError(
                        f"{model}@{rate}Hz re-traced after warmup: "
                        f"traces+{co['traces_delta']} "
                        f"misses+{co['cache_misses_delta']} — the bucket "
                        f"ladder leaked a new batch signature")
                if strict and rate == mid_rate \
                        and speedup < MIN_MID_RATE_SPEEDUP:
                    raise RuntimeError(
                        f"{model}@{rate}Hz coalesced p50 speedup "
                        f"{speedup:.2f}x below the "
                        f"{MIN_MID_RATE_SPEEDUP}x acceptance line")
    return rows, records


def smoke(rate_hz=800, n=300, trees=10):
    """The CI serve-smoke job: one tenant, mid arrival rate, short
    window.  RAISES on any post-warmup retrace or a p99 beyond
    ``SMOKE_P99_MULT`` x max(p50, ``SMOKE_P50_FLOOR_S``) — an erratic
    flush cadence fails even with a healthy median."""
    x, _ = C.bench_data(DATASET, scale=0.1)
    eng = build_engine((trees,))
    with eng:
        co = run_coalesced(eng, f"forest{trees}", x, rate_hz, n)
    if co["traces_delta"] != 0 or co["cache_misses_delta"] != 0:
        raise RuntimeError(
            f"serve-smoke re-traced after warmup: "
            f"traces+{co['traces_delta']} "
            f"misses+{co['cache_misses_delta']}")
    ceiling_ms = SMOKE_P99_MULT * max(co["p50_ms"], SMOKE_P50_FLOOR_S * 1e3)
    if co["p99_ms"] > ceiling_ms:
        raise RuntimeError(
            f"serve-smoke p99 {co['p99_ms']:.2f}ms beyond the tail "
            f"ceiling {ceiling_ms:.2f}ms "
            f"({SMOKE_P99_MULT}x max(p50, {SMOKE_P50_FLOOR_S * 1e3}ms))")
    print(f"# serve-smoke ok: rate={rate_hz}Hz n={n} "
          f"p50={co['p50_ms']}ms p99={co['p99_ms']}ms "
          f"width={co['mean_coalesce_width']} ticks={co['ticks']} "
          f"retraces=0")
    return co


def write_serve_json(records, path=BENCH_SERVE_JSON):
    payload = {"bench": "serve", "created_at": time.time(),
               "records": records}
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: one short run, raise on retrace or "
                         "tail blowout; writes no JSON")
    ap.add_argument("--fast", action="store_true",
                    help="reduced grid (one model scale, shorter windows)")
    ap.add_argument("--duration", type=float, default=None)
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    trees = (MODEL_TREES[0],) if args.fast else MODEL_TREES
    dur = args.duration if args.duration is not None else \
        (0.4 if args.fast else 1.0)
    rows, records = run(trees_grid=trees, duration_s=dur,
                        max_requests=400 if args.fast else 1200)
    C.print_rows(rows, extra_cols=("rate_hz",))
    path = write_serve_json(records)
    for r in records:
        print(C.csv_line(
            f"serve/{r['model']}/rate{r['rate_hz']}",
            r["p50_ms"] / 1e3,
            f"speedup_p50={r['speedup_p50']}x width="
            f"{r['mean_coalesce_width']} retrace="
            f"{0 if r['zero_retrace'] else 1}"))
    print(f"# serve trajectory -> {path}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
