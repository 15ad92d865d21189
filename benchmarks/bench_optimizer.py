"""Regret grid for the cost-based optimizer (BENCH_optimizer.json).

The honesty check the optimizer ships with: over the paper's
model-scale × data-scale quadrants, measure EVERY static
(algorithm × plan) cell, then measure ``infer(plan="auto",
algorithm="auto")`` — first call (pays the decision: score + bounded
autotune) and repeat calls (must be a persisted-decision lookup feeding
the compiled-plan cache).  Per quadrant we report:

  regret_vs_best    auto steady-state wall / best static wall — the
                    gate: ≤ ``REGRET_LIMIT`` (1.25×) everywhere;
  win_vs_worst      worst static wall / auto wall — somewhere in the
                    grid this must clear ``WIN_FLOOR`` (2×): the choice
                    actually flips, picking by hand can lose big;
  autotune_reruns   ``optimizer.autotune_runs`` delta across the repeat
                    queries — must be 0 (decision cached);
  decision_hits     ``optimizer.decision_cache_hits`` delta across the
                    repeats — must be ≥ 1.

``--smoke`` is the CI optimizer-smoke job: a reduced grid with the same
assertions, raising on any violation; writes no JSON.  The full run
writes ``BENCH_optimizer.json`` (field contract: ``docs/optimizer.md``).

    PYTHONPATH=src python -m benchmarks.bench_optimizer [--smoke|--fast]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from benchmarks import common as C
from repro.core.train import TrainConfig, train_forest
from repro.db.query import ForestQueryEngine
from repro.db.store import TensorBlockStore
from repro.obs import METRICS

BENCH_OPTIMIZER_JSON = os.path.join(os.path.dirname(__file__), "..",
                                    "BENCH_optimizer.json")

REGRET_LIMIT = 1.25      # auto must stay within this of the best static
WIN_FLOOR = 2.0          # and beat the worst static by this somewhere

ALGORITHMS = ("predicated", "hummingbird", "quickscorer")
PLANS = ("udf", "rel+reuse")

#: model-scale × data-scale quadrants (trees, rows); depth stays
#: moderate so the hummingbird GEMM ([B,T,L]·[L,I] against I=2^d-1)
#: scales visibly with model size without CPU-minutes per cell
GRID_TREES = (10, 120)
GRID_ROWS = (2_048, 16_384)
SMOKE_TREES = (10, 60)
SMOKE_ROWS = (2_048, 8_192)
DEPTH = 5
FEATURES = 16
PAGE_ROWS = 256


def _forest(trees: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(2_048, FEATURES)).astype(np.float32)
    y = (x[:, 0] * x[:, 1] > 0).astype(np.float32)
    return train_forest(x, y, TrainConfig(model_type="xgboost",
                                          num_trees=trees,
                                          max_depth=DEPTH, seed=seed))


def _data(rows: int, seed: int = 1) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.normal(size=(rows, FEATURES)).astype(np.float32)


def _measure(engine, dataset, forest, *, iters: int, **kw) -> float:
    engine.infer(dataset, forest, **kw)          # warm: compile + caches
    return C.time_best(lambda: engine.infer(dataset, forest, **kw),
                       iters=iters)


def run_grid(trees_grid=GRID_TREES, rows_grid=GRID_ROWS, *,
             iters: int = 3, measure_budget_s: float = 8.0):
    """One record per (trees, rows) quadrant."""
    records = []
    for trees in trees_grid:
        forest = _forest(trees)
        for rows in rows_grid:
            store = TensorBlockStore(default_page_rows=PAGE_ROWS)
            store.put("grid", _data(rows))
            engine = ForestQueryEngine(store)
            engine.optimizer.measure_budget_s = measure_budget_s
            # the cells the autotune must separate are ms-scale and can
            # sit within ~1.3x of each other: extra warm probes keep one
            # scheduler hiccup from locking in the wrong cell
            engine.optimizer.probe_iters = 5
            statics = []
            for algorithm in ALGORITHMS:
                for plan in PLANS:
                    s = _measure(engine, "grid", forest, iters=iters,
                                 algorithm=algorithm, plan=plan)
                    statics.append(dict(algorithm=algorithm, plan=plan,
                                        static_s=round(s, 6)))
            best = min(statics, key=lambda r: r["static_s"])
            worst = max(statics, key=lambda r: r["static_s"])

            # first auto call: pays the decision (score + autotune)
            t0 = time.perf_counter()
            first = engine.infer("grid", forest, plan="auto",
                                 algorithm="auto")
            first_s = time.perf_counter() - t0
            dec = first.decision
            # repeat autos: persisted decision, zero autotune re-runs
            before = METRICS.counter_values()
            auto_s = C.time_best(
                lambda: engine.infer("grid", forest, plan="auto",
                                     algorithm="auto"), iters=iters)
            after = METRICS.counter_values()

            def delta(name):
                return after.get(name, 0) - before.get(name, 0)

            records.append(dict(
                trees=trees, depth=DEPTH, rows=rows, features=FEATURES,
                statics=statics,
                best_algorithm=best["algorithm"], best_plan=best["plan"],
                best_static_s=best["static_s"],
                worst_algorithm=worst["algorithm"],
                worst_plan=worst["plan"],
                worst_static_s=worst["static_s"],
                auto_algorithm=first.algorithm, auto_plan=first.plan,
                auto_s=round(auto_s, 6),
                first_auto_s=round(first_s, 6),
                decision_source=dec.source,
                cells_scored=dec.cells_scored,
                cells_measured=dec.cells_measured,
                regret_vs_best=round(auto_s / max(best["static_s"], 1e-9),
                                     4),
                win_vs_worst=round(worst["static_s"] / max(auto_s, 1e-9),
                                   4),
                autotune_reruns=delta("optimizer.autotune_runs"),
                decision_hits=delta("optimizer.decision_cache_hits"),
            ))
            # fresh stores per quadrant; drop what this one pinned
            engine.invalidate()
    return records


def check(records, *, context: str) -> None:
    """The gates — raise on any violation (used by --smoke AND the full
    run, so a published BENCH_optimizer.json can never show a losing
    auto)."""
    for r in records:
        cell = f"trees={r['trees']} rows={r['rows']}"
        if r["regret_vs_best"] > REGRET_LIMIT:
            raise RuntimeError(
                f"{context}: auto regret {r['regret_vs_best']}x > "
                f"{REGRET_LIMIT}x vs best static "
                f"({r['best_algorithm']}/{r['best_plan']}) at {cell}")
        if r["autotune_reruns"] != 0:
            raise RuntimeError(
                f"{context}: repeated infer(plan='auto') re-ran the "
                f"autotune pass {r['autotune_reruns']}x at {cell} — "
                f"decision not cached")
        if r["decision_hits"] < 1:
            raise RuntimeError(
                f"{context}: repeat auto queries never hit the decision "
                f"cache at {cell}")
    best_win = max(r["win_vs_worst"] for r in records)
    if best_win < WIN_FLOOR:
        raise RuntimeError(
            f"{context}: auto never beat the worst static cell by "
            f"{WIN_FLOOR}x (best win {best_win}x) — the choice never "
            f"flips on this grid, which defeats the optimizer's point")


def write_optimizer_json(records, path=BENCH_OPTIMIZER_JSON):
    payload = {
        "bench": "optimizer",
        "created_at": time.time(),
        "protocol": {
            "iters": "min-of-iters per cell, warm (compiled plans "
                     "resident)",
            "regret_limit": REGRET_LIMIT,
            "win_floor": WIN_FLOOR,
        },
        "env": C.env_info(),
        "records": records,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return os.path.normpath(path)


def print_records(records) -> None:
    for r in records:
        print(f"  trees={r['trees']:>4} rows={r['rows']:>6}  "
              f"auto={r['auto_algorithm']}/{r['auto_plan']}"
              f" {r['auto_s'] * 1e3:8.2f}ms  "
              f"best={r['best_algorithm']}/{r['best_plan']}"
              f" {r['best_static_s'] * 1e3:8.2f}ms  "
              f"regret={r['regret_vs_best']:.2f}x  "
              f"win_vs_worst={r['win_vs_worst']:.2f}x  "
              f"reruns={r['autotune_reruns']}")


def smoke() -> None:
    """The CI optimizer-smoke job: reduced grid, full assertions."""
    records = run_grid(SMOKE_TREES, SMOKE_ROWS, iters=2,
                       measure_budget_s=8.0)
    print_records(records)
    check(records, context="optimizer-smoke")
    print(f"# optimizer-smoke ok: {len(records)} quadrants, max regret "
          f"{max(r['regret_vs_best'] for r in records)}x, best win "
          f"{max(r['win_vs_worst'] for r in records)}x, 0 autotune "
          f"re-runs")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: reduced grid, raise on regret > "
                         f"{REGRET_LIMIT}x or autotune re-run; no JSON")
    ap.add_argument("--fast", action="store_true",
                    help="reduced grid but writes BENCH_optimizer.json")
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    trees = SMOKE_TREES if args.fast else GRID_TREES
    rows = SMOKE_ROWS if args.fast else GRID_ROWS
    records = run_grid(trees, rows, iters=2 if args.fast else 3)
    print_records(records)
    check(records, context="bench_optimizer")
    path = write_optimizer_json(records)
    print(f"# optimizer trajectory -> {path}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
