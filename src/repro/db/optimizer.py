"""Self-driving cost-based query optimizer (the paper's punchline, closed).

The paper's central finding is that the best inference configuration —
UDF-centric vs relation-centric plan, algorithm, and placement — FLIPS
with model scale × data scale.  Every call site used to hand-pick
``plan=`` / ``algorithm=`` / ``n_parts=`` / ``batch_pages=``;
``CostBasedOptimizer`` is the one decision point that replaces those
scattered heuristics: ``ForestQueryEngine.infer(plan="auto",
algorithm="auto")`` and the serve plane's ``register_model`` both route
through it.

Three phases, only the first two ever run more than once per key:

  lookup     decisions persist in the store's decision catalog keyed by
             (model fingerprint, dataset name, dataset signature, mesh
             signature) — the steady state is a dictionary lookup
             feeding the existing compiled-plan cache.  Swept exactly
             like compiled plans: ``engine.invalidate(model_id)``,
             ``store.drop`` / re-``put`` of the dataset.
  score      every feasible (algorithm × plan × tier placement) cell
             gets an ANALYTIC roofline cost: closed-form FLOP / byte
             counts per algorithm (the conventions of
             ``launch/hlo_cost.py`` — dot FLOPs are ``2·result·K``,
             bytes are top-level operand+result traffic, trip counts
             multiply) pushed through ``launch/roofline.roofline_terms``
             with BACKEND-CALIBRATED peaks (``launch/roofline.
             resolve_peaks``), not the hardcoded TPU-v5e table — cost
             ranking is meaningful on the CI backend.  Gather traffic
             gets its own calibrated bandwidth (it differs from
             streaming bandwidth in either direction per backend).
  autotune   cells whose analytic cost lands within ``uncertainty_band``
             of the best are refined by a bounded measure-and-cache
             pass: each uncertain cell is probed with a real warm query
             (min of ``probe_iters``), then the winner's ``n_parts`` /
             ``batch_pages`` are hillclimbed (half / double neighbors,
             ``launch/hillclimb.py``-style) while the wall budget lasts.
             Budgeted (``measure_budget_s``, ``max_measurements``) and
             OFF the hot path: it runs at most once per decision key —
             the regret bench and CI gate assert zero autotune re-runs
             on repeat queries via the ``optimizer.autotune_runs``
             counter.

Tier placement is scored (a host/disk dataset that fits the device
budget is costed at zero steady-state transfer) and the winning rung is
recorded on ``Decision.tier`` as ADVICE; execution stays on the
dataset's current tier unless the caller opts in (``infer(...,
auto_move=True)``), because silently migrating a dataset is a store
mutation no query should hide.  See ``docs/optimizer.md`` for the cost
model terms, the calibration table, and the decision-cache contract.
"""

from __future__ import annotations

import dataclasses
import time
import weakref
from typing import Any, Sequence

from repro.launch.roofline import resolve_peaks, roofline_terms
from repro.obs import METRICS, TRACER

__all__ = ["Decision", "CostBasedOptimizer", "dataset_signature",
           "DEFAULT_ALGORITHMS", "DEFAULT_PLANS"]

#: candidate algorithms — the three jnp backends the paper compares.
#: (Pallas kernels run ``interpret=True`` off-TPU, so auto-selection on
#: the CI backend would never pick them; callers targeting TPU can pass
#: ``algorithms=(..., "predicated_pallas_fused", ...)`` explicitly.)
DEFAULT_ALGORITHMS = ("predicated", "hummingbird", "quickscorer")

#: candidate plans — ``rel`` (bare) is the paper's deliberately
#: UNCACHED baseline: it re-partitions the model every query, so it can
#: never win steady state and is excluded from auto-selection.
DEFAULT_PLANS = ("udf", "rel+reuse")

#: sentinel dataset slot for row-batch (serving-plane) decisions —
#: mirrors ``db.query.ROW_PLAN_DATASET`` ("#" never names a real
#: catalog entry, so dataset sweeps cannot touch row decisions).
ROW_DECISION_DATASET = "#rows"


def dataset_signature(ds) -> tuple:
    """The dataset facts a decision is conditioned on.  Any change —
    row count, width, storage format, TIER, page layout — yields a new
    key, so a stale decision can never be served for reshaped data
    (re-``put`` additionally sweeps the old key eagerly)."""
    return (int(ds.num_rows), int(ds.num_features),
            getattr(ds, "storage_format", "dense"),
            getattr(ds, "tier", "device"),
            int(ds.page_rows), int(ds.num_pages))


@dataclasses.dataclass(frozen=True)
class Decision:
    """One persisted optimizer verdict: the winning execution cell."""

    algorithm: str
    plan: str                     # "udf" | "rel+reuse"
    tier: str                     # recommended scan tier (advice — the
    #                               engine only applies it under
    #                               ``infer(..., auto_move=True)``)
    n_parts: int | None           # rel tree-partition count (None: engine
    #                               default), winner of the hillclimb
    batch_pages: int | None       # scan batch size (None: engine default)
    predicted_s: float            # analytic roofline estimate of the cell
    measured_s: float | None      # autotune probe wall (None: model-trusted)
    source: str                   # "measured" | "model"
    cells_scored: int = 0         # analytic candidates enumerated
    cells_measured: int = 0       # probes the autotune pass paid

    def overrides(self) -> dict[str, Any]:
        """kwargs for ``engine._infer`` executing this decision."""
        return dict(algorithm=self.algorithm, plan=self.plan,
                    n_parts=self.n_parts, batch_pages=self.batch_pages)


@dataclasses.dataclass
class _Cell:
    """A feasible configuration under scoring."""
    algorithm: str
    plan: str
    tier: str
    n_parts: int | None = None
    batch_pages: int | None = None
    predicted_s: float = float("inf")
    measured_s: float | None = None


def _forest_flop_bytes(algorithm: str, *, rows: int, trees: int,
                       depth: int, f_used: int) -> tuple[float, float, float]:
    """Closed-form (flops, stream_bytes, gather_bytes) of one algorithm
    over ``rows`` samples — the analytic mirror of what
    ``launch/hlo_cost.analyze`` reads off the compiled HLO.

    Conventions follow ``hlo_cost``: dot FLOPs are ``2·result·K``,
    elementwise ops are one FLOP per output element, bytes are
    per-boundary operand+result traffic (f32), and loop trip counts
    multiply (predicated's ``fori_loop`` over depth is a while body
    executed ``depth`` times).  Gather traffic (data-dependent row
    lookups — tree traversal's access pattern) is returned separately
    because its effective bandwidth differs from streaming bandwidth —
    in either direction, per backend (see ``roofline.calibrate_peaks``).
    """
    B, T, d = float(rows), float(trees), float(depth)
    I = float(2 ** depth - 1)         # internal nodes (complete tree)
    L = float(2 ** depth)             # leaves
    W = float(-(-int(L) // 32))       # quickscorer uint32 mask words
    if algorithm.startswith("predicated") or algorithm.startswith("compiled"):
        # per level: 3 node-table gathers + take_along_axis on x [B,T],
        # compare + index update (~6 elementwise ops on [B,T])
        flops = d * B * T * 6.0
        gather_bytes = d * B * T * 4.0 * 4.0          # f/thr/dl/xv lookups
        stream_bytes = d * B * T * 4.0 * 2.0          # idx read+write
        flops += B * T * 2.0                          # leaf gather + sum
        gather_bytes += B * T * 4.0
    elif algorithm.startswith("hummingbird"):
        # S = predicates [B,T,I]; S @ C -> [B,T,L] (2·B·T·L·I flops);
        # one-hot count-match [B,T,L]; onehot ⊙ leaf -> [B] (2·B·T·L)
        flops = 2.0 * B * T * L * I + B * T * I * 4.0 + B * T * L * 3.0
        gather_bytes = B * T * I * 4.0                # xv feature gather
        stream_bytes = B * T * (I * 3.0 + L * 4.0) * 4.0
    elif algorithm.startswith("quickscorer"):
        # all-node predicates [B,T,I], mask AND-reduce over [B,T,I,W]
        # words, lowest-surviving-bit leaf pick [B,T,W]
        flops = B * T * I * (4.0 + 2.0 * W) + B * T * W * 3.0
        gather_bytes = B * T * I * 4.0
        stream_bytes = (B * T * I * W + B * T * (I + W) * 2.0) * 4.0
    elif algorithm.startswith("naive"):
        # while_loop per (sample, tree): ~depth iterations, serial gathers
        flops = d * B * T * 8.0
        gather_bytes = d * B * T * 4.0 * 5.0
        stream_bytes = d * B * T * 4.0
    else:                             # unknown / kernel variant: model as
        flops = d * B * T * 6.0       # predicated-shaped work
        gather_bytes = d * B * T * 16.0
        stream_bytes = d * B * T * 8.0
    return flops, stream_bytes, gather_bytes


class CostBasedOptimizer:
    """Scores, measures, and caches (algorithm × plan × tier × blocks)
    decisions for a ``ForestQueryEngine`` (see module docstring)."""

    def __init__(self, engine, *,
                 algorithms: Sequence[str] = DEFAULT_ALGORITHMS,
                 plans: Sequence[str] = DEFAULT_PLANS,
                 measure_budget_s: float = 4.0,
                 max_measurements: int = 12,
                 uncertainty_band: float = 16.0,
                 probe_iters: int = 3,
                 hillclimb: bool = True):
        # weak: the engine owns its optimizer — a strong back-reference
        # would cycle and keep dead engines' store invalidation hooks
        # alive until a gc pass
        self._engine = weakref.ref(engine)
        self.algorithms = tuple(algorithms)
        self.plans = tuple(plans)
        self.measure_budget_s = measure_budget_s
        self.max_measurements = max_measurements
        self.uncertainty_band = uncertainty_band
        self.probe_iters = probe_iters
        self.hillclimb = hillclimb

    @property
    def engine(self):
        eng = self._engine()
        if eng is None:
            raise ReferenceError("optimizer outlived its query engine")
        return eng

    # ------------------------------------------------------------------
    # analytic roofline scoring
    # ------------------------------------------------------------------
    def score_cell(self, cell: _Cell, *, rows: int, trees: int, depth: int,
                   f_used: int, data_nbytes: int, num_pages: int,
                   page_rows: int, batch_pages: int, peaks: dict) -> float:
        """Analytic seconds for one cell over the whole dataset scan;
        ``batch_pages`` is the batch the engine runs when the cell sets
        none (``ForestQueryEngine.default_batch_pages``)."""
        flops, stream_b, gather_b = _forest_flop_bytes(
            cell.algorithm, rows=rows, trees=trees, depth=depth,
            f_used=f_used)
        # gather traffic at its own (calibrated) effective bandwidth,
        # folded into roofline_terms' single memory term as equivalent
        # streaming bytes
        gather_bw = peaks.get("gather_bandwidth", peaks["hbm_bandwidth"])
        eq_bytes = stream_b + gather_b * (peaks["hbm_bandwidth"] / gather_bw)
        # rel plans materialize [n_parts, B] partials at a stage
        # boundary and fold them; udf keeps everything in one stage
        n_parts = cell.n_parts or 1
        if cell.plan.startswith("rel"):
            eq_bytes += 3.0 * 4.0 * n_parts * rows    # write+read+fold
        coll = 0.0
        fplan = getattr(self.engine, "fplan", None)
        if fplan is not None and getattr(fplan, "model_axis", None) \
                is not None and cell.plan.startswith("rel"):
            coll = 4.0 * rows                          # psum over model
        terms = roofline_terms(flops_per_chip=flops,
                               bytes_per_chip=eq_bytes,
                               coll_bytes_per_chip=coll, peak=peaks)
        cost = terms["step_s_lower_bound"]
        # tier transfer: scanning an off-device dataset streams every
        # byte through host→device DMA once per query (disk additionally
        # pays the file read, modeled at half the DMA rate)
        h2d = peaks.get("h2d_bandwidth", peaks["hbm_bandwidth"])
        if cell.tier in ("pinned", "host"):
            cost += data_nbytes / h2d
        elif cell.tier == "disk":
            cost += data_nbytes / h2d + data_nbytes / (h2d / 2.0)
        # dispatch overhead: one per stage per batch (udf: 1 fused
        # stage; rel: cross-product + aggregate + postprocess)
        dispatch = peaks.get("dispatch_s", 5e-6)
        bp = cell.batch_pages or batch_pages
        n_batches = max(1, -(-num_pages // max(bp, 1)))
        stages = 1 if cell.plan == "udf" else 3
        cost += dispatch * n_batches * stages
        return cost

    # ------------------------------------------------------------------
    # candidate enumeration
    # ------------------------------------------------------------------
    def _enumerate(self, *, tier: str, fits_device: bool,
                   algorithms: Sequence[str], plans: Sequence[str],
                   ) -> list[_Cell]:
        tiers = [tier]
        if tier != "device" and fits_device:
            tiers.append("device")    # promotion candidate (advice)
        return [_Cell(algorithm=a, plan=p, tier=t)
                for t in tiers for a in algorithms for p in plans]

    # ------------------------------------------------------------------
    # measurement probes
    # ------------------------------------------------------------------
    def _probe(self, run, budget_left: float) -> float | None:
        """Warm once (compile), then min-of-``probe_iters`` timed runs.
        Returns None when the budget is already spent."""
        if budget_left <= 0:
            return None
        run()                          # warm: compile + cache the plan
        best = float("inf")
        for _ in range(max(1, self.probe_iters)):
            t0 = time.perf_counter()
            run()
            best = min(best, time.perf_counter() - t0)
        METRICS.counter("optimizer.measurements").inc()
        return best

    def _autotune(self, cells: list[_Cell], make_runner,
                  neighbors) -> tuple[_Cell, int]:
        """Measure-and-refine pass over the uncertain ``cells`` (already
        sorted best-analytic-first).  ``make_runner(cell)`` returns a
        zero-arg callable executing the cell; ``neighbors(cell)`` yields
        hillclimb variants of the winner.  Returns (winner, probes)."""
        METRICS.counter("optimizer.autotune_runs").inc()
        t0 = time.perf_counter()
        measured = 0
        with TRACER.span("optimizer.autotune", candidates=len(cells)):
            live: list[tuple[_Cell, Any]] = []
            for cell in cells:
                if measured >= self.max_measurements:
                    break
                left = self.measure_budget_s - (time.perf_counter() - t0)
                # always measure at least the top-2 candidates — a
                # budget too small to compare anything would silently
                # degrade to pure-model ranking
                if measured >= 2 and left <= 0:
                    break
                run = make_runner(cell)
                run()                  # warm: compile + cache the plan
                live.append((cell, run))
                measured += 1
                METRICS.counter("optimizer.measurements").inc()
            # timed runs INTERLEAVED across cells round-robin (the
            # bench_obs protocol): a transient load spike lands on every
            # candidate equally instead of sinking whichever cell was
            # being probed sequentially when it hit — close calls stay
            # fair.  At least one full round even past the budget.
            for round_ in range(max(1, self.probe_iters)):
                if round_ > 0 and time.perf_counter() - t0 \
                        >= self.measure_budget_s:
                    break
                for cell, run in live:
                    t1 = time.perf_counter()
                    run()
                    dt = time.perf_counter() - t1
                    if cell.measured_s is None or dt < cell.measured_s:
                        cell.measured_s = dt
            done = [c for c in cells if c.measured_s is not None]
            best = min(done, key=lambda c: c.measured_s) if done \
                else cells[0]
            # hillclimb the winner's block sizes while budget remains
            if self.hillclimb and done:
                improved = True
                while improved:
                    improved = False
                    for cand in neighbors(best):
                        left = self.measure_budget_s - \
                            (time.perf_counter() - t0)
                        if left <= 0 or measured >= self.max_measurements:
                            break
                        got = self._probe(make_runner(cand), left)
                        if got is None:
                            break
                        cand.measured_s = got
                        measured += 1
                        if got < best.measured_s:
                            best, improved = cand, True
        return best, measured

    # ------------------------------------------------------------------
    # dataset-scan decisions (ForestQueryEngine.infer)
    # ------------------------------------------------------------------
    def decide(self, dataset: str, forest, *, model_id: str | None = None,
               algorithms: Sequence[str] | None = None,
               plans: Sequence[str] | None = None) -> Decision:
        """Decision for a full dataset scan — cached in the store's
        decision catalog; first call per key pays the score + autotune
        passes, every later call is a dictionary lookup."""
        from repro.core.reuse import mesh_signature
        eng = self.engine
        store = eng.store
        ds = store.get(dataset)
        sig = dataset_signature(ds)
        mid = eng._model_key(forest, model_id)
        # the candidate sets are part of the key: a decision made under a
        # pinned axis (algorithm="hummingbird", plan="auto") must never be
        # served for — or clobbered by — the unconstrained auto query
        algorithms = tuple(algorithms or self.algorithms)
        plans = tuple(plans or self.plans)
        key = (mid, dataset, sig, mesh_signature(eng.mesh),
               algorithms, plans)
        hit = store.get_decision(key)
        if hit is not None:
            METRICS.counter("optimizer.decision_cache_hits").inc()
            return hit
        METRICS.counter("optimizer.decision_cache_misses").inc()
        with TRACER.span("optimizer.decide", dataset=dataset,
                         model=mid[:12]) as sp:
            peaks = resolve_peaks()
            budget = store.device_budget_bytes
            fits = budget is None or \
                store.device_nbytes + ds.nbytes <= budget
            cells = self._enumerate(
                tier=sig[3], fits_device=fits,
                algorithms=algorithms, plans=plans)
            kw = dict(rows=int(ds.num_pages) * int(ds.page_rows),
                      trees=int(forest.num_trees),
                      depth=int(forest.depth),
                      f_used=int(forest.n_features),
                      data_nbytes=int(ds.nbytes),
                      num_pages=int(ds.num_pages),
                      page_rows=int(ds.page_rows), peaks=peaks)
            for c in cells:
                if c.plan.startswith("rel"):
                    c.n_parts = eng._resolve_n_parts(forest, c.algorithm,
                                                     None)
                c.predicted_s = self.score_cell(
                    c, batch_pages=eng.default_batch_pages(ds, forest,
                                                           c.algorithm),
                    **kw)
            cells.sort(key=lambda c: c.predicted_s)
            # the executable winner must run on the CURRENT tier; other
            # rungs are scored for the tier recommendation only
            here = [c for c in cells if c.tier == sig[3]]
            uncertain = [c for c in here if c.predicted_s
                         <= here[0].predicted_s * self.uncertainty_band]

            def make_runner(cell: _Cell):
                return lambda: eng._infer(
                    dataset, forest, model_id=model_id,
                    algorithm=cell.algorithm, plan=cell.plan,
                    n_parts=cell.n_parts, batch_pages=cell.batch_pages)

            def neighbors(cell: _Cell):
                out = []
                if cell.plan.startswith("rel") and cell.n_parts:
                    for np_ in (max(1, cell.n_parts // 2),
                                min(int(forest.num_trees),
                                    cell.n_parts * 2)):
                        if np_ != cell.n_parts:
                            out.append(dataclasses.replace(
                                cell, n_parts=np_, measured_s=None))
                if sig[3] != "device":
                    # doubling stops at the predictor's working-set cap
                    bp = cell.batch_pages or eng.default_batch_pages(
                        ds, forest, cell.algorithm)
                    cap = eng.max_batch_pages(ds, forest, cell.algorithm)
                    for bp_ in (max(1, bp // 2),
                                min(int(ds.num_pages), cap, bp * 2)):
                        if bp_ != bp:
                            out.append(dataclasses.replace(
                                cell, batch_pages=bp_, measured_s=None))
                return out

            measured = 0
            if len(uncertain) > 1:
                best, measured = self._autotune(uncertain, make_runner,
                                                neighbors)
            else:
                best = here[0]
            decision = Decision(
                algorithm=best.algorithm, plan=best.plan,
                tier=cells[0].tier,           # best overall rung = advice
                n_parts=best.n_parts, batch_pages=best.batch_pages,
                predicted_s=best.predicted_s,
                measured_s=best.measured_s,
                source="measured" if best.measured_s is not None
                else "model",
                cells_scored=len(cells), cells_measured=measured)
            sp.set(algorithm=decision.algorithm, plan=decision.plan,
                   source=decision.source, measured=measured)
        store.put_decision(key, decision)
        METRICS.counter("optimizer.decisions").inc()
        TRACER.event("optimizer.decision", dataset=dataset,
                     algorithm=decision.algorithm, plan=decision.plan,
                     tier=decision.tier, source=decision.source)
        return decision

    # ------------------------------------------------------------------
    # row-batch decisions (serving plane: register_model)
    # ------------------------------------------------------------------
    def decide_rows(self, forest, batch_rows: int, *,
                    model_id: str | None = None,
                    algorithms: Sequence[str] | None = None,
                    plans: Sequence[str] | None = None) -> Decision:
        """Decision for the serving plane's padded row batches: same
        score → autotune → persist pipeline, probed through
        ``engine.infer_rows`` at the largest bucket signature.  Keyed
        under the ``#rows`` sentinel so dataset sweeps never touch it;
        ``engine.invalidate(model_id)`` sweeps it like any plan."""
        import numpy as np
        from repro.core.reuse import mesh_signature
        eng = self.engine
        store = eng.store
        mid = eng._model_key(forest, model_id)
        B, F = int(batch_rows), int(forest.n_features)
        algorithms = tuple(algorithms or self.algorithms)
        plans = tuple(p for p in (plans or self.plans)
                      if p in ("udf", "rel+reuse"))
        key = (mid, ROW_DECISION_DATASET, (B, F), mesh_signature(eng.mesh),
               algorithms, plans)
        hit = store.get_decision(key)
        if hit is not None:
            METRICS.counter("optimizer.decision_cache_hits").inc()
            return hit
        METRICS.counter("optimizer.decision_cache_misses").inc()
        with TRACER.span("optimizer.decide", dataset=ROW_DECISION_DATASET,
                         model=mid[:12]) as sp:
            peaks = resolve_peaks()
            cells = [_Cell(algorithm=a, plan=p, tier="device")
                     for a in algorithms for p in plans]
            for c in cells:
                if c.plan.startswith("rel"):
                    c.n_parts = eng._resolve_n_parts(forest, c.algorithm,
                                                     None)
                c.predicted_s = self.score_cell(
                    c, rows=B, trees=int(forest.num_trees),
                    depth=int(forest.depth), f_used=F,
                    data_nbytes=B * F * 4, num_pages=1, page_rows=B,
                    batch_pages=1, peaks=peaks)
            cells.sort(key=lambda c: c.predicted_s)
            uncertain = [c for c in cells if c.predicted_s
                         <= cells[0].predicted_s * self.uncertainty_band]
            x = np.zeros((B, F), np.float32)

            def make_runner(cell: _Cell):
                return lambda: eng.infer_rows(
                    forest, x, algorithm=cell.algorithm, plan=cell.plan,
                    model_id=mid, n_parts=cell.n_parts)

            measured = 0
            if len(uncertain) > 1:
                best, measured = self._autotune(uncertain, make_runner,
                                                lambda c: [])
            else:
                best = cells[0]
            decision = Decision(
                algorithm=best.algorithm, plan=best.plan, tier="device",
                n_parts=best.n_parts, batch_pages=None,
                predicted_s=best.predicted_s, measured_s=best.measured_s,
                source="measured" if best.measured_s is not None
                else "model",
                cells_scored=len(cells), cells_measured=measured)
            sp.set(algorithm=decision.algorithm, plan=decision.plan,
                   source=decision.source, measured=measured)
        store.put_decision(key, decision)
        METRICS.counter("optimizer.decisions").inc()
        TRACER.event("optimizer.decision", dataset=ROW_DECISION_DATASET,
                     algorithm=decision.algorithm, plan=decision.plan,
                     tier=decision.tier, source=decision.source)
        return decision
