"""Online forest serving plane: micro-batch coalescing onto the
compiled-plan cache.

The paper's motivating deployments (fraud gating, ranking, admission
control) are REQUEST-serving workloads: single rows (or tiny batches)
arriving continuously, with per-request latency under concurrent
traffic as the metric — not the batch scans the in-database side of the
paper measures.  Served naively, every single-row request pays the full
``ForestQueryEngine.infer`` overhead per request: a store round-trip, a
plan-cache lookup against a one-row batch signature, possibly a fresh
trace.  This module closes that gap with the standard serving-systems
move, applied to the repo's own machinery:

  * **Micro-batch coalescing** — requests for the same registered model
    are queued and flushed together as ONE padded row batch whose size
    is drawn from a small fixed BUCKET LADDER (default 8/32/128 rows).
    Because every flushed batch has one of ``len(buckets)`` shapes, the
    steady state hits an existing ``CompiledQueryPlan`` in the
    ``ModelReuseCache`` every tick — ZERO re-tracing, verified against
    the ``plan.cache_hits`` / ``plan.cache_misses`` / ``plan.traces``
    counters of the observability plane.  Padding rows are masked and
    their predictions forced to NaN (``ForestQueryEngine.infer_rows``),
    so they can never leak into a caller's results.
  * **Latency tiers with deadline flush** — a dedicated TICKER thread
    flushes each model's queue when a bucket fills, when the oldest
    ``TIER_INTERACTIVE`` request has waited ``interactive_deadline_s``,
    or (batch-only queues, which otherwise wait for full buckets) when
    the oldest request has waited ``batch_deadline_s``.  The
    ``ForestRouter`` — the paper's technique serving the stack — gates
    the serve plane's OWN traffic: an unprioritized submit is routed
    into a tier from live request features, with the arrival-load
    feature read from the process-global ``serve.queue_depth`` metric.
    The PR 6 admission-timeout contract carries over: an interactive
    request queued past its ``timeout_s`` is SHED to the batch tier
    (``shed=True``, counted) instead of forcing a premature flush.
  * **Multi-model tenancy** — ``register_model`` pins the forest in the
    ``TensorBlockStore`` model catalog (the system of record for what
    is served); compiled plans live in the query engine's
    ``ModelReuseCache`` with plain LRU as the eviction policy, so a
    cold model's executables age out under pressure while the pin keeps
    it re-compilable — an evicted model re-serves bit-identically after
    a warmup miss.  Per-model ``stats()`` report queue-wait /
    coalesce-width / e2e p50+p99 from histogram-backed
    ``MetricsRegistry`` instruments (docs/observability.md).

``benchmarks/bench_serve.py`` drives this plane with open-loop
synthetic traffic (BENCH_serve.json); design notes in
docs/serving.md.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Any

import numpy as np

from repro.core.reuse import ModelReuseCache, fingerprint_forest
from repro.db.query import ForestQueryEngine
from repro.db.store import TensorBlockStore
from repro.obs import METRICS, MetricsRegistry, TRACER
from repro.serve.router import (QUEUE_DEPTH_METRIC, TIER_BATCH,
                                TIER_INTERACTIVE, ForestRouter,
                                request_features)

__all__ = ["ForestRequest", "ServedModel", "ForestServeEngine",
           "DEFAULT_BUCKETS"]

#: the default bucket ladder: every coalesced batch is padded to the
#: smallest bucket that fits, so the compiled-plan cache sees at most
#: ``len(DEFAULT_BUCKETS)`` batch signatures per (model, plan)
DEFAULT_BUCKETS = (8, 32, 128)


@dataclasses.dataclass
class ForestRequest:
    """One in-flight serving request (a single row or a small batch)."""

    uid: int
    model: str
    rows: np.ndarray                   # [k, F] f32, k >= 1
    priority: int = TIER_BATCH         # router tier (named constants)
    timeout_s: float | None = None     # admission timeout: an interactive
    #                                    request still queued past this
    #                                    SHEDS to the batch tier (PR 6
    #                                    contract; docs/reliability.md)
    shed: bool = False
    submitted_at: float = 0.0
    admitted_at: float = 0.0           # taken by the flush that served it
    finished_at: float = 0.0
    predictions: np.ndarray | None = None   # [k] on completion
    error: BaseException | None = None
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event)

    @property
    def num_rows(self) -> int:
        return int(self.rows.shape[0])

    def wait(self, timeout: float | None = None) -> np.ndarray:
        """Block until served; returns the [k] predictions (raises the
        tick's error if the flush that carried this request failed)."""
        if not self.done.wait(timeout):
            raise TimeoutError(
                f"request {self.uid} not served within {timeout}s")
        if self.error is not None:
            raise self.error
        return self.predictions


@dataclasses.dataclass
class ServedModel:
    """A registered tenant: the pinned forest + its serving config and
    per-model telemetry (one ``MetricsRegistry`` per model — tenancy
    means stats never conflate tenants)."""

    name: str
    forest: Any
    model_id: str                      # content fingerprint (cache keys)
    algorithm: str
    plan: str
    metrics: MetricsRegistry = dataclasses.field(
        default_factory=MetricsRegistry)
    pending: deque = dataclasses.field(default_factory=deque)
    registered_at: float = dataclasses.field(default_factory=time.time)


class ForestServeEngine:
    """Serves registered forest models behind a micro-batch coalescer.

    Construction wires (or accepts) a ``TensorBlockStore`` +
    ``ForestQueryEngine`` pair; the engine's compiled plans live in the
    query engine's ``plan_cache`` (``ModelReuseCache``, LRU), which is
    the multi-model eviction policy.  Use as a context manager (or
    ``start()``/``stop()``) to run the ticker thread; tests and
    synchronous callers can drive ``tick()`` / ``drain()`` directly.
    """

    def __init__(self, store: TensorBlockStore | None = None, *,
                 query_engine: ForestQueryEngine | None = None,
                 buckets: tuple[int, ...] = DEFAULT_BUCKETS,
                 interactive_deadline_s: float = 0.002,
                 batch_deadline_s: float = 0.02,
                 router: ForestRouter | None = None,
                 max_plans: int = 32,
                 tick_interval_s: float = 0.0005,
                 algorithm: str = "predicated",
                 plan: str = "udf"):
        self.store = store if store is not None else TensorBlockStore()
        self.qe = query_engine if query_engine is not None else \
            ForestQueryEngine(self.store,
                              reuse_cache=ModelReuseCache(max_plans),
                              plan_cache=ModelReuseCache(max_plans))
        # bucket sizes must divide the mesh data axis (infer_rows places
        # batches under the store's data sharding) — round each rung up
        nd = max(1, self.qe.fplan.n_data)
        self.buckets = tuple(sorted({-(-int(b) // nd) * nd
                                     for b in buckets if b > 0}))
        if not self.buckets:
            raise ValueError("bucket ladder must not be empty")
        self.interactive_deadline_s = interactive_deadline_s
        self.batch_deadline_s = batch_deadline_s
        self.router = router
        self.tick_interval_s = tick_interval_s
        self.default_algorithm = algorithm
        self.default_plan = plan
        self._models: dict[str, ServedModel] = {}
        self._lock = threading.Lock()
        self._uid = 0
        self._ticker: threading.Thread | None = None
        self._running = threading.Event()
        self.last_error: BaseException | None = None
        # engine-level telemetry aggregated across tenants (per-model
        # registries hold the per-tenant view); queue depth itself is
        # the PROCESS-global serve.queue_depth counter shared with the
        # LM ServeEngine, which is what the router's arrival-load
        # feature reads
        self.metrics = MetricsRegistry()
        self._width_h = self.metrics.histogram(
            "serve.coalesce_width", bounds=tuple(
                float(b) for b in self.buckets))

    # ------------------------------------------------------------------
    # tenancy
    # ------------------------------------------------------------------
    def register_model(self, name: str, forest, *,
                       algorithm: str | None = None,
                       plan: str | None = None,
                       warmup: bool = True) -> ServedModel:
        """Register (or replace) a served model.

        Pins the forest in the store's model catalog, and — with
        ``warmup`` (default) — compiles one plan per bucket rung so the
        first real tick already hits the cache (the benchmarks' zero-
        retrace-after-warmup assertion starts here).  Replacing a name
        sweeps the old model's compiled plans first.

        ``algorithm="auto"`` / ``plan="auto"`` resolve HERE, once per
        tenant, through the cost-based optimizer's row-batch decision
        (``db/optimizer.py``) at the largest bucket signature — the
        per-request hot path then always runs a concrete, persisted
        choice."""
        algorithm = algorithm or self.default_algorithm
        plan = plan or self.default_plan
        if algorithm == "auto" or plan == "auto":
            dec = self.qe.optimizer.decide_rows(
                forest, max(self.buckets),
                algorithms=None if algorithm == "auto" else (algorithm,),
                plans=None if plan == "auto" else (plan,))
            algorithm, plan = dec.algorithm, dec.plan
        old = self._models.get(name)
        if old is not None and old.pending:
            raise RuntimeError(
                f"model {name!r} has {len(old.pending)} pending requests")
        if old is not None:
            self.qe.invalidate(old.model_id)
        mid = fingerprint_forest(forest)
        self.store.put_model(name, forest, fingerprint=mid,
                             algorithm=algorithm, plan=plan)
        m = ServedModel(name=name, forest=forest, model_id=mid,
                        algorithm=algorithm, plan=plan)
        with self._lock:
            self._models[name] = m
        if warmup:
            self.warmup(name)
        return m

    def register_from_catalog(self, name: str, *,
                              algorithm: str | None = None,
                              plan: str | None = None,
                              warmup: bool = True) -> ServedModel:
        """Serve a model already pinned in the store's model catalog —
        the in-database trainer's handoff (``ForestQueryEngine.train``
        lands its forest via ``store.put_model``; this picks it up
        without the forest ever leaving the database).  Catalog metadata
        supplies the algorithm/plan defaults when the trainer (or a
        previous registration) recorded them; explicit arguments win."""
        forest = self.store.get_model(name)
        meta = self.store.model_catalog().get(name, {})
        return self.register_model(
            name, forest,
            algorithm=algorithm or meta.get("algorithm"),
            plan=plan or meta.get("plan"),
            warmup=warmup)

    def warmup(self, name: str) -> int:
        """Compile (or re-touch) one plan per bucket rung for ``name``.
        Returns the number of plan-cache MISSES the warmup paid — 0
        means every rung was already resident."""
        m = self._get(name)
        misses = 0
        for b in self.buckets:
            x = np.zeros((b, m.forest.n_features), np.float32)
            res = self.qe.infer_rows(m.forest, x, algorithm=m.algorithm,
                                     plan=m.plan, model_id=m.model_id)
            misses += int(not res.plan_reuse_hit)
        return misses

    def unregister_model(self, name: str) -> int:
        """Drop a tenant: unpin from the store catalog and sweep its
        compiled plans + materializations.  Returns entries swept.
        Refuses while requests are pending."""
        m = self._get(name)
        if m.pending:
            raise RuntimeError(
                f"model {name!r} has {len(m.pending)} pending requests")
        with self._lock:
            self._models.pop(name, None)
        self.store.drop_model(name)
        return self.qe.invalidate(m.model_id)

    def models(self) -> dict[str, dict[str, Any]]:
        """Tenant catalog view (mirrors ``store.model_catalog()``)."""
        return {n: dict(algorithm=m.algorithm, plan=m.plan,
                        fingerprint=m.model_id, pending=len(m.pending))
                for n, m in self._models.items()}

    def _get(self, name: str) -> ServedModel:
        try:
            return self._models[name]
        except KeyError:
            raise KeyError(f"model {name!r} not registered; "
                           f"have {sorted(self._models)}")

    # ------------------------------------------------------------------
    # request path
    # ------------------------------------------------------------------
    def submit(self, model: str, rows, *, priority: int | None = None,
               timeout_s: float | None = None) -> ForestRequest:
        """Queue a request ([F] single row or [k, F] small batch) for
        ``model``.  ``priority=None`` lets the ``ForestRouter`` (when
        configured) gate the serve plane's own traffic — request
        features with the arrival-load read from the LIVE
        ``serve.queue_depth`` counter; without a router, unprioritized
        requests default to ``TIER_INTERACTIVE``.  Returns the request
        handle; ``req.wait()`` blocks for the predictions."""
        m = self._get(model)
        rows = np.atleast_2d(np.asarray(rows, np.float32))
        if rows.shape[1] != m.forest.n_features:
            raise ValueError(
                f"request has {rows.shape[1]} features, model {model!r} "
                f"expects {m.forest.n_features}")
        if rows.shape[0] > self.buckets[-1]:
            raise ValueError(
                f"request of {rows.shape[0]} rows exceeds the largest "
                f"bucket ({self.buckets[-1]}); use infer() for scans")
        if priority is None:
            if self.router is not None:
                feats = request_features(
                    rows.shape[0], 1, None, len(self._models),
                    self._width_h.mean if self._width_h.count else 0.0)
                priority = int(self.router.route(feats))
            else:
                priority = TIER_INTERACTIVE
        with self._lock:
            self._uid += 1
            req = ForestRequest(uid=self._uid, model=model, rows=rows,
                                priority=priority, timeout_s=timeout_s,
                                submitted_at=time.perf_counter())
            # interactive requests coalesce at the queue FRONT so the
            # next flush carries them (same admission rule as the LM
            # engine's priority queue)
            if priority == TIER_INTERACTIVE:
                m.pending.appendleft(req)
            else:
                m.pending.append(req)
        m.metrics.counter("serve.requests").inc()
        METRICS.counter(QUEUE_DEPTH_METRIC).inc()
        return req

    def predict(self, model: str, rows, *,
                timeout: float | None = 30.0, **kw) -> np.ndarray:
        """Blocking convenience: submit + wait.  Without a running
        ticker the queue is drained synchronously."""
        req = self.submit(model, rows, **kw)
        if not self._running.is_set():
            self.drain()
        return req.wait(timeout)

    # ------------------------------------------------------------------
    # coalescer
    # ------------------------------------------------------------------
    def _shed_timed_out(self, m: ServedModel, now: float) -> None:
        """PR 6 admission-timeout ladder, coalescer edition: demote
        interactive requests whose wait exceeded ``timeout_s`` to the
        batch tier (queue BACK, ``shed`` flagged) — they stop pulling
        the short interactive deadline and wait for a full bucket like
        any batch-tier work."""
        with self._lock:
            kept, shed = [], []
            for req in m.pending:
                if (req.timeout_s is not None
                        and req.priority == TIER_INTERACTIVE
                        and now - req.submitted_at >= req.timeout_s):
                    req.priority = TIER_BATCH
                    req.shed = True
                    shed.append(req)
                else:
                    kept.append(req)
            if shed:
                m.pending.clear()
                m.pending.extend(kept + shed)
        for req in shed:
            m.metrics.counter("serve.shed").inc()
            TRACER.event("serve.shed", uid=req.uid)

    def _due(self, m: ServedModel, now: float) -> bool:
        """Flush policy: a full largest bucket flushes any queue;
        otherwise the oldest INTERACTIVE request flushes at the short
        deadline, and a batch-only queue — which by contract waits for
        full buckets — is bounded by the long batch deadline so a lone
        request can never starve."""
        if not m.pending:
            return False
        if sum(r.num_rows for r in m.pending) >= self.buckets[-1]:
            return True
        interactive = [r for r in m.pending
                       if r.priority == TIER_INTERACTIVE]
        if interactive:
            oldest = min(r.submitted_at for r in interactive)
            return now - oldest >= self.interactive_deadline_s
        oldest = min(r.submitted_at for r in m.pending)
        return now - oldest >= self.batch_deadline_s

    def _select(self, m: ServedModel) -> list[ForestRequest]:
        """Pop a FIFO prefix of the queue that fits the largest bucket
        (requests are never split across ticks — row order within a
        request, and across requests within a tick, is preserved)."""
        batch: list[ForestRequest] = []
        total = 0
        with self._lock:
            while m.pending and \
                    total + m.pending[0].num_rows <= self.buckets[-1]:
                req = m.pending.popleft()
                batch.append(req)
                total += req.num_rows
        return batch

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _flush(self, m: ServedModel) -> int:
        """Coalesce one padded batch for ``m`` and serve it through
        ``infer_rows``.  Returns rows served (0 if the queue was
        empty).  Each request is admitted when ``_select`` hands this
        flush its batch: a tick loop under a backlog flushes several
        batches, each with its own admission time."""
        batch = self._select(m)
        if not batch:
            return 0
        admitted = time.perf_counter()
        n = sum(r.num_rows for r in batch)
        bucket = self._bucket(n)
        with TRACER.span("serve.tick", model=m.name, requests=len(batch),
                         rows=n, bucket=bucket) as sp:
            with TRACER.span("serve.coalesce", model=m.name):
                F = int(m.forest.n_features)
                x = np.zeros((bucket, F), np.float32)
                mask = np.zeros(bucket, bool)
                off = 0
                for req in batch:
                    x[off:off + req.num_rows] = req.rows
                    mask[off:off + req.num_rows] = True
                    off += req.num_rows
                    req.admitted_at = admitted
                    m.metrics.histogram("serve.queue_wait_s").record(
                        admitted - req.submitted_at)
            for reg in (m.metrics, self.metrics):
                reg.counter("serve.ticks").inc()
                reg.counter("serve.padding_rows").inc(bucket - n)
                reg.histogram("serve.coalesce_width",
                              bounds=tuple(float(b) for b in self.buckets)
                              ).record(n)
            METRICS.counter(QUEUE_DEPTH_METRIC).inc(-len(batch))
            try:
                res = self.qe.infer_rows(
                    m.forest, x, row_mask=mask, algorithm=m.algorithm,
                    plan=m.plan, model_id=m.model_id)
            except BaseException as e:      # noqa: BLE001 — re-raised by
                self.last_error = e         # every waiter's .wait()
                for req in batch:
                    req.error = e
                    req.done.set()
                raise
            m.metrics.counter("serve.plan_hits" if res.plan_reuse_hit
                              else "serve.plan_misses").inc()
            sp.set(plan_hit=res.plan_reuse_hit)
            with TRACER.span("serve.result_wait"):
                out = np.asarray(res.predictions)
            done_at = time.perf_counter()
            with TRACER.span("serve.handback"):
                off = 0
                for req in batch:
                    req.predictions = out[off:off + req.num_rows].copy()
                    off += req.num_rows
                    req.finished_at = done_at
                    m.metrics.histogram("serve.e2e_latency_s").record(
                        done_at - req.submitted_at)
                    req.done.set()
        return n

    def tick(self, now: float | None = None, force: bool = False) -> int:
        """One coalescer pass over every model: shed lapsed admission
        timeouts, then flush every due queue (every non-empty queue,
        with ``force``).  Returns total rows served.  The ticker thread
        calls this in a loop; tests and synchronous callers can drive
        it directly."""
        now = time.perf_counter() if now is None else now
        served = 0
        with self._lock:
            models = list(self._models.values())
        for m in models:
            self._shed_timed_out(m, now)
            while m.pending and (force or self._due(m, now)):
                served += self._flush(m)
        return served

    def drain(self, max_ticks: int = 10_000) -> int:
        """Force-flush until every queue is empty (synchronous callers
        / tests).  Returns total rows served."""
        served = 0
        for _ in range(max_ticks):
            if not any(m.pending for m in self._models.values()):
                break
            served += self.tick(force=True)
        return served

    # ------------------------------------------------------------------
    # ticker thread
    # ------------------------------------------------------------------
    def start(self) -> None:
        """Start the dedicated ticker thread (idempotent)."""
        if self._running.is_set():
            return
        self._running.set()

        def loop():
            while self._running.is_set():
                try:
                    if self.tick() == 0:
                        time.sleep(self.tick_interval_s)
                except BaseException:       # noqa: BLE001 — recorded on
                    # last_error + the affected requests by _flush; the
                    # ticker keeps serving other tenants
                    time.sleep(self.tick_interval_s)

        self._ticker = threading.Thread(target=loop, daemon=True,
                                        name="forest-serve-tick")
        self._ticker.start()

    def stop(self) -> None:
        """Stop the ticker thread and join it (queued work stays queued
        — call ``drain()`` to finish it synchronously)."""
        if not self._running.is_set():
            return
        self._running.clear()
        if self._ticker is not None:
            self._ticker.join(timeout=5.0)
            self._ticker = None

    def __enter__(self) -> "ForestServeEngine":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------------
    # telemetry
    # ------------------------------------------------------------------
    def stats(self, model: str | None = None) -> dict[str, Any]:
        """Per-model serving stats (or, with ``model=None``, the
        engine-level rollup plus every tenant's row).  Percentiles come
        from the per-model histogram-backed registries."""
        if model is not None:
            m = self._get(model)
            qw = m.metrics.histogram("serve.queue_wait_s")
            e2e = m.metrics.histogram("serve.e2e_latency_s")
            cw = m.metrics.histogram(
                "serve.coalesce_width",
                bounds=tuple(float(b) for b in self.buckets))
            return {
                "requests": m.metrics.counter("serve.requests").value,
                "ticks": m.metrics.counter("serve.ticks").value,
                "shed": m.metrics.counter("serve.shed").value,
                "plan_hits": m.metrics.counter("serve.plan_hits").value,
                "plan_misses":
                    m.metrics.counter("serve.plan_misses").value,
                "padding_rows":
                    m.metrics.counter("serve.padding_rows").value,
                "pending": len(m.pending),
                "mean_coalesce_width": cw.mean if cw.count else 0.0,
                "p50_queue_wait_s": qw.percentile(50),
                "p99_queue_wait_s": qw.percentile(99),
                "p50_latency_s": e2e.percentile(50),
                "p99_latency_s": e2e.percentile(99),
            }
        return {
            "models": len(self._models),
            "queue_depth":
                METRICS.counter(QUEUE_DEPTH_METRIC).value,
            "ticks": self.metrics.counter("serve.ticks").value,
            "padding_rows":
                self.metrics.counter("serve.padding_rows").value,
            "mean_coalesce_width":
                self._width_h.mean if self._width_h.count else 0.0,
            "per_model": {n: self.stats(n) for n in self._models},
        }
