"""The canonical catalog of exported span / event / metric names.

Instrumentation across the data plane imports its names from nowhere —
names are string literals at the call sites — but THIS module is the
authoritative list of what the observability plane exports, and
``benchmarks/check_docs.py`` (stdlib-only CI gate) asserts every name
below appears, in backticks, in ``docs/observability.md``.  Add an
instrument without cataloging + documenting it and CI fails.

``SPAN_PREFIXES`` covers dynamically named spans (per-stage spans are
``stage:<stage name>`` — the stage names themselves are plan-derived).
"""

from __future__ import annotations

__all__ = ["SPAN_NAMES", "SPAN_PREFIXES", "EVENT_NAMES", "METRIC_NAMES"]

#: every statically named span the instrumentation can emit
SPAN_NAMES = (
    # query engine (db/query.py)
    "query.infer",
    "query.infer_rows",
    "query.rows_upload",
    "query.rows_dispatch",
    "query.result_upload",
    "plan.build",
    "plan.partition",
    "query.write",
    # streaming scan executor (db/executor.py)
    "scan.execute",
    "scan.batch",
    "scan.disk_read",
    "scan.dma_in",
    "scan.page_copy",
    "scan.transfer_wait",
    "scan.compute",
    "scan.drain_submit",
    "scan.drain_write",
    "scan.drain_join",
    # tensor-block store (db/store.py)
    "store.put",
    "store.put_sparse",
    "store.move",
    "store.pin",
    # external loaders (db/loader.py)
    "load.parse",
    "load.convert",
    "load.transfer",
    # serving plane (serve/engine.py, serve/forest.py)
    "serve.prefill",
    "serve.execute",
    "serve.tick",
    "serve.coalesce",
    "serve.result_wait",
    "serve.handback",
    # cost-based optimizer (db/optimizer.py)
    "optimizer.decide",
    "optimizer.autotune",
    # in-database streamed training (db/train.py)
    "train.forest",
    "train.sketch",
    "train.bin_ingest",
    "train.level",
)

#: prefixes of dynamically named spans
SPAN_PREFIXES = (
    "stage:",            # per-pipeline-stage spans (db/operators.Stage.run)
)

#: every span-event (instant) name
EVENT_NAMES = (
    "fault.injected",    # FaultInjector.fire hit an armed site
    "retry",             # RetryPolicy re-attempt at a site
    "degrade.sync_drain",   # drain-worker death -> mid-scan sync fallback
    "batch.resubmit",    # disk-read re-enqueue / transfer halving ladder
    "deadline.hit",      # cooperative deadline stopped the scan
    "plan.cache",        # compiled-plan cache consulted (hit= attr)
    "serve.shed",        # admission timeout demoted a request to batch
    "optimizer.decision",   # a decision was made + persisted (cell attrs)
)

#: every process-global METRICS counter (and the serve engine's
#: per-engine histogram names)
METRIC_NAMES = (
    # plan / tracing accounting (db/operators.py, db/query.py)
    "plan.traces",
    "plan.cache_hits",
    "plan.cache_misses",
    # streaming scan rollups (db/executor.py)
    "scan.batches",
    "scan.bytes_streamed",
    "scan.pinned_bytes",
    "scan.host_relayout_bytes",
    "scan.retries",
    "scan.faults_injected",
    "scan.batch_resubmits",
    "scan.degraded_to_sync",
    "scan.deadline_hits",
    # fused kernels: traces of each predicated walk
    # (kernels/forest_predicated.py)
    "kernel.predicated.node_major_traces",
    "kernel.predicated.row_major_traces",
    # store / loader (db/store.py, db/loader.py)
    "store.puts",
    "store.moves",
    "load.external_loads",
    # serving plane (serve/engine.py + serve/forest.py; per-engine /
    # per-model registries except serve.queue_depth, which is the
    # process-global arrival-load gauge the router reads)
    "serve.requests",
    "serve.shed",
    "serve.queue_wait_s",
    "serve.e2e_latency_s",
    "serve.queue_depth",
    "serve.ticks",
    "serve.coalesce_width",
    "serve.padding_rows",
    "serve.plan_hits",
    "serve.plan_misses",
    # cost-based optimizer (db/optimizer.py)
    "optimizer.decisions",
    "optimizer.decision_cache_hits",
    "optimizer.decision_cache_misses",
    "optimizer.autotune_runs",
    "optimizer.measurements",
    # in-database streamed training (db/train.py)
    "train.runs",
    "train.trees_grown",
    "train.level_scans",
)
