"""Observability plane (``src/repro/obs``): tracer, metrics, export.

The contracts under test, per ``docs/observability.md``:

  * DISABLED IS FREE — a disabled tracer returns the shared
    ``NULL_SPAN`` singleton from every ``span()`` call (identity
    asserted: zero allocation per trace point) and records nothing;
    ``QueryResult.trace`` stays ``None``.
  * SPANS NEST, ACROSS THREADS TOO — thread-stack nesting on one
    thread, explicit ``parent=`` for the drain worker's writes, which
    must nest under the owning batch span even though that span lives
    (and may have closed) on the compute thread.
  * EVENTS ARE EXACT — one injected fault produces exactly one
    ``fault.injected`` and one ``retry`` in the query's summary; the
    drain-death ladder produces its ``degrade.sync_drain``.
  * THE EXPORT IS VALID — Chrome trace-event JSON round-trips, every
    span row carries ``ph``/``ts``/``dur``/``tid``, parent chains
    resolve (``validate_chrome_trace`` below).
  * SUMMARIES AGREE WITH STATS — ``trace.phase("scan.compute")`` clocks
    the same region as ``ScanStats.compute_s``.
  * ONE CLOCK WITH THE DEVICE — under ``jax.profiler`` an armed tracer's
    spans appear on the trace's ``/host:CPU`` plane by their catalogue
    names alone, nested as the tracer nests them; a disarmed one leaves
    nothing there.
"""

import glob
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.core.reuse import ModelReuseCache
from repro.core.train import TrainConfig, train_forest
from repro.db.faults import FaultInjector, RetryPolicy
from repro.db.operators import TRACE_STATS
from repro.db.query import ForestQueryEngine
from repro.db.store import TensorBlockStore
from repro.obs import (METRICS, NULL_SPAN, TRACER, Counter, Histogram,
                       MetricsRegistry)

N, F, T, PAGE = 384, 16, 24, 32
FUSED = "predicated_pallas_fused"
FAST = RetryPolicy(backoff_base_s=0.0, max_backoff_s=0.0)


@pytest.fixture(autouse=True)
def _tracer_clean():
    """Every test starts and ends with the tracer disarmed and empty."""
    TRACER.disable()
    TRACER.reset()
    yield
    TRACER.disable()
    TRACER.reset()


@pytest.fixture(scope="module")
def env():
    rng = np.random.default_rng(7)
    x = rng.normal(size=(N, F)).astype(np.float32)
    w = rng.normal(size=F).astype(np.float32)
    y = (x @ w > 0).astype(np.float32)
    forest = train_forest(x, y, TrainConfig(model_type="xgboost",
                                            num_trees=T, max_depth=4))
    store = TensorBlockStore(default_page_rows=PAGE)
    store.put("dense@host", x, tier="host")
    store.put("dense@disk", x, tier="disk")
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                               plan_cache=ModelReuseCache())
    return engine, forest, x


def validate_chrome_trace(payload: dict) -> dict:
    """Structural validation of an exported Chrome trace: round-trips
    through json, checks the trace-event contract on every row, and
    resolves parent chains.  Returns summary counts; raises on any
    violation."""
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


def traced_infer(engine, forest, name, **kw):
    TRACER.reset()
    TRACER.enable()
    try:
        return engine.infer(name, forest, algorithm=FUSED, **kw)
    finally:
        TRACER.disable()


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------


def test_counter_inc_value_reset():
    c = Counter("t")
    assert c.value == 0
    c.inc()
    c.inc(4)
    assert c.value == 5
    c.reset()
    assert c.value == 0


def test_histogram_percentiles_interpolate_within_observed_range():
    h = Histogram("lat", bounds=(0.001, 0.01, 0.1, 1.0))
    assert np.isnan(h.percentile(50))
    for v in (0.002, 0.003, 0.004, 0.05, 0.5):
        h.record(v)
    assert h.count == 5 and h.min == 0.002 and h.max == 0.5
    p50 = h.percentile(50)
    assert 0.001 <= p50 <= 0.01       # median lands in the second bucket
    p99 = h.percentile(99)
    assert 0.1 <= p99 <= 0.5          # clamped to the observed max
    s = h.summary()
    assert s["count"] == 5 and s["p50"] == p50 and s["max"] == 0.5
    h.record(100.0)                   # overflow bucket (past last bound)
    assert h.percentile(100) == 100.0


def test_registry_get_or_create_and_reset_keep_instances():
    reg = MetricsRegistry()
    c1 = reg.counter("a")
    c1.inc(3)
    assert reg.counter("a") is c1             # get-or-create, same object
    h1 = reg.histogram("h")
    h1.record(0.5)
    assert reg.counter_values() == {"a": 3}
    snap = reg.snapshot()
    assert snap["a"] == 3 and snap["h"]["count"] == 1
    reg.reset()
    assert reg.counter("a") is c1 and c1.value == 0
    assert reg.histogram("h") is h1 and h1.count == 0


def test_trace_stats_alias_mirrors_plan_traces_counter():
    """The pre-obs ``TRACE_STATS`` dict is a live view over the
    ``plan.traces`` counter: reads, ``+=`` writes, both directions."""
    c = METRICS.counter("plan.traces")
    before = TRACE_STATS["traces"]
    assert before == c.value
    c.inc(2)
    assert TRACE_STATS["traces"] == before + 2
    TRACE_STATS["traces"] += 1                # legacy increment style
    assert c.value == before + 3


# ---------------------------------------------------------------------------
# tracer: disabled path, nesting, events
# ---------------------------------------------------------------------------


def test_disabled_tracer_returns_the_null_span_singleton():
    assert not TRACER.enabled
    s1 = TRACER.span("anything", attr=1)
    s2 = TRACER.span("else")
    assert s1 is NULL_SPAN and s2 is NULL_SPAN    # identity: no allocation
    with s1 as s:
        s.set(x=1).event("noop")
        assert s.duration_s == 0.0
    TRACER.event("orphan")                         # no-op while disabled
    assert TRACER.finished() == []
    assert TRACER.export_chrome()["traceEvents"][-1]["ph"] == "M"


def test_span_nesting_attrs_and_summary():
    TRACER.enable()
    with TRACER.span("root", kind="test") as root:
        with TRACER.span("child") as child:
            TRACER.event("ping", n=1)          # attaches to innermost
        with TRACER.span("child"):
            pass
        root.set(late=True)
    assert child.parent_id == root.span_id
    assert root.parent_id is None
    assert root.attrs == {"kind": "test", "late": True}
    summ = TRACER.summarize(root)
    assert summ.num_spans == 3
    assert summ.span_counts == {"root": 1, "child": 2}
    assert summ.event_counts == {"ping": 1}
    assert summ.phase("child") <= summ.wall_s
    assert summ.phase("absent") == 0.0


def test_cross_thread_parenting_survives_parent_close():
    """The drain-worker pattern: the child opens on another thread with
    an explicit ``parent=`` AFTER the parent span already closed, and
    must still nest (summaries use the id map, not close order)."""
    TRACER.enable()
    with TRACER.span("query") as root:
        with TRACER.span("batch") as batch:
            pass

    def worker():
        with TRACER.span("drain", parent=batch):
            pass

    t = threading.Thread(target=worker, name="fake-drain")
    t.start()
    t.join()
    drain = [s for s in TRACER.finished() if s.name == "drain"][0]
    assert drain.parent_id == batch.span_id
    assert drain.tid != batch.tid
    summ = TRACER.summarize(root)
    assert summ.num_spans == 3 and summ.span_counts["drain"] == 1
    shape = validate_chrome_trace(TRACER.export_chrome())
    assert shape["cross_thread"] == 1 and shape["threads"] == 2


def test_null_span_parent_means_no_parent():
    """A parent handle captured while the tracer was disabled is the
    NULL_SPAN; a span opened with it (tracer now enabled) is a root."""
    parent = TRACER.span("captured-disabled")      # NULL_SPAN
    TRACER.enable()
    with TRACER.span("child", parent=parent) as ch:
        pass
    assert ch.parent_id is None


def test_orphan_events_are_exported():
    TRACER.enable()
    TRACER.event("free-standing", why="no open span")
    payload = TRACER.export_chrome()
    inst = [e for e in payload["traceEvents"] if e["ph"] == "i"]
    assert len(inst) == 1 and inst[0]["name"] == "free-standing"


# ---------------------------------------------------------------------------
# the instrumented data plane
# ---------------------------------------------------------------------------


def test_disabled_infer_leaves_no_trace(env):
    engine, forest, _ = env
    res = engine.infer("dense@host", forest, algorithm=FUSED,
                       batch_pages=2)
    assert res.trace is None
    assert TRACER.finished() == []


def test_traced_infer_spans_cross_thread_drain_and_stats_agree(env):
    """One traced host-tier streamed query: per-batch spans counted
    exactly, the drain worker's writes parented cross-thread, the
    export structurally valid, and the trace's compute phase clocking
    the same region as ``ScanStats.compute_s``."""
    engine, forest, x = env
    res = traced_infer(engine, forest, "dense@host", batch_pages=2)
    tr = res.trace
    assert tr is not None and tr.root == "query.infer"
    sc = res.scan
    assert tr.span_counts["scan.execute"] == 1
    assert tr.span_counts["scan.batch"] == sc.batches
    assert tr.span_counts["scan.dma_in"] == sc.batches
    assert tr.span_counts["scan.compute"] == sc.batches
    assert tr.span_counts["scan.drain_write"] == sc.batches
    assert tr.event_counts == {"plan.cache": 1}    # no faults, one lookup
    # phase totals vs ScanStats: same code region, same clock
    assert abs(tr.phase("scan.compute") - sc.compute_s) \
        <= max(0.5 * sc.compute_s, 0.05)
    assert tr.wall_s >= tr.phase("scan.execute") > 0
    # counters are per-query deltas
    assert tr.counters["scan.batches"] == sc.batches
    assert tr.counters["scan.bytes_streamed"] == sc.bytes_streamed
    assert "scan.retries" not in tr.counters       # zero deltas dropped
    # the export: valid, nested, and the drain edge is cross-thread
    payload = TRACER.export_chrome()
    shape = validate_chrome_trace(payload)
    spans = {e["args"]["span_id"]: e for e in payload["traceEvents"]
             if e["ph"] == "X"}
    drains = [e for e in spans.values() if e["name"] == "scan.drain_write"]
    assert len(drains) == sc.batches
    for d in drains:
        parent = spans[d["args"]["parent_id"]]
        assert parent["name"] == "scan.batch"
        assert parent["tid"] != d["tid"]           # async drain thread
    assert shape["threads"] >= 2


def test_traced_rerun_reports_plan_cache_hit(env):
    engine, forest, _ = env
    traced_infer(engine, forest, "dense@host", batch_pages=2)
    res = traced_infer(engine, forest, "dense@host", batch_pages=2)
    assert res.reuse_hit
    assert res.trace.counters.get("plan.cache_hits") == 1
    assert "plan.cache_misses" not in res.trace.counters


def test_fault_events_exact_counts(env):
    """One transient dma fault: exactly one ``fault.injected`` and one
    ``retry`` instant in the query's summary, mirrored by the counter
    deltas, predictions unchanged."""
    engine, forest, _ = env
    ref = np.asarray(engine.infer("dense@host", forest, algorithm=FUSED,
                                  batch_pages=2).predictions)
    inj = FaultInjector().inject("page_dma_in", fail_at=2)
    res = traced_infer(engine, forest, "dense@host", batch_pages=2,
                       injector=inj, retry_policy=FAST)
    tr = res.trace
    assert tr.event_counts["fault.injected"] == 1
    assert tr.event_counts["retry"] == 1
    assert tr.counters["scan.faults_injected"] == 1
    assert tr.counters["scan.retries"] == 1
    assert np.array_equal(np.asarray(res.predictions), ref)


def test_drain_death_emits_degrade_event(env):
    engine, forest, _ = env
    inj = FaultInjector().inject("drain_worker", fail_at=1)
    res = traced_infer(engine, forest, "dense@host", batch_pages=2,
                       injector=inj, retry_policy=FAST)
    assert res.scan.degraded_to_sync
    assert res.trace.event_counts["degrade.sync_drain"] == 1
    assert res.trace.counters["scan.degraded_to_sync"] == 1


def test_disk_tier_trace_has_disk_read_spans(env):
    engine, forest, _ = env
    res = traced_infer(engine, forest, "dense@disk", batch_pages=2)
    tr = res.trace
    assert tr.span_counts["scan.disk_read"] == res.scan.batches
    validate_chrome_trace(TRACER.export_chrome())


def test_export_chrome_writes_loadable_json(env, tmp_path):
    engine, forest, _ = env
    traced_infer(engine, forest, "dense@host", batch_pages=2)
    out = tmp_path / "trace.json"
    TRACER.enable()                    # export works regardless; reset not
    payload = TRACER.export_chrome(str(out))
    on_disk = json.loads(out.read_text())
    assert on_disk == json.loads(json.dumps(payload))
    validate_chrome_trace(on_disk)
    names = {e["name"] for e in on_disk["traceEvents"] if e["ph"] == "M"}
    assert {"thread_name", "process_name"} <= names


def test_store_and_loader_spans(env):
    engine, forest, _ = env
    store = engine.store
    rng = np.random.default_rng(3)
    TRACER.enable()
    store.put("obs-put", rng.normal(size=(64, F)).astype(np.float32))
    store.move("obs-put", "host")
    TRACER.disable()
    names = [s.name for s in TRACER.finished()]
    assert "store.put" in names and "store.move" in names
    move = [s for s in TRACER.finished() if s.name == "store.move"][0]
    assert move.attrs["src"] == "device" and move.attrs["dst"] == "host"
    store.drop("obs-put")


# ---------------------------------------------------------------------------
# one clock with the device: spans as profiler annotations
# ---------------------------------------------------------------------------

#: where each phase span nests, by the innermost engine span around it
PHASE_PARENTS = {
    "query.rows_upload": "query.infer_rows",
    "query.rows_dispatch": "query.infer_rows",
    "query.infer_rows": "serve.tick",
    "serve.result_wait": "serve.tick",
    "serve.handback": "serve.tick",
    "scan.drain_join": "scan.execute",
    "query.result_upload": "query.infer",
}


def _engine_name(name: str) -> bool:
    from repro.obs import SPAN_NAMES, SPAN_PREFIXES
    base = name.split("#")[0]
    return base in SPAN_NAMES or base.startswith(SPAN_PREFIXES)


def _profiled(env, tmp_path, armed: bool):
    """A streamed scan and a served tick under ``jax.profiler``; the
    engine's events on the trace's ``/host:CPU`` plane, per thread, as
    (name, start_ns, end_ns)."""
    import jax
    from jax.profiler import ProfileData
    from repro.serve.forest import ForestServeEngine
    engine, forest, x = env
    serve = ForestServeEngine(buckets=(8,), algorithm=FUSED)
    serve.register_model("m", forest)
    engine.infer("dense@host", forest, algorithm=FUSED, batch_pages=2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        if armed:
            TRACER.enable()
        try:
            engine.infer("dense@host", forest, algorithm=FUSED,
                         batch_pages=2)
            reqs = [serve.submit("m", x[i]) for i in range(3)]
            serve.drain()
            for r in reqs:
                r.wait(5.0)
        finally:
            TRACER.disable()
    serve.unregister_model("m")
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    host, = [p for p in ProfileData.from_file(path).planes
             if p.name == "/host:CPU"]
    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for e in line.events if _engine_name(e.name)]
            for line in host.lines]


def _innermost_parent(event, line):
    name, s, e = event
    around = [o for o in line if o is not event and o[1] <= s and e <= o[2]]
    return min(around, key=lambda o: o[2] - o[1])[0] if around else None


def test_armed_spans_land_on_the_profiler_host_plane(env, tmp_path):
    lines = _profiled(env, tmp_path, armed=True)
    events = [ev for line in lines for ev in line]
    names = {n for n, _, _ in events}
    assert set(PHASE_PARENTS) | {"serve.tick", "scan.execute"} <= names
    # the catalogue name alone: TraceMe folds attrs into the name as #k=v#
    assert not any("#" in n for n in names)
    for line in lines:
        for ev in line:
            if ev[0] in PHASE_PARENTS:
                assert _innermost_parent(ev, line) == PHASE_PARENTS[ev[0]], ev


def test_disarmed_tracer_leaves_no_annotation(env, tmp_path):
    lines = _profiled(env, tmp_path, armed=False)
    assert [ev for line in lines for ev in line] == []


def test_obs_imports_and_traces_without_jax():
    """``repro.obs`` is stdlib-only: with JAX unimportable it imports,
    arms and records spans, which then simply do not annotate."""
    code = (
        "import sys; sys.modules['jax'] = None\n"
        "from repro.obs import TRACER\n"
        "TRACER.enable()\n"
        "with TRACER.span('query.infer'):\n"
        "    pass\n"
        "assert TRACER._annotate is None\n"
        "assert [s.name for s in TRACER.finished()] == ['query.infer']\n")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
