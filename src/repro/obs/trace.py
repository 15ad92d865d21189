"""Per-query span tracing with Perfetto (Chrome trace-event) export.

The other half of the observability plane (see ``metrics.py``).  A
``Tracer`` records SPANS — named, attributed, monotonic-clocked wall
intervals — nested per thread via a thread-local stack, with explicit
cross-thread parenting so the ``scan-drain`` worker's result-buffer
writes nest under the compute-thread batch that produced them.  Span
EVENTS (instants: an injected fault, a retry, a degradation-ladder
transition) attach to whatever span is open on the firing thread.

The contract that keeps this off the hot path:

  * DISABLED BY DEFAULT, and the disabled path allocates NOTHING —
    ``tracer.span(...)`` returns the shared ``NULL_SPAN`` singleton
    whose enter/exit/event/set are no-ops (asserted by
    ``tests/test_obs.py``); arming is ``tracer.enable()``.
  * ARMED overhead is measured on the chip: traced against untraced
    runs of the benchmark's cells (``docs/observability.md``).
  * Monotonic clocks only (``time.perf_counter_ns``) — span math never
    sees wall-clock adjustments.
  * ONE CLOCK WITH THE DEVICE — while armed, each span also enters a
    ``jax.profiler.TraceAnnotation`` named by the span's catalogue name
    (no attrs: TraceMe would fold them into the event name), entered and
    left on the span's own thread.  Under ``jax.profiler`` the engine's
    phases then land on the trace's ``/host:CPU`` plane, on the clock of
    the device's ``XLA Ops``.
  * Stdlib only at import — ``jax.profiler`` is resolved lazily, once,
    by ``enable()``; without JAX spans simply do not annotate.  Nothing
    here can be traced into a jitted stage, and the CI docs gate can
    import the module without jax.

Export: ``tracer.export_chrome(path)`` writes Chrome trace-event JSON
(the ``traceEvents`` array format) loadable in Perfetto / chrome://
tracing — spans as ``ph: "X"`` complete events, span events as
``ph: "i"`` instants, one track per thread (``tid`` + ``M`` metadata
rows carrying the thread names), microsecond timestamps.  The async
drain's overlap is directly visible: ``scan.drain_write`` spans on the
``scan-drain`` track overlap ``scan.compute`` spans on the main track.

``TraceSummary`` is the per-query rollup attached to
``QueryResult.trace``: per-span-name wall totals, span/event counts,
and the ``METRICS`` counter deltas that accrued during the query.  The
span taxonomy and every exported name live in ``obs/names.py`` and are
documented (CI-enforced) in ``docs/observability.md``.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import json
import threading
import time
from collections import deque
from typing import Any

__all__ = ["Span", "SpanEvent", "NullSpan", "NULL_SPAN", "Tracer",
           "TRACER", "TraceSummary"]


@dataclasses.dataclass
class SpanEvent:
    """An instant inside (or beside) a span: retries, injected faults,
    ladder transitions, cache hits."""

    name: str
    ts_ns: int
    tid: int
    thread_name: str
    attrs: dict[str, Any]


class Span:
    """One named wall interval.  Context manager: enter starts the
    clock and pushes onto the owning thread's stack; exit stops it,
    pops, and publishes the span to the tracer's finished list."""

    __slots__ = ("name", "attrs", "span_id", "parent_id", "start_ns",
                 "end_ns", "tid", "thread_name", "events", "_tracer",
                 "_parent", "_annotation")

    def __init__(self, tracer: "Tracer", name: str,
                 parent: "Span | None" = None, attrs: dict | None = None):
        self.name = name
        self.attrs = attrs or {}
        self.span_id = next(tracer._ids)
        self.parent_id: int | None = None
        self._parent = parent               # explicit cross-thread parent
        self.start_ns = 0
        self.end_ns = 0
        self.tid = 0
        self.thread_name = ""
        self.events: list[SpanEvent] = []
        self._tracer = tracer
        self._annotation = None

    # -- context manager ----------------------------------------------------
    def __enter__(self) -> "Span":
        t = threading.current_thread()
        self.tid = t.ident or 0
        self.thread_name = t.name
        parent = self._parent
        if parent is None:
            parent = self._tracer._current()
        if isinstance(parent, Span):
            self.parent_id = parent.span_id
        self._tracer._push(self)
        annotate = self._tracer._annotate
        if annotate is not None:
            self._annotation = annotate(self.name)
            self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end_ns = time.perf_counter_ns()
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        self._tracer._pop(self)
        self._tracer._finished.append(self)

    # -- in-flight mutation -------------------------------------------------
    def set(self, **attrs) -> "Span":
        """Attach/overwrite attributes (usable after close too — exports
        read lazily)."""
        self.attrs.update(attrs)
        return self

    def event(self, name: str, **attrs) -> None:
        """Record an instant event on this span (timestamped now, on the
        CALLING thread's track)."""
        t = threading.current_thread()
        self.events.append(SpanEvent(name=name,
                                     ts_ns=time.perf_counter_ns(),
                                     tid=t.ident or 0, thread_name=t.name,
                                     attrs=attrs))

    @property
    def duration_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class NullSpan:
    """The disabled tracer's span: a shared no-op singleton.  Every
    method is a no-op and ``tracer.span(...)`` returns THE SAME object,
    so a disabled trace point allocates nothing per call."""

    __slots__ = ()

    def __enter__(self) -> "NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None

    def set(self, **attrs) -> "NullSpan":
        return self

    def event(self, name: str, **attrs) -> None:
        return None

    @property
    def duration_s(self) -> float:
        return 0.0


NULL_SPAN = NullSpan()


@dataclasses.dataclass
class TraceSummary:
    """Per-query rollup: where did the time go? (``QueryResult.trace``)

    ``phase_s`` sums span wall seconds BY SPAN NAME over the query's
    span tree (so ``phase_s["scan.compute"]`` is comparable with
    ``ScanStats.compute_s`` — both clock the same code region: the
    host's dispatch of a batch's stages, not the device's work);
    ``span_counts`` / ``event_counts`` count spans and events by name;
    ``counters`` holds the process-global ``METRICS`` counter deltas
    that accrued while the query ran.
    """

    root: str
    wall_s: float
    phase_s: dict[str, float]
    span_counts: dict[str, int]
    event_counts: dict[str, int]
    counters: dict[str, int | float]
    num_spans: int = 0

    def phase(self, name: str) -> float:
        """Total seconds of spans named ``name`` (0.0 when absent)."""
        return self.phase_s.get(name, 0.0)


@functools.cache
def _profiler_annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use; None
    where JAX is not installed."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


def _jsonable(v):
    return v if isinstance(v, (str, int, float, bool, type(None))) \
        else str(v)


class Tracer:
    """Thread-safe span tracer, process-global as ``obs.TRACER``.

    Spans nest through a per-thread stack; cross-thread children pass
    ``parent=`` explicitly (the drain worker parents its writes under
    the owning batch's span even though that span lives — and may have
    already closed — on the compute thread).  Finished spans land in an
    append-only deque (GIL-atomic appends; ``mark()``/``finished()``
    window it), which ``export_chrome`` / ``summarize`` consume.
    """

    def __init__(self):
        self.enabled = False
        #: ``jax.profiler.TraceAnnotation`` once ``enable()`` found JAX;
        #: None before that, or without JAX
        self._annotate = None
        self._ids = itertools.count(1)
        self._finished: deque[Span] = deque()
        self._orphan_events: deque[SpanEvent] = deque()
        self._stacks = threading.local()
        self._epoch_ns = time.perf_counter_ns()

    # -- arming -------------------------------------------------------------
    def enable(self) -> "Tracer":
        self._annotate = _profiler_annotation()
        self.enabled = True
        return self

    def disable(self) -> "Tracer":
        self.enabled = False
        return self

    def reset(self) -> None:
        """Drop every recorded span/event and restart the export epoch
        (open spans on live stacks are unaffected — they will publish
        into the fresh window when they close)."""
        self._finished = deque()
        self._orphan_events = deque()
        self._epoch_ns = time.perf_counter_ns()

    # -- per-thread stack ---------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._stacks, "spans", None)
        if st is None:
            st = self._stacks.spans = []
        return st

    def _current(self) -> Span | None:
        st = getattr(self._stacks, "spans", None)
        return st[-1] if st else None

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        st = self._stack()
        if st and st[-1] is span:
            st.pop()
        elif span in st:                 # out-of-order exit: still correct
            st.remove(span)

    # -- recording ----------------------------------------------------------
    def span(self, name: str, parent: Span | NullSpan | None = None,
             **attrs):
        """Open a span (use as a context manager).  Disabled tracer:
        returns the shared ``NULL_SPAN`` — no allocation, no clock.
        ``parent=`` overrides the thread-stack parent (cross-thread
        nesting); a ``NullSpan`` parent (captured while disabled) is
        treated as no parent."""
        if not self.enabled:
            return NULL_SPAN
        if not isinstance(parent, Span):
            parent = None
        return Span(self, name, parent=parent, attrs=attrs)

    def event(self, name: str, **attrs) -> None:
        """Record an instant on the calling thread's open span (or as a
        free-standing orphan instant when no span is open)."""
        if not self.enabled:
            return
        cur = self._current()
        if cur is not None:
            cur.event(name, **attrs)
        else:
            t = threading.current_thread()
            self._orphan_events.append(SpanEvent(
                name=name, ts_ns=time.perf_counter_ns(),
                tid=t.ident or 0, thread_name=t.name, attrs=attrs))

    # -- consumption --------------------------------------------------------
    def mark(self) -> int:
        """Index into the finished-span window: ``finished(mark)`` /
        ``summarize(..., since=mark)`` scope to spans closed after it."""
        return len(self._finished)

    def finished(self, since: int = 0) -> list[Span]:
        return list(itertools.islice(self._finished, since, None))

    def summarize(self, root: Span, *, since: int = 0,
                  counters_before: dict | None = None,
                  counters_now: dict | None = None) -> TraceSummary:
        """Roll the span tree under ``root`` up into a ``TraceSummary``.

        Membership is by parent chain (children may close after their
        parent — the cross-thread drain writes do), so the walk uses the
        id->span map of the window, not append order.
        """
        window = self.finished(since)
        by_id = {s.span_id: s for s in window}
        under: set[int] = {root.span_id}
        # spans close child-before-parent on one thread but the window
        # can interleave threads; iterate to a fixpoint (tree depth is
        # tiny, this converges in 2-3 passes)
        changed = True
        while changed:
            changed = False
            for s in window:
                if s.span_id not in under and s.parent_id in under:
                    under.add(s.span_id)
                    changed = True
        phase_s: dict[str, float] = {}
        span_counts: dict[str, int] = {}
        event_counts: dict[str, int] = {}
        n = 0
        for s in window:
            if s.span_id not in under:
                continue
            n += 1
            phase_s[s.name] = phase_s.get(s.name, 0.0) + s.duration_s
            span_counts[s.name] = span_counts.get(s.name, 0) + 1
            for ev in s.events:
                event_counts[ev.name] = event_counts.get(ev.name, 0) + 1
        counters: dict[str, int | float] = {}
        if counters_now is not None:
            before = counters_before or {}
            for k, v in counters_now.items():
                d = v - before.get(k, 0)
                if d:
                    counters[k] = d
        return TraceSummary(root=root.name, wall_s=root.duration_s,
                            phase_s=phase_s, span_counts=span_counts,
                            event_counts=event_counts, counters=counters,
                            num_spans=n)

    # -- Perfetto / chrome://tracing export ---------------------------------
    def export_chrome(self, path: str | None = None,
                      since: int = 0) -> dict:
        """Serialize the finished-span window as Chrome trace-event JSON.

        One track per thread: ``tid`` is a dense index with an ``M``
        (metadata) row naming it after the Python thread, so Perfetto
        shows ``MainThread`` and ``scan-drain`` as separate lanes and
        the async drain's overlap is visible as overlapping spans.
        Spans are ``ph: "X"`` complete events (``ts``/``dur`` in
        microseconds since the tracer epoch); span events are
        ``ph: "i"`` thread-scoped instants.  Returns the payload dict;
        writes JSON to ``path`` when given.
        """
        tid_names: dict[int, tuple[int, str]] = {}

        def track(ident: int, name: str) -> int:
            if ident not in tid_names:
                tid_names[ident] = (len(tid_names) + 1, name)
            return tid_names[ident][0]

        def us(ts_ns: int) -> float:
            return (ts_ns - self._epoch_ns) / 1000.0

        events: list[dict] = []
        for sp in self.finished(since):
            args = {k: _jsonable(v) for k, v in sp.attrs.items()}
            args["span_id"] = sp.span_id
            if sp.parent_id is not None:
                args["parent_id"] = sp.parent_id
            events.append({"name": sp.name, "cat": "span", "ph": "X",
                           "ts": us(sp.start_ns), "dur": sp.duration_s * 1e6,
                           "pid": 1, "tid": track(sp.tid, sp.thread_name),
                           "args": args})
            for ev in sp.events:
                events.append({
                    "name": ev.name, "cat": "event", "ph": "i", "s": "t",
                    "ts": us(ev.ts_ns), "pid": 1,
                    "tid": track(ev.tid, ev.thread_name),
                    "args": dict(
                        {k: _jsonable(v) for k, v in ev.attrs.items()},
                        span_id=sp.span_id)})
        for ev in self._orphan_events:
            events.append({"name": ev.name, "cat": "event", "ph": "i",
                           "s": "t", "ts": us(ev.ts_ns), "pid": 1,
                           "tid": track(ev.tid, ev.thread_name),
                           "args": {k: _jsonable(v)
                                    for k, v in ev.attrs.items()}})
        for _, (tid, name) in sorted(tid_names.items(),
                                     key=lambda kv: kv[1][0]):
            events.append({"name": "thread_name", "ph": "M", "pid": 1,
                           "tid": tid, "args": {"name": name}})
        events.append({"name": "process_name", "ph": "M", "pid": 1,
                       "tid": 0, "args": {"name": "repro-data-plane"}})
        payload = {"traceEvents": events, "displayTimeUnit": "ms"}
        if path is not None:
            with open(path, "w") as fh:
                json.dump(payload, fh)
        return payload


#: the process-global tracer every layer of the data plane reports to
#: (disabled by default; ``TRACER.enable()`` arms it)
TRACER = Tracer()
