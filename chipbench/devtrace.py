"""Reduction of a JAX profiler trace to the device's busy time, the time
of each device operation, and the device's idle time by what the host
was doing.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Its
planes named ``/device:TPU:<n>`` hold a line ``XLA Ops``: one event per
operation that ran on that chip, named by its HLO text (a Pallas kernel
is a ``custom-call`` with ``custom_call_target="tpu_custom_call"``).  The
plane ``/host:CPU`` holds one line per host thread, with the runtime's
own events and the benchmark's ``jax.profiler.TraceAnnotation``s
(``bench.window``, ``bench.query``, ...).  Host and device events share
one clock.

* The window is the ``bench.window`` annotation.
* Busy time is the union of the ``XLA Ops`` intervals inside the window
  (operations that overlap count once), averaged over the chips.
* Each idle stretch of a chip is sampled at least every
  ``IDLE_SAMPLE_S``, and each sample is named by the innermost
  ``bench.*`` annotation that covers it and by the innermost other host
  event that covers it on a Python thread (a thread where JAX's Python
  layer records events), or on any thread where no Python thread has
  one.
"""

from __future__ import annotations

import dataclasses
import glob
import math
import os
import re
from collections import defaultdict

__all__ = ["DeviceTrace", "reduce_dir", "reduce_file", "op_label",
           "is_pallas_kernel", "union_s", "IDLE_SAMPLE_S"]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
BENCH = "bench."
PALLAS = 'custom_call_target="tpu_custom_call"'

#: the longest stretch of idle time named by one look at the host
IDLE_SAMPLE_S = 1e-3

_HLO = re.compile(r"^%([^ ]+?)(?:\.\d+)? = .*? ([a-z][\w.-]*)\(")


def op_label(hlo_text: str) -> str:
    """A short name for a device operation: its HLO instruction name
    without the numeric suffix, and its opcode (``fused (custom-call)``,
    ``copy (copy)``)."""
    m = _HLO.match(hlo_text)
    return f"{m.group(1)} ({m.group(2)})" if m else hlo_text[:80]


def is_pallas_kernel(hlo_text: str) -> bool:
    return PALLAS in hlo_text


def union_s(intervals: list[tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals, in their unit."""
    total, end = 0.0, -math.inf
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _gaps(intervals, lo, hi):
    """The stretches of [lo, hi) that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class DeviceTrace:
    window_s: float
    busy_s: float                     # mean over chips
    ops_s: dict[str, float]           # op label -> seconds, mean over chips
    kernel_s: float                   # Pallas kernels, mean over chips
    idle_s: dict[str, float]          # host activity -> idle seconds, mean
    chips: int

    def breakdown(self, top: int = 10) -> dict:
        def head(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": head(self.ops_s), "idle_gaps": head(self.idle_s)}


#: events that JAX's Python layer records on the thread that calls it:
#: a host line holding one of them is a Python thread of the process
PYTHON_EVENTS = ("PjitFunction(", "np.asarray(", "DevicePut", BENCH)


def _host_threads(plane):
    """Per host thread: (its events with a duration as (start, end, name)
    in ns, ordered by start and, at one start, outermost first; whether
    it is a Python thread).  Python threads come first."""
    threads = []
    for line in plane.lines:
        evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
               for e in line.events if e.duration_ns > 0]
        if evs:
            evs.sort(key=lambda t: (t[0], -t[1]))
            python = any(n.startswith(PYTHON_EVENTS) for _, _, n in evs)
            threads.append((evs, python))
    return sorted(threads, key=lambda t: not t[1])


def _name_points(threads, points):
    """For each time (sorted, ns): the innermost covering ``bench.*``
    annotation, and the innermost other host event that covers it on a
    Python thread (on any thread where no Python thread has one)."""
    bench = [None] * len(points)
    inner = [None] * len(points)     # (rank, duration, name), least wins
    for evs, python in threads:
        rank = 0 if python else 1
        stack, j = [], 0
        for i, p in enumerate(points):
            while j < len(evs) and evs[j][0] <= p:
                while stack and stack[-1][1] <= evs[j][0]:
                    stack.pop()
                stack.append(evs[j])
                j += 1
            while stack and stack[-1][1] <= p:
                stack.pop()
            found_bench = found_inner = False
            for s, e, name in reversed(stack):
                if not s <= p < e:
                    continue
                if name.startswith(BENCH):
                    if not found_bench:
                        found_bench = True
                        if bench[i] is None:
                            bench[i] = name
                elif not found_inner:
                    found_inner = True
                    if inner[i] is None or (rank, e - s) < inner[i][:2]:
                        inner[i] = (rank, e - s, name)
                if found_bench and found_inner:
                    break
    return [f"{b or 'no annotation'} / {n[2] if n else 'no host event'}"
            for b, n in zip(bench, inner)]


def reduce_file(path: str) -> DeviceTrace | None:
    """The trace at ``path`` reduced; None when it holds no TPU plane
    (a run off the chip)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(e.start_ns, e.start_ns + e.duration_ns,
                                     e.name) for e in line.events])
        elif plane.name == HOST_PLANE:
            host = plane
    if not devices:
        return None
    threads = _host_threads(host) if host is not None else []
    windows = [(s, e) for evs, _ in threads for s, e, n in evs
               if n == WINDOW]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(s for evs in devices for s, _, _ in evs)
        hi = max(e for evs in devices for _, e, _ in evs)

    n = len(devices)
    busy = kernel = 0.0
    ops: dict[str, float] = defaultdict(float)
    samples, weights = [], []
    for evs in devices:
        spans = []
        for s, e, name in evs:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            spans.append((s, e))
            ops[op_label(name)] += (e - s) / 1e9 / n
            if is_pallas_kernel(name):
                kernel += (e - s) / 1e9 / n
        busy += union_s(spans) / 1e9 / n
        for s, e in _gaps(spans, lo, hi):
            k = max(1, math.ceil((e - s) / 1e9 / IDLE_SAMPLE_S))
            step = (e - s) / k
            samples += [s + (i + 0.5) * step for i in range(k)]
            weights += [step / 1e9 / n] * k
    order = sorted(range(len(samples)), key=samples.__getitem__)
    labels = _name_points(threads, [samples[i] for i in order])
    idle: dict[str, float] = defaultdict(float)
    for i, label in zip(order, labels):
        idle[label] += weights[i]
    return DeviceTrace(window_s=(hi - lo) / 1e9, busy_s=busy, ops_s=dict(ops),
                       kernel_s=kernel, idle_s=dict(idle), chips=n)


def reduce_dir(log_dir: str) -> DeviceTrace | None:
    """Reduce the one trace ``jax.profiler`` wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"{log_dir}: expected one .xplane.pb, found "
                         f"{len(paths)}")
    return reduce_file(paths[0])
