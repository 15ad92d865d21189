#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Steps, in order: turn on the engine's compile cache in
``<checkout>/.jax_cache`` (the benchmark sets ``JAX_COMPILATION_CACHE_DIR``
to it, and the engine takes the directory the variable names); refuse any
device that is not a TPU, or fewer chips than the cell asks for (exit 2,
no result); build the cell's deployment from the seed and warm the
shapes its traffic uses (set-up); measure for ``--seconds``; read the
peak device memory; free the engine; compare what the window returned
with the plain reference; print each compared number beside its limit
on standard error and, last on standard output, one JSON line:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown",] "checks"}

``--trace 0`` reports the cell's end-to-end metrics.  ``--trace 1``
records the window with the JAX profiler and the engine's span tracer
and reports the cell's per-layer metrics, the device's busy and window
seconds, and a breakdown of device time and idle time.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

if __name__ == "__main__":
    # run as a script: import ``chipbench`` as a package from the
    # checkout (not its modules from the script's own directory) and the
    # engine from ``src``
    _root = Path(__file__).resolve().parent.parent
    sys.path[:1] = [str(_root), str(_root / "src")]

from chipbench import spec  # noqa: E402

__all__ = ["NoChip", "RunView", "check_device", "run_cell", "main"]


class NoChip(Exception):
    """The machine holds no TPU, or fewer chips than the cell asks."""


def check_device(chips: int) -> dict:
    """The device JAX found, as the result line reports it; raises
    ``NoChip`` for any other platform than a TPU or too few chips."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise NoChip(f"JAX found platform {d.platform!r} ({d.device_kind}),"
                     f" not a TPU: the benchmark measures the chip only")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip of the cell."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


class RunView:
    """What a metric reader sees of one run."""

    def __init__(self, cell, cfg, mix, setup_s, window, device_trace,
                 spans, peaks):
        self.cell, self.config, self.mix = cell, cfg, mix
        self.setup_s = setup_s
        self.window = window            # loads.Window
        self.device = device_trace      # devtrace.DeviceTrace or None
        self.spans = spans              # the engine's TRACER spans
        self.peaks = peaks              # peaks.Peaks of this chip

    def query_work(self):
        """The operations and bytes one whole-table query needs."""
        from chipbench.work import query_work
        ds, f = self.config["dataset"], self.config["forest"]
        return query_work(ds["rows"], ds["features"], f["trees"], f["depth"])


class _Recording:
    """The profiler and the engine's span tracer around the window."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None
        self.spans = []

    def __enter__(self):
        if self.on:
            import jax
            from repro.obs import TRACER
            self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # host annotations, no Python calls
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            TRACER.reset()
            TRACER.enable()
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax
            from repro.obs import TRACER
            TRACER.disable()
            self.spans = TRACER.finished()
            jax.profiler.stop_trace()

    def reduce(self):
        if not self.on:
            return None
        from chipbench import devtrace
        try:
            return devtrace.reduce_dir(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _note(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
             traced: bool, device: dict) -> dict:
    """One run of ``cell``: build, warm, measure, check.  Returns the
    result line as a dict (``checks`` last)."""
    from chipbench import check, loads
    from chipbench.peaks import peaks_for

    peaks = peaks_for(device["kind"]) if device["platform"] == "tpu" \
        else None
    cfg = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    t_build = time.perf_counter()
    system = loads.build(cfg, mix, seed)
    t_warm = time.perf_counter()
    system.warm()
    t_window = time.perf_counter()
    setup_s = t_window - T_START
    _note(f"set-up {setup_s:.3f} s: start to build {t_build - T_START:.3f}, "
          f"build {t_warm - t_build:.3f}, warm-up {t_window - t_warm:.3f}")

    recording = _Recording(traced)
    with recording:
        with loads.annotation("bench.window", traced):
            window = system.measure(seconds, traced)
    device = dict(device, memory_peak_bytes=memory_peak_bytes(cell["chips"]))
    _note(f"window {window.seconds:.3f} s, {window.attempted} attempted, "
          f"engine counters {window.counters}")
    if window.queries:
        _note("query seconds " + " ".join(
            f"{q.end_s - q.start_s:.4f}" for q in window.queries))
    system.close()
    del system
    gc.collect()

    t_check = time.perf_counter()
    values = check.readings(cfg, mix, seed, window)
    correct, checks = check.verdict(values)
    _note(f"check {time.perf_counter() - t_check:.3f} s")
    device_trace = recording.reduce()
    if device_trace is not None:
        device["busy_s"] = device_trace.busy_s
        device["window_s"] = device_trace.window_s

    view = RunView(cell, cfg, mix, setup_s, window, device_trace,
                   recording.spans, peaks)
    metrics = {}
    for m in spec.cell_metrics(bench, cell["name"], traced):
        value = spec.metric_reader(m["name"]).read(view)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": correct, "attempted": window.attempted,
              "failed": window.failed, "metrics": metrics, "device": device}
    if device_trace is not None:
        result["breakdown"] = device_trace.breakdown()
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    # the compile cache lives in the checkout, at a fixed path, whatever
    # the environment names: two checkouts measured side by side share
    # nothing, and only a checkout's first run of a cell compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(spec.ROOT / ".jax_cache")
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    # every program of the run goes into the persistent cache, however
    # quickly it compiled, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    t_import = time.perf_counter()
    device = check_device(cell["chips"])
    _note(f"start to JAX imported {t_import - T_START:.3f} s, to devices "
          f"{time.perf_counter() - T_START:.3f} s")
    result = run_cell(bench, cell, args.seed, args.seconds,
                      bool(args.trace), device)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
