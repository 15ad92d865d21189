"""The traffic generator: it reads a mix file and drives the engine
through the loop the mix names.

A traffic mix is data, ``chipbench/traffic/<mix>.json``.  Its ``loop``
key names the module that drives it, ``chipbench/loops/<loop>.py``, found
by file name as the metric readers are.  A loop module provides

``build(cfg, mix, seed)``
    the deployment ``cfg`` names, built from the seed, as an object with
    ``warm()`` (set-up: every shape the mix uses), ``measure(seconds,
    annotate) -> Window`` (the window) and ``close()``;
``readings(cfg, mix, seed, window, *, narrow=False)``
    the numbers ``chipbench.check`` compares, taken from what the
    window's own calls returned (``narrow``: the control in the engine's
    place, at the same positions);
``positions(cfg, mix, seconds)``
    a window of that length whose every output is due and answered, for
    reading the control without running the engine.

So a new mix of a kind that exists is a new data file, and a new kind of
traffic is a new loop module: no file here changes.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax

from chipbench import data, spec

__all__ = ["ANSWER_WAIT_S", "WARM_S", "Window", "annotation",
           "program_forest", "counter_values", "counter_deltas", "loop",
           "build"]

#: how long past the window's close a loop waits for answers
ANSWER_WAIT_S = 60.0

#: seconds of the mix's own traffic sent as warm-up before the window
WARM_S = 1.0


def annotation(name: str, on: bool):
    """A ``jax.profiler.TraceAnnotation`` in traced runs, else nothing."""
    return jax.profiler.TraceAnnotation(name) if on \
        else contextlib.nullcontext()


@dataclasses.dataclass
class Window:
    """What a loop's window did.  A loop reports its outputs as
    ``queries`` (closed loops) or ``requests`` (open loops); the metric
    readers read whichever is there."""

    seconds: float                 # host clock, start to end of the work
    attempted: int
    failed: int
    counters: dict                 # the engine's METRICS counter deltas
    queries: list | None = None
    requests: object | None = None


def program_forest(seed: int, cfg: dict):
    """The configuration's forest from the seed, as the engine holds it."""
    from repro.core.forest import make_forest
    f, ds = cfg["forest"], cfg["dataset"]
    a = data.forest_arrays(seed, f["trees"], f["depth"], ds["features"])
    return make_forest(a["feature"], a["threshold"], a["leaf_value"],
                       default_left=a["default_left"],
                       n_features=ds["features"],
                       model_type=f["model_type"], task=f["task"])


def counter_values() -> dict:
    from repro.obs import METRICS
    return METRICS.counter_values()


def counter_deltas(before: dict) -> dict:
    """The engine's counters that moved since ``before``."""
    now = counter_values()
    return {k: now[k] - before.get(k, 0) for k in now
            if now[k] != before.get(k, 0)}


def loop(mix: dict):
    """The module that drives ``mix``."""
    return spec.loop_module(mix["loop"])


def build(cfg: dict, mix: dict, seed: int):
    """The deployment of ``cfg`` under the loop ``mix`` names."""
    return loop(mix).build(cfg, mix, seed)
