"""Finds a cell's pieces by the names ``BENCHMARK.json`` gives them.

    configs   the file named by the configuration's ``file`` entry
    traffic   ``chipbench/traffic/<name>.json``, a mix of parameters
    loops     ``chipbench/loops/<loop>.py``, the module that drives the
              mixes whose ``loop`` key names it (``chipbench.loads``)
    metrics   ``chipbench/metrics/<name>.py``, a module with
              ``read(run) -> float | None``; a metric split by the cells
              that report it, ``<base>.<part>``, may instead share the
              reader ``chipbench/metrics/<base>.py``

A new cell, mix, kind of traffic or metric is therefore a new file and a
new entry; no code here changes.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType

__all__ = ["HERE", "ROOT", "load_benchmark", "workload", "config",
           "traffic", "loop_module", "metric_reader", "cell_metrics"]

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _checked(name: str) -> str:
    if not _NAME.match(name) or ".." in name:
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def _entry(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; have "
                   f"{sorted(e['name'] for e in entries)}")


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    return _entry(bench["workloads"], _checked(name), "workload")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    entry = _entry(bench["configs"], _checked(name), "configuration")
    with open(root / entry["file"]) as f:
        return json.load(f)


def traffic(name: str) -> dict:
    with open(HERE / "traffic" / f"{_checked(name)}.json") as f:
        return json.load(f)


@functools.cache
def _module(kind: str, name: str) -> ModuleType:
    path = HERE / kind / f"{name}.py"
    qualified = f"chipbench.{kind}." + name.replace(".", "_")
    spec = importlib.util.spec_from_file_location(qualified, path)
    module = importlib.util.module_from_spec(spec)
    # registered before it runs, as an import would: a dataclass looks
    # its module up by name
    sys.modules[qualified] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[qualified]
        raise
    return module


def loop_module(name: str) -> ModuleType:
    return _module("loops", _checked(name))


def metric_reader(name: str) -> ModuleType:
    name = _checked(name)
    base = name.rpartition(".")[0]
    if base and not (HERE / "metrics" / f"{name}.py").exists():
        name = base
    return _module("metrics", name)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_metrics(bench: dict, cell: str, traced: bool) -> list[dict]:
    """The metrics a run of ``cell`` prints: its end-to-end metrics, or
    with ``traced`` its per-layer metrics.  A metric without a
    ``workloads`` list belongs to every cell that reports the metric it
    moves (per-layer) or to every cell (end-to-end)."""
    e2e = [m for m in bench["end_to_end"] if _reports(m, cell)]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]
