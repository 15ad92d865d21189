"""Cells cut to a size the CPU holds: a few thousand rows, a few dozen
trees, a few hundred requests a second.  The runs here exercise the
harness's control flow and its comparison; they measure nothing."""

import copy

import pytest

from chipbench import spec

#: what a run reports as its device off the chip
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture
def tiny(monkeypatch):
    config, traffic = spec.config, spec.traffic

    def small_config(bench, name, root=spec.ROOT):
        c = copy.deepcopy(config(bench, name, root))
        c["dataset"]["rows"] = 3000
        c["forest"]["trees"] = min(c["forest"]["trees"], 24)
        return c

    def slow_traffic(name):
        m = dict(traffic(name))
        if m["loop"] == "open":
            m["rate_per_s"] = min(m["rate_per_s"], 200)
            m["pool_rows"] = 1024
        return m

    monkeypatch.setattr(spec, "config", small_config)
    monkeypatch.setattr(spec, "traffic", slow_traffic)


def run_tiny(cell: str, traced: bool = False, seconds: float = 1.0):
    from chipbench import run
    bench = spec.load_benchmark()
    return run.run_cell(bench, spec.workload(bench, cell), 2**31 + 5,
                        seconds, traced, dict(CPU))


CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
