"""``BENCHMARK.json`` keeps the benchmark's contract, and every piece it
names is found by its file name."""

import re

import pytest

from chipbench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert list(BENCH) == ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_keys():
    names = set()
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        for e in BENCH[group]:
            assert set(e) == keys
            assert NAME.match(e["name"]) and e["name"] not in names
            names.add(e["name"])
            assert 1 <= len(e["why"]) <= 200
    for e in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(e["name"]) and e["name"] not in names
        names.add(e["name"])
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in BENCH["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    e2e = {e["name"] for e in BENCH["end_to_end"]}
    for e in BENCH["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert e["moves"] in e2e


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    w = spec.workload(BENCH, cell)
    cfg = spec.config(BENCH, w["config"])
    assert cfg["name"] == w["config"] and cfg["reduced"] == []
    loop = spec.loop_module(spec.traffic(w["traffic"])["loop"])
    assert all(callable(getattr(loop, f))
               for f in ("build", "readings", "positions"))
    e2e = spec.cell_metrics(BENCH, cell, False)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    per_layer = spec.cell_metrics(BENCH, cell, True)
    assert per_layer
    for m in e2e + per_layer:
        assert callable(spec.metric_reader(m["name"]).read)


def test_unknown_and_unsafe_names_are_refused():
    with pytest.raises(KeyError):
        spec.workload(BENCH, "no-such.cell")
    with pytest.raises(ValueError):
        spec.traffic("../BENCHMARK")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(FileNotFoundError):
        spec.loop_module("no_such_loop")
    with pytest.raises(ValueError):
        spec.loop_module("../run")


def test_split_metrics_share_their_base_reader():
    for part in ("large_table", "serve"):
        reader = spec.metric_reader(f"device_idle_pct.{part}")
        assert reader.__file__.endswith("metrics/device_idle_pct.py")
    # a metric with a reader of its own keeps it
    assert spec.metric_reader("serve_queue_ms.p99").__file__.endswith(
        "metrics/serve_queue_ms.p99.py")


def test_per_layer_metric_without_workloads_follows_what_it_moves():
    bench = {"end_to_end": [
                {"name": "rate", "workloads": ["a.scan"]},
                {"name": "tail", "workloads": ["a.serve"]},
                {"name": "setup_s"}],
             "per_layer": [
                {"name": "k", "moves": "rate"},
                {"name": "q", "moves": "tail", "workloads": ["a.serve"]},
                {"name": "s", "moves": "setup_s"}]}
    names = {c: [m["name"] for m in spec.cell_metrics(bench, c, True)]
             for c in ("a.scan", "a.serve")}
    assert names == {"a.scan": ["k", "s"], "a.serve": ["q", "s"]}
    assert [m["name"] for m in spec.cell_metrics(bench, "a.scan", False)] \
        == ["rate", "setup_s"]
