"""Every cell driven through the harness at a tiny size, with the look
for a chip set aside: the result line's schema, and the metrics it
reports found by their file names."""

import json

import pytest

from chipbench import spec
from chipbench.tests.conftest import CELLS, run_tiny


def _names(metrics):
    return {m["name"]: m["unit"] for m in metrics}


@pytest.mark.parametrize("cell", CELLS)
def test_run_prints_the_contract_line(tiny, cell):
    res = run_tiny(cell)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert json.loads(json.dumps(res)) == res
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(res["device"])
    want = _names(spec.cell_metrics(spec.load_benchmark(), cell, False))
    assert {k: v["unit"] for k, v in res["metrics"].items()} == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics(tiny, cell):
    res = run_tiny(cell, traced=True)
    assert res["correct"] is True
    per_layer = _names(spec.cell_metrics(spec.load_benchmark(), cell, True))
    assert res["metrics"] and set(res["metrics"]) <= set(per_layer)
    # off the chip the trace holds no TPU plane: no device metric, no
    # breakdown, no busy time (a reader with nothing to read is silent)
    assert "breakdown" not in res and "busy_s" not in res["device"]
    assert not any(k.startswith(("device_idle_pct", "kernel_ms"))
                   for k in res["metrics"])
