"""The reduction of a profiler trace, on a trace recorded on one TPU v5e
(``data/higgs-xgb10.scan.xplane.pb``: two queries of the
``higgs-xgb10.scan`` cell, ``--trace 1 --seconds 2``) and on made-up
intervals."""

from pathlib import Path

import pytest

from chipbench import devtrace

TRACE = Path(__file__).parent / "data" / "higgs-xgb10.scan.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return devtrace.reduce_file(str(TRACE))


def test_recorded_trace_busy_and_window(reduced):
    # the numbers the run that recorded the trace printed
    assert reduced.chips == 1
    assert reduced.window_s == pytest.approx(2.199623763, rel=1e-9)
    assert reduced.busy_s == pytest.approx(2.052472895, rel=1e-9)
    # overlapping operations count once: busy is at most their sum
    assert reduced.busy_s <= sum(reduced.ops_s.values()) + 1e-9


def test_recorded_trace_kernel_time_by_name(reduced):
    assert reduced.kernel_s == pytest.approx(1.99854134, rel=1e-9)
    assert reduced.ops_s["fused (custom-call)"] == \
        pytest.approx(reduced.kernel_s)
    assert reduced.kernel_s <= reduced.busy_s
    assert {"copy (copy)", "divide_reduce_fusion (fusion)"} \
        <= set(reduced.ops_s)


def test_recorded_trace_idle_named_by_annotations(reduced):
    idle = reduced.idle_s
    assert sum(idle.values()) == \
        pytest.approx(reduced.window_s - reduced.busy_s, rel=1e-6)
    # every idle stretch lies inside the window, under a bench annotation
    assert all(label.startswith(("bench.query / ", "bench.wait / "))
               for label in idle)
    top = reduced.breakdown()
    assert len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10
    assert top["device_ops"][0][0] == "fused (custom-call)"


def test_union_and_gaps():
    spans = [(0, 2), (1, 3), (5, 6), (5.5, 5.7)]
    assert devtrace.union_s(spans) == 4
    assert devtrace._gaps(spans, -1, 8) == [(-1, 0), (3, 5), (6, 8)]
    assert devtrace._gaps(spans, 0, 3) == []


def test_op_label():
    assert devtrace.op_label(
        '%fused.21 = f32[285696,1]{1,0:T(8,128)} custom-call(f32[285696,28]'
        '{1,0} %copy.16), custom_call_target="tpu_custom_call"') \
        == "fused (custom-call)"
    assert devtrace.op_label(
        "%copy.2 = f32[8,28]{1,0} copy(f32[8,28]{0,1} %x)") == "copy (copy)"
    assert devtrace.is_pallas_kernel('x custom_call_target="tpu_custom_call"')


def test_idle_named_by_python_thread_first():
    python = ([(0, 100, "bench.query"), (10, 20, "PjitFunction(f)")], True)
    runtime = ([(0, 50, "ReadSyncFlag"), (12, 14, "Linearize")], False)
    labels = devtrace._name_points([python, runtime], [15, 30, 70])
    assert labels == ["bench.query / PjitFunction(f)",
                      "bench.query / ReadSyncFlag",
                      "bench.query / no host event"]
