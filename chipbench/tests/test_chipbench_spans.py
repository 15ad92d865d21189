"""The readers of the engine's phase spans, fed made-up span lists: each
value, the tick's four parts adding up to ``serve_tick_ms``, and silence
where the program records no such span; then the same metrics read from
traced runs of the tiny cells."""

import dataclasses
import itertools

import pytest

from chipbench import spec
from chipbench.tests.conftest import CELLS, run_tiny

_ids = itertools.count(1)


@dataclasses.dataclass
class FakeSpan:
    name: str
    duration_s: float
    parent_id: int | None = None
    attrs: dict = dataclasses.field(default_factory=dict)
    span_id: int = dataclasses.field(default_factory=lambda: next(_ids))


@dataclasses.dataclass
class FakeRun:
    spans: list


def tick(total, upload, dispatch, wait, rows=3, bucket=8):
    """A ``serve.tick`` span and its phases, nested as the engine nests
    them; children come first, as spans close."""
    t = FakeSpan("serve.tick", total, attrs={"rows": rows, "bucket": bucket})
    rows_span = FakeSpan("query.infer_rows", upload + dispatch, t.span_id)
    return [FakeSpan("serve.coalesce", 1e-4, t.span_id),
            FakeSpan("query.rows_upload", upload, rows_span.span_id),
            FakeSpan("query.rows_dispatch", dispatch, rows_span.span_id),
            rows_span,
            FakeSpan("serve.result_wait", wait, t.span_id),
            FakeSpan("serve.handback", 2e-4, t.span_id), t]


def read(metric, spans):
    return spec.metric_reader(metric).read(FakeRun(spans))


@pytest.fixture
def window():
    spans = tick(4e-3, 1e-3, 0.5e-3, 1e-3, rows=3, bucket=8) \
        + tick(6e-3, 2e-3, 1.5e-3, 1e-3, rows=30, bucket=32)
    # phases outside a recorded tick: the window opened mid-tick
    spans += [FakeSpan("query.rows_upload", 9.0),
              FakeSpan("serve.result_wait", 9.0, parent_id=10**9)]
    return spans


def test_tick_phases_per_tick(window):
    assert read("serve_upload_ms", window) == pytest.approx(1.5)
    assert read("serve_dispatch_ms", window) == pytest.approx(1.0)
    assert read("serve_result_wait_ms", window) == pytest.approx(1.0)
    assert read("serve_tick_self_ms", window) == pytest.approx(1.5)


def test_tick_parts_add_up_to_the_tick(window):
    parts = sum(read(m, window) for m in (
        "serve_upload_ms", "serve_dispatch_ms", "serve_result_wait_ms",
        "serve_tick_self_ms"))
    assert parts == pytest.approx(read("serve_tick_ms", window), rel=1e-12)


def test_padding_share(window):
    # (8 - 3) + (32 - 30) padding rows of 8 + 32
    assert read("serve_padding_pct", window) == pytest.approx(7 / 40 * 100)


def test_scan_result_upload_mean_per_query():
    spans = [FakeSpan("query.result_upload", 0.02),
             FakeSpan("query.result_upload", 0.04),
             FakeSpan("scan.drain_join", 0.5)]
    for regime in ("large_table", "large_model"):
        assert read(f"scan_result_upload_ms.{regime}", spans) == \
            pytest.approx(30.0)


@pytest.mark.parametrize("metric", [
    "serve_upload_ms", "serve_dispatch_ms", "serve_result_wait_ms",
    "serve_tick_self_ms", "scan_result_upload_ms.large_table"])
def test_silent_without_the_phase_spans(metric):
    """A program that records ticks but none of their phases (one older
    than the phases) reports nothing, rather than a tick's whole time as
    its own."""
    old = [s for s in tick(4e-3, 1e-3, 1e-3, 1e-3)
           if s.name in ("serve.tick", "serve.coalesce", "query.infer_rows")]
    assert read(metric, old) is None
    assert read(metric, []) is None


def test_padding_silent_without_ticks():
    assert read("serve_padding_pct", []) is None


@pytest.mark.parametrize("cell", CELLS)
def test_traced_tiny_cell_reports_its_program_metrics(tiny, cell):
    """Every metric the engine itself records is in a traced line of the
    cells that list it, and a served tick's four parts add up to it."""
    res = run_tiny(cell, traced=True)
    assert res["correct"] is True
    program = {m["name"]
               for m in spec.cell_metrics(spec.load_benchmark(), cell, True)
               if m["source"] in ("program_span", "program_counter")}
    assert program <= set(res["metrics"])
    if "serve_tick_ms" in program:
        parts = sum(res["metrics"][m]["value"] for m in (
            "serve_upload_ms", "serve_dispatch_ms", "serve_result_wait_ms",
            "serve_tick_self_ms"))
        assert parts == pytest.approx(res["metrics"]["serve_tick_ms"]
                                      ["value"], rel=0.02)
