"""The loop modules: where a scan is compared, the open loop's
arrivals, and the control read through each cell's own loop."""

import json

import numpy as np
import pytest

from chipbench import spec
from chipbench.tests.conftest import CELLS

closed = spec.loop_module("closed")
open_loop = spec.loop_module("open")


@pytest.mark.parametrize("rows,trees", [(11_000_000, 10), (285_000, 500)])
def test_scan_sample_holds_every_tile_boundary(rows, trees):
    idx = closed.sample_rows(rows, trees, 2**31 + 7)
    assert idx.size == closed.sample_size(rows, trees) < rows
    assert np.all(np.diff(idx) > 0) and idx[-1] < rows
    tile = closed.TILE_ROWS
    starts = np.arange(0, rows, tile)
    assert np.isin(starts, idx).all()
    assert np.isin(np.minimum(starts + tile, rows) - 1, idx).all()
    assert np.isin(np.arange(rows - rows % tile - tile, rows), idx).all()
    np.testing.assert_array_equal(idx, closed.sample_rows(rows, trees,
                                                          2**31 + 7))
    assert not np.array_equal(idx, closed.sample_rows(rows, trees, 3))


def test_small_table_is_compared_whole():
    np.testing.assert_array_equal(closed.sample_rows(3000, 24, 1),
                                  np.arange(3000))


def test_arrival_gaps_are_one_set_in_seeded_orders():
    a = open_loop.arrival_gaps(8000.0, 4000, 1)
    b = open_loop.arrival_gaps(8000.0, 4000, 2**31 + 9)
    np.testing.assert_allclose(np.sort(a), np.sort(b))
    assert not np.array_equal(a, b)
    assert abs(a.mean() * 8000.0 - 1) < 1e-2


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny, capsys, cell):
    from chipbench import control
    assert control.main(["--workload", cell, "--seeds", "4,5",
                         "--seconds", "0.5"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["seed"] for x in lines] == [4, 5]
    assert not any(x["control_passes"] for x in lines)
