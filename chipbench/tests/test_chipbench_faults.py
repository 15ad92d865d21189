"""The comparison fails a run whose timed path is broken underneath:
an answer altered where the kernel produces it, and half of each batch
left out."""

import jax.numpy as jnp
import pytest

from chipbench.tests.conftest import CELLS, run_tiny

FUSED = "predicated_pallas_fused"


def _altered(kernel):
    def broken(forest, x, **kw):
        return kernel(forest, x, **kw).at[0].add(0.05)
    return broken


def _half_left_out(kernel):
    def broken(forest, x, **kw):
        out = kernel(forest, x, **kw)
        n = out.shape[0]
        return jnp.where(jnp.arange(n) < n // 2, 0.0, out)
    return broken


@pytest.mark.parametrize("fault", [_altered, _half_left_out])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_kernel_is_not_correct(tiny, monkeypatch, cell, fault):
    from repro.kernels import ops
    monkeypatch.setitem(ops.FUSED_KERNEL_ALGORITHMS, FUSED,
                        fault(ops.FUSED_KERNEL_ALGORITHMS[FUSED]))
    res = run_tiny(cell)
    assert res["correct"] is False
    assert res["checks"]["max_prob_gap"]["value"] \
        > res["checks"]["max_prob_gap"]["limit"]


@pytest.mark.parametrize("cell", [c for c in CELLS if c.endswith(".scan")])
def test_sampled_scan_catches_a_fault_at_each_call_start(tiny, monkeypatch,
                                                         cell):
    """With the comparison reading a sample, not the whole table, an
    answer altered at the start of every kernel call still fails: the
    sample always holds the first and last row of every tile."""
    from repro.kernels import ops
    from chipbench import spec
    monkeypatch.setattr(spec.loop_module("closed"), "SAMPLE_ROWS", 32)
    monkeypatch.setitem(ops.FUSED_KERNEL_ALGORITHMS, FUSED,
                        _altered(ops.FUSED_KERNEL_ALGORITHMS[FUSED]))
    res = run_tiny(cell)
    assert res["correct"] is False
