"""The forest kernels compile for a TPU v5e, ahead of time, without a chip.

The TPU compiler is installed with JAX and compiles for a described
``v5e:2x2`` topology: what Mosaic refuses (an unaligned reshape, an
unsupported cast, a tile over the scoped VMEM) is refused here, at no chip
time.  Interpret-mode tests cannot see any of that.  Shapes are the real
ones: depth 8 (I = 255, L = 256), 500 trees, higgs (F = 28), year
(F = 90) and the compact CSR tile (F_used = 255), at the block sizes the
wrappers pick for a 1024-row scan batch and for the serve plane's
8-row bucket.

The topology is described inside a module-scoped fixture, never while the
module is imported, so every pytest-xdist worker collects the same tests
and only the worker running this file loads the TPU library.  The
persistent compilation cache is off around these compiles: an entry
written for a described chip cannot be read back without one.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.forest import make_forest
from repro.kernels import ops
from repro.kernels.ops import FUSED_KERNEL_ALGORITHMS, KERNEL_ALGORITHMS

BASES = ("predicated", "hummingbird", "quickscorer")
DEPTH, TREES, BATCH = 8, 500, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    saved_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", saved_cache)
        cc.reset_cache()
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _forest(T, F, seed=0):
    I, L = (1 << DEPTH) - 1, 1 << DEPTH
    r = np.random.default_rng(seed)
    return make_forest(r.integers(0, F, (T, I)).astype(np.int32),
                       r.normal(size=(T, I)).astype(np.float32),
                       r.normal(size=(T, L)).astype(np.float32),
                       default_left=r.random((T, I)) < 0.5, n_features=F)


def _compile(fn, forest, B, F, sharding, **kw):
    """AOT-compile ``fn(forest, x, interpret=False, **kw)`` for the chip
    from shapes alone (nothing can be placed on a described device)."""
    def run(x, arrays):
        return fn(dataclasses.replace(forest, **arrays), x,
                  interpret=False, **kw)

    arrays = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)
              for k, v in forest.arrays().items()}
    x = jax.ShapeDtypeStruct((B, F), jnp.float32, sharding=sharding)
    return jax.jit(run).lower(x, arrays).compile()


@pytest.mark.parametrize("F", [28, 90, 255], ids=["higgs", "year", "csr"])
@pytest.mark.parametrize("base", BASES)
def test_fused_kernel_compiles_for_v5e(one_chip, base, F):
    forest = _forest(TREES, F)
    compiled = _compile(FUSED_KERNEL_ALGORITHMS[base + "_pallas_fused"],
                        forest, BATCH, F, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    # the blocks the wrapper picked are the full-size ones
    assert ops._blocks(forest, BATCH, None, None, fused=True) == (128, 32)


@pytest.mark.parametrize("base", BASES)
def test_fused_kernel_bf16_tree_tiles_compile_for_v5e(one_chip, base):
    compiled = _compile(FUSED_KERNEL_ALGORITHMS[base + "_pallas_fused"],
                        _forest(TREES, 28), BATCH, 28, one_chip,
                        tree_dtype=jnp.bfloat16)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("base", BASES)
def test_unfused_kernel_compiles_for_v5e(one_chip, base):
    compiled = _compile(KERNEL_ALGORITHMS[base + "_pallas"],
                        _forest(TREES, 28), BATCH, 28, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("base", BASES)
def test_serve_bucket_compiles_for_v5e(one_chip, base):
    """The serve plane's smallest bucket (8 rows) on a 10-tree tenant:
    both blocks are whole (padded) array dims."""
    forest = _forest(10, 28)
    compiled = _compile(FUSED_KERNEL_ALGORITHMS[base + "_pallas_fused"],
                        forest, 8, 28, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    assert ops._blocks(forest, 8, None, None, fused=True) == (8, 10)


def test_compiled_path_rejects_illegal_block():
    """A block the TPU compiler would refuse is a clear error in the
    wrapper, before any lowering — no fallback to the interpreter."""
    forest = _forest(40, 28)
    x = jnp.zeros((64, 28), jnp.float32)
    with pytest.raises(ValueError, match="not a TPU block"):
        FUSED_KERNEL_ALGORITHMS["predicated_pallas_fused"](
            forest, x, block_b=12, block_t=8, interpret=False)


def test_interpret_refused_on_tpu(monkeypatch):
    """On a TPU backend no kernel path may run the interpreter."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    forest = _forest(8, 28)
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        FUSED_KERNEL_ALGORITHMS["predicated_pallas_fused"](
            forest, jnp.zeros((8, 28), jnp.float32), interpret=True)
    assert ops._resolve_interpret(None) is False
