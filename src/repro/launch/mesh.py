"""Production meshes (DESIGN.md §5).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and smoke tests / benches must keep seeing 1 device.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh", "V5E", "TPU_PEAKS"]


# TPU v5e hardware constants used by the roofline (per chip; Google Cloud
# documentation, "TPU v5e").
V5E = {
    "peak_flops_bf16": 197e12,     # FLOP/s
    "hbm_bandwidth": 819e9,        # B/s
    "ici_bandwidth": 50e9,         # B/s per link
    "hbm_bytes": 16 * 1024**3,
}

#: published per-chip peaks keyed by ``jax.Device.device_kind``
TPU_PEAKS = {"TPU v5 lite": V5E}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests)."""
    n = len(jax.devices())
    assert data * model <= n, f"need {data * model} devices, have {n}"
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[: data * model])
