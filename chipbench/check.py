"""The comparison that decides ``correct``.

What the window's own calls returned is compared with the plain f32
reference (``chipbench.reference``) on inputs regenerated from the seed.
Each loop module (``chipbench/loops/<loop>.py``) says where it compares
and reads the numbers; the limits are here:

``max_prob_gap``  the widest gap between a returned probability and the
                  reference's, over every compared prediction
``nonfinite``     returned predictions that are NaN or infinite
``missing``       queries or requests that returned nothing, or the
                  wrong number of rows

The limit of ``max_prob_gap`` lies between the engine's own readings on
a dozen seeds and the readings of the control, the reference computed
from bfloat16 inputs (``PERF.md`` gives both).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import data, loads

__all__ = ["LIMITS", "forest", "gap", "readings", "verdict"]

LIMITS = {"max_prob_gap": 1e-3, "nonfinite": 0, "missing": 0}


def forest(cfg: dict, seed: int) -> dict:
    """The configuration's forest from the seed, as the reference reads
    it."""
    f = cfg["forest"]
    return data.forest_arrays(seed, f["trees"], f["depth"],
                              cfg["dataset"]["features"])


def gap(got: jax.Array, want: jax.Array) -> tuple[float, int]:
    """The widest gap over the finite predictions of ``got``, and how
    many are not finite."""
    bad = ~jnp.isfinite(got)
    widest = jnp.max(jnp.where(bad, 0.0, jnp.abs(got - want)))
    return float(widest), int(jnp.sum(bad))


def readings(cfg: dict, mix: dict, seed: int, window, *,
             narrow: bool = False) -> dict[str, float]:
    """The compared numbers of one run.  ``narrow`` puts the control in
    the engine's place: the bfloat16 reference's predictions at the same
    positions."""
    return loads.loop(mix).readings(cfg, mix, seed, window, narrow=narrow)


def verdict(values: dict[str, float]) -> tuple[bool, dict]:
    """``correct`` and each number beside its limit."""
    checks = {k: {"value": values[k], "limit": LIMITS[k]} for k in LIMITS}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
