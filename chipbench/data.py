"""Seeded inputs: the stored table, the forest and the rows clients send.

Everything is drawn from ``--seed`` so that one seed gives the same
inputs in every process.  The table and the forest are made on the
device, each in one jitted call, in the dtype they are served in; the
rows a serving client sends are host arrays, as a client holds them.

Shapes follow the paper's datasets (normal f32 features, as the engine's
own ``db.loader.synth_dataset`` draws them) and its forests: complete
xgboost trees with features uniform over the columns, N(0, 1)
thresholds, 0.1 * N(0, 1) leaves and a default direction for missing
values drawn with p = 0.5.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["TABLE", "FOREST", "REQUESTS", "seed_key", "table",
           "forest_arrays", "request_pool"]

# independent streams of one seed
TABLE, FOREST, REQUESTS = 1, 2, 3


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key for ``stream`` of ``seed``.  ``jax.random.key`` keeps
    32 bits of a Python int, so the bits above are folded in: seeds that
    differ only above bit 31 still give different keys."""
    s = int(seed) % (1 << 62)
    key = jax.random.key(s & 0x7FFFFFFF)
    key = jax.random.fold_in(key, s >> 31)
    return jax.random.fold_in(key, stream)


@partial(jax.jit, static_argnums=(1, 2))
def _table(key, rows: int, features: int):
    return jax.random.normal(key, (rows, features), jnp.float32)


def table(seed: int, rows: int, features: int) -> jax.Array:
    """The stored table, [rows, features] f32 on the device."""
    return _table(seed_key(seed, TABLE), rows, features)


@partial(jax.jit, static_argnums=(1, 2, 3))
def _forest(key, trees: int, depth: int, features: int):
    internal, leaves = (1 << depth) - 1, 1 << depth
    kf, kt, kl, kd = jax.random.split(key, 4)
    return {
        "feature": jax.random.randint(kf, (trees, internal), 0, features,
                                      jnp.int32),
        "threshold": jax.random.normal(kt, (trees, internal), jnp.float32),
        "leaf_value": 0.1 * jax.random.normal(kl, (trees, leaves),
                                              jnp.float32),
        "default_left": jax.random.bernoulli(kd, 0.5, (trees, internal)),
    }


def forest_arrays(seed: int, trees: int, depth: int,
                  features: int) -> dict[str, jax.Array]:
    """A complete forest as four device arrays: ``feature`` [T, I] int32,
    ``threshold`` [T, I] f32, ``default_left`` [T, I] bool and
    ``leaf_value`` [T, L] f32, nodes in heap order (children of node i
    at 2i + 1 and 2i + 2)."""
    return _forest(seed_key(seed, FOREST), trees, depth, features)


def request_pool(seed: int, rows: int, features: int) -> np.ndarray:
    """The rows serving clients send, [rows, features] f32 on the host;
    request k sends row k modulo ``rows``."""
    rng = np.random.default_rng([int(seed) % (1 << 62), REQUESTS])
    return rng.standard_normal((rows, features), dtype=np.float32)
