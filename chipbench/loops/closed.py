"""The closed loop (scan): one client scores the whole stored table with
``ForestQueryEngine.infer``, waits for the predictions, and sends the
next query at once.  A query still running when the window's seconds are
up finishes and counts; the window ends with it.

The mix may set ``clients`` (only 1 is driven).  The table and the
forest are made on the device from the seed, and the table is put on the
configuration's tier.

The comparison reads every query of the window at the same rows: the
first and the last row of every tile of ``TILE_ROWS`` rows (every batch,
kernel block and page of the scan starts and ends on such a boundary),
the table's ragged tail, and rows drawn from the seed up to
``sample_size``.  A small table is compared whole.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import check, data, loads, reference

#: at most this many rows of a scanned table are compared in every query
SAMPLE_ROWS = 1 << 18

#: and at most this many row-tree visits: the reference's gathers take
#: about 0.35 us a visit on a TPU v5e, so this keeps it near 3 s
SAMPLE_ROW_TREES = 1 << 23

#: rows of the tiles whose first and last rows are always compared: the
#: fused kernel's row blocks (at most 128 rows) and the store's pages
#: (1,024 rows) start on a multiple of it
TILE_ROWS = 128


@dataclasses.dataclass
class Query:
    """One query; times in seconds from the window's start."""

    start_s: float
    end_s: float
    rows: int                      # real rows scored and delivered
    predictions: jax.Array         # [rows]
    scan: object                   # the engine's ScanStats


class ClosedScan:
    """One client, whole-table scoring queries back to back."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro.core.reuse import ModelReuseCache
        from repro.db.query import ForestQueryEngine
        from repro.db.store import TensorBlockStore
        if mix.get("clients", 1) != 1:
            raise ValueError("the closed loop runs one client")
        ds = cfg["dataset"]
        self.dataset = ds["name"]
        self.store = TensorBlockStore()
        self.store.put(self.dataset,
                       data.table(seed, ds["rows"], ds["features"]),
                       tier=cfg["tier"])
        self.forest = loads.program_forest(seed, cfg)
        self.engine = ForestQueryEngine(self.store,
                                        reuse_cache=ModelReuseCache(),
                                        plan_cache=ModelReuseCache())
        self.kw = dict(algorithm=cfg["algorithm"], plan=cfg["plans"]["scan"])

    def _query(self, annotate: bool):
        with loads.annotation("bench.query", annotate):
            res = self.engine.infer(self.dataset, self.forest, **self.kw)
        with loads.annotation("bench.wait", annotate):
            jax.block_until_ready(res.predictions)
        return res

    def warm(self) -> None:
        """One query: it compiles every batch shape of the scan and fills
        the model and plan caches that every later query hits."""
        self._query(False)

    def measure(self, seconds: float, annotate: bool) -> loads.Window:
        before = loads.counter_values()
        queries = []
        t0 = time.perf_counter()
        while True:
            start = time.perf_counter() - t0
            res = self._query(annotate)
            end = time.perf_counter() - t0
            queries.append(Query(start, end, int(res.predictions.shape[0]),
                                 res.predictions, res.scan))
            if end >= seconds:
                break
        return loads.Window(seconds=end, attempted=len(queries), failed=0,
                            counters=loads.counter_deltas(before),
                            queries=queries)

    def close(self) -> None:
        self.store = self.engine = self.forest = None


build = ClosedScan


def sample_size(rows: int, trees: int) -> int:
    return min(rows, SAMPLE_ROWS, max(1, SAMPLE_ROW_TREES // trees))


def boundary_rows(rows: int) -> np.ndarray:
    """The first and last row of every ``TILE_ROWS`` tile and every row
    of the last two tiles (the ragged tail and the full tile before it)."""
    starts = np.arange(0, rows, TILE_ROWS)
    tail = np.arange(max(0, (len(starts) - 2) * TILE_ROWS), rows)
    return np.unique(np.concatenate([starts, np.minimum(starts + TILE_ROWS,
                                                        rows) - 1, tail]))


def sample_rows(rows: int, trees: int, seed: int) -> np.ndarray:
    """The sorted rows of a scanned table that are compared: the
    boundary rows, and rows drawn from the seed up to ``sample_size``."""
    n = sample_size(rows, trees)
    if n == rows:
        return np.arange(rows)
    fixed = boundary_rows(rows)
    rest = np.setdiff1d(np.arange(rows), fixed, assume_unique=True)
    rng = np.random.default_rng([int(seed) % (1 << 62), rows])
    drawn = rng.choice(rest, max(0, n - fixed.size), replace=False)
    return np.sort(np.concatenate([fixed, drawn]))


def readings(cfg: dict, mix: dict, seed: int, window: loads.Window, *,
             narrow: bool = False) -> dict[str, float]:
    ds = cfg["dataset"]
    idx = jnp.asarray(sample_rows(ds["rows"], cfg["forest"]["trees"], seed))
    x = data.table(seed, ds["rows"], ds["features"])[idx]
    forest = check.forest(cfg, seed)
    want = reference.predict(forest, x)
    control = reference.predict(forest, x, narrow=True) if narrow else None
    gap, nonfinite, missing = 0.0, 0, 0
    for q in window.queries:
        if q.predictions is None or q.predictions.shape != (ds["rows"],):
            missing += 1
            continue
        g, n = check.gap(control if narrow else q.predictions[idx], want)
        gap, nonfinite = max(gap, g), nonfinite + n
    return {"max_prob_gap": gap, "nonfinite": nonfinite, "missing": missing}


def positions(cfg: dict, mix: dict, seconds: float) -> loads.Window:
    rows = cfg["dataset"]["rows"]
    q = Query(0.0, seconds, rows, np.zeros(rows, np.float32), None)
    return loads.Window(seconds=seconds, attempted=1, failed=0, counters={},
                        queries=[q])
