"""In-database streamed training through the tier ladder (BENCH_train.json).

The lifecycle's other half, measured honestly: train a 100-tree model ON
a stored dataset whose float32 source is >= 4x the host budget — so the
source relation lives on the DISK tier and every pass (quantile sketch,
uint8 bin ingest, per-level histogram scans) must stream page batches
through the same ``StreamingScanExecutor`` the inference plans use.  No
pass may ever hold the full matrix: every scan's peak single-batch bytes
are asserted below the source size and ``TrainResult.materialized_full_x``
must stay ``False``.

Gates (raise on violation — smoke AND full run, so a published
BENCH_train.json can never show a broken contract):

  parity            the streamed forest must be BIT-IDENTICAL to the
                    resident ``core.train.train_forest`` reference given
                    the streamed run's own sketch edges (the reference
                    reads the matrix resident — it is the checker, not
                    the streamed path);
  tiering           source tier must resolve to ``disk`` (the 256 KiB
                    ladder actually engaged);
  streaming         every executor pass: ``batches > 1``,
                    ``max_in_flight <= 2`` (double-buffer bound),
                    ``bytes_streamed > 0``, peak batch < source bytes;
  no densify        ``materialized_full_x`` is ``False`` — a silent
                    full-X fallback fails the run;
  scan count        ``num_scans == 2 + trees * (depth + 1)`` (sketch +
                    bin ingest + per-level/per-tree histogram passes).

``--smoke`` is the CI train-smoke job: 20 trees, same dataset geometry,
same gates, no JSON.  The full run trains 100 trees and writes
``BENCH_train.json`` (field contract: ``docs/training.md``).

    PYTHONPATH=src python -m benchmarks.bench_train [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from benchmarks import common as C
from repro.core.train import TrainConfig, train_forest
from repro.db.query import ForestQueryEngine
from repro.db.store import TensorBlockStore

BENCH_TRAIN_JSON = os.path.join(os.path.dirname(__file__), "..",
                                "BENCH_train.json")

ROWS = 8_192
FEATURES = 32            # 8192 x 32 f32 = 1 MiB = 4x the 256 KiB budget
PAGE_ROWS = 256
TREES = 100
SMOKE_TREES = 20
DEPTH = 3
NUM_BINS = 32
SKETCH_ROWS = 2_048      # < ROWS so the sketch actually samples
NAN_FRAC = 0.05          # exercise the MISSING bin end to end


def _dataset(seed: int = 0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(ROWS, FEATURES)).astype(np.float32)
    x[rng.random((ROWS, FEATURES)) < NAN_FRAC] = np.nan
    margin = np.where(np.isnan(x[:, 0]), 0.3, np.nan_to_num(x[:, 0])) \
        + 0.5 * np.nan_to_num(x[:, 3])
    y = (margin > 0).astype(np.float32)
    return x, y


def run(trees: int, *, device_budget: int, host_budget: int):
    x, y = _dataset()
    cfg = TrainConfig(model_type="xgboost", num_trees=trees,
                      max_depth=DEPTH, num_bins=NUM_BINS, seed=0)
    store = TensorBlockStore(default_page_rows=PAGE_ROWS,
                             device_budget_bytes=device_budget,
                             host_budget_bytes=host_budget)
    src = store.put("train-src", x, labels=y)
    engine = ForestQueryEngine(store)

    t0 = time.perf_counter()
    res = engine.train("train-src", cfg, sketch_rows=SKETCH_ROWS)
    streamed_s = time.perf_counter() - t0

    # resident reference on the SAME edges — the checker, not the path
    t0 = time.perf_counter()
    ref = train_forest(x, y, cfg, edges=res.edges)
    resident_s = time.perf_counter() - t0

    import jax
    parity = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(res.forest),
                        jax.tree_util.tree_leaves(ref)))

    bins_ds = store.get(res.bins_dataset)
    peak_batch = max(
        (s.bytes_streamed + max(s.batches - 1, 1) - 1)
        // max(s.batches, 1) for s in res.scan_stats)
    record = dict(
        rows=ROWS, features=FEATURES, page_rows=PAGE_ROWS,
        nan_frac=NAN_FRAC,
        device_budget_bytes=device_budget,
        host_budget_bytes=host_budget,
        source_nbytes=int(src.nbytes),
        source_tier=res.tier,
        storage_format=res.storage_format,
        bins_tier=bins_ds.tier,
        bins_nbytes=int(bins_ds.nbytes),
        num_trees=trees, max_depth=DEPTH, num_bins=NUM_BINS,
        sketch_rows=SKETCH_ROWS,
        sketch_rows_used=res.sketch_rows_used,
        num_scans=res.num_scans,
        batches_total=sum(s.batches for s in res.scan_stats),
        bytes_streamed_total=sum(s.bytes_streamed
                                 for s in res.scan_stats),
        peak_batch_bytes=int(peak_batch),
        max_in_flight=max(s.max_in_flight for s in res.scan_stats),
        streamed_s=round(streamed_s, 4),
        resident_s=round(resident_s, 4),
        streamed_over_resident=round(
            streamed_s / max(resident_s, 1e-9), 4),
        parity_bitwise=bool(parity),
        materialized_full_x=bool(res.materialized_full_x),
        fingerprint=res.fingerprint,
        model_name=res.model_name,
    )
    return record


def check(r, *, context: str) -> None:
    """The gates — raise on any violation."""
    if r["source_tier"] != "disk":
        raise RuntimeError(
            f"{context}: source landed on tier {r['source_tier']!r}, "
            f"not 'disk' — the {r['host_budget_bytes']}-byte ladder "
            f"never engaged ({r['source_nbytes']} source bytes)")
    if r["source_nbytes"] < 4 * r["host_budget_bytes"]:
        raise RuntimeError(
            f"{context}: dataset is only {r['source_nbytes']} bytes, "
            f"< 4x the {r['host_budget_bytes']}-byte host budget")
    if not r["parity_bitwise"]:
        raise RuntimeError(
            f"{context}: streamed forest is NOT bit-identical to the "
            f"resident reference on identical edges")
    if r["materialized_full_x"]:
        raise RuntimeError(
            f"{context}: training fell back to materializing the full "
            f"matrix (materialized_full_x=True)")
    want = 2 + r["num_trees"] * (r["max_depth"] + 1)
    if r["num_scans"] != want:
        raise RuntimeError(
            f"{context}: {r['num_scans']} executor passes, expected "
            f"{want} (sketch + bin ingest + trees*(depth+1))")
    if r["batches_total"] <= r["num_scans"]:
        raise RuntimeError(
            f"{context}: {r['batches_total']} batches over "
            f"{r['num_scans']} scans — some pass ran single-batch, "
            f"nothing streamed")
    if r["max_in_flight"] > 2:
        raise RuntimeError(
            f"{context}: {r['max_in_flight']} device page buffers in "
            f"flight — double-buffer bound broken")
    if r["bytes_streamed_total"] <= 0:
        raise RuntimeError(f"{context}: no bytes streamed")
    if r["peak_batch_bytes"] >= r["source_nbytes"]:
        raise RuntimeError(
            f"{context}: a single batch moved {r['peak_batch_bytes']} "
            f"bytes >= the {r['source_nbytes']}-byte source — that is "
            f"a full materialization, not streaming")


def write_train_json(record, path=BENCH_TRAIN_JSON):
    payload = {
        "bench": "train",
        "created_at": time.time(),
        "protocol": {
            "parity": "streamed forest bitwise == resident reference "
                      "given the streamed run's sketch edges",
            "tier_ladder": "f32 source >= 4x host budget -> disk",
        },
        "env": C.env_info(),
        "record": record,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return os.path.normpath(path)


def print_record(r) -> None:
    print(f"  trees={r['num_trees']} depth={r['max_depth']} "
          f"rows={r['rows']} features={r['features']} "
          f"tier={r['source_tier']}")
    print(f"  scans={r['num_scans']} batches={r['batches_total']} "
          f"streamed={r['bytes_streamed_total'] / 1e6:.1f}MB "
          f"peak_batch={r['peak_batch_bytes'] / 1e3:.0f}KB "
          f"in_flight<={r['max_in_flight']}")
    print(f"  streamed={r['streamed_s']:.2f}s "
          f"resident={r['resident_s']:.2f}s "
          f"({r['streamed_over_resident']:.2f}x)  "
          f"parity={'BITWISE' if r['parity_bitwise'] else 'BROKEN'}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: 20 trees, full tier ladder + parity "
                         "assertions; no JSON")
    ap.add_argument("--device-budget-bytes", type=int, default=262_144)
    ap.add_argument("--host-budget-bytes", type=int, default=262_144)
    args = ap.parse_args()
    trees = SMOKE_TREES if args.smoke else TREES
    record = run(trees, device_budget=args.device_budget_bytes,
                 host_budget=args.host_budget_bytes)
    print_record(record)
    check(record, context="train-smoke" if args.smoke else "bench_train")
    if args.smoke:
        print(f"# train-smoke ok: {trees} trees streamed off "
              f"{record['source_tier']} bit-identical to resident, "
              f"{record['num_scans']} scans, no full-X materialization")
        return
    path = write_train_json(record)
    print(f"# train trajectory -> {path}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
