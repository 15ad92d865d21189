#!/usr/bin/env python3
"""Drive the in-database forest engine once on a TPU and check its answers.

    python chip_smoke.py [--seed N] [--rows N]      # one chip
    python chip_smoke.py --chips 4                  # the mesh phase only

One process, through the entry points a user calls:

  (a) device     the first JAX device is a TPU (any other platform: exit 1)
  (b) scan       higgs (11M x 28 f32, seeded) resident on the device tier
                 of a ``TensorBlockStore``; a 500-tree depth-8 xgboost
                 forest scored by ``ForestQueryEngine.infer`` with the fused
                 predicated kernel, plan ``udf`` then ``rel+reuse`` twice
                 (the second a model- and plan-cache hit); predictions of
                 the first 65,536 rows against the plain f32 jnp reference
  (c) host tier  the same table under a 256 MiB device budget: it streams
                 through the scan executor with pinned D2H staging, and its
                 predictions are bit-identical to (b)
  (d) serve      a ``ForestServeEngine`` with a 10-tree and a 500-tree
                 tenant answers single-row requests; no re-trace after
                 warm-up, answers match the reference
  (e) train      ``engine.train`` grows 2 depth-8 trees on 1,048,576 rows;
                 the model lands in the catalog and scores a query

With ``--chips 4`` only the mesh phase runs: ``rel+reuse`` on the
(data=2, model=2) and (data=1, model=4) meshes, each compared with the
single-device result of the same process.

Every phase prints one line; any failure exits non-zero.  The last line of
standard output is the JSON verdict
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

HIGGS_ROWS, HIGGS_F = 11_000_000, 28
TREES, DEPTH = 500, 8
CHECK_ROWS = 65_536
HOST_TIER_BUDGET = 256 << 20      # device budget of phase (c)
SERVE_REQUESTS = 300              # single-row requests of phase (d)
TRAIN_ROWS = 1 << 20
FUSED = "predicated_pallas_fused"
RTOL, ATOL = 1e-5, 1e-6          # tests/test_multidevice.py


class SmokeFailure(Exception):
    """A phase produced a wrong or missing result."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def phase_device(chips: int) -> dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SmokeFailure(
            f"[a] device: JAX found platform {d.platform!r} "
            f"({d.device_kind}), not a TPU — this smoke runs on the chip only")
    check(len(devices) >= chips,
          f"[a] device: --chips {chips} but JAX sees {len(devices)}")
    log(f"[a] device: platform={d.platform} kind={d.device_kind!r} "
        f"count={len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def random_forest(T: int, F: int, seed: int):
    """A complete depth-8 xgboost forest from random arrays."""
    from repro.core.forest import make_forest
    r = np.random.default_rng(seed)
    I, L = (1 << DEPTH) - 1, 1 << DEPTH
    return make_forest(r.integers(0, F, (T, I)).astype(np.int32),
                       r.normal(size=(T, I)).astype(np.float32),
                       (0.1 * r.normal(size=(T, L))).astype(np.float32),
                       default_left=r.random((T, I)) < 0.5, n_features=F,
                       model_type="xgboost")


def reference(forest, x: np.ndarray) -> np.ndarray:
    """The plain f32 jnp predicated traversal, summed and post-processed."""
    import jax.numpy as jnp
    from repro.core.postprocess import predict_proba
    return np.asarray(predict_proba(forest, jnp.asarray(x),
                                    algorithm="predicated"))


def lowered_has_kernel(forest, F: int) -> bool:
    """Lower the fused backend the plan stages call, at a scan-batch
    shape: a compiled Mosaic kernel shows up as ``tpu_custom_call``; the
    Pallas interpreter would lower to plain HLO loops."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import FUSED_KERNEL_ALGORITHMS
    fn = FUSED_KERNEL_ALGORITHMS[FUSED]
    x = jax.ShapeDtypeStruct((1024, F), jnp.float32)
    return "tpu_custom_call" in jax.jit(
        lambda xx: fn(forest, xx)).lower(x).as_text()


def phase_scan(x: np.ndarray, forest) -> np.ndarray:
    from repro.core.reuse import ModelReuseCache
    from repro.db.query import ForestQueryEngine
    from repro.db.store import TensorBlockStore

    check(lowered_has_kernel(forest, x.shape[1]),
          "[b] scan: the fused stage lowered without a tpu_custom_call")
    store = TensorBlockStore()
    t0 = time.perf_counter()
    ds = store.put("higgs", x, tier="device")
    put_s = time.perf_counter() - t0
    check(ds.tier == "device", f"[b] scan: table landed on {ds.tier}")
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                               plan_cache=ModelReuseCache())
    t0 = time.perf_counter()
    udf = engine.infer("higgs", forest, algorithm=FUSED, plan="udf")
    udf_s = time.perf_counter() - t0
    rels = []
    for _ in range(2):
        t0 = time.perf_counter()
        rels.append((engine.infer("higgs", forest, algorithm=FUSED,
                                  plan="rel+reuse"),
                     time.perf_counter() - t0))
    (rel1, rel1_s), (rel2, rel2_s) = rels
    check(rel2.reuse_hit and rel2.plan_reuse_hit,
          f"[b] scan: second rel+reuse missed its caches "
          f"(reuse_hit={rel2.reuse_hit}, plan_reuse_hit={rel2.plan_reuse_hit})")
    got = np.asarray(udf.predictions)
    check(got.shape == (x.shape[0],) and np.isfinite(got).all(),
          f"[b] scan: udf predictions shape {got.shape} or non-finite")
    want = reference(forest, x[:CHECK_ROWS])
    err = float(np.max(np.abs(got[:CHECK_ROWS] - want)))
    check(np.allclose(got[:CHECK_ROWS], want, rtol=RTOL, atol=ATOL),
          f"[b] scan: udf vs jnp reference max |diff| {err}")
    for name, r in (("rel+reuse#1", rel1), ("rel+reuse#2", rel2)):
        p = np.asarray(r.predictions)
        check(np.allclose(p, got, rtol=RTOL, atol=ATOL),
              f"[b] scan: {name} vs udf max |diff| "
              f"{float(np.max(np.abs(p - got)))}")
    log(f"[b] scan: ok tier=device rows={x.shape[0]} trees={forest.num_trees} "
        f"batches={udf.scan.batches} n_parts={rel1.n_parts} "
        f"ref_max_abs_diff={err:.3g} reuse_hit={rel2.reuse_hit} "
        f"plan_reuse_hit={rel2.plan_reuse_hit} smoke_wall_s: put={put_s:.2f} "
        f"udf={udf_s:.2f} rel1={rel1_s:.2f} rel2={rel2_s:.2f}")
    return got


def phase_host_tier(x: np.ndarray, forest, device_preds: np.ndarray) -> None:
    from repro.core.reuse import ModelReuseCache
    from repro.db.query import ForestQueryEngine
    from repro.db.store import TensorBlockStore

    store = TensorBlockStore(device_budget_bytes=HOST_TIER_BUDGET)
    # host tier asked for, not inferred: a --rows cut may fit the budget
    ds = store.put("higgs", x, tier="host")
    check(ds.tier == "host", f"[c] host tier: table landed on {ds.tier}")
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                               plan_cache=ModelReuseCache())
    t0 = time.perf_counter()
    res = engine.infer("higgs", forest, algorithm=FUSED, plan="udf")
    wall = time.perf_counter() - t0
    sc = res.scan
    check(res.tier == "host" and sc.batches > 1 and sc.bytes_streamed > 0,
          f"[c] host tier: did not stream (tier={res.tier}, "
          f"batches={sc.batches}, bytes={sc.bytes_streamed})")
    check(sc.pinned_staging, "[c] host tier: D2H skipped pinned staging")
    check(np.array_equal(np.asarray(res.predictions), device_preds),
          "[c] host tier: predictions differ from the device-tier scan")
    log(f"[c] host tier: ok batches={sc.batches} batch_pages={sc.batch_pages} "
        f"bytes_streamed={sc.bytes_streamed} pinned_staging="
        f"{sc.pinned_staging} max_in_flight={sc.max_in_flight} "
        f"bit_identical=True smoke_wall_s={wall:.2f}")


def phase_serve(seed: int) -> None:
    from repro.obs import METRICS
    from repro.serve.forest import ForestServeEngine

    requests = SERVE_REQUESTS
    tenants = {"small": random_forest(10, HIGGS_F, seed + 1),
               "large": random_forest(TREES, HIGGS_F, seed + 2)}
    rows = np.random.default_rng(seed + 3).normal(
        size=(requests, HIGGS_F)).astype(np.float32)
    with ForestServeEngine(algorithm=FUSED) as serve:
        for name, forest in tenants.items():
            serve.register_model(name, forest)          # warms every bucket
        traces0 = METRICS.counter("plan.traces").value
        misses0 = METRICS.counter("plan.cache_misses").value
        names = list(tenants)
        reqs = [(i, names[i % 2], serve.submit(names[i % 2], rows[i]))
                for i in range(requests)]
        got = {n: {} for n in names}
        for i, name, req in reqs:
            got[name][i] = float(req.wait(60.0)[0])
        traces = METRICS.counter("plan.traces").value - traces0
        misses = METRICS.counter("plan.cache_misses").value - misses0
    check(traces == 0 and misses == 0,
          f"[d] serve: re-traced after warm-up (traces_delta={traces}, "
          f"cache_misses_delta={misses})")
    for name, forest in tenants.items():
        idx = np.array(sorted(got[name]))
        want = reference(forest, rows[idx])
        have = np.array([got[name][i] for i in idx], np.float32)
        check(np.allclose(have, want, rtol=RTOL, atol=ATOL),
              f"[d] serve: tenant {name} max |diff| "
              f"{float(np.max(np.abs(have - want)))}")
    log(f"[d] serve: ok tenants=10+500 trees requests={requests} "
        f"traces_delta={traces} cache_misses_delta={misses}")


def phase_train(x: np.ndarray, y: np.ndarray) -> None:
    from repro.core.reuse import ModelReuseCache
    from repro.core.train import TrainConfig
    from repro.db.query import ForestQueryEngine
    from repro.db.store import TensorBlockStore

    store = TensorBlockStore()
    store.put("higgs_train", x, labels=y, tier="device")
    engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                               plan_cache=ModelReuseCache())
    t0 = time.perf_counter()
    res = engine.train("higgs_train", TrainConfig(num_trees=2, max_depth=DEPTH))
    train_s = time.perf_counter() - t0
    check(res.model_name in store.model_catalog(),
          f"[e] train: {res.model_name!r} not in the model catalog")
    forest = store.get_model(res.model_name)
    check(forest.num_trees == 2 and forest.depth == DEPTH,
          f"[e] train: catalog model has {forest.num_trees} trees, "
          f"depth {forest.depth}")
    q = engine.infer("higgs_train", forest, algorithm=FUSED, plan="udf")
    got = np.asarray(q.predictions)
    want = reference(forest, x[:CHECK_ROWS])
    check(got.shape == (x.shape[0],) and np.isfinite(got).all()
          and np.allclose(got[:CHECK_ROWS], want, rtol=RTOL, atol=ATOL),
          "[e] train: catalog model's query disagrees with the reference")
    log(f"[e] train: ok rows={x.shape[0]} trees=2 depth={DEPTH} "
        f"scans={res.num_scans} model={res.model_name!r} "
        f"query_rows={got.shape[0]} smoke_wall_s={train_s:.2f}")


def phase_mesh(x: np.ndarray, forest) -> None:
    from repro.core.reuse import ModelReuseCache
    from repro.db.query import ForestQueryEngine
    from repro.db.store import TensorBlockStore
    from repro.launch.mesh import make_local_mesh

    def run(mesh):
        store = TensorBlockStore(mesh)
        store.put("higgs", x, tier="device")
        engine = ForestQueryEngine(store, reuse_cache=ModelReuseCache(),
                                   plan_cache=ModelReuseCache())
        t0 = time.perf_counter()
        res = engine.infer("higgs", forest, algorithm=FUSED,
                           plan="rel+reuse")
        return res, time.perf_counter() - t0

    single, single_s = run(None)
    want = np.asarray(single.predictions)
    for data, model in ((2, 2), (1, 4)):
        res, wall = run(make_local_mesh(data, model))
        got = np.asarray(res.predictions)
        err = float(np.max(np.abs(got - want)))
        check(res.mesh_devices == 4 and res.n_parts == model,
              f"[mesh] ({data},{model}): ran on {res.mesh_devices} devices "
              f"with {res.n_parts} tree partitions")
        check(np.allclose(got, want, rtol=RTOL, atol=ATOL),
              f"[mesh] ({data},{model}) vs single device max |diff| {err}")
        log(f"[mesh] data={data} model={model}: ok rows={x.shape[0]} "
            f"n_parts={res.n_parts} max_abs_diff={err:.3g} "
            f"bitwise={bool(np.array_equal(got, want))} "
            f"smoke_wall_s={wall:.2f} (single device {single_s:.2f})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=HIGGS_ROWS,
                    help="higgs rows to generate (a cut of the 11M table)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    device = phase_device(args.chips)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SmokeFailure(f"no src/repro next to {Path(__file__).name}")
    sys.path.insert(0, str(src))
    from repro.compile_cache import enable_compile_cache
    from repro.db.loader import synth_dataset

    log(f"compile cache: {enable_compile_cache()}")
    if args.rows != HIGGS_ROWS:
        log(f"cut: higgs rows {HIGGS_ROWS} -> {args.rows} (rows only; "
            f"F={HIGGS_F}, {TREES} trees, depth {DEPTH} unchanged)")
    t0 = time.perf_counter()
    x, y = synth_dataset("higgs", seed=args.seed, max_rows=args.rows)
    forest = random_forest(TREES, HIGGS_F, args.seed)
    log(f"data: higgs {x.shape[0]}x{x.shape[1]} f32 ({x.nbytes} B), "
        f"forest {TREES} trees depth {DEPTH}, seed={args.seed}, "
        f"smoke_wall_s={time.perf_counter() - t0:.2f}")

    if args.chips == 4:
        phase_mesh(x, forest)
    else:
        preds = phase_scan(x, forest)
        phase_host_tier(x, forest, preds)
        phase_serve(args.seed)
        phase_train(x[:TRAIN_ROWS], y[:TRAIN_ROWS])
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
