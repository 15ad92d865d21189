"""Paper Tab. 10: inference-ONLY algorithm comparison (single 'thread' —
one CPU device here), across tree counts.  Claim: QuickScorer has the
best single-thread latency; the naive traversal is the slowest; the
tensorized (HummingBird) form pays for its dense path tensors.

Also benchmarks the Pallas kernels in interpret mode — NOT a wall-clock
claim (interpret mode is a Python emulator; the compiled-TPU story lives
in §Roofline) but a per-call overhead record, so the kernel path is
exercised by the same harness.

FUSED section (``run_fused`` / BENCH_fused.json): jitted fused
(in-kernel SUM aggregation, no [B, T] round-trip) vs jitted unfused
(predict + aggregate_raw) for every Pallas backend on the 500/1600-tree
grid.  Off-TPU both run through the compiled interpreter path, so the
comparison isolates exactly the materialization the fusion removes; the
JSON is the perf trajectory record for this optimization from this PR
onward."""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from benchmarks import common as C
from repro.core.algorithms import ALGORITHMS, predict_raw
from repro.core.postprocess import aggregate_raw

ALGOS = ("naive", "predicated", "compiled", "hummingbird", "quickscorer")
FUSED_TREE_GRID = (500, 1600)
BENCH_FUSED_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_fused.json")


def _time(fn, *args, warmup=1, iters=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run(dataset="higgs", trees=(10, 500, 1600), batch=2048,
        include_naive_upto=100, include_pallas=False):
    rows = []
    x, _ = C.bench_data(dataset, scale=1.0)
    x = jnp.asarray(x[:batch])
    for T in trees:
        forest = C.get_forest(dataset, "xgboost", T)
        for algo in ALGOS:
            if algo == "naive" and T > include_naive_upto:
                continue  # per-(sample,tree) while_loop: prohibitive
            fn = jax.jit(lambda xx, a=algo: predict_raw(forest, xx, a))
            dt = _time(fn, x)
            rows.append(dict(dataset=dataset, model="xgboost", trees=T,
                             platform=f"algo-{algo}", load_s=0.0,
                             infer_s=round(dt, 5), write_s=0.0,
                             total_s=round(dt, 5),
                             checksum=float(jnp.sum(fn(x)))))
        if include_pallas and T <= 100:
            from repro.kernels.ops import KERNEL_ALGORITHMS
            xs = x[:64]
            for name, kfn in KERNEL_ALGORITHMS.items():
                dt = _time(lambda xx: kfn(forest, xx, interpret=True), xs,
                           warmup=0, iters=1)
                rows.append(dict(dataset=dataset, model="xgboost", trees=T,
                                 platform=f"pallas-{name}(interp)",
                                 load_s=0.0, infer_s=round(dt, 5),
                                 write_s=0.0, total_s=round(dt, 5),
                                 checksum=0.0))
    return rows


def run_fused(dataset="higgs", trees=FUSED_TREE_GRID, batch=512, iters=3):
    """Fused vs unfused Pallas backends, jitted end to end.

    Returns (rows, records): rows in the common CSV schema, records as the
    BENCH_fused.json trajectory entries {trees, algorithm, unfused_s,
    fused_s, bf16_s, speedup, bf16_speedup, batch, backend}.  The bf16
    row stages the tree tiles (thresholds/leaves) at half width with f32
    accumulation — off-TPU the timing mostly tracks the cast overhead;
    on TPU it is the tree-tile VMEM/bandwidth shrink record.
    """
    from repro.kernels.ops import FUSED_KERNEL_ALGORITHMS, KERNEL_ALGORITHMS

    x, _ = C.bench_data(dataset, scale=1.0)
    x = jnp.asarray(x[:batch])
    rows, records = [], []
    for T in trees:
        forest = C.get_forest(dataset, "xgboost", T)
        for name, kfn in KERNEL_ALGORITHMS.items():
            fname = name + "_fused"
            ffn = FUSED_KERNEL_ALGORITHMS[fname]
            unfused = jax.jit(lambda xx, f=kfn: aggregate_raw(f(forest, xx)))
            fused = jax.jit(lambda xx, f=ffn: f(forest, xx))
            fused_bf16 = jax.jit(
                lambda xx, f=ffn: f(forest, xx, tree_dtype=jnp.bfloat16))

            t_un = C.time_best(unfused, x, iters=iters)
            t_fu = C.time_best(fused, x, iters=iters)
            t_bf = C.time_best(fused_bf16, x, iters=iters)
            for plat, dt, fn in ((f"pallas-{name}+agg", t_un, unfused),
                                 (f"pallas-{fname}", t_fu, fused),
                                 (f"pallas-{fname}-bf16", t_bf, fused_bf16)):
                rows.append(dict(dataset=dataset, model="xgboost", trees=T,
                                 platform=plat, load_s=0.0,
                                 infer_s=round(dt, 5), write_s=0.0,
                                 total_s=round(dt, 5),
                                 checksum=float(jnp.sum(fn(x)))))
            records.append(dict(trees=T, algorithm=name, batch=batch,
                                unfused_s=round(t_un, 5),
                                fused_s=round(t_fu, 5),
                                bf16_s=round(t_bf, 5),
                                speedup=round(t_un / max(t_fu, 1e-9), 3),
                                bf16_speedup=round(t_un / max(t_bf, 1e-9),
                                                   3),
                                **C.env_info()))
    return rows, records


def run_fused_mesh(dataset="higgs", trees=(500,), batch=256, iters=3,
                   algorithm="predicated"):
    """Mesh-size trajectory rows for BENCH_fused.json.

    Measures the rel plan's kernel stage in isolation: the single-device
    fused call (all trees, one launch) vs the shard_map form — the tree
    axis sharded over the mesh ``model`` axis, ONE local fused launch per
    device, one psum.  With a single host device the mesh row degenerates
    to the single-device call (recorded with mesh_devices=1), so the
    trajectory file always carries a mesh-size row; the CI multi-device
    smoke and TPU runs fill in the >1 points.  Off-TPU both paths run the
    interpret-mode kernel, so treat multi-device CPU numbers as overhead
    records, not speedup claims.
    """
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.forest import pad_trees
    from repro.kernels.ops import FUSED_KERNEL_ALGORITHMS

    fn = FUSED_KERNEL_ALGORITHMS[algorithm + "_pallas_fused"]
    devs = jax.devices()
    D = len(devs)
    mesh = (Mesh(np.array(devs).reshape(1, D), ("data", "model"))
            if D > 1 else None)
    x, _ = C.bench_data(dataset, scale=1.0)
    x = jnp.asarray(x[:batch])
    rows, records = [], []

    for T in trees:
        forest = C.get_forest(dataset, "xgboost", T)
        single = jax.jit(lambda xx: fn(forest, xx))
        t_single = C.time_best(single, x, iters=iters)
        if mesh is not None:
            fp, _ = pad_trees(forest, D)
            shardings = jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, P("model")), fp)
            fp = jax.device_put(fp, shardings)

            def body(xl, fl):
                return jax.lax.psum(fn(fl, xl), "model")

            sm = jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=(P("data", None), P("model")),
                out_specs=P("data"), check_vma=False))
            t_mesh = C.time_best(sm, x, fp, iters=iters)
        else:
            t_mesh = t_single
        rows.append(dict(dataset=dataset, model="xgboost", trees=T,
                         platform=f"pallas-{algorithm}_fused@mesh{D}",
                         load_s=0.0, infer_s=round(t_mesh, 5), write_s=0.0,
                         total_s=round(t_mesh, 5), checksum=0.0))
        records.append(dict(kind="mesh", trees=T, algorithm=algorithm,
                            batch=batch,
                            single_device_s=round(t_single, 5),
                            mesh_s=round(t_mesh, 5),
                            mesh_speedup=round(t_single / max(t_mesh, 1e-9),
                                               3),
                            **C.env_info(mesh)))
    return rows, records


def write_fused_json(records, path=BENCH_FUSED_JSON):
    payload = {"bench": "fused_vs_unfused", "created_at": time.time(),
               "env": C.env_info(), "records": records}
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trees", default="10,500,1600")
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--fast", action="store_true",
                    help="reduced batch/iters; the fused section keeps the "
                         "500/1600 grid (its claim lives there)")
    ap.add_argument("--no-fused", action="store_true")
    ap.add_argument("--fused-out", default=BENCH_FUSED_JSON)
    args = ap.parse_args()
    trees = tuple(int(t) for t in args.trees.split(","))
    if args.fast:
        trees = tuple(t for t in trees if t <= 100) or (10, 100)
    C.print_rows(run(trees=trees, batch=min(args.batch, 512) if args.fast
                     else args.batch, include_pallas=args.pallas))
    if not args.no_fused:
        rows, records = run_fused(
            batch=256 if args.fast else 512,
            iters=3 if args.fast else 5)
        C.print_rows(rows)
        mrows, mrecords = run_fused_mesh(
            trees=(trees[-1],) if args.fast else (FUSED_TREE_GRID[0],),
            batch=128 if args.fast else 256,
            iters=2 if args.fast else 3)
        C.print_rows(mrows, header=False)
        path = write_fused_json(records + mrecords, args.fused_out)
        ok = all(r["speedup"] > 1.0 for r in records)
        ndev = mrecords[-1]["mesh_devices"] if mrecords else 1
        print(f"# fused trajectory -> {path}  "
              f"(all fused faster: {ok}; mesh rows at {ndev} device(s))")


if __name__ == "__main__":
    enable_compile_cache()
    main()
