"""Benchmark aggregator: one section per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--fast]`` runs the reduced
grid (CPU-minutes); ``--full`` runs the paper tree grid {10, 500, 1600}
everywhere.  Output: CSV blocks per section plus a final
``name,us_per_call,derived`` summary (one line per table, total seconds
of the netsDB-best platform vs the standalone baseline)."""

from __future__ import annotations

import argparse
import sys
import time

from repro.compile_cache import enable_compile_cache
from benchmarks import common as C


def _summary(rows, table):
    """Best in-DB total vs best standalone total per (dataset, trees)."""
    out = []
    bykey = {}
    for r in rows:
        key = (r.get("dataset"), r.get("trees"))
        bykey.setdefault(key, []).append(r)
    for (ds, T), rs in bykey.items():
        indb = [r for r in rs if str(r["platform"]).startswith("netsdb")]
        ext = [r for r in rs if str(r["platform"]).startswith("standalone")]
        if not indb or not ext:
            continue
        b_in = min(indb, key=lambda r: r["total_s"])
        b_ex = min(ext, key=lambda r: r["total_s"])
        speedup = b_ex["total_s"] / max(b_in["total_s"], 1e-9)
        out.append(C.csv_line(
            f"{table}/{ds}/trees{T}", b_in["total_s"],
            f"best_indb={b_in['platform']} speedup_vs_standalone="
            f"{speedup:.2f}x"))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--scale", type=float, default=None)
    args = ap.parse_args()
    trees = C.TREE_GRID if args.full else ((10, 100) if args.fast
                                           else (10, 500))
    scale = args.scale if args.scale is not None else \
        (0.25 if args.fast else 1.0)

    summary = []
    t_start = time.time()

    from benchmarks import bench_small
    print("## Tab2-3: small dense datasets (fraud, year)")
    rows = bench_small.run(trees=trees, scale=scale)
    C.print_rows(rows)
    summary += _summary(rows, "tab2-3")

    from benchmarks import bench_large
    print("\n## Tab4-6: medium/large dense datasets (higgs scaled)")
    rows = bench_large.run(datasets=("higgs",) if not args.full else
                           ("higgs", "airline", "tpcxai"),
                           trees=trees, scale=scale)
    C.print_rows(rows)
    summary += _summary(rows, "tab4-6")

    print("\n## Out-of-core streaming scan: host tier + double-buffered DMA")
    srows, stream_records = bench_large.run_stream(
        trees=(trees[0],) if args.fast else trees[:2], scale=scale)
    C.print_rows(srows)
    stream_path = bench_large.write_stream_json(stream_records)
    for r in stream_records:
        summary.append(C.csv_line(
            f"stream/{r['dataset']}/{r['plan']}/trees{r['trees']}",
            r["stream_wall_s"],
            f"overlap={r['overlap_fraction']} batches={r['batches']} "
            f"budget={r['device_budget_bytes']}B"))
    print(f"# streaming trajectory -> {stream_path}")

    from benchmarks import bench_faults
    print("\n## Fault plane: zero-fault overhead + recovery latency")
    frows, fault_records = bench_faults.run(
        trees=trees[0] if args.fast else trees[-1],
        scale=min(scale, 0.25), iters=3 if args.fast else 5)
    C.print_rows(frows)
    fault_path = bench_faults.write_faults_json(fault_records)
    for r in fault_records:
        wall = r["instrumented_wall_s"] if r["recovery_wall_s"] is None \
            else r["recovery_wall_s"]
        summary.append(C.csv_line(
            f"faults/{r['scenario']}", wall,
            f"overhead={r['overhead_fraction']:+.1%} "
            f"vs_baseline={r['baseline_wall_s']}s"))
    print(f"# fault trajectory -> {fault_path}")

    from benchmarks import bench_wide_sparse
    print("\n## Tab7-9: wide/sparse datasets (bosch, epsilon, criteo)")
    rows = bench_wide_sparse.run(trees=trees, scale=scale)
    C.print_rows(rows, extra_cols=("file_kind",))
    summary += _summary(rows, "tab7-9")

    print("\n## Sparse plane: CSR pages + gather prepass vs dense fallback")
    rows, sparse_records = bench_wide_sparse.run_sparse(
        trees=(trees[0],) if args.fast else trees[:2], scale=scale)
    C.print_rows(rows, extra_cols=("file_kind",))
    sparse_path = bench_wide_sparse.write_sparse_json(sparse_records)
    for r in sparse_records:
        summary.append(C.csv_line(
            f"sparse/{r['dataset']}/trees{r['trees']}", r["csr_total_s"],
            f"csr_vs_dense={r['csr_vs_dense']}x density={r['density']}"))
    print(f"# sparse trajectory -> {sparse_path}")

    from benchmarks import bench_algorithms
    print("\n## Tab10: single-device inference-only algorithm comparison")
    rows = bench_algorithms.run(trees=trees, batch=1024)
    C.print_rows(rows)
    for r in rows:
        summary.append(C.csv_line(
            f"tab10/{r['platform']}/trees{r['trees']}", r["infer_s"]))

    print("\n## Fused-vs-unfused kernel trajectory (BENCH_fused.json)")
    rows, fused_records = bench_algorithms.run_fused(
        trees=(trees[-1],) if args.fast else bench_algorithms.FUSED_TREE_GRID,
        batch=256 if args.fast else 512, iters=3 if args.fast else 5)
    C.print_rows(rows)
    print("\n## Mesh-size rows: shard_map tree-parallel fused kernel stage")
    mrows, mesh_records = bench_algorithms.run_fused_mesh(
        trees=(trees[-1],) if args.fast
        else (bench_algorithms.FUSED_TREE_GRID[0],),
        batch=128 if args.fast else 256, iters=2 if args.fast else 3)
    C.print_rows(mrows)
    fused_path = bench_algorithms.write_fused_json(
        fused_records + mesh_records)
    for r in fused_records:
        summary.append(C.csv_line(
            f"fused/{r['algorithm']}/trees{r['trees']}", r["fused_s"],
            f"speedup={r['speedup']}x bf16_speedup={r['bf16_speedup']}x"))
    for r in mesh_records:
        summary.append(C.csv_line(
            f"fused-mesh/{r['algorithm']}/trees{r['trees']}", r["mesh_s"],
            f"devices={r['mesh_devices']} mesh={r['mesh']}"))
    print(f"# fused trajectory -> {fused_path}")

    from benchmarks import bench_serve
    print("\n## Online serving: open-loop coalesced vs per-request")
    srows, serve_records = bench_serve.run(
        trees_grid=(bench_serve.MODEL_TREES[0],) if args.fast
        else bench_serve.MODEL_TREES,
        duration_s=0.4 if args.fast else 1.0,
        max_requests=400 if args.fast else 1200)
    C.print_rows(srows, extra_cols=("rate_hz",))
    serve_path = bench_serve.write_serve_json(serve_records)
    for r in serve_records:
        summary.append(C.csv_line(
            f"serve/{r['model']}/rate{r['rate_hz']}", r["p50_ms"] / 1e3,
            f"speedup_p50={r['speedup_p50']}x "
            f"width={r['mean_coalesce_width']} "
            f"retrace={0 if r['zero_retrace'] else 1}"))
    print(f"# serve trajectory -> {serve_path}")

    from benchmarks import bench_optimizer
    print("\n## Cost-based optimizer: auto vs static regret grid")
    opt_records = bench_optimizer.run_grid(
        bench_optimizer.SMOKE_TREES if args.fast
        else bench_optimizer.GRID_TREES,
        bench_optimizer.SMOKE_ROWS if args.fast
        else bench_optimizer.GRID_ROWS,
        iters=2 if args.fast else 3)
    bench_optimizer.print_records(opt_records)
    bench_optimizer.check(opt_records, context="run.py optimizer")
    opt_path = bench_optimizer.write_optimizer_json(opt_records)
    for r in opt_records:
        summary.append(C.csv_line(
            f"optimizer/trees{r['trees']}/rows{r['rows']}", r["auto_s"],
            f"auto={r['auto_algorithm']}+{r['auto_plan']} "
            f"regret={r['regret_vs_best']}x "
            f"win={r['win_vs_worst']}x"))
    print(f"# optimizer trajectory -> {opt_path}")

    from benchmarks import bench_conversion
    print("\n## Fig8: model conversion + loading overheads")
    rows = bench_conversion.run(trees_grid=trees)
    C.print_rows(rows)
    for r in rows:
        summary.append(C.csv_line(
            f"fig8/{r['platform']}/trees{r['trees']}", r["total_s"],
            "compile+convert"))

    from benchmarks import bench_batching
    print("\n## Sec7: batching / vectorization granularity")
    rows = bench_batching.run(trees=trees[-1], scale=scale)
    C.print_rows(rows)
    for r in rows:
        summary.append(C.csv_line(
            f"sec7/{r['platform']}", r["total_s"]))

    print(f"\n## summary (name,us_per_call,derived) "
          f"[total bench wall: {time.time() - t_start:.0f}s]")
    for line in summary:
        print(line)


if __name__ == "__main__":
    enable_compile_cache()
    main()
