"""Paper Fig. 8: model conversion + loading overheads.

Mapping (DESIGN.md §6.3): 'conversion' = node-list → dense-tensor layout
(complete_from_nodes) + algorithm side-tensor builds; the COMPILED
traversal's conversion cost (TreeLite/lleaves' hours of codegen) maps to
XLA jit-compile time of the unrolled select-chain graph, measured here
per algorithm.  'loading' = device_put of the converted arrays (+ the
model-reuse cache hit path, which is the paper's netsDB loading story)."""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from benchmarks import common as C
from repro.core.algorithms import predict_raw
from repro.core.forest import (complete_from_nodes, hb_path_matrix,
                               qs_bitvectors)


def _dense_to_nodelist(forest):
    """Rebuild a sklearn-style node list from the dense layout (stand-in
    for an imported external model)."""
    T, I = forest.feature.shape
    L = forest.num_leaves
    trees = []
    fe = np.asarray(forest.feature)
    th = np.asarray(forest.threshold)
    lv = np.asarray(forest.leaf_value)
    n_nodes = 2 * I + 1
    for t in range(T):
        cl = np.full(n_nodes, -1, np.int64)
        cr = np.full(n_nodes, -1, np.int64)
        feat = np.zeros(n_nodes, np.int64)
        thr = np.zeros(n_nodes, np.float32)
        val = np.zeros(n_nodes, np.float32)
        for i in range(I):
            cl[i], cr[i] = 2 * i + 1, 2 * i + 2
            feat[i], thr[i] = fe[t, i], th[t, i]
        val[I:I + L] = lv[t]
        trees.append(dict(children_left=cl, children_right=cr,
                          feature=feat, threshold=thr, value=val))
    return trees


def run(trees_grid=(10, 500, 1600), depth=8):
    rows = []
    for T in trees_grid:
        forest = C.get_forest("higgs", "lightgbm", T, depth=depth)
        nodelist = _dense_to_nodelist(forest)

        t0 = time.perf_counter()
        f2 = complete_from_nodes(nodelist, depth=depth,
                                 n_features=forest.n_features,
                                 model_type="lightgbm")
        convert_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        hb_path_matrix(depth)
        qs_bitvectors(depth)
        aux_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        arrays = {k: jax.device_put(v) for k, v in f2.arrays().items()}
        jax.block_until_ready(arrays)
        load_s = time.perf_counter() - t0

        x = jnp.zeros((256, forest.n_features), jnp.float32)
        for algo in ("predicated", "compiled", "hummingbird",
                     "quickscorer"):
            t0 = time.perf_counter()
            fn = jax.jit(lambda xx, a=algo: predict_raw(f2, xx, a))
            jax.block_until_ready(fn(x))
            compile_s = time.perf_counter() - t0
            rows.append(dict(dataset="higgs", model="lightgbm", trees=T,
                             platform=f"convert+compile-{algo}",
                             load_s=round(load_s, 4),
                             infer_s=round(compile_s, 4),
                             write_s=round(convert_s + aux_s, 4),
                             total_s=round(load_s + compile_s + convert_s
                                           + aux_s, 4)))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trees", default="10,500,1600")
    args = ap.parse_args()
    C.print_rows(run(tuple(int(t) for t in args.trees.split(","))))


if __name__ == "__main__":
    enable_compile_cache()
    main()
