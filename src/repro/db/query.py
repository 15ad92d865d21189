"""Physical query plans for in-database forest inference (netsDB's core).

Three plans over the same logical query  SCAN -> PREDICT -> AGGREGATE -> WRITE
(paper Sec. 3.2/3.3, Fig. 3):

  udf        UDF-centric: the whole forest inside one transform UDF;
             DATA parallelism (mesh axis ``data`` shards sample blocks, the
             forest is replicated per device).  Compiles to ONE stage.
  rel        Relation-centric: CROSS-PRODUCT(tree partitions x sample
             blocks) -> partial aggregate -> final aggregate -> postprocess/
             write.  MODEL parallelism (mesh axis ``model`` shards the tree
             dimension).  Compiles to FOUR stages, the first being the
             model-partitioning stage.
  rel+reuse  netsDB-OPT: the partition stage's output is materialized in the
             ModelReuseCache and reused across queries on the same model,
             collapsing steady-state execution to the three data stages.

Fused backends (``*_pallas_fused``) run phase-2 aggregation INSIDE the
kernel: both plans then consume [B] (or [n_parts, B]) partial sums and the
[B, T] per-tree score matrix never exists in the query path — the
materialization the paper charges stage boundaries with, eliminated at the
kernel level.

Compiled-plan cache: ``ModelReuseCache`` generalized from the partition
stage's OUTPUT to the whole plan's EXECUTABLE.  The jitted stage list —
keyed on (model fingerprint, algorithm, plan, STORAGE FORMAT, batch
signature, mesh) — is built once; steady-state queries skip partitioning
AND tracing/compilation (the first-query vs steady-state distinction of
Sec. 3.3, lifted one level).  ``rel`` deliberately stays uncached: it is
the paper's no-reuse baseline.

Sparse data plane: a dataset stored as CSR pages (``store.put_sparse``)
runs the SAME logical plans through a feature-gather prepass — the plan
compacts the forest onto its used-feature union (``core.forest.
compact_forest``), scatters each CSR page block into dense
``[rows, F_used]`` compact tiles (``kernels.gather``), and feeds the
existing (fused) kernels.  The ``[BT, I, F]`` one-hot never exists at
full F, so criteo-scale feature counts execute instead of being modeled.
Dense and CSR plans over the same model are distinct cache entries (the
storage format is part of both cache keys).

Multi-device fused inference: on a mesh (``dist.sharding.make_forest_plan``
axis mapping: ``data`` shards sample blocks / CSR pages, ``model`` shards
tree blocks) the kernel stages run under ``jax.shard_map``.
The udf plan's body is the fused kernel over the LOCAL sample shard with a
replicated forest; the rel plan's cross-product + partial-aggregate
collapse into ONE local fused kernel launch per device followed by a
single ``psum`` over ``model`` — the ``[n_parts, B]`` partials never cross
a stage boundary (they exist only as the per-device ``[B_local]`` sums
inside the manual region).  The CSR feature-gather prepass also moves
INSIDE the body, so compact tiles only ever exist at the local batch.
Without a mesh (or without the relevant axis) the single-device template
keeps the same stage structure: the rel cross-product is an unrolled loop
over tree partitions (``n_parts`` derived from the kernel tree-block
heuristic, overridable per query), and the aggregate stage folds the
partials sequentially in partition order — the same association XLA:CPU's
all-reduce uses, which is what makes mesh and mesh-less fused predictions
bit-identical in f32.

Streaming scan execution: the per-batch loop lives in ONE place — the
``StreamingScanExecutor`` (``db/executor.py``).  Every plan (udf / rel),
storage format (dense / CSR), and memory tier (device-resident / host /
disk out-of-core) runs the same double-buffered loop: batch *i+1*'s
pages are in DMA flight (async ``device_put`` under the store's
``data_sharding``) while batch *i* runs its kernel stages and batch
*i−1*'s predictions drain — on a DEDICATED WORKER THREAD — into a
preallocated host result buffer, so the D2H never blocks the next
batch's kernels.  Device-tier datasets take the identical loop with a
no-op transfer stage; disk-tier datasets feed it ``np.memmap`` page
views, so a LIBSVM file larger than both the device and host budgets
streams end to end.  On every tier the default batch is also bounded by
the predictor's device working set (``PREDICT_BATCH_BYTES``), so a
resident table is never scored in one whole-table batch whose
intermediates would not fit device memory.

Each stage is timed and its materialized bytes recorded, reproducing the
paper's latency breakdowns.  See ``docs/architecture.md`` for the plan /
cache / tier design and ``docs/benchmarks.md`` for how the timings
surface in the BENCH_*.json trajectories.
"""

from __future__ import annotations

import dataclasses
import time
import weakref
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import algorithms as algs
from repro.core import postprocess as post
from repro.core.forest import Forest, compact_forest, pad_trees, tree_slice
from repro.core.reuse import (GLOBAL_CACHE, GLOBAL_PLAN_CACHE,
                              MaterializedModel, ModelReuseCache,
                              fingerprint_forest, mesh_signature)
from repro.db.executor import (DEFAULT_STREAM_BATCH_BYTES, ScanStats,
                               StreamingScanExecutor)
from repro.db.faults import (Deadline, DegradedReport, FaultInjector,
                             RetryPolicy)
from repro.db.operators import (Operator, StageReport, ndevices,
                                run_stages, split_into_stages)
from repro.db.optimizer import CostBasedOptimizer, Decision
from repro.db.store import TensorBlockStore
from repro.dist.sharding import ForestShardingPlan, make_forest_plan
from repro.obs import METRICS, TRACER, TraceSummary
from repro.kernels.gather import csr_block_to_dense, gather_inverse_map
from repro.kernels.ops import default_tree_block

__all__ = ["QueryResult", "RowBatchResult", "CompiledQueryPlan",
           "ForestQueryEngine", "PREDICT_BATCH_BYTES", "predict_row_bytes"]

#: sentinel occupying the DATASET slot (key[2]) of row-plan cache keys:
#: row batches come from the serving plane, not a stored dataset, so
#: ``store.drop`` -> ``invalidate_dataset`` must never sweep them ("#"
#: cannot appear in a catalog name a well-behaved caller would drop).
ROW_PLAN_DATASET = "#rows"


@dataclasses.dataclass
class QueryResult:
    predictions: jax.Array            # [N] final probabilities / regressands
    plan: str
    algorithm: str
    num_stages: int
    stage_reports: list[StageReport]
    partition_s: float                # model-partition stage (0 on reuse hit)
    infer_s: float                    # cross-product / UDF stages
    aggregate_s: float
    write_s: float
    total_s: float
    reuse_hit: bool = False           # model-cache OR plan-cache hit
    plan_reuse_hit: bool = False      # compiled-plan cache hit specifically
    storage_format: str = "dense"     # which data plane executed (dense/csr)
    n_parts: int = 1                  # tree partitions (rel plans; mesh =
    #                                   model-axis size, else heuristic)
    mesh_devices: int = 1             # devices the query executed across
    tier: str = "device"              # memory tier the scan read from
    scan: ScanStats | None = None     # streaming-executor telemetry
    degraded: DegradedReport | None = None   # set when the result is a
    #                                   PARTIAL (deadline_s expired):
    #                                   scored rows are exact, missing
    #                                   rows are NaN, row_mask says which
    trace: TraceSummary | None = None  # per-query span rollup when the
    #                                   obs TRACER is enabled (else None);
    #                                   the full span tree is exportable
    #                                   via TRACER.export_chrome()
    decision: Decision | None = None  # the optimizer verdict this query
    #                                   executed under (plan="auto" /
    #                                   algorithm="auto" only, else None)

    def breakdown(self) -> dict[str, float]:
        return {
            "partition": self.partition_s,
            "inference": self.infer_s,
            "aggregate": self.aggregate_s,
            "write": self.write_s,
            "total": self.total_s,
        }


@dataclasses.dataclass
class RowBatchResult:
    """Result of the row-level serving entry point (``infer_rows``).

    Much lighter than ``QueryResult`` on purpose: the serving plane
    calls this at request rate, so there is no stage-report list, no
    scan telemetry, no per-call store round-trip — just the predictions,
    whether the compiled plan was reused, and the wall the tick paid.
    """

    predictions: jax.Array            # [B]; masked-out padding rows are NaN
    plan_reuse_hit: bool              # compiled-plan cache hit (zero retrace)
    algorithm: str
    plan: str
    batch_rows: int                   # the padded batch signature B
    rows_scored: int                  # real rows (row_mask True count)
    total_s: float


@dataclasses.dataclass
class CompiledQueryPlan:
    """A materialized plan executable: the jitted stage list + its model.

    The stages close over the padded/partitioned device-resident forest, so
    a cache hit reuses BOTH the partition-stage output (model reuse) and
    every stage's jit cache (no re-tracing, no re-compilation for already
    seen batch shapes).
    """

    stages: list                      # list[operators.Stage]
    num_stages: int                   # reported count (incl. partition stage)
    mat: Any = None                   # rel plans: pins the MaterializedModel
    #                                   whose id() keys this entry, so the id
    #                                   cannot be reused while the entry lives
    build_time_s: float = 0.0         # set by ModelReuseCache.get_or_build


def _predict_fn(algorithm: str):
    """Raw per-tree score backend: jnp algorithms or Pallas kernels."""
    if algorithm in algs.ALGORITHMS:
        return partial(algs.predict_raw, algorithm=algorithm)
    from repro.kernels.ops import KERNEL_ALGORITHMS
    if algorithm in KERNEL_ALGORITHMS:
        return KERNEL_ALGORITHMS[algorithm]
    raise ValueError(f"unknown algorithm {algorithm!r}")


def _predict_sum_fn(algorithm: str):
    """(forest, x) -> [B] summed raw margins; returns (fn, is_fused).

    Fused Pallas backends aggregate in-kernel; everything else composes
    ``aggregate_raw`` over the raw [B, T] backend (the unfused reference
    data path).
    """
    from repro.kernels.ops import FUSED_KERNEL_ALGORITHMS
    if algorithm in FUSED_KERNEL_ALGORITHMS:
        return FUSED_KERNEL_ALGORITHMS[algorithm], True
    predict = _predict_fn(algorithm)
    return (lambda forest, x: post.aggregate_raw(predict(forest, x))), False


#: device bytes the predictor's intermediates may take per scan batch
#: (a default batch is cut to fit; explicit ``batch_pages`` win)
PREDICT_BATCH_BYTES = 1 << 30


def predict_row_bytes(algorithm: str, forest: Forest, features: int) -> int:
    """Device bytes one scanned row costs the predictor at its peak: the
    row itself (and its batch copy), plus the algorithm's largest live
    per-row intermediates.

    fused kernels  one [rows] partial sum per tree (at most; rel plans)
    unfused        the [rows, T] score matrix and its re-laid copy
    hummingbird    [rows, T, I] predicates and [rows, T, L] paths
    quickscorer    [rows, T, I, W] bit-vector masks
    other jnp      [rows, T] index, gathered operands and scores
    """
    from repro.kernels.ops import FUSED_KERNEL_ALGORITHMS, KERNEL_ALGORITHMS
    T, I, L = forest.num_trees, forest.num_internal, forest.num_leaves
    row = 2 * 4 * features
    if algorithm in FUSED_KERNEL_ALGORITHMS:
        return row + 4 * (T + 4)
    if algorithm in KERNEL_ALGORITHMS:
        return row + 2 * 4 * T
    if algorithm == "hummingbird":
        return row + 2 * 4 * T * (I + L)
    if algorithm == "quickscorer":
        return row + 4 * T * I * ((L + 31) // 32) + 2 * 4 * T * I
    return row + 8 * 4 * T


class ForestQueryEngine:
    """Executes forest-inference queries against a TensorBlockStore."""

    def __init__(self, store: TensorBlockStore, mesh: Mesh | None = None,
                 reuse_cache: ModelReuseCache | None = None,
                 plan_cache: ModelReuseCache | None = None):
        self.store = store
        self.mesh = mesh if mesh is not None else store.mesh
        # axis mapping for shard_map execution (data = sample blocks,
        # model = tree blocks); a None axis disables that parallelism
        self.fplan: ForestShardingPlan = make_forest_plan(self.mesh)
        self.cache = reuse_cache if reuse_cache is not None else GLOBAL_CACHE
        self.plan_cache = (plan_cache if plan_cache is not None
                           else GLOBAL_PLAN_CACHE)
        # id -> content fingerprint, invalidated when the Forest is GC'd
        self._fingerprints: dict[int, str] = {}
        # store.drop sweeps this engine's dataset-dependent plan entries
        store.register_invalidator(self.invalidate_dataset)
        # store.put_model re-pins sweep the replaced fingerprint's plans
        # and decisions through this engine (stale-after-retrain fix)
        store.register_model_invalidator(self.invalidate)
        # the cost-based optimizer behind plan="auto"/algorithm="auto"
        # (db/optimizer.py); replaceable — tests install tighter budgets
        self.optimizer = CostBasedOptimizer(self)

    # ------------------------------------------------------------------
    # cache-key components
    # ------------------------------------------------------------------
    # model identity: content hash, computed once per live Forest object
    def _model_key(self, forest: Forest, model_id: str | None) -> str:
        if model_id is not None:
            return model_id
        k = id(forest)
        fp = self._fingerprints.get(k)
        if fp is None:
            fp = fingerprint_forest(forest)
            self._fingerprints[k] = fp
            weakref.finalize(forest, self._fingerprints.pop, k, None)
        return fp

    # ------------------------------------------------------------------
    # cache sweeping (paper: model updates must drop BOTH materializations)
    # ------------------------------------------------------------------
    def invalidate(self, model_id: str | None = None) -> int:
        """Sweep BOTH the partition-model cache and the compiled-plan
        cache (all entries, or one model's).  Returns entries dropped.

        The raw ``ModelReuseCache.invalidate`` matches ``key[0]``, but
        plan keys lead with a kind tag (``'udf-plan'``/``'rel-plan'``) and
        carry the model id at ``key[1]`` — a key[0]-only sweep silently
        leaves every compiled plan (and the device buffers its stages
        close over) alive.  This is the engine-level sweep that gets both.
        """
        n = self.cache.invalidate(model_id)
        n += self.plan_cache.invalidate(model_id, key_index=1)
        # persisted optimizer decisions are keyed on the fingerprint at
        # key[0] — a model update must re-decide, not serve stale picks
        n += self.store.drop_decisions(model_id=model_id)
        return n

    def invalidate_dataset(self, dataset: str) -> int:
        """``TensorBlockStore.drop``'s hook: sweep compiled plans built
        against ``dataset`` (plan keys carry the dataset name at
        ``key[2]``) AND any persisted optimizer decisions keyed on it
        (``store.drop`` sweeps those itself first; this keeps direct
        calls equivalent).  Model materializations are
        dataset-independent and survive — only the plan executables,
        whose batch signatures came from the dropped dataset, are
        stale.  Returns entries dropped."""
        n = self.plan_cache.invalidate(dataset, key_index=2)
        n += self.store.drop_decisions(dataset=dataset)
        return n

    # ------------------------------------------------------------------
    # sparse prepass (the wide-sparse data plane's plan-build half)
    # ------------------------------------------------------------------
    def _sparse_prepass(self, forest: Forest):
        """Compact the forest onto its used-feature union and build the
        CSR gather's inverse map.  Host-side, once per plan build (cached
        with the plan/materialization, like the partition stage)."""
        cf, gather_idx = compact_forest(forest)
        inv_map = jnp.asarray(gather_inverse_map(gather_idx,
                                                 forest.n_features))
        return cf, inv_map, int(gather_idx.size)

    def _gather_operator(self, inv_map: jax.Array, f_used: int) -> Operator:
        """SCAN-side feature-gather prepass: CSR page block -> dense
        compact tile.  Not a breaker — it fuses into the same jitted
        stage as the kernel call (no extra materialization boundary)."""

        def gather(state):
            state = dict(state)
            state["x"] = csr_block_to_dense(state["x"], inv_map, f_used)
            return state

        return Operator("gather:csr-compact", gather)

    # ------------------------------------------------------------------
    # model partition stage (the reusable one)
    # ------------------------------------------------------------------
    def _partition_model(self, forest: Forest, algorithm: str,
                         num_parts: int, *,
                         storage_format: str = "dense") -> MaterializedModel:
        with TRACER.span("plan.partition", algorithm=algorithm,
                         num_parts=num_parts,
                         storage_format=storage_format):
            return self._partition_model_impl(
                forest, algorithm, num_parts, storage_format=storage_format)

    def _partition_model_impl(self, forest: Forest, algorithm: str,
                              num_parts: int, *,
                              storage_format: str = "dense"
                              ) -> MaterializedModel:
        aux: dict[str, Any] = {}
        if storage_format == "csr":
            forest, inv_map, f_used = self._sparse_prepass(forest)
            aux["inv_map"] = inv_map
            aux["f_used"] = f_used
        forest_p, true_T = pad_trees(forest, num_parts)
        spec = None
        shardings = self.fplan.forest_shardings(forest_p)
        if shardings is not None:
            spec = self.fplan.tree_spec
            forest_p = jax.device_put(forest_p, shardings)
        else:
            forest_p = jax.tree_util.tree_map(jnp.asarray, forest_p)
        jax.block_until_ready(forest_p.arrays())
        return MaterializedModel(forest=forest_p, true_num_trees=true_T,
                                 aux=aux, partition_spec=spec, build_time_s=0.0)

    # ------------------------------------------------------------------
    # plan bodies
    # ------------------------------------------------------------------
    def _udf_ops(self, forest: Forest, algorithm: str, true_T: int,
                 sparse_aux: tuple | None = None):
        """UDF-centric plan body.  ``sparse_aux`` = (inv_map, f_used) when
        the dataset is CSR pages (the feature-gather prepass input)."""
        predict_sum, _ = _predict_sum_fn(algorithm)
        meta = dict(model_type=forest.model_type, task=forest.task,
                    num_trees=true_T, base_score=forest.base_score)
        fplan = self.fplan

        if fplan.mesh is not None and fplan.data_axis is not None:
            # DATA parallelism under shard_map: sample blocks sharded over
            # ``data``, the forest replicated per device.  The CSR gather
            # runs INSIDE the body, so the dense compact tile only ever
            # exists at the LOCAL batch (never [B_global, F_used]).
            if sparse_aux is not None:
                inv_map, f_used = sparse_aux

                def body(x_local, f_local, inv_local):
                    tile = csr_block_to_dense(x_local, inv_local, f_used)
                    return predict_sum(f_local, tile)
            else:
                inv_map = jnp.zeros((1,), jnp.int32)    # unused placeholder

                def body(x_local, f_local, inv_local):
                    return predict_sum(f_local, x_local)

            sm = jax.shard_map(body, mesh=fplan.mesh,
                               in_specs=(fplan.x_spec, fplan.replicated_spec,
                                         fplan.replicated_spec),
                               out_specs=fplan.out_spec, check_vma=False)

            def udf(state):
                state = dict(state)
                x = state.pop("x")
                state["pred"] = post.postprocess(sm(x, forest, inv_map),
                                                 **meta)
                return state

            return [
                Operator("scan", lambda s: s),
                Operator("transform:forest-udf@shard_map", udf),
                Operator("write", lambda s: s, breaker=True),
            ]

        # single-device template (also: mesh without a data axis)
        def udf(state):
            x = state["x"]
            state = dict(state)
            state["pred"] = post.postprocess(predict_sum(forest, x), **meta)
            return state

        ops = [Operator("scan", lambda s: s)]
        if sparse_aux is not None:
            ops.append(self._gather_operator(*sparse_aux))
        ops += [
            Operator("transform:forest-udf", udf),
            Operator("write", lambda s: s, breaker=True),
        ]
        return ops

    def _rel_ops(self, mat: MaterializedModel, algorithm: str,
                 n_parts: int):
        predict_sum, fused = _predict_sum_fn(algorithm)
        forest = mat.forest
        meta = dict(model_type=forest.model_type, task=forest.task,
                    num_trees=mat.true_num_trees, base_score=forest.base_score)
        fplan = self.fplan
        sparse_aux = (mat.aux["inv_map"], mat.aux["f_used"]) \
            if "inv_map" in mat.aux else None

        def postprocess_op(state):
            state = dict(state)
            state["pred"] = post.postprocess(state.pop("summed"), **meta)
            return state

        if fplan.mesh is not None and fplan.model_axis is not None:
            # MODEL (x DATA) parallelism under shard_map: the partition
            # stage laid the tree axis out over ``model`` (n_parts ==
            # n_model), so CROSS-PRODUCT + PARTIAL-AGGREGATE collapse into
            # ONE local fused kernel launch per device — each body call
            # sums its LOCAL tree shard in-kernel — followed by a single
            # psum over ``model``.  The [n_parts, B] partials never cross
            # a stage boundary; the CSR gather prepass runs inside the
            # body so compact tiles only ever exist at the local batch.
            inv_map = sparse_aux[0] if sparse_aux else \
                jnp.zeros((1,), jnp.int32)
            f_used = sparse_aux[1] if sparse_aux else 0
            model_axis = fplan.model_axis

            def body(x_local, f_local, inv_local):
                if sparse_aux is not None:
                    x_local = csr_block_to_dense(x_local, inv_local, f_used)
                part = predict_sum(f_local, x_local)       # [B_local]
                return jax.lax.psum(part, model_axis)

            sm = jax.shard_map(body, mesh=fplan.mesh,
                               in_specs=(fplan.x_spec, fplan.tree_spec,
                                         fplan.replicated_spec),
                               out_specs=fplan.out_spec, check_vma=False)

            def cross_product(state):
                state = dict(state)
                state["summed"] = sm(state.pop("x"), forest, inv_map)
                return state

            return [
                Operator("scan", lambda s: s),
                Operator("cross-product:psum-agg", cross_product,
                         breaker=True),
                Operator("postprocess", postprocess_op),
                Operator("write", lambda s: s, breaker=True),
            ]

        # --- mesh-less template (the paper's stage-by-stage rel plan) ----
        def cross_product(state):
            """CROSS-PRODUCT(tree partition, sample block) -> partial sums.

            Model parallelism: partial[p, b] = sum of tree scores of
            partition p on sample b.  Fused backends aggregate in-kernel
            per partition, so the per-partition call already yields [B]
            and the unrolled partition loop replaces the vmap (pallas
            grids don't batch).  This unrolled loop is the template the
            shard_map path above distributes: partition p's launch is
            device p's local launch."""
            x = state["x"]
            T = forest.num_trees
            per = T // n_parts

            if fused:
                partial_scores = jnp.stack(
                    [predict_sum(tree_slice(forest, p * per, per), x)
                     for p in range(n_parts)])                # [P, B]
            else:
                parts = jax.tree_util.tree_map(
                    lambda a: a.reshape((n_parts, per) + a.shape[1:]),
                    forest)
                partial_scores = jax.vmap(
                    lambda tree_part: predict_sum(tree_part, x))(parts)
            state = dict(state)
            state["partials"] = partial_scores
            return state

        def aggregate(state):
            state = dict(state)
            parts = state.pop("partials")                     # [P, B]
            # sequential fold in partition order — the association
            # XLA:CPU's all-reduce uses, so the shard_map+psum path above
            # reproduces this sum BIT-identically in f32 (jnp.sum's
            # reduction tree would not)
            summed = parts[0]
            for p in range(1, parts.shape[0]):
                summed = summed + parts[p]
            state["summed"] = summed
            return state

        ops = [Operator("scan", lambda s: s)]
        if sparse_aux is not None:
            # sparse plane: the gather prepass shares the cross-product
            # stage (the compact tile is its VMEM input, not a new
            # materialization boundary)
            ops.append(self._gather_operator(*sparse_aux))
        ops += [
            Operator("cross-product:partial-agg", cross_product,
                     breaker=True),
            Operator("aggregate", aggregate, breaker=True),
            Operator("postprocess", postprocess_op),
            Operator("write", lambda s: s, breaker=True),
        ]
        return ops

    # ------------------------------------------------------------------
    # rel-plan partitioning granularity
    # ------------------------------------------------------------------
    def _resolve_n_parts(self, forest: Forest, algorithm: str,
                         n_parts: int | None) -> int:
        """Tree-partition count for the rel plans.

        On a model mesh the physical partitioning IS the mesh: one tree
        shard per device along ``model`` (an explicit ``n_parts`` is
        ignored — the partition stage must lay trees out evenly over the
        axis).  Mesh-less, kernel-backed algorithms derive the default
        from the kernel's tree-block heuristic (ceil(T / tree_block)):
        one partition per kernel tree block, so the unrolled
        cross-product launches exactly the passes the kernel would make
        anyway — replacing the old magic ``n_parts = 4``.  The jnp
        backends have no tree blocks (their vmap'd partial is one fused
        XLA op regardless), so they keep the small thread-count-like
        default.  Callers can override via ``infer(..., n_parts=...)``.
        """
        if self.fplan.model_axis is not None:
            return self.fplan.n_model
        if n_parts is None:
            if "pallas" not in algorithm:
                return min(4, forest.num_trees)
            _, fused = _predict_sum_fn(algorithm)
            bt = default_tree_block(forest, fused=fused)
            return max(1, -(-forest.num_trees // bt))
        return max(1, int(n_parts))

    # ------------------------------------------------------------------
    # scan batch sizing
    # ------------------------------------------------------------------
    def max_batch_pages(self, ds, forest: Forest, algorithm: str) -> int:
        """Largest scan batch, in pages, whose predictor intermediates
        fit ``PREDICT_BATCH_BYTES`` (a resident table scored in one batch
        would hold [rows, T]-sized intermediates for all of its rows).
        Rounded down to data-axis units; floor one page per device."""
        unit = max(1, self.fplan.n_data)
        features = ds.num_features \
            if getattr(ds, "storage_format", "dense") == "dense" \
            else min(ds.num_features, forest.num_trees * forest.num_internal)
        page_cost = ds.page_rows * predict_row_bytes(algorithm, forest,
                                                     features)
        fit = PREDICT_BATCH_BYTES // max(page_cost, 1)
        return max(unit, fit // unit * unit)

    def default_batch_pages(self, ds, forest: Forest, algorithm: str) -> int:
        """The scan batch ``infer`` runs when no ``batch_pages`` is given
        (the optimizer's hillclimb start and cost-model batch too): the
        whole table on the device tier; off it (host AND disk) half the
        device budget, so two in-flight page buffers together fit it, or
        a fixed footprint when no budget is set — an explicit off-device
        ingest must still stream, never whole-dataset device_put.  Sized
        in data-axis units, rounding DOWN, so the mesh divisibility
        round-up cannot push the pair past the budget.  On every tier
        the batch is capped at ``max_batch_pages``."""
        batch_pages = ds.num_pages
        if getattr(ds, "tier", "device") != "device":
            budget = self.store.device_budget_bytes
            target = budget // 2 if budget else DEFAULT_STREAM_BATCH_BYTES
            unit = max(1, self.fplan.n_data)
            fit = target // max(ds.page_nbytes, 1)
            batch_pages = min(ds.num_pages, max(unit, fit // unit * unit))
        return min(batch_pages, self.max_batch_pages(ds, forest, algorithm))

    # ------------------------------------------------------------------
    # entry point
    # ------------------------------------------------------------------
    def infer(self, dataset: str, forest: Forest, **kw) -> QueryResult:
        """Run the end-to-end inference query — see ``_infer`` for the
        full parameter contract.  This wrapper is the observability
        boundary: with ``obs.TRACER`` enabled the whole query runs under
        a ``query.infer`` root span and the result carries a
        ``TraceSummary`` (per-phase wall totals + the ``METRICS``
        counter deltas the query accrued) at ``QueryResult.trace``;
        disabled (the default), it is a tail call with zero overhead.
        """
        if not TRACER.enabled:
            return self._infer(dataset, forest, **kw)
        mark = TRACER.mark()
        before = METRICS.counter_values()
        with TRACER.span("query.infer", dataset=dataset,
                         plan=kw.get("plan", "udf"),
                         algorithm=kw.get("algorithm", "predicated")
                         ) as root:
            res = self._infer(dataset, forest, **kw)
            root.set(tier=res.tier, storage_format=res.storage_format,
                     reuse_hit=res.reuse_hit)
        res.trace = TRACER.summarize(
            root, since=mark, counters_before=before,
            counters_now=METRICS.counter_values())
        return res

    # ------------------------------------------------------------------
    # in-database training entry point (db/train.py)
    # ------------------------------------------------------------------
    def train(self, dataset: str, cfg, **kw):
        """Train a forest ON a stored dataset, streaming through the same
        tier ladder and ``StreamingScanExecutor`` the inference plans use
        — the other half of the lifecycle (see ``db/train.py`` and
        ``docs/training.md`` for the full contract).

        The trained ``Forest`` lands in the store's model catalog under
        ``model_name`` (default ``f"{dataset}:model"``), tree blocks
        sharded over the mesh ``model`` axis, so it flows straight into
        the serving plane and the optimizer's decision catalog without
        leaving the database.  Returns a ``TrainResult`` whose forest is
        bit-identical to ``core.train.train_forest`` on the resident
        rows given identical bin edges.
        """
        from repro.db.train import train_streaming
        return train_streaming(self, dataset, cfg, **kw)

    # ------------------------------------------------------------------
    # row-level serving entry point (serve/forest.py's hot path)
    # ------------------------------------------------------------------
    def infer_rows(
        self,
        forest: Forest,
        x,
        *,
        row_mask: np.ndarray | None = None,
        algorithm: str = "predicated",
        plan: str = "udf",
        model_id: str | None = None,
        n_parts: int | None = None,
    ) -> RowBatchResult:
        """Score a PRE-PADDED row batch against the compiled-plan cache.

        The serving plane's hot path: ``x`` is ``[B, F]`` dense rows
        already padded to a fixed batch signature (the coalescer's
        bucket ladder), so every call with the same ``(model, algorithm,
        plan, B, F, mesh)`` hits an existing ``CompiledQueryPlan`` —
        no store round-trip, no scan executor, no re-partitioning
        (``rel+reuse`` reuses the cached ``MaterializedModel``), and in
        the steady state ZERO re-tracing (asserted via the
        ``plan.cache_hits``/``plan.cache_misses`` counters and
        ``plan.traces``, exactly like ``infer``).

        ``row_mask`` marks the real rows: predictions for padding rows
        are forced to NaN so coalescer padding can never leak into a
        caller's results.  The bare ``rel`` plan is rejected — serving
        always runs cached executables.

        On a data mesh ``B`` must divide the ``data`` axis; the batch is
        placed under the store's ``data_sharding`` like any scan batch.

        ``plan="auto"`` / ``algorithm="auto"`` resolve through the
        optimizer's row-batch decision (``decide_rows``, persisted per
        (model, batch signature, mesh) — the serve plane resolves this
        once at ``register_model`` instead of per call).
        """
        if plan == "auto" or algorithm == "auto":
            dec = self.optimizer.decide_rows(
                forest, int(getattr(x, "shape", (len(x),))[0]),
                model_id=model_id,
                algorithms=None if algorithm == "auto" else (algorithm,),
                plans=None if plan == "auto" else (plan,))
            algorithm, plan = dec.algorithm, dec.plan
            if n_parts is None:
                n_parts = dec.n_parts
        if plan not in ("udf", "rel+reuse"):
            raise ValueError(
                f"infer_rows serves cached plans only (udf / rel+reuse), "
                f"got {plan!r}")
        t0 = time.perf_counter()
        x = jnp.asarray(x, jnp.float32)
        if x.ndim != 2:
            raise ValueError(f"expected [B, F] rows, got shape {x.shape}")
        B, F = int(x.shape[0]), int(x.shape[1])
        if self.fplan.n_data > 1 and B % self.fplan.n_data:
            raise ValueError(
                f"row batch {B} must divide the mesh data axis "
                f"({self.fplan.n_data}) — pick bucket sizes that are "
                f"axis multiples")
        sharding = self.store.data_sharding()
        if sharding is not None:
            x = jax.device_put(x, sharding)
        mid = self._model_key(forest, model_id)
        mesh_id = mesh_signature(self.mesh)
        batch_sig = (B, F)

        with TRACER.span("query.infer_rows", plan=plan,
                         algorithm=algorithm, batch_rows=B) as sp:
            if plan == "udf":
                pkey = ("udf-row-plan", mid, ROW_PLAN_DATASET, algorithm,
                        "dense", batch_sig, mesh_id)

                def build() -> CompiledQueryPlan:
                    with TRACER.span("plan.build", plan="udf-rows",
                                     algorithm=algorithm):
                        fp, true_T = pad_trees(forest, 1)
                        stages = split_into_stages(
                            self._udf_ops(fp, algorithm, true_T))
                        return CompiledQueryPlan(stages=stages,
                                                 num_stages=len(stages))
            else:
                n_parts = self._resolve_n_parts(forest, algorithm, n_parts)
                mkey = (mid, algorithm, n_parts, mesh_id, "dense")
                mat = self.cache.get_or_build(
                    mkey, lambda: self._partition_model(
                        forest, algorithm, n_parts))
                pkey = ("rel-row-plan", mid, ROW_PLAN_DATASET, algorithm,
                        n_parts, "dense", batch_sig, mesh_id, id(mat))

                def build() -> CompiledQueryPlan:
                    with TRACER.span("plan.build", plan="rel-rows",
                                     algorithm=algorithm):
                        stages = split_into_stages(
                            self._rel_ops(mat, algorithm, n_parts))
                        return CompiledQueryPlan(stages=stages,
                                                 num_stages=len(stages) + 1,
                                                 mat=mat)

            before = self.plan_cache.stats.hits
            qplan = self.plan_cache.get_or_build(pkey, build)
            plan_hit = self.plan_cache.stats.hits > before
            METRICS.counter("plan.cache_hits" if plan_hit
                            else "plan.cache_misses").inc()
            TRACER.event("plan.cache", hit=plan_hit, plan=f"{plan}-rows")

            state, _ = run_stages(qplan.stages, {"x": x})
            preds = state["pred"]
            rows_scored = B
            if row_mask is not None:
                mask = np.asarray(row_mask, bool)
                if mask.shape != (B,):
                    raise ValueError(
                        f"row_mask shape {mask.shape} != ({B},)")
                rows_scored = int(mask.sum())
                # padding rows never leak: their predictions are NaN
                preds = jnp.where(jnp.asarray(mask), preds, jnp.nan)
            sp.set(reuse_hit=plan_hit, rows=rows_scored)

        return RowBatchResult(
            predictions=preds,
            plan_reuse_hit=plan_hit,
            algorithm=algorithm,
            plan=plan,
            batch_rows=B,
            rows_scored=rows_scored,
            total_s=time.perf_counter() - t0,
        )

    def _infer(
        self,
        dataset: str,
        forest: Forest,
        *,
        algorithm: str = "predicated",
        plan: str = "udf",
        batch_pages: int | None = None,
        write_as: str | None = None,
        model_id: str | None = None,
        n_parts: int | None = None,
        prefetch_depth: int = 2,
        deadline_s: float | None = None,
        injector: FaultInjector | None = None,
        retry_policy: RetryPolicy | None = None,
        auto_move: bool = False,
    ) -> QueryResult:
        """Run the end-to-end inference query (paper's measured pipeline).

        ``plan="auto"`` / ``algorithm="auto"`` route through the
        cost-based optimizer (``db/optimizer.py``): the first query per
        (model fingerprint, dataset signature, mesh) pays a bounded
        score + measure pass, every later query resolves the persisted
        decision with a dictionary lookup.  Either axis can be pinned
        while the other stays auto; explicit ``n_parts`` /
        ``batch_pages`` always win over the decision's.  ``auto_move``
        additionally applies the decision's TIER recommendation
        (``store.move`` promotion before the scan — off by default: a
        query should not silently migrate a dataset).

        ``n_parts`` overrides the rel plans' tree-partition count on the
        MESH-LESS path (default: one partition per kernel tree block); a
        model mesh fixes the count to its ``model``-axis size.
        ``prefetch_depth`` controls the streaming executor: 2 (default)
        double-buffers page DMA against compute, 1 runs the synchronous
        reference pipeline (the benchmarks' overlap baseline).

        Reliability (``db/faults.py``, ``docs/reliability.md``):
        ``injector`` / ``retry_policy`` arm the scan's fault sites and
        bound their recovery; ``deadline_s`` is the per-query budget —
        checked cooperatively at batch boundaries, an expired budget
        returns a PARTIAL result whose ``degraded`` report carries the
        rows scored / missing and the exact ``row_mask`` (scored rows
        are bit-identical to an unbounded run; missing rows are NaN).
        """
        decision: Decision | None = None
        if plan == "auto" or algorithm == "auto":
            decision = self.optimizer.decide(
                dataset, forest, model_id=model_id,
                algorithms=None if algorithm == "auto" else (algorithm,),
                plans=None if plan == "auto" else (plan,))
            if auto_move and decision.tier != \
                    getattr(self.store.get(dataset), "tier", "device"):
                self.store.move(dataset, decision.tier)
                # the move changed the dataset signature; re-decide once
                # under the new tier (persisted, so still one-shot)
                decision = self.optimizer.decide(
                    dataset, forest, model_id=model_id,
                    algorithms=None if algorithm == "auto"
                    else (algorithm,),
                    plans=None if plan == "auto" else (plan,))
            algorithm, plan = decision.algorithm, decision.plan
            if n_parts is None:
                n_parts = decision.n_parts
            if batch_pages is None:
                batch_pages = decision.batch_pages
        if plan not in ("udf", "rel", "rel+reuse"):
            raise ValueError(f"unknown plan {plan!r}")
        ds = self.store.get(dataset)
        fmt = getattr(ds, "storage_format", "dense")
        tier = getattr(ds, "tier", "device")
        t_query0 = time.perf_counter()
        # the deadline budgets the WHOLE query from here (plan build +
        # scan), matching what a caller on the request path experiences
        deadline = Deadline(deadline_s, start=t_query0) \
            if deadline_s is not None else None
        if batch_pages is None:
            batch_pages = self.default_batch_pages(ds, forest, algorithm)
        if self.fplan.n_data > 1:
            # shard_map needs page batches that divide evenly over the
            # data axis; num_pages itself is a data-axis multiple (the
            # store pads ingests to guarantee it), so round up and clamp
            nd = self.fplan.n_data
            batch_pages = min(-(-batch_pages // nd) * nd, ds.num_pages)

        # the batch signature pins every block shape the stage jits will
        # see, so a plan-cache hit implies zero re-tracing.  The storage
        # format itself is a SEPARATE plan-key component (a dense and a
        # CSR plan over the same model are different executables); the
        # CSR signature additionally pins the per-page entry capacity.
        mesh_id = mesh_signature(self.mesh)
        if fmt == "csr":
            batch_sig = (ds.num_features, ds.pages.capacity,
                         ds.num_pages, ds.page_rows, batch_pages)
        else:
            batch_sig = (ds.data.shape[1], ds.num_pages,
                         ds.page_rows, batch_pages)

        partition_s = 0.0
        model_hit = False
        plan_hit = False
        prefix_reports: list[StageReport] = []

        # plan keys carry the model id at key[1] (engine.invalidate) and
        # the DATASET NAME at key[2] (store.drop -> invalidate_dataset:
        # a dropped dataset must not leave compiled plans keyed on its
        # batch signature resident)
        if plan == "udf":
            mid = self._model_key(forest, model_id)
            pkey = ("udf-plan", mid, dataset, algorithm, fmt, batch_sig,
                    mesh_id)

            def build_udf() -> CompiledQueryPlan:
                with TRACER.span("plan.build", plan="udf",
                                 algorithm=algorithm, storage_format=fmt):
                    f, sparse_aux = forest, None
                    if fmt == "csr":
                        cf, inv_map, f_used = self._sparse_prepass(forest)
                        f = cf
                        sparse_aux = (inv_map, f_used)
                    fp, true_T = pad_trees(f, 1)
                    stages = split_into_stages(
                        self._udf_ops(fp, algorithm, true_T,
                                      sparse_aux=sparse_aux))
                    return CompiledQueryPlan(stages=stages,
                                             num_stages=len(stages))

            before = self.plan_cache.stats.hits
            qplan = self.plan_cache.get_or_build(pkey, build_udf)
            plan_hit = self.plan_cache.stats.hits > before
            n_parts = 1
        else:
            n_parts = self._resolve_n_parts(forest, algorithm, n_parts)
            t0 = time.perf_counter()
            if plan == "rel+reuse":
                mid = self._model_key(forest, model_id)
                mkey = (mid, algorithm, n_parts, mesh_id, fmt)
                before_hits = self.cache.stats.hits
                mat = self.cache.get_or_build(
                    mkey, lambda: self._partition_model(
                        forest, algorithm, n_parts, storage_format=fmt))
                model_hit = self.cache.stats.hits > before_hits
            else:
                mat = self._partition_model(forest, algorithm, n_parts,
                                            storage_format=fmt)
            partition_s = time.perf_counter() - t0
            prefix_reports = [StageReport(
                name="stageP:model-partition",
                operators=("partition-model",),
                seconds=partition_s,
                materialized_bytes=sum(
                    a.size * a.dtype.itemsize
                    for a in mat.forest.arrays().values()),
                devices=ndevices(mat.forest.arrays()),
            )]

            if plan == "rel+reuse":
                # id(mat) ties the plan entry to THIS materialization: if
                # the model cache evicted and rebuilt the model, the new
                # mat has a new id and the stale plan misses instead of
                # serving stages over the old arrays.  The entry stores
                # mat itself (CompiledQueryPlan.mat) so the keyed id stays
                # pinned for the entry's lifetime — the stage closures
                # alone only capture mat.forest, which would let the
                # wrapper be freed and its id reused
                pkey = ("rel-plan", mid, dataset, algorithm, n_parts, fmt,
                        batch_sig, mesh_id, id(mat))

                def build_rel() -> CompiledQueryPlan:
                    with TRACER.span("plan.build", plan="rel+reuse",
                                     algorithm=algorithm,
                                     storage_format=fmt):
                        stages = split_into_stages(
                            self._rel_ops(mat, algorithm, n_parts))
                        return CompiledQueryPlan(stages=stages,
                                                 num_stages=len(stages) + 1,
                                                 mat=mat)

                before = self.plan_cache.stats.hits
                qplan = self.plan_cache.get_or_build(pkey, build_rel)
                plan_hit = self.plan_cache.stats.hits > before
            else:
                stages = split_into_stages(
                    self._rel_ops(mat, algorithm, n_parts))
                qplan = CompiledQueryPlan(stages=stages,
                                          num_stages=len(stages) + 1)

        reuse_hit = model_hit or plan_hit
        if plan != "rel":
            # the compiled-plan cache was consulted (rel is the paper's
            # deliberately uncached baseline — no consult, no count)
            METRICS.counter("plan.cache_hits" if plan_hit
                            else "plan.cache_misses").inc()
            TRACER.event("plan.cache", hit=plan_hit, plan=plan)

        # F3 batching through the streaming scan executor: ONE loop for
        # every plan/format/tier.  Host-tier pages double-buffer their
        # DMA against the kernel stages; device-tier datasets take the
        # no-op transfer stage.  Per-batch predictions land in the
        # executor's preallocated host buffer — no concatenate on the hot
        # path.
        executor = StreamingScanExecutor(
            qplan.stages,
            sharding=self.store.data_sharding(),
            prefetch_depth=prefetch_depth,
            injector=injector,
            retry_policy=retry_policy,
            deadline=deadline,
            # the device-transfer halving ladder's floor: halved batches
            # must stay divisible by the mesh data axis
            min_batch_pages=max(1, self.fplan.n_data))
        out_np, batch_reports, scan = executor.execute(ds, batch_pages)
        reports: list[StageReport] = list(prefix_reports) + batch_reports
        predictions = jnp.asarray(out_np)

        degraded = None
        if scan.deadline_hit:
            mask = executor.last_mask
            rows_scored = int(mask.sum()) if mask is not None else 0
            degraded = DegradedReport(
                rows_scored=rows_scored,
                rows_missing=ds.num_rows - rows_scored,
                cause="deadline", deadline_s=deadline_s, row_mask=mask)

        write_s = 0.0
        if write_as is not None:
            t0 = time.perf_counter()
            with TRACER.span("query.write", dataset=write_as):
                out = self.store.put_result(write_as, predictions,
                                            ds.num_rows)
                jax.block_until_ready(out.data)
            write_s = time.perf_counter() - t0

        total_s = time.perf_counter() - t_query0

        def _has(rep, *names):
            return any(any(n in op for n in names) for op in rep.operators)

        infer_s = sum(r.seconds for r in reports
                      if _has(r, "forest-udf", "cross-product"))
        agg_s = sum(r.seconds for r in reports
                    if _has(r, "aggregate", "postprocess")
                    and not _has(r, "cross-product", "forest-udf"))
        return QueryResult(
            predictions=predictions,
            plan=plan,
            algorithm=algorithm,
            num_stages=qplan.num_stages,
            stage_reports=reports,
            partition_s=partition_s if not reuse_hit else 0.0,
            infer_s=infer_s,
            aggregate_s=agg_s,
            write_s=write_s,
            total_s=total_s,
            reuse_hit=reuse_hit,
            plan_reuse_hit=plan_hit,
            storage_format=fmt,
            n_parts=n_parts,
            mesh_devices=(self.mesh.size if self.mesh is not None else 1),
            tier=tier,
            scan=scan,
            degraded=degraded,
            decision=decision,
        )
