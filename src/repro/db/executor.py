"""Streaming scan executor: ONE batch loop for every plan and tier.

The paper's headline scenario — in-database inference over datasets that
dwarf the model — only works because netsDB STREAMS page-partitioned
tensor blocks through the scan instead of requiring the whole table to be
resident (Sec. 3.1/6).  Our analogue: the tensor-block store grew a HOST
memory tier (``db/store.py``: page-aligned numpy blocks, spilled to
automatically when an ingest exceeds ``device_budget_bytes``), and this
module is the scan loop that pages those blocks through device memory.

``StreamingScanExecutor`` replaces the hand-rolled per-batch loop that
used to live inside ``ForestQueryEngine.infer``: every plan (udf / rel),
every storage format (dense rows / CSR pages), and every tier (device /
pinned / host / disk) runs the SAME loop.  Sources implement the
``ScanSource`` protocol (``page_slice`` + ``to_device``), so nothing
downstream ever branches on where the pages live — a disk-tier source's
``page_slice`` is an ``np.memmap`` view, so its DMA reads straight off
the file.

The loop is a double-buffered DMA pipeline (``prefetch_depth=2``) with a
TRULY asynchronous drain:

    batch i+1   pages in flight: a copy out of page-locked host memory
                (pinned tier), an async ``jax.device_put`` honoring the
                store's ``data_sharding`` (host/disk tiers), or a no-op
                view (device tier) — issued by the compute thread
    batch i     runs its (shard_map-wrapped or mesh-less) fused kernel
                stages on the compute thread
    batch i-1   predictions drain on a DEDICATED DRAIN WORKER THREAD:
                the compute thread issues ``copy_to_host_async`` and
                hands the prediction to the worker, which completes the
                D2H (through a pinned host staging buffer where the
                backend supports the ``pinned_host`` memory kind) and
                writes it into the preallocated host result buffer

Because the drain runs off the compute thread, batch i−1's D2H no longer
blocks batch i's kernel stages — on hardware with real DMA engines the
copy overlaps compute, and on XLA:CPU the (cheap) host writes still come
off the critical path.  ``ScanStats`` accounts it honestly:
``drain_s`` is the worker's total write time, ``drain_wait_s`` the time
the COMPUTE thread was actually blocked on the drain (queue backpressure
+ the final join), and ``drain_overlap_s`` their difference — the share
of drain work hidden behind compute.  ``prefetch_depth=1`` is the fully
synchronous reference pipeline (inline drain, no worker), which the
benchmarks use as the overlap baseline.

RELIABILITY (``db/faults.py``, ``docs/reliability.md``): every fallible
call in the loop is a named injection site wrapped in a bounded
``RetryPolicy``, and recovery that retries cannot buy degrades down a
ladder instead of failing hard:

  ``disk_page_read``   retries, then RE-ENQUEUES the batch once at the
                       end of the scan plan (slots are deterministic, so
                       order never matters), then raises ``ScanFault``;
  ``page_dma_in``      retries, then resubmits the batch at HALVED
                       ``batch_pages`` (aligned to the data-axis unit —
                       the OOM/transfer-fault ladder), down to one unit,
                       then raises ``ScanFault``;
  ``kernel_launch``    retries, then raises ``ScanFault``;
  ``drain_copy_out``   retries on the worker, then surfaces as a
                       ``ScanFault`` on the compute thread;
  ``drain_worker``     worker-thread DEATH: the submit path uses a
                       timeout-put that re-checks worker liveness (a
                       dead worker + full queue can no longer deadlock
                       the compute thread), recovers the worker's
                       orphaned items, and falls back MID-SCAN to the
                       synchronous ``prefetch_depth=1`` reference path —
                       which is bit-identical, so the fallback is
                       parity-safe (``degraded_to_sync``).

A ``Deadline`` makes the scan budgeted: checked between batches (and
before retry backoffs — cooperative, never preempting a jitted call),
an expired budget stops the scan with ``deadline_hit`` set and the rows
already drained intact; ``ForestQueryEngine.infer(deadline_s=...)``
turns that into a partial ``QueryResult`` with a ``DegradedReport``.
All of this lives in Python driver code between jitted calls — nothing
is traced, so the zero-fault path stays the compiled hot path
(measured in ``BENCH_faults.json``).

At most ``MAX_IN_FLIGHT = 2`` device page buffers exist at any moment —
asserted on every acquire, and reported as ``ScanStats.max_in_flight``.
The drain worker holds per-batch PREDICTIONS ([rows]-sized, not page
buffers), bounded by the queue, so the invariant is unaffected.

See ``docs/architecture.md`` (tier ladder, drain pipeline),
``docs/reliability.md`` (fault sites, ladders, deadline contract) and
``docs/benchmarks.md`` (how the stats surface in BENCH_stream.json /
BENCH_faults.json).
"""

from __future__ import annotations

import dataclasses
import functools
import queue as queue_mod
import threading
import time
from collections import deque
from typing import Any, Iterator, Protocol, runtime_checkable

import jax
import numpy as np

from repro.db.faults import (Deadline, DeadlineExceeded, FaultInjector,
                             InjectedFault, RetryPolicy, ScanFault)
from repro.db.operators import StageReport, run_stages
from repro.obs import METRICS, TRACER

__all__ = ["ScanSource", "ScanStats", "StreamingScanExecutor",
           "MAX_IN_FLIGHT"]

#: hard ceiling on simultaneously live device page buffers: the one being
#: computed on plus the one in DMA flight.  The executor asserts it.
MAX_IN_FLIGHT = 2

#: default per-batch device footprint for HOST-tier scans when the store
#: has no ``device_budget_bytes``: an explicit host ingest must still
#: STREAM (a whole-dataset device_put would defeat the tier), so the
#: query engine caps the default batch at this many bytes per in-flight
#: buffer.
DEFAULT_STREAM_BATCH_BYTES = 64 << 20

#: how long one ``queue.put`` attempt blocks before the submit path
#: re-checks drain-worker liveness.  The put still wakes IMMEDIATELY
#: when the worker frees a slot (condition notify) — the timeout only
#: bounds how long a DEAD worker with a full queue can stall the
#: compute thread before the sync-drain fallback kicks in.
DRAIN_PUT_TIMEOUT_S = 0.05


@runtime_checkable
class ScanSource(Protocol):
    """What the executor needs from a stored dataset (any tier/format).

    Both ``StoredDataset`` and ``SparseStoredDataset`` implement this
    structurally — callers (the executor, the query engine) never branch
    on ``tier`` or ``storage_format``; the source's own ``page_slice`` /
    ``to_device`` encapsulate where pages live and how they reach the
    device.
    """

    name: str
    tier: str                        # "device" | "pinned" | "host" | "disk"
    num_rows: int                    # true N (pre-padding)

    @property
    def num_pages(self) -> int: ...

    @property
    def page_rows(self) -> int: ...

    def page_slice(self, first_page: int, num_pages: int) -> Any:
        """Contiguous page range in the source's OWN tier (device view,
        ``store.PinnedSpan``, host numpy view, or disk mmap view — views,
        not copies, on every tier; a disk view faults in only the pages
        the batch touches)."""
        ...

    def to_device(self, block: Any, sharding: Any = None) -> Any:
        """Stage a block onto device(s).  Pinned tier: one jitted slice of
        the page-locked table, already in the device's layout, into
        device memory — a single DMA with no host-side relayout, no
        staging copy on the host CPU and no host argument (the start row
        is a cached device scalar).  Host/disk tiers: an (async)
        ``jax.device_put`` honoring ``sharding``, which relays the numpy
        rows out on the host CPU.  Device tier: identity (the
        no-op transfer stage)."""
        ...


@dataclasses.dataclass
class ScanStats:
    """Per-query streaming telemetry (attached to ``QueryResult.scan``).

    Every field is documented, with its BENCH_stream.json /
    BENCH_faults.json counterpart, in ``docs/benchmarks.md``.
    """

    tier: str                        # source tier the scan ran against
    batches: int                     # page batches actually executed
    batch_pages: int                 # pages per (full) batch as planned
    prefetch_depth: int              # 1 = synchronous, 2 = double-buffered
    max_in_flight: int = 0           # peak live device page buffers (<= 2)
    bytes_streamed: int = 0          # off-device->device bytes shipped
    pinned_bytes: int = 0            # of which copied from pinned memory
    host_relayout_bytes: int = 0     # of which shipped from numpy pages
    #                                  (host/disk), relaid out on the host
    transfer_issue_s: float = 0.0    # time spent ISSUING device_puts
    transfer_wait_s: float = 0.0     # EXPOSED wait for pages to be ready
    #                                  (what double-buffering hides)
    compute_s: float = 0.0           # host time dispatching each batch's
    #                                  stages: an enqueue, not device time
    drain_s: float = 0.0             # device->host result-buffer writes
    #                                  (on the WORKER thread when async)
    drain_wait_s: float = 0.0        # compute-thread time BLOCKED on the
    #                                  drain (backpressure + final join) —
    #                                  the drain's EXPOSED cost
    drain_async: bool = False        # drain ran on a dedicated worker
    pinned_staging: bool = False     # D2H staged through pinned host mem
    wall_s: float = 0.0              # whole scan loop
    # -- reliability accounting (docs/reliability.md) -----------------------
    retries: int = 0                 # retry re-attempts across all sites
    faults_injected: int = 0         # injector fires observed this scan
    degraded_to_sync: bool = False   # drain-worker death -> mid-scan
    #                                  fallback to the synchronous path
    batch_resubmits: int = 0         # batches re-enqueued (disk-read
    #                                  ladder) or resubmitted at halved
    #                                  size (device-transfer ladder)
    deadline_hit: bool = False       # scan stopped early on its deadline
    #                                  (the result is a PARTIAL)

    @property
    def drain_overlap_s(self) -> float:
        """Drain work hidden behind compute: worker write time minus the
        compute thread's exposed drain wait.  The inline drain (depth 1)
        charges every write to BOTH fields, so this is 0 there — only the
        async drain can hide work."""
        return max(0.0, self.drain_s - self.drain_wait_s)


@dataclasses.dataclass
class _InFlight:
    """One acquired batch: its page span + the (maybe mid-DMA) block."""

    index: int
    first_page: int
    num_pages: int
    block: Any


def _block_nbytes(block) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(block)
               if hasattr(x, "dtype"))


@functools.lru_cache(maxsize=None)
def _pinned_host_sharding(device):
    """A ``pinned_host`` sharding on ``device`` — the D2H staging target
    of a prediction that lives there (TPU/GPU: a real DMA fast path).

    Only an XLA:CPU build without the memory kind yields None.  An
    accelerator without it raises: pinned staging must never switch off
    in silence where it is the fast path."""
    try:
        device.memory("pinned_host")  # raises if the kind doesn't exist
    except (RuntimeError, ValueError):
        if device.platform == "cpu":
            return None
        raise
    return jax.sharding.SingleDeviceSharding(device, memory_kind="pinned_host")


class _ResultSink:
    """The preallocated host result buffer + the drain that fills it.

    ``write`` completes one batch's D2H (optionally staging through a
    pinned host buffer) and stores the rows at their deterministic slot
    — retried under the ``drain_copy_out`` site (the slot write is
    idempotent, so a retried write is parity-safe).  ``drain_loop`` is
    the dedicated worker thread's body: it consumes (first_page,
    num_pages, prediction) items until the ``None`` sentinel, never
    letting one batch's failure wedge the queue (a write error is kept
    and re-raised on the compute thread; an injected ``drain_worker``
    fault models the THREAD dying — the worker parks the item it was
    holding in ``orphans`` and exits, and the compute thread recovers
    it through ``drain_pending`` when the sync fallback kicks in).
    """

    def __init__(self, total_rows: int, page_rows: int,
                 stats: ScanStats, *,
                 injector: FaultInjector | None = None,
                 policy: RetryPolicy | None = None,
                 track_mask: bool = False):
        self.total_rows = total_rows
        self.page_rows = page_rows
        self.stats = stats
        self.injector = injector
        self.policy = policy
        self.result: np.ndarray | None = None    # allocated at first write
        self.error: BaseException | None = None
        self.dead = False                # drain_worker fault: thread died
        self.orphans: list = []          # items a dying worker parked
        self.rows_written = 0            # padded rows landed so far
        # which rows landed — only tracked for deadline-budgeted scans
        # (the partial result's DegradedReport needs the exact mask; the
        # unbudgeted hot path skips the bookkeeping)
        self.mask: np.ndarray | None = (
            np.zeros(total_rows, bool) if track_mask else None)

    @staticmethod
    def pinned_for(pred):
        """The pinned staging sharding on ``pred``'s own device, or None.
        Pinned staging applies to single-device predictions only: sharded
        mesh outputs take the per-shard host gather instead."""
        sharding = getattr(pred, "sharding", None)
        if sharding is None or len(sharding.device_set) != 1:
            return None
        (device,) = sharding.device_set
        return _pinned_host_sharding(device)

    def _write_once(self, first_page: int, num_pages: int, pred) -> None:
        t0 = time.perf_counter()
        pinned = self.pinned_for(pred)
        if pinned is not None:
            # D2H DMA into pinned staging; np.asarray of a pinned_host
            # array is then a cheap host-side view/copy.  This is the
            # ONLY transfer on this path — submit() skips the plain
            # copy_to_host_async for pinned-eligible predictions, else
            # every batch would pay the D2H twice.
            pred = jax.device_put(pred, pinned)
            self.stats.pinned_staging = True
        host = np.asarray(pred)                  # per-shard copy + stitch
        if self.result is None:
            fill = (np.full(self.total_rows, np.nan, host.dtype)
                    if self.mask is not None
                    else np.empty(self.total_rows, host.dtype))
            self.result = fill
        lo = first_page * self.page_rows
        hi = lo + num_pages * self.page_rows
        self.result[lo:hi] = host.reshape(-1)
        if self.mask is not None:
            self.mask[lo:hi] = True
        self.rows_written += hi - lo
        self.stats.drain_s += time.perf_counter() - t0

    def _count_retry(self):
        self.stats.retries += 1

    def write(self, first_page: int, num_pages: int, pred,
              parent=None) -> None:
        """One batch's drain, guarded at the ``drain_copy_out`` site.

        ``parent`` is the owning batch's span (captured on the COMPUTE
        thread): the drain worker's write span nests under it even
        though the two live on different threads — that cross-thread
        edge is what makes the async drain's overlap legible in the
        exported trace."""
        with TRACER.span("scan.drain_write", parent=parent,
                         first_page=first_page, num_pages=num_pages):
            if self.policy is None and self.injector is None:
                return self._write_once(first_page, num_pages, pred)
            if self.policy is None:
                self.injector.fire("drain_copy_out")
                return self._write_once(first_page, num_pages, pred)
            return self.policy.run(
                lambda: self._write_once(first_page, num_pages, pred),
                site="drain_copy_out", injector=self.injector,
                on_retry=self._count_retry)

    def drain_loop(self, q: queue_mod.Queue) -> None:
        while True:
            item = q.get()
            try:
                if item is None:
                    return
                if self.injector is not None:
                    try:
                        self.injector.fire("drain_worker")
                    except InjectedFault:
                        # the THREAD dies here (not a write error): park
                        # the item so the compute thread can recover it,
                        # then exit without draining the rest
                        self.dead = True
                        self.orphans.append(item)
                        return
                if self.error is None:           # fail fast, keep draining
                    try:
                        self.write(*item)
                    except BaseException as e:   # noqa: BLE001 — re-raised
                        self.error = e           # on the compute thread
            finally:
                q.task_done()

    def drain_pending(self, q: queue_mod.Queue | None) -> None:
        """Compute-thread recovery: write everything a dead worker left
        behind — its parked orphan plus any queued-but-unprocessed items
        (and swallow the stranded sentinel).  Idempotent and safe on a
        healthy shutdown (both lists empty)."""
        items = list(self.orphans)
        self.orphans = []
        if q is not None:
            while True:
                try:
                    items.append(q.get_nowait())
                except queue_mod.Empty:
                    break
        for it in items:
            if it is None or self.error is not None:
                continue
            try:
                self.write(*it)
            except BaseException as e:           # noqa: BLE001 — re-raised
                self.error = e                   # by the caller


class StreamingScanExecutor:
    """Runs compiled plan stages over a ``ScanSource``, page batch by
    page batch, with double-buffered host->device paging.

    One instance per query execution; ``stages`` is the compiled stage
    list (``db/operators.Stage``) whose final state carries the per-batch
    predictions under ``result_key``.

    ``injector`` / ``retry_policy`` / ``deadline`` opt the scan into the
    reliability layer (``db/faults.py``); all three default off, and the
    fault-free path with them off is byte-for-byte the old loop.
    ``min_batch_pages`` is the floor of the device-transfer halving
    ladder (the query engine passes the mesh data-axis unit so halved
    batches stay shard_map-divisible).
    """

    def __init__(self, stages, *, sharding=None, prefetch_depth: int = 2,
                 result_key: str | None = "pred",
                 injector: FaultInjector | None = None,
                 retry_policy: RetryPolicy | None = None,
                 deadline: Deadline | None = None,
                 min_batch_pages: int = 1):
        if not 1 <= prefetch_depth <= MAX_IN_FLIGHT:
            raise ValueError(
                f"prefetch_depth must be in [1, {MAX_IN_FLIGHT}], "
                f"got {prefetch_depth}")
        self.stages = stages
        self.sharding = sharding          # store.data_sharding() (or None)
        self.prefetch_depth = prefetch_depth
        self.result_key = result_key
        self.injector = injector
        # an armed injector with no explicit policy still recovers: the
        # default policy is the documented 3-attempt/backoff contract
        self.retry_policy = retry_policy if retry_policy is not None \
            else (RetryPolicy() if injector is not None else None)
        self.deadline = deadline
        self.min_batch_pages = max(1, int(min_batch_pages))
        # row mask of the last execute() when it hit its deadline (the
        # engine turns it into the DegradedReport); None otherwise
        self.last_mask: np.ndarray | None = None

    # -- batch plan ---------------------------------------------------------
    @staticmethod
    def batch_plan(num_pages: int, batch_pages: int
                   ) -> Iterator[tuple[int, int, int]]:
        """Deterministic (batch_index, first_page, num_pages) plan — the
        F3 batching loop AND the replay unit: batch k always covers the
        same pages, whatever tier they live on.  Under the fault ladders
        the plan is only ever REORDERED or SPLIT (re-enqueue, halving) —
        every page span still lands at its deterministic slot."""
        for k, first in enumerate(range(0, num_pages, batch_pages)):
            yield k, first, min(batch_pages, num_pages - first)

    # -- guarded sites ------------------------------------------------------
    def _guard(self, fn, site: str, stats: ScanStats):
        """Run ``fn`` at injection site ``site`` under the retry policy.
        The zero-instrumentation path (no injector, no policy) is a
        direct call — nothing on the hot path but this dispatch."""
        if self.retry_policy is None:
            if self.injector is not None:
                self.injector.fire(site)
            return fn()

        def count():
            stats.retries += 1

        return self.retry_policy.run(fn, site=site, injector=self.injector,
                                     on_retry=count, deadline=self.deadline)

    @property
    def _retryable(self) -> tuple:
        return (self.retry_policy.retryable if self.retry_policy is not None
                else (InjectedFault, OSError))

    @property
    def _attempts(self) -> int:
        return (self.retry_policy.max_attempts
                if self.retry_policy is not None else 1)

    # -- execution ----------------------------------------------------------
    def execute(self, source: ScanSource, batch_pages: int, *,
                extras=None, on_batch=None
                ) -> tuple[np.ndarray | None, list[StageReport], ScanStats]:
        """Stream every page batch of ``source`` through the stages.

        Returns (predictions [num_rows] host f32, per-batch stage
        reports, ScanStats).  Predictions land in a PREALLOCATED host
        buffer slot by slot — no concatenate anywhere on the hot path.
        With ``prefetch_depth=2`` the buffer is filled by a dedicated
        drain worker thread, so batch i−1's D2H never blocks batch i's
        kernel stages; depth 1 drains inline (the synchronous reference).

        Two hooks open the loop to REDUCTION scans (the in-database
        trainer, ``db/train.py``); both default off and cost nothing when
        unused:

          * ``extras(first_page, num_pages) -> dict`` — per-batch extra
            stage inputs, merged into the initial stage state next to
            ``"x"`` (the trainer feeds each batch's slice of the node-of
            relation to the routing stage this way);
          * ``on_batch(first_page, num_pages, state)`` — called on the
            compute thread right after the stages, BEFORE the drain
            submit, in plan order (the trainer accumulates its gradient
            histograms here).  On an injector-free scan the plan is never
            reordered or split, so the hook sees every batch exactly once
            in global row order — order-sensitive reductions must run
            with the reliability ladders off.

        A scan whose only product flows through ``on_batch`` can pass
        ``result_key=None`` to the constructor: the drain (worker thread,
        result buffer, D2H) is skipped entirely and ``execute`` returns
        ``None`` predictions.

        Failure semantics: transient faults at the injection sites are
        retried and degraded down the ladders (see the module
        docstring); recovery is bit-identical.  Exhausted ladders raise
        a structured ``ScanFault``; an expired ``deadline`` returns the
        PARTIAL buffer with ``stats.deadline_hit`` set and
        ``self.last_mask`` marking the rows that landed.
        """
        R = source.page_rows
        # (first_page, num_pages) spans; a deque because the fault
        # ladders re-enqueue (append) and split (appendleft) mid-scan
        pending: deque[tuple[int, int]] = deque(
            (first, n) for _, first, n in
            self.batch_plan(source.num_pages, batch_pages))
        n_planned = len(pending)
        stats = ScanStats(tier=source.tier, batches=0,
                          batch_pages=batch_pages,
                          prefetch_depth=self.prefetch_depth)
        reports: list[StageReport] = []
        bufs: deque[_InFlight] = deque()   # acquired, not yet computed
        live = 0                           # live device page buffers
        batch_idx = 0
        resubmitted: set[tuple[int, int]] = set()   # disk-ladder once-only
        fired0 = self.injector.total_fired if self.injector else 0
        deadline = self.deadline
        retryable = self._retryable
        t_wall = time.perf_counter()

        # the async drain rides with double-buffering; depth 1 keeps the
        # drain inline as the fully synchronous reference pipeline
        # (drainless reduction scans skip the worker entirely)
        async_drain = (self.prefetch_depth >= 2 and n_planned > 1
                       and self.result_key is not None)
        # effective depth can DEGRADE mid-scan (drain-worker death ->
        # the synchronous reference path); the stats keep the requested
        # depth and flag the degradation separately
        depth = self.prefetch_depth
        sink = _ResultSink(source.num_pages * R, R, stats,
                           injector=self.injector,
                           policy=self.retry_policy,
                           track_mask=deadline is not None)
        drain_q: queue_mod.Queue | None = None
        worker: threading.Thread | None = None
        drain_active = False
        if async_drain:
            stats.drain_async = True
            # bounded: backpressure caps how many [rows]-sized prediction
            # arrays (NOT page buffers) the drain can hold behind compute
            drain_q = queue_mod.Queue(maxsize=MAX_IN_FLIGHT)
            worker = threading.Thread(target=sink.drain_loop,
                                      args=(drain_q,),
                                      name="scan-drain", daemon=True)
            worker.start()
            drain_active = True

        def put_drain(item) -> bool:
            """Timeout-put that re-checks worker liveness: a dead worker
            with a full queue can no longer wedge the compute thread in
            a blocking ``put`` forever (the latent deadlock).  Returns
            False when the worker is dead — the caller degrades to the
            synchronous drain."""
            while True:
                if sink.dead or not worker.is_alive():
                    return False
                try:
                    drain_q.put(item, timeout=DRAIN_PUT_TIMEOUT_S)
                    return True
                except queue_mod.Full:
                    continue

        def degrade_to_sync():
            """Drain-worker death ladder: recover the worker's orphaned
            items on the compute thread and continue as the synchronous
            ``prefetch_depth=1`` reference path — bit-identical, so the
            mid-scan switch is parity-safe."""
            nonlocal drain_active, depth
            drain_active = False
            depth = 1
            stats.degraded_to_sync = True
            TRACER.event("degrade.sync_drain")
            worker.join(timeout=5.0)
            sink.drain_pending(drain_q)

        def try_acquire() -> bool:
            """Acquire the next pending span through the disk-read and
            device-transfer sites.  Returns False when a fault ladder
            consumed the attempt (the span was re-enqueued or split) —
            the caller just loops."""
            nonlocal live
            first, n = pending[0]
            try:
                if source.tier == "disk":
                    with TRACER.span("scan.disk_read", first_page=first,
                                     num_pages=n):
                        block = self._guard(
                            lambda: source.page_slice(first, n),
                            "disk_page_read", stats)
                else:
                    block = source.page_slice(first, n)
            except DeadlineExceeded:
                raise
            except retryable as e:
                # disk-read ladder: re-enqueue the batch ONCE at the end
                # of the plan (deterministic slots: order is irrelevant),
                # then fail structured
                pending.popleft()
                if (first, n) not in resubmitted:
                    resubmitted.add((first, n))
                    pending.append((first, n))
                    stats.batch_resubmits += 1
                    TRACER.event("batch.resubmit", site="disk_page_read",
                                 first_page=first, num_pages=n)
                    return False
                raise ScanFault("disk_page_read",
                                attempts=2 * self._attempts,
                                rows_completed=min(sink.rows_written,
                                                   source.num_rows),
                                cause=e) from e
            t0 = time.perf_counter()
            try:
                with TRACER.span("scan.dma_in", first_page=first,
                                 num_pages=n):
                    block = self._guard(
                        lambda: source.to_device(block, self.sharding),
                        "page_dma_in", stats)         # async DMA
            except DeadlineExceeded:
                raise
            except retryable as e:
                # device-transfer ladder: resubmit at HALVED batch size
                # (aligned to the data-axis unit) before erroring — the
                # OOM answer: two half-batches fit where one batch faulted
                unit = self.min_batch_pages
                pending.popleft()
                if n > unit:
                    n1 = max(unit, (n // 2) // unit * unit)
                    pending.appendleft((first + n1, n - n1))
                    pending.appendleft((first, n1))
                    stats.batch_resubmits += 1
                    TRACER.event("batch.resubmit", site="page_dma_in",
                                 first_page=first, num_pages=n)
                    return False
                raise ScanFault("page_dma_in", attempts=self._attempts,
                                rows_completed=min(sink.rows_written,
                                                   source.num_rows),
                                cause=e) from e
            stats.transfer_issue_s += time.perf_counter() - t0
            pending.popleft()
            if source.tier != "device":
                nbytes = _block_nbytes(block)
                stats.bytes_streamed += nbytes
                if source.tier == "pinned":
                    stats.pinned_bytes += nbytes
                else:
                    stats.host_relayout_bytes += nbytes
            live += 1
            stats.max_in_flight = max(stats.max_in_flight, live)
            assert live <= MAX_IN_FLIGHT, \
                f"{live} device page buffers in flight (max {MAX_IN_FLIGHT})"
            bufs.append(_InFlight(len(resubmitted) + live, first, n, block))
            return True

        def submit(first: int, n: int, pred, batch_span=None):
            """Hand batch i's prediction to the drain.  The D2H copy is
            issued async HERE (on the compute thread) so it progresses
            while the worker is busy; the worker completes and writes it.
            Pinned-eligible predictions skip the plain async copy — their
            one and only D2H is the worker's device_put into pinned
            staging (two transfers would waste the DMA bandwidth the
            pinned path exists to save).  ``batch_span`` rides the queue
            item so the drain worker's ``scan.drain_write`` span nests
            under the owning batch even across the thread hop."""
            if sink.pinned_for(pred) is None \
                    and hasattr(pred, "copy_to_host_async"):
                pred.copy_to_host_async()
            t0 = time.perf_counter()
            with TRACER.span("scan.drain_submit", first_page=first,
                             num_pages=n):
                if drain_active:
                    if put_drain((first, n, pred, batch_span)):
                        stats.drain_wait_s += time.perf_counter() - t0
                        return
                    degrade_to_sync()    # dead worker: recover + go sync
                try:
                    sink.write(first, n, pred, batch_span)
                except retryable as e:
                    raise ScanFault("drain_copy_out",
                                    attempts=self._attempts,
                                    rows_completed=min(sink.rows_written,
                                                       source.num_rows),
                                    cause=e) from e
                finally:
                    stats.drain_wait_s += time.perf_counter() - t0

        # one span per execute(); everything the loop does — dma-in,
        # per-batch compute, the drain worker's cross-thread writes —
        # nests under it, so one exported trace IS the scan timeline
        with TRACER.span("scan.execute", tier=source.tier,
                         batch_pages=batch_pages,
                         prefetch_depth=self.prefetch_depth) as scan_span:
            try:
                while pending or bufs:
                    if sink.error is not None:
                        break             # a drained batch already
                    #                       failed: don't pay for the
                    #                       rest of the scan first
                    if deadline is not None and deadline.expired:
                        stats.deadline_hit = True
                        TRACER.event("deadline.hit")
                        break             # budget spent: keep what landed
                    try:
                        if not bufs:
                            if not try_acquire():
                                continue  # ladder adjusted the plan
                        cur = bufs.popleft()
                        # batch i+1: issue its page DMA while batch i
                        # computes.  The prefetch acquire runs BEFORE the
                        # batch span opens so next-batch scan.dma_in spans
                        # parent to scan.execute, not to a batch they
                        # don't belong to.
                        while len(bufs) + 1 < depth and pending:
                            if not try_acquire():
                                break     # ladder adjusted the plan
                        with TRACER.span("scan.batch", index=batch_idx,
                                         first_page=cur.first_page,
                                         num_pages=cur.num_pages
                                         ) as batch_span:
                            t0 = time.perf_counter()
                            with TRACER.span("scan.transfer_wait"):
                                jax.block_until_ready(cur.block)
                            stats.transfer_wait_s += \
                                time.perf_counter() - t0
                            t0 = time.perf_counter()
                            init_state = {"x": cur.block}
                            if extras is not None:
                                init_state.update(
                                    extras(cur.first_page, cur.num_pages))
                            try:
                                with TRACER.span("scan.compute"):
                                    state, reps = self._guard(
                                        lambda: run_stages(
                                            self.stages, init_state),
                                        "kernel_launch", stats)
                            except retryable as e:
                                raise ScanFault(
                                    "kernel_launch",
                                    attempts=self._attempts,
                                    rows_completed=min(sink.rows_written,
                                                       source.num_rows),
                                    cause=e) from e
                            stats.compute_s += time.perf_counter() - t0
                            reports.extend(reps)
                            stats.batches += 1
                            batch_idx += 1
                            if on_batch is not None:
                                on_batch(cur.first_page, cur.num_pages,
                                         state)
                            if self.result_key is not None:
                                submit(cur.first_page, cur.num_pages,
                                       state[self.result_key], batch_span)
                        # release the page buffer NOW: some plans thread
                        # "x" through to the final stage output, so
                        # dropping `state` (not just cur.block) is what
                        # actually frees the device pages — else a third
                        # buffer would be alive during the next prefetch
                        state = None
                        cur.block = None          # at most 2 ever live
                        live -= 1
                    except DeadlineExceeded:
                        # budget expired inside a retry loop: same
                        # graceful exit as the between-batches check
                        stats.deadline_hit = True
                        TRACER.event("deadline.hit")
                        break
            finally:
                # shut the worker down on EVERY exit: a failing stage
                # (or the in-flight assert) must not strand the daemon
                # thread in q.get() pinning the result buffer for the
                # process lifetime.  put_drain (not a blocking put) so a
                # dead worker + full queue cannot deadlock the shutdown
                # either; drain_pending then recovers anything a dead
                # worker left behind.
                if async_drain:
                    t0 = time.perf_counter()
                    # the query's exposed tail: the last batches' drain
                    with TRACER.span("scan.drain_join"):
                        if drain_active:
                            put_drain(None)   # sentinel: no more batches
                        worker.join(timeout=5.0)
                        if sink.dead:
                            stats.degraded_to_sync = True
                        sink.drain_pending(drain_q)
                    stats.drain_wait_s += time.perf_counter() - t0
                scan_span.set(batches=stats.batches,
                              bytes_streamed=stats.bytes_streamed,
                              retries=stats.retries,
                              batch_resubmits=stats.batch_resubmits,
                              degraded_to_sync=stats.degraded_to_sync,
                              deadline_hit=stats.deadline_hit)
                # process-global rollups (docs/observability.md):
                # counted on every exit — a faulted scan still counts
                METRICS.counter("scan.batches").inc(stats.batches)
                METRICS.counter("scan.bytes_streamed").inc(
                    stats.bytes_streamed)
                METRICS.counter("scan.pinned_bytes").inc(stats.pinned_bytes)
                METRICS.counter("scan.host_relayout_bytes").inc(
                    stats.host_relayout_bytes)
                METRICS.counter("scan.retries").inc(stats.retries)
                METRICS.counter("scan.batch_resubmits").inc(
                    stats.batch_resubmits)
                if stats.degraded_to_sync:
                    METRICS.counter("scan.degraded_to_sync").inc()
                if stats.deadline_hit:
                    METRICS.counter("scan.deadline_hits").inc()
        if self.injector is not None:
            stats.faults_injected = self.injector.total_fired - fired0
            METRICS.counter("scan.faults_injected").inc(
                stats.faults_injected)
        if sink.error is not None:
            e = sink.error
            if isinstance(e, retryable):
                raise ScanFault("drain_copy_out", attempts=self._attempts,
                                rows_completed=min(sink.rows_written,
                                                   source.num_rows),
                                cause=e) from e
            raise e

        stats.wall_s = time.perf_counter() - t_wall
        if self.result_key is None:   # drainless reduction scan
            self.last_mask = None
            return None, reports, stats
        if sink.result is None:
            assert stats.deadline_hit, "scan produced no batches"
            # deadline expired before the first batch landed: an all-NaN
            # partial (rows_scored == 0) is still the graceful contract
            sink.result = np.full(source.num_pages * R, np.nan, np.float32)
        self.last_mask = (sink.mask[: source.num_rows]
                          if stats.deadline_hit and sink.mask is not None
                          else None)
        return sink.result[: source.num_rows], reports, stats
