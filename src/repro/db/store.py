"""The tensor-block store: netsDB's native storage, tiered.

Paper Sec. 3.1: "the input samples are stored as a collection of tensor
blocks, called sample blocks. Each block is a 2D tensor that represents a
vector of feature vectors."  Our mapping (DESIGN.md Sec. 3): a stored dataset
is ONE array [N, F] laid out as ``page_rows``-row pages, sharded over the
mesh ``data`` axis (and replicated over ``model``), plus a catalog entry.
"In-database inference" = the query plan consumes these buffers directly;
the external path (db/loader.py) must parse + convert + transfer through
the host first — exactly the boundary whose cost the paper measures.

Pages are the batching unit (paper F3): a batch is a contiguous page range,
and the page↔step mapping is deterministic (page p of batch k is always the
same rows), which is what makes failure replay exact (DESIGN.md Sec. 8).

Storage formats: the catalog tags every dataset with a ``storage_format``.
``dense`` is the original [N, F] layout; ``csr`` is the sparse data plane
(``db/sparse.CSRPages``: fixed-capacity CSR page blocks, same page↔batch
determinism, consumed through the feature-gather prepass instead of being
densified at full F).  Query plans key their compiled-plan cache on the
format, so a dense and a CSR plan over the same model never collide.

Memory tiers: every dataset also lives on exactly one rung of the TIER
LADDER (see ``docs/architecture.md`` for the full design):

  ``device``  the original layout — device-resident jax arrays, consumed
              by kernels with zero staging (dataset size capped by HBM);
  ``pinned``  page-locked host memory in the chip's own layout: one jax
              array in the device's ``pinned_host`` memory kind, relaid
              out once at ingest (``store.pin``).  Its pages stay in
              host RAM, and a scan batch reaches the device as one
              memory-space copy of its page range (``_pinned_take``),
              with no host-side relayout — dense rows only;
  ``host``    page-aligned host numpy blocks — the in-RAM out-of-core
              tier.  The streaming scan executor (``db/executor.py``)
              pages a host dataset through device memory batch by batch,
              double buffered, so datasets far larger than device memory
              execute; each batch is relaid out into the device's layout
              on the host CPU as it is shipped;
  ``disk``    page-aligned memory-mapped files under the store's
              ``spill_dir`` — the bottom rung.  Dense rows are one mmap
              file; a CSR dataset is three (indptr / indices / values
              page arrays).  A disk dataset's ``page_slice`` is an
              ``np.memmap`` VIEW: only the pages a batch actually
              touches are ever faulted in, so the SCAN's steady-state
              host residency is bounded by the batch, not the dataset.
              (Ingest itself still materializes the array once in host
              RAM while writing the file — the tier bounds scan-time
              residency, not ingest residency.)

``put(..., tier=...)`` / ``put_sparse(..., tier=...)`` accept an explicit
tier or ``"auto"``: the auto cascade walks device -> host -> disk — an
ingest that would push the device-resident total past
``device_budget_bytes`` spills to host, and one that would also push the
host-resident total past ``host_budget_bytes`` spills to disk.  The
``pinned`` rung is only ever asked for by name; its bytes are host RAM,
so they count toward ``host_nbytes`` and the host budget.  Catalog
entries carry the tier, and the store accounts ``nbytes`` PER TIER
(``device_nbytes`` / ``pinned_nbytes`` / ``host_nbytes`` /
``disk_nbytes``).  ``store.move`` migrates a dataset between any two
tiers preserving the page layout exactly; ``store.drop`` deletes the
spill files the store created.  Both dataset classes implement the
executor's ``ScanSource`` protocol (``page_slice`` in their own tier +
``to_device`` staging), so no caller ever branches on where pages live.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import re
import tempfile
import time
import weakref
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.db.faults import (FaultInjector, InjectedFault, RetryPolicy,
                             ScanFault)
from repro.db.sparse import CSRPages, csr_from_dense, paginate_csr
from repro.obs import METRICS, TRACER

__all__ = ["StoredDataset", "SparseStoredDataset", "TensorBlockStore",
           "DenseStreamWriter", "PinnedSpan", "mmap_array", "TIERS"]

#: the tier ladder, fastest first.  The ``auto`` cascade walks it
#: top-down but skips ``pinned``, which an ingest names explicitly.
TIERS = ("device", "pinned", "host", "disk")

#: memory kind of the ``pinned`` tier's pages
PINNED = "pinned_host"


def _check_tier(tier: str) -> str:
    if tier not in TIERS:
        raise ValueError(f"unknown tier {tier!r}; expected one of {TIERS}")
    return tier


def _dense_only(tier: str) -> str:
    """The tiers a CSR dataset may live on: all but ``pinned``."""
    if _check_tier(tier) == "pinned":
        raise ValueError("the pinned tier holds dense rows only; store a "
                         "CSR dataset on the device, host or disk tier")
    return tier


@functools.partial(jax.jit, static_argnames="rows")
def _pinned_take(data, lo, *, rows):
    """Rows ``[lo, lo + rows)`` of a pinned table, copied to device memory
    by one program: a slice out of the ``pinned_host`` memory space,
    which the compiler issues as one DMA.  ``lo`` is a traced device
    scalar (``_row_start``), so every batch of one size shares one
    executable and ships no host argument."""
    return jax.device_put(jax.lax.dynamic_slice_in_dim(data, lo, rows, 0),
                          jax.memory.Space.Device)


@functools.lru_cache(maxsize=4096)
def _row_start(lo: int, sharding) -> jax.Array:
    """Row ``lo`` as an int32 scalar in device memory beside a pinned
    table of ``sharding``.  Each start is put once and kept, so a scan's
    page copies after its first carry no host-to-device transfer."""
    if isinstance(sharding, NamedSharding):
        where = NamedSharding(sharding.mesh, P())
    else:
        where = SingleDeviceSharding(next(iter(sharding.device_set)))
    return jax.device_put(np.int32(lo), where)


@dataclasses.dataclass(frozen=True)
class PinnedSpan:
    """A page range of a ``pinned`` table: where the rows lie, not a
    copy.  ``StoredDataset.to_device`` copies it to device memory."""

    data: jax.Array               # the whole [N_padded, F] pinned table
    lo: int                       # first row
    rows: int


def _host_copy(a) -> np.ndarray:
    """Materialize ANY tier's array as a plain host ndarray copy (mmap
    views must be read fully off the file before the file can go away)."""
    out = jax.device_get(a)
    return np.array(out) if isinstance(out, np.memmap) \
        else np.ascontiguousarray(out)


def mmap_array(path: str, arr: np.ndarray) -> np.memmap:
    """Write ``arr`` to ``path`` as a raw page-aligned memory-mapped file
    and return the live map.

    Raw (headerless) layout at offset 0, C-contiguous: logical store page
    ``p`` occupies exactly bytes ``[p * page_nbytes, (p+1) * page_nbytes)``
    of the file, so a ``page_slice`` view faults in only the OS pages that
    batch touches.  An existing file is unlinked first (never truncated in
    place — truncating a mapped file SIGBUSes readers of the old map; the
    unlinked inode stays alive for them).
    """
    if os.path.exists(path):
        os.unlink(path)
    mm = np.memmap(path, dtype=arr.dtype, mode="w+", shape=arr.shape)
    mm[...] = arr
    mm.flush()
    return mm


@dataclasses.dataclass
class StoredDataset:
    name: str
    data: Any                     # [N_padded, F]: jax.Array (device tier,
    #                               row-sharded; in the pinned_host
    #                               memory kind on the pinned tier),
    #                               np.ndarray (host tier,
    #                               page-aligned pages), or np.memmap
    #                               (disk tier, page-aligned mmap file)
    num_rows: int                 # true N (pre-padding)
    page_rows: int
    labels: jax.Array | None = None
    task: str = "classification"
    created_at: float = dataclasses.field(default_factory=time.time)
    storage_format: str = "dense"
    tier: str = "device"

    @property
    def num_features(self) -> int:
        return self.data.shape[1]

    @property
    def num_pages(self) -> int:
        return self.data.shape[0] // self.page_rows

    @property
    def nbytes(self) -> int:
        return self.data.size * self.data.dtype.itemsize

    @property
    def page_nbytes(self) -> int:
        """Bytes of ONE page — the unit the streaming executor budgets."""
        return self.nbytes // max(self.num_pages, 1)

    def page_slice(self, first_page: int, num_pages: int):
        """[num_pages * page_rows, F] contiguous page range, a VIEW in the
        dataset's own tier (device slice / ``PinnedSpan`` / host numpy
        view / np.memmap view — a disk-tier slice stays lazy: only the OS
        pages the batch touches are faulted in, never the whole file)."""
        lo = first_page * self.page_rows
        rows = num_pages * self.page_rows
        if self.tier == "device":
            return jax.lax.dynamic_slice_in_dim(self.data, lo, rows, axis=0)
        if self.tier == "pinned":
            return PinnedSpan(self.data, lo, rows)
        return self.data[lo: lo + rows]

    def to_device(self, block, sharding=None):
        """ScanSource staging: the pinned tier copies its span from pinned
        host memory to device memory (``scan.page_copy``,
        ``_pinned_take``; the rows keep the table's sharding);
        host/disk tiers issue an (async)
        device_put honoring the store's data sharding, which relays the
        numpy rows out on the host CPU (a disk-tier mmap view is read
        straight into the transfer — no intermediate host copy of the
        whole dataset ever exists); device tier is a no-op."""
        if self.tier == "device":
            return block
        if self.tier == "pinned":
            with TRACER.span("scan.page_copy", rows=block.rows):
                return _pinned_take(
                    block.data, _row_start(block.lo, block.data.sharding),
                    rows=block.rows)
        return jax.device_put(block, sharding)

    def batches(self, pages_per_batch: int) -> Iterator[tuple[int, Any]]:
        """Deterministic (batch_index, block) iteration — the F3 batching
        loop AND the replay unit: batch k always covers the same pages."""
        for k, first in enumerate(range(0, self.num_pages, pages_per_batch)):
            n = min(pages_per_batch, self.num_pages - first)
            yield k, self.page_slice(first, n)


@dataclasses.dataclass
class SparseStoredDataset:
    """A CSR-paged dataset: the sparse plane's analogue of StoredDataset.

    Same page↔batch determinism (a batch is a contiguous page range and
    every page block has one fixed shape), but rows live compressed —
    pages beyond ``num_rows`` are EMPTY rows (every feature missing),
    mirroring the dense store's NaN padding rows.  On the host tier the
    three page arrays are numpy, on the disk tier three memory-mapped
    page files; ``to_device`` ships all three under the store's data
    sharding (a CSRPages pytree is one ``device_put``).
    """

    name: str
    pages: CSRPages                # CSR page blocks (device or host arrays)
    num_rows: int                  # true N (pre-padding)
    labels: jax.Array | None = None
    task: str = "classification"
    created_at: float = dataclasses.field(default_factory=time.time)
    storage_format: str = "csr"
    tier: str = "device"

    @property
    def num_features(self) -> int:
        return self.pages.n_features

    @property
    def page_rows(self) -> int:
        return self.pages.page_rows

    @property
    def num_pages(self) -> int:
        return self.pages.num_pages

    @property
    def nbytes(self) -> int:
        return self.pages.nbytes

    @property
    def page_nbytes(self) -> int:
        return self.nbytes // max(self.num_pages, 1)

    @property
    def nnz(self) -> int:
        """True stored-entry count (excludes capacity padding)."""
        return int(np.sum(np.asarray(self.pages.indptr[:, -1])))

    def page_slice(self, first_page: int, num_pages: int) -> CSRPages:
        return self.pages.page_slice(first_page, num_pages)

    def to_device(self, block: CSRPages, sharding=None) -> CSRPages:
        if self.tier == "device":
            return block
        return jax.device_put(block, sharding)

    def batches(self, pages_per_batch: int) -> Iterator[tuple[int, CSRPages]]:
        """Deterministic (batch_index, CSR block) iteration — identical
        page→batch mapping to the dense plane's ``batches``."""
        for k, first in enumerate(range(0, self.num_pages, pages_per_batch)):
            n = min(pages_per_batch, self.num_pages - first)
            yield k, self.page_slice(first, n)


class TensorBlockStore:
    """Catalog of tiered datasets (one store per pod; DESIGN §8).

    ``device_budget_bytes`` / ``host_budget_bytes``: soft caps on the
    device- and host-resident dataset totals.  ``tier="auto"`` ingests
    cascade down the ladder: past the device budget they spill to host,
    past the host budget too they spill to disk (page-aligned mmap files
    under ``spill_dir``), where the streaming scan executor pages them
    through device memory.
    """

    def __init__(self, mesh: Mesh | None = None, *,
                 default_page_rows: int = 1024,
                 device_budget_bytes: int | None = None,
                 host_budget_bytes: int | None = None,
                 spill_dir: str | None = None,
                 injector: FaultInjector | None = None,
                 retry_policy: RetryPolicy | None = None):
        self.mesh = mesh
        self.default_page_rows = default_page_rows
        self.device_budget_bytes = device_budget_bytes
        self.host_budget_bytes = host_budget_bytes
        # reliability wiring (db/faults.py): ``move`` reads off the disk
        # tier through the ``disk_page_read`` site under the policy, and
        # rolls back on exhaustion.  An armed injector with no explicit
        # policy gets the documented default retry contract.
        self.injector = injector
        self.retry_policy = retry_policy if retry_policy is not None \
            else (RetryPolicy() if injector is not None else None)
        self._spill_dir = spill_dir
        # spill files THIS store wrote, per dataset (loader-owned page
        # files handed over via put_sparse(pages=...) are not tracked —
        # the store only deletes what it created)
        self._disk_paths: dict[str, list[str]] = {}
        self._datasets: dict[str, StoredDataset | SparseStoredDataset] = {}
        # model catalog: the serving plane's tenancy anchor.  A
        # registered model is PINNED here (the forest object stays
        # alive, so its fingerprint-keyed cache entries stay coherent);
        # what gets EVICTED under pressure is its compiled plans, via
        # the engines' ModelReuseCache LRU — never the model itself.
        self._models: dict[str, dict[str, Any]] = {}
        # drop-invalidation hooks: engines register their
        # invalidate_dataset so dropping a dataset sweeps the compiled
        # plans built against it (weakrefs — a dead engine unregisters
        # itself by getting collected)
        self._invalidators: list[weakref.ref] = []
        # model-id invalidation hooks: engines register ``invalidate`` so
        # re-pinning a model NAME sweeps the replaced forest's compiled
        # plans and persisted decisions by fingerprint (re-train must not
        # serve the old verdict)
        self._model_invalidators: list[weakref.ref] = []
        # decision catalog (db/optimizer.py): persisted optimizer
        # verdicts keyed (model fingerprint, dataset name, dataset
        # signature, mesh signature).  Swept on the same events that
        # sweep compiled plans: drop / re-put of the dataset here,
        # ``ForestQueryEngine.invalidate(model_id)`` by fingerprint.
        self._decisions: dict[tuple, Any] = {}

    # -- disk-tier spill files ----------------------------------------------
    @property
    def spill_dir(self) -> str:
        """Directory holding this store's disk-tier page files (created
        lazily: stores that never spill to disk touch no filesystem)."""
        if self._spill_dir is None:
            self._spill_dir = tempfile.mkdtemp(prefix="tbstore-disk-")
        return self._spill_dir

    def _disk_path(self, name: str, label: str) -> str:
        """Spill-file path for one page array.  The filename carries a
        short digest of the RAW dataset name: sanitization is lossy
        ("a/b" and "a:b" both flatten to "a_b"), and two datasets sharing
        a path would unlink each other's backing files through the spill
        lifecycle."""
        digest = hashlib.blake2s(name.encode(), digest_size=4).hexdigest()
        stem = f"{re.sub(r'[^A-Za-z0-9._@+-]', '_', name)}-{digest}"
        return os.path.join(self.spill_dir, f"{stem}.{label}.bin")

    def _disk_array(self, name: str, label: str, arr: np.ndarray
                    ) -> np.memmap:
        """Spill one page array to ``spill_dir`` and track the file."""
        path = self._disk_path(name, label)
        mm = mmap_array(path, arr)
        self._disk_paths.setdefault(name, []).append(path)
        return mm

    def _disk_empty(self, name: str, label: str, shape, dtype
                    ) -> np.memmap:
        """Create an EMPTY page-aligned spill file and track it — the
        streamed-ingest target: batches are written straight into the map
        so the full array never exists in host RAM.  An existing file is
        unlinked first (same SIGBUS note as :func:`mmap_array`)."""
        path = self._disk_path(name, label)
        if os.path.exists(path):
            os.unlink(path)
        mm = np.memmap(path, dtype=np.dtype(dtype), mode="w+", shape=shape)
        self._disk_paths.setdefault(name, []).append(path)
        return mm

    def _release_disk(self, name: str) -> None:
        """Delete the spill files written for ``name`` (live memmap views
        keep the unlinked inodes readable until they are collected)."""
        for path in self._disk_paths.pop(name, ()):
            try:
                os.unlink(path)
            except OSError:
                pass

    # -- mesh contract ------------------------------------------------------
    @property
    def data_axis_size(self) -> int:
        """Mesh ``data``-axis size (1 off-mesh).  Every ingest pads its
        page count to a multiple of this, so any whole-dataset batch
        divides evenly for the query plans' shard_map."""
        if self.mesh is not None and "data" in self.mesh.axis_names:
            return int(self.mesh.shape["data"])
        return 1

    def _pin(self, arr: np.ndarray) -> jax.Array:
        """Lay page-aligned ``arr`` out in page-locked host memory in the
        device's layout: the pinned tier's one relayout, at ingest.  On a
        mesh the rows keep the data sharding, in the ``pinned_host``
        memory kind; off it, they lie in the first device's."""
        sharding = self.data_sharding()
        sharding = (SingleDeviceSharding(jax.devices()[0], memory_kind=PINNED)
                    if sharding is None else sharding.with_memory_kind(PINNED))
        with TRACER.span("store.pin", bytes=int(arr.nbytes)):
            return jax.block_until_ready(jax.device_put(arr, sharding))

    def data_sharding(self) -> NamedSharding | None:
        """Row/page sharding for stored blocks: dim 0 over ``data``,
        replicated over ``model`` (None off-mesh).  One definition for
        dense pages, CSR page arrays, result writes, AND the streaming
        executor's host->device page transfers."""
        if self.mesh is not None and "data" in self.mesh.axis_names:
            return NamedSharding(self.mesh, P("data", None))
        return None

    # -- tier accounting ----------------------------------------------------
    @property
    def device_nbytes(self) -> int:
        return sum(d.nbytes for d in self._datasets.values()
                   if d.tier == "device")

    @property
    def pinned_nbytes(self) -> int:
        return sum(d.nbytes for d in self._datasets.values()
                   if d.tier == "pinned")

    @property
    def host_nbytes(self) -> int:
        """Host-RAM dataset bytes: the host tier and the pinned tier."""
        return sum(d.nbytes for d in self._datasets.values()
                   if d.tier in ("host", "pinned"))

    @property
    def disk_nbytes(self) -> int:
        return sum(d.nbytes for d in self._datasets.values()
                   if d.tier == "disk")

    def _resolve_tier(self, tier: str, ingest_nbytes: int) -> str:
        """``auto`` cascades down the tier ladder: an ingest that would
        push the device-resident total past ``device_budget_bytes``
        spills to host, and one that would also push the host-resident
        total past ``host_budget_bytes`` spills to disk."""
        if tier != "auto":
            return _check_tier(tier)
        if (self.device_budget_bytes is None
                or self.device_nbytes + ingest_nbytes
                <= self.device_budget_bytes):
            return "device"
        if (self.host_budget_bytes is None
                or self.host_nbytes + ingest_nbytes
                <= self.host_budget_bytes):
            return "host"
        return "disk"

    # -- ingestion ----------------------------------------------------------
    def put(self, name: str, data, **kw) -> StoredDataset:
        """Ingest [N, F] dense rows — see ``_put_impl`` for the full
        contract.  Instrumented: a ``store.put`` span (its ``tier`` attr
        is the RESOLVED tier, so auto-cascade spills are visible per
        ingest) and the ``store.puts`` counter."""
        with TRACER.span("store.put", dataset=name) as sp:
            ds = self._put_impl(name, data, **kw)
            sp.set(tier=ds.tier)
        METRICS.counter("store.puts").inc()
        return ds

    def _put_impl(
        self,
        name: str,
        data: np.ndarray | jax.Array,
        *,
        labels: np.ndarray | None = None,
        page_rows: int | None = None,
        task: str = "classification",
        dtype=jnp.float32,
        tier: str = "auto",
    ) -> StoredDataset:
        """Ingest [N, F] rows: pad to whole pages (NaN rows — never counted
        in results), resolve the tier, lay out (device: shard rows over the
        mesh ``data`` axis; pinned: page-locked host memory in the
        device's layout; host: keep page-aligned numpy; disk: write one
        page-aligned mmap file), register."""
        page_rows = page_rows or self.default_page_rows
        arr = np.asarray(jax.device_get(data))
        n = arr.shape[0]
        # page padding AND divisibility by the data axis
        row_multiple = self.data_axis_size * page_rows
        pad = (-n) % row_multiple
        if pad:
            arr = np.concatenate(
                [arr, np.full((pad, arr.shape[1]), np.nan, arr.dtype)])
        np_dtype = np.dtype(dtype)
        tier = self._resolve_tier(tier, arr.size * np_dtype.itemsize)
        self._release_disk(name)          # re-put: old spill files go away
        self.drop_decisions(dataset=name)  # re-put: old decisions are stale
        if tier == "host":
            stored = np.ascontiguousarray(arr, np_dtype)
        elif tier == "pinned":
            stored = self._pin(np.ascontiguousarray(arr, np_dtype))
        elif tier == "disk":
            stored = self._disk_array(
                name, "rows", np.ascontiguousarray(arr, np_dtype))
        else:
            stored = jnp.asarray(arr, dtype)
            sharding = self.data_sharding()
            if sharding is not None:
                stored = jax.device_put(stored, sharding)
        lab = None
        if labels is not None:
            lab = jnp.asarray(np.asarray(labels), jnp.float32)
        ds = StoredDataset(name=name, data=stored, num_rows=n,
                           page_rows=page_rows, labels=lab, task=task,
                           tier=tier)
        self._datasets[name] = ds
        return ds

    def put_sparse(self, name: str, data=None, **kw
                   ) -> SparseStoredDataset:
        """Ingest a CSR dataset — see ``_put_sparse_impl`` for the full
        contract.  Instrumented like ``put`` (``store.put_sparse`` span
        with the resolved tier + the ``store.puts`` counter)."""
        with TRACER.span("store.put_sparse", dataset=name) as sp:
            ds = self._put_sparse_impl(name, data, **kw)
            sp.set(tier=ds.tier)
        METRICS.counter("store.puts").inc()
        return ds

    def _put_sparse_impl(
        self,
        name: str,
        data: np.ndarray | None = None,
        *,
        csr: tuple[np.ndarray, np.ndarray, np.ndarray] | None = None,
        num_rows: int | None = None,
        num_features: int | None = None,
        pages: CSRPages | None = None,
        labels: np.ndarray | None = None,
        page_rows: int | None = None,
        task: str = "classification",
        drop_zeros: bool = False,
        tier: str = "auto",
    ) -> SparseStoredDataset:
        """Ingest a CSR dataset (the sparse data plane).

        Three entry points, most-compressed first:
          * ``pages`` — already-paginated CSRPages, device, host, or disk
            arrays (the LIBSVM→CSR loader hands these over; with
            ``tier="host"`` / ``tier="disk"`` a loader result already on
            that tier is registered with ZERO device work AND zero copy —
            criteo-scale files never round-trip the device);
          * ``csr`` — host (indptr [N+1], indices, values) triple;
          * ``data`` — dense-with-NaN host rows (NaN = missing; explicit
            zeros kept unless ``drop_zeros``), converted here.

        Page padding mirrors ``put``: rows pad to whole pages as EMPTY
        rows, and the page count pads to the mesh ``data`` axis.  The
        ``pinned`` tier holds dense rows only: asking for it raises
        ``ValueError``.
        """
        if tier != "auto":
            _dense_only(tier)
        page_rows = page_rows or self.default_page_rows
        pages_multiple = self.data_axis_size
        self._release_disk(name)          # re-put: old spill files go away
        self.drop_decisions(dataset=name)  # re-put: old decisions are stale

        if pages is not None:
            # already-paginated pages: never round-trip through the host
            # (a handoff already on the resolved tier is zero-copy; only
            # a tier MISMATCH migrates)
            if num_rows is None:
                raise ValueError("num_rows is required with pages=")
            num_features = pages.n_features
            tier = self._resolve_tier(tier, pages.nbytes)
            if tier == "device":
                # jnp.asarray is a no-op on arrays already on device
                stored = CSRPages(indptr=jnp.asarray(pages.indptr),
                                  indices=jnp.asarray(pages.indices),
                                  values=jnp.asarray(pages.values),
                                  n_features=int(num_features))
                sharding = self.data_sharding()
                if sharding is not None:
                    stored = jax.device_put(stored, sharding)
            elif tier == pages.tier:
                stored = pages            # zero-copy handoff
            elif tier == "host":
                stored = CSRPages(
                    indptr=_host_copy(pages.indptr),
                    indices=_host_copy(pages.indices),
                    values=_host_copy(pages.values),
                    n_features=int(num_features))
            else:                         # spill the handoff to disk
                stored = CSRPages(
                    indptr=self._disk_array(
                        name, "indptr", _host_copy(pages.indptr)),
                    indices=self._disk_array(
                        name, "indices", _host_copy(pages.indices)),
                    values=self._disk_array(
                        name, "values", _host_copy(pages.values)),
                    n_features=int(num_features))
        else:
            if csr is None:
                if data is None:
                    raise ValueError("need one of data=, csr=, pages=")
                arr = np.asarray(jax.device_get(data))
                num_rows = arr.shape[0]
                num_features = arr.shape[1]
                csr = csr_from_dense(arr, drop_zeros=drop_zeros)
            if num_rows is None or num_features is None:
                raise ValueError("num_rows/num_features required with csr=")
            indptr, indices, values = csr
            ip, ix, vl = paginate_csr(indptr, indices, values,
                                      num_rows=num_rows, page_rows=page_rows,
                                      n_features=num_features,
                                      pages_multiple=pages_multiple)
            nbytes = sum(a.size * a.dtype.itemsize for a in (ip, ix, vl))
            tier = self._resolve_tier(tier, nbytes)
            if tier == "host":
                stored = CSRPages(indptr=ip, indices=ix, values=vl,
                                  n_features=int(num_features))
            elif tier == "disk":
                stored = CSRPages(
                    indptr=self._disk_array(name, "indptr", ip),
                    indices=self._disk_array(name, "indices", ix),
                    values=self._disk_array(name, "values", vl),
                    n_features=int(num_features))
            else:
                stored = CSRPages(indptr=jnp.asarray(ip),
                                  indices=jnp.asarray(ix),
                                  values=jnp.asarray(vl),
                                  n_features=int(num_features))
                sharding = self.data_sharding()
                if sharding is not None:
                    stored = jax.device_put(stored, sharding)
        lab = None
        if labels is not None:
            lab = jnp.asarray(np.asarray(labels), jnp.float32)
        ds = SparseStoredDataset(name=name, pages=stored,
                                 num_rows=int(num_rows),
                                 labels=lab, task=task, tier=tier)
        self._datasets[name] = ds
        return ds

    def put_result(self, name: str, result: jax.Array, num_rows: int) -> StoredDataset:
        """The WRITE operator's sink: register an output dataset."""
        ds = StoredDataset(name=name, data=result[:, None] if result.ndim == 1
                           else result,
                           num_rows=num_rows, page_rows=self.default_page_rows)
        self._datasets[name] = ds
        return ds

    # -- streamed ingest ------------------------------------------------------
    def stream_writer(self, name: str, *, num_rows: int, num_features: int,
                      dtype=jnp.float32, page_rows: int | None = None,
                      tier: str = "auto", fill=np.nan,
                      labels: np.ndarray | None = None,
                      task: str = "classification") -> "DenseStreamWriter":
        """Open a batch-by-batch dense ingest under ``name``.

        The full [N, F] array never needs to exist in caller memory: rows
        arrive in order via ``write(batch)`` and land DIRECTLY on the
        resolved tier — on the disk tier each batch is written straight
        into the page-aligned mmap file, so ingest-time host residency is
        bounded by the batch, not the dataset (the in-database trainer's
        binning pass ingests its binned relation this way).  ``fill``
        pads the page-alignment tail rows (NaN for float data, the
        MISSING bin for binned relations).  ``close()`` registers and
        returns the ``StoredDataset``; the tier is resolved UP FRONT from
        the declared total size, so the auto cascade sees the whole
        ingest, not the first batch.
        """
        return DenseStreamWriter(self, name, num_rows=num_rows,
                                 num_features=num_features, dtype=dtype,
                                 page_rows=page_rows or self.default_page_rows,
                                 tier=tier, fill=fill, labels=labels,
                                 task=task)

    def put_stream(self, name: str, batches, **kw) -> StoredDataset:
        """Ingest an iterator of [rows_i, F] host batches (in row order)
        through :meth:`stream_writer` — see there for the contract."""
        w = self.stream_writer(name, **kw)
        try:
            for batch in batches:
                w.write(batch)
        except BaseException:
            w.abort()
            raise
        return w.close()

    # -- tier migration -----------------------------------------------------
    def move(self, name: str, tier: str):
        """Migrate a dataset between tiers — see ``_move_impl`` for the
        full contract (rollback semantics included).  Instrumented: a
        ``store.move`` span carrying the from/to rungs and the
        ``store.moves`` counter (counted per ATTEMPT — a rolled-back
        move still counts, its span's ``error`` attr marks it)."""
        src_tier = self.get(name).tier
        METRICS.counter("store.moves").inc()
        with TRACER.span("store.move", dataset=name,
                         src=src_tier, dst=tier):
            return self._move_impl(name, tier)

    def _move_impl(self, name: str, tier: str):
        """Migrate a dataset between any two tiers of the ladder
        (eviction: device -> pinned -> host -> disk; promotion: the
        reverse; a CSR dataset skips ``pinned``, which holds dense rows
        only).  Page layout is preserved exactly, so the page↔batch
        mapping — and therefore every prediction — is unchanged; compiled
        plans stay valid (tier is a runtime property of the scan, not of
        the plan).
        Moving OFF the disk tier deletes the spill files this store wrote
        (after the copy — live views keep the unlinked inodes alive).

        Failure semantics: reading the source off the DISK tier goes
        through the ``disk_page_read`` fault site under the store's
        retry policy; a fault that survives the retries ROLLS THE MOVE
        BACK — any spill files this move already wrote are unlinked, the
        tracked-path list is restored, and the catalog entry (and with
        it the per-tier ``*_nbytes`` accounting) is untouched — then a
        structured ``ScanFault`` is raised.  A failed move never leaks
        orphaned page files and never corrupts tier accounting."""
        ds = self.get(name)
        if ds.storage_format == "csr":
            _dense_only(tier)
        else:
            _check_tier(tier)
        if ds.tier == tier:
            return ds
        was_disk = ds.tier == "disk"
        sharding = self.data_sharding()
        injector, policy = self.injector, self.retry_policy

        def read_source(arr) -> np.ndarray:
            """Materialize one source page array on the host — the
            ``disk_page_read`` site when the source is the disk tier."""
            if not was_disk or (injector is None and policy is None):
                return _host_copy(arr)
            if policy is None:
                injector.fire("disk_page_read")
                return _host_copy(arr)
            return policy.run(lambda: _host_copy(arr),
                              site="disk_page_read", injector=injector)

        def relocate(label: str, arr):
            """One page array, source tier -> target tier."""
            src = read_source(arr)
            if tier == "host":
                return src
            if tier == "pinned":
                return self._pin(src)
            if tier == "disk":
                return self._disk_array(name, label, src)
            out = jnp.asarray(src)
            return out if sharding is None else jax.device_put(out, sharding)

        # rollback bookkeeping: anything _disk_array appends past this
        # snapshot was written BY THIS MOVE and must not survive a failure
        paths_before = list(self._disk_paths.get(name, ()))
        try:
            if ds.storage_format == "csr":
                pages = CSRPages(indptr=relocate("indptr", ds.pages.indptr),
                                 indices=relocate("indices",
                                                  ds.pages.indices),
                                 values=relocate("values", ds.pages.values),
                                 n_features=ds.pages.n_features)
                new = dataclasses.replace(ds, pages=pages, tier=tier)
            else:
                new = dataclasses.replace(ds, data=relocate("rows", ds.data),
                                          tier=tier)
        except BaseException as e:
            for path in self._disk_paths.get(name, ()):
                if path not in paths_before:
                    try:
                        os.unlink(path)
                    except OSError:
                        pass
            if paths_before:
                self._disk_paths[name] = paths_before
            else:
                self._disk_paths.pop(name, None)
            retryable = (policy.retryable if policy is not None
                         else (InjectedFault, OSError))
            # only the GUARDED disk read gets the structured wrap; a
            # failure elsewhere (e.g. the target-tier write) propagates
            # as itself — it is not a disk_page_read exhaustion
            if was_disk and isinstance(e, retryable):
                attempts = policy.max_attempts if policy is not None else 1
                raise ScanFault(
                    "disk_page_read", attempts=attempts, rows_completed=0,
                    cause=e,
                    detail=f"move({name!r} -> {tier!r}) rolled back") from e
            raise
        if was_disk:
            self._release_disk(name)
        self._datasets[name] = new
        return new

    # -- catalog --------------------------------------------------------------
    def get(self, name: str) -> StoredDataset:
        try:
            return self._datasets[name]
        except KeyError:
            raise KeyError(f"dataset {name!r} not in store; "
                           f"have {sorted(self._datasets)}")

    def register_invalidator(self, fn: Callable[[str], int]) -> None:
        """Register a per-dataset invalidation hook (weakly).  Engines
        register ``invalidate_dataset`` so ``drop`` sweeps the compiled
        plans whose keys carry the dropped dataset's name."""
        ref = weakref.WeakMethod(fn) if hasattr(fn, "__self__") \
            else weakref.ref(fn)
        self._invalidators.append(ref)

    def register_model_invalidator(self, fn: Callable[[str], int]) -> None:
        """Register a per-model-fingerprint invalidation hook (weakly).
        Engines register ``invalidate`` so re-pinning a model name via
        ``put_model`` sweeps the REPLACED forest's compiled plans."""
        ref = weakref.WeakMethod(fn) if hasattr(fn, "__self__") \
            else weakref.ref(fn)
        self._model_invalidators.append(ref)

    def drop(self, name: str) -> int:
        """Drop a dataset AND invalidate dependent engine cache entries
        (compiled plans close over batch signatures derived from the
        dataset — leaving them resident after a drop pins device buffers
        and serves entries for data that no longer exists).  Persisted
        optimizer decisions keyed on the dataset are swept the same way.
        Returns the number of cache entries (plans + decisions)
        invalidated across registered engines.  Disk-tier spill files
        this store wrote are deleted."""
        existed = self._datasets.pop(name, None)
        self._release_disk(name)
        invalidated = 0
        if existed is not None:
            # persisted optimizer decisions keyed on this dataset go
            # first (the engine hooks below then find nothing to re-drop)
            invalidated += self.drop_decisions(dataset=name)
            for ref in list(self._invalidators):
                fn = ref()
                if fn is None:
                    self._invalidators.remove(ref)
                else:
                    invalidated += int(fn(name) or 0)
        return invalidated

    # -- decision catalog (cost-based optimizer; db/optimizer.py) ------------
    def put_decision(self, key: tuple, decision) -> None:
        """Persist an optimizer decision.  Key layout is fixed by
        ``db/optimizer.py``: ``key[0]`` is the model fingerprint,
        ``key[1]`` the dataset name (or the ``#rows`` sentinel for
        serving-plane row-batch decisions) — the two slots the sweeps
        below match on."""
        self._decisions[key] = decision

    def get_decision(self, key: tuple):
        """Steady-state lookup (None on miss) — the dictionary read that
        replaces the score + autotune passes on repeat queries."""
        return self._decisions.get(key)

    def drop_decisions(self, *, model_id: str | None = None,
                       dataset: str | None = None) -> int:
        """Sweep persisted decisions by model fingerprint (``key[0]``)
        and/or dataset name (``key[1]``); both None sweeps everything.
        Returns entries dropped.  Mirrors the compiled-plan sweeps: a
        decision must never outlive the model or dataset it ranked."""
        doomed = [k for k in self._decisions
                  if (model_id is None or k[0] == model_id)
                  and (dataset is None or k[1] == dataset)]
        for k in doomed:
            del self._decisions[k]
        return len(doomed)

    def decision_catalog(self) -> dict[tuple, dict[str, Any]]:
        """Catalog view of persisted decisions (dataclass → dict)."""
        return {k: dataclasses.asdict(d)
                for k, d in self._decisions.items()}

    # -- model catalog (serving-plane tenancy) -------------------------------
    def put_model(self, name: str, forest, **meta) -> dict[str, Any]:
        """Pin a forest model in the catalog under ``name``.

        The store is the system of record for WHAT is served
        (``serve/forest.ForestServeEngine.register_model`` goes through
        here); the engines' ``ModelReuseCache`` LRU decides what stays
        COMPILED.  Re-putting a name REPLACES the pinned forest and
        sweeps the replaced fingerprint's state — its persisted
        optimizer decisions here, and its compiled plans through every
        registered model invalidator — so a re-trained model can never
        serve the old forest's verdicts (the stale-decision-after-retrain
        regression, ``tests/test_train_streaming.py``)."""
        old = self._models.get(name)
        entry = dict(forest=forest, trees=int(forest.num_trees),
                     depth=int(forest.depth),
                     features=int(forest.n_features),
                     model_type=forest.model_type, task=forest.task,
                     created_at=time.time(), **meta)
        self._models[name] = entry
        if old is not None and old["forest"] is not forest:
            old_fp = old.get("fingerprint")
            if old_fp is None:
                from repro.core.reuse import fingerprint_forest
                old_fp = fingerprint_forest(old["forest"])
            self.drop_decisions(model_id=old_fp)
            for ref in list(self._model_invalidators):
                fn = ref()
                if fn is None:
                    self._model_invalidators.remove(ref)
                else:
                    fn(old_fp)
        return entry

    def get_model(self, name: str):
        try:
            return self._models[name]["forest"]
        except KeyError:
            raise KeyError(f"model {name!r} not in store; "
                           f"have {sorted(self._models)}")

    def drop_model(self, name: str) -> bool:
        """Unpin a model.  Compiled plans keyed on its fingerprint are
        the caller's to sweep (``ForestQueryEngine.invalidate``) — the
        store only owns the pin."""
        return self._models.pop(name, None) is not None

    def model_catalog(self) -> dict[str, dict[str, Any]]:
        """Catalog view of pinned models (without the forest objects)."""
        return {n: {k: v for k, v in e.items() if k != "forest"}
                for n, e in self._models.items()}

    def __contains__(self, name: str) -> bool:
        return name in self._datasets

    def catalog(self) -> dict[str, dict[str, Any]]:
        out = {}
        for n, d in self._datasets.items():
            entry = dict(rows=d.num_rows, features=d.num_features,
                         pages=d.num_pages, page_rows=d.page_rows,
                         bytes=d.nbytes, task=d.task,
                         format=getattr(d, "storage_format", "dense"),
                         tier=getattr(d, "tier", "device"))
            if entry["format"] == "csr":
                entry["nnz"] = d.nnz
            out[n] = entry
        return out


class DenseStreamWriter:
    """Batch-by-batch dense ingest (``TensorBlockStore.stream_writer``).

    Rows arrive in order and are written straight into the resolved
    tier's backing storage — for the disk tier an EMPTY page-aligned
    mmap file created up front, so the full [N, F] matrix never exists
    in host RAM during ingest.  ``close()`` pads the page-alignment tail
    with ``fill``, flushes, registers, and returns the ``StoredDataset``;
    ``abort()`` unlinks anything this writer created.
    """

    def __init__(self, store: TensorBlockStore, name: str, *,
                 num_rows: int, num_features: int, dtype, page_rows: int,
                 tier: str, fill, labels, task: str):
        self.store = store
        self.name = name
        self.num_rows = int(num_rows)
        self.page_rows = int(page_rows)
        self.fill = fill
        self.labels = labels
        self.task = task
        self._np_dtype = np.dtype(dtype)
        row_multiple = store.data_axis_size * page_rows
        self.total_rows = self.num_rows + (-self.num_rows) % row_multiple
        nbytes = self.total_rows * int(num_features) * self._np_dtype.itemsize
        self.tier = store._resolve_tier(tier, nbytes)
        # re-put semantics mirror _put_impl: old spill files and stale
        # optimizer decisions for this name go away when the ingest opens
        store._release_disk(name)
        store.drop_decisions(dataset=name)
        shape = (self.total_rows, int(num_features))
        if self.tier == "disk":
            self._buf = store._disk_empty(name, "rows", shape,
                                          self._np_dtype)
        else:
            self._buf = np.empty(shape, self._np_dtype)
        self._cursor = 0
        self._closed = False

    def write(self, batch: np.ndarray) -> None:
        """Append one [rows, F] host batch at the current row cursor."""
        if self._closed:
            raise RuntimeError(f"stream_writer({self.name!r}) is closed")
        arr = np.asarray(batch)
        if arr.dtype != self._np_dtype:
            arr = arr.astype(self._np_dtype)
        end = self._cursor + arr.shape[0]
        if end > self.num_rows:
            raise ValueError(
                f"stream_writer({self.name!r}): batch overruns the "
                f"declared num_rows ({end} > {self.num_rows})")
        self._buf[self._cursor:end] = arr
        self._cursor = end

    def abort(self) -> None:
        """Drop everything this writer created (nothing is registered)."""
        if self._closed:
            return
        self._closed = True
        self._buf = None
        if self.tier == "disk":
            self.store._release_disk(self.name)

    def close(self) -> StoredDataset:
        """Pad, flush, register — returns the new ``StoredDataset``."""
        if self._closed:
            raise RuntimeError(f"stream_writer({self.name!r}) is closed")
        if self._cursor != self.num_rows:
            raise ValueError(
                f"stream_writer({self.name!r}): wrote {self._cursor} rows, "
                f"declared {self.num_rows}")
        self._closed = True
        store = self.store
        with TRACER.span("store.put", dataset=self.name,
                         streamed=True) as sp:
            if self._cursor < self.total_rows:  # page-alignment tail
                self._buf[self._cursor:] = self.fill
            if self.tier == "disk":
                self._buf.flush()
                stored = self._buf
            elif self.tier == "host":
                stored = self._buf
            elif self.tier == "pinned":
                stored = store._pin(self._buf)
            else:
                stored = jnp.asarray(self._buf)
                sharding = store.data_sharding()
                if sharding is not None:
                    stored = jax.device_put(stored, sharding)
            lab = None
            if self.labels is not None:
                lab = jnp.asarray(np.asarray(self.labels), jnp.float32)
            ds = StoredDataset(name=self.name, data=stored,
                               num_rows=self.num_rows,
                               page_rows=self.page_rows, labels=lab,
                               task=self.task, tier=self.tier)
            store._datasets[self.name] = ds
            sp.set(tier=self.tier)
        METRICS.counter("store.puts").inc()
        return ds
