"""The EXTERNAL data path: parse + convert + transfer (what netsDB avoids).

Paper Sec. 4: every non-netsDB platform loads testing data from an external
store (PostgreSQL via ConnectorX, or LIBSVM files for Criteo) into the ML
runtime's format before inference; the paper measures this load/convert time
as part of end-to-end latency and finds it DOMINATES for small-model/
large-data and wide-data workloads.

Our boundary (DESIGN.md Sec. 3/6.4): host-side text parse (CSV = the tabular
PostgreSQL stand-in, LIBSVM = the sparse path, array-typed rows = the Epsilon
path) → dtype/layout conversion → host→device transfer.  Every stage is
timed separately so benchmarks reproduce the paper's latency-breakdown
figures (Fig. 4/5/6/7).

Also here: synthetic generators for the paper's dataset grid (Tab. 1),
shape-faithful but scale-parameterized so benchmarks run on CPU.

Out-of-core ingest: ``load_libsvm_csr_external`` accepts ``tier=`` so a
criteo-scale file can parse straight onto any rung of the store's tier
ladder — ``"host"`` (page-aligned numpy, no device transfer) or
``"disk"`` (page-aligned mmap files; scan-time residency bounded by the
batch, though the parse itself still holds the CSR arrays in host RAM
once) — with ``transfer_s == 0``; ``store.put_sparse(pages=...)`` then
registers the result zero-copy.  See ``db/store.py`` and
``docs/architecture.md`` §1.
"""

from __future__ import annotations

import dataclasses
import io
import os
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import METRICS, TRACER

__all__ = [
    "LoadTiming",
    "DATASETS",
    "synth_dataset",
    "write_csv",
    "load_csv_external",
    "write_libsvm",
    "load_libsvm_external",
    "load_libsvm_csr_external",
    "write_array_rows",
    "load_array_rows_external",
]


@dataclasses.dataclass
class LoadTiming:
    parse_s: float = 0.0       # text -> host arrays
    convert_s: float = 0.0     # layout/dtype conversion (e.g. array-col -> matrix)
    transfer_s: float = 0.0    # host -> device
    total_s: float = 0.0


def _guarded_transfer(fn, *, injector=None, retry_policy=None):
    """Run one host->device transfer through the ``page_dma_in`` fault
    site under a retry policy (``db/faults.py``) — the loaders' leg of
    the reliability layer.  The default (no injector, no policy) is a
    direct call, so the measured transfer timings are untouched."""
    if injector is None and retry_policy is None:
        return fn()
    from repro.db.faults import RetryPolicy
    policy = retry_policy if retry_policy is not None else RetryPolicy()
    return policy.run(fn, site="page_dma_in", injector=injector)


# ---------------------------------------------------------------------------
# Synthetic replicas of the paper's Tab. 1 grid (scale-parameterized).
# `rows` are the full-size row counts; benchmarks pass a scale factor.
# ---------------------------------------------------------------------------

DATASETS = {
    # name: (rows, features, task, nan_fraction, kind)
    "epsilon": (100_000, 2000, "classification", 0.0, "wide-dense"),
    "fraud": (285_000, 28, "classification", 0.0, "narrow-dense"),
    "year": (515_000, 90, "regression", 0.0, "narrow-dense"),
    "bosch": (1_184_000, 968, "classification", 0.81, "wide-sparse"),
    "higgs": (11_000_000, 28, "classification", 0.0, "narrow-dense"),
    "criteo": (51_000_000, 10_000, "classification", 0.96, "sparse-libsvm"),
    "airline": (115_000_000, 13, "classification", 0.0, "narrow-dense"),
    "tpcxai": (131_000_000, 7, "classification", 0.0, "narrow-dense"),
}


def synth_dataset(name: str, *, scale: float = 1.0, seed: int = 0,
                  max_rows: int | None = None):
    """Generate (x [N, F] float32 w/ NaNs, y [N]) mirroring Tab. 1 shapes.

    Criteo's 1M one-hot features are scale-reduced (10k) but stay extremely
    sparse — the claim under test (sparse format shrinks transfer) is about
    density, not the absolute feature count.
    """
    rows, F, task, nan_frac, kind = DATASETS[name]
    n = int(rows * scale)
    if max_rows is not None:
        n = min(n, max_rows)
    # crc32, not hash(): str hashing is salted per process, and seeded
    # data must be the same in every process
    rng = np.random.default_rng(seed + zlib.crc32(name.encode()) % 2**16)
    x = rng.normal(size=(n, F)).astype(np.float32)
    w = rng.normal(size=(min(F, 16),)).astype(np.float32)
    signal = x[:, : w.size] @ w
    if task == "regression":
        y = signal + 0.1 * rng.normal(size=n).astype(np.float32)
    else:
        y = (signal > 0).astype(np.float32)
    if nan_frac > 0:
        x[rng.random((n, F)) < nan_frac] = np.nan
    return x, y


# ---------------------------------------------------------------------------
# CSV (tabular PostgreSQL stand-in)
# ---------------------------------------------------------------------------


def write_csv(path: str, x: np.ndarray) -> None:
    np.savetxt(path, x, delimiter=",", fmt="%.6g")


def load_csv_external(path: str, *, device=None, dtype=jnp.float32,
                      injector=None, retry_policy=None):
    """Timed external load: parse CSV -> convert -> device transfer."""
    METRICS.counter("load.external_loads").inc()
    t0 = time.perf_counter()
    with TRACER.span("load.parse", format="csv"):
        host = np.loadtxt(path, delimiter=",", dtype=np.float64, ndmin=2)
    t1 = time.perf_counter()
    with TRACER.span("load.convert"):
        host32 = np.ascontiguousarray(host, dtype=np.float32)
    t2 = time.perf_counter()
    with TRACER.span("load.transfer"):
        dev = _guarded_transfer(
            lambda: jax.device_put(jnp.asarray(host32, dtype), device),
            injector=injector, retry_policy=retry_policy)
        dev.block_until_ready()
    t3 = time.perf_counter()
    return dev, LoadTiming(parse_s=t1 - t0, convert_s=t2 - t1,
                           transfer_s=t3 - t2, total_s=t3 - t0)


# ---------------------------------------------------------------------------
# LIBSVM (the sparse Criteo path)
# ---------------------------------------------------------------------------


def write_libsvm(path: str, x: np.ndarray, y: np.ndarray) -> None:
    """Rows as 'label idx:val idx:val ...' with NaN treated as missing."""
    with open(path, "w") as fh:
        for i in range(x.shape[0]):
            row = x[i]
            nz = np.flatnonzero(~np.isnan(row) & (row != 0.0))
            items = " ".join(f"{j}:{row[j]:.6g}" for j in nz)
            fh.write(f"{y[i]:g} {items}\n")


def _parse_libsvm(path: str):
    """Text -> host CSR lists (the parse stage both LIBSVM loaders share)."""
    indptr = [0]
    indices: list[int] = []
    values: list[float] = []
    labels: list[float] = []
    with open(path) as fh:
        for line in fh:
            parts = line.split()
            labels.append(float(parts[0]))
            for item in parts[1:]:
                j, v = item.split(":")
                indices.append(int(j))
                values.append(float(v))
            indptr.append(len(indices))
    return indptr, indices, values, labels


def load_libsvm_external(path: str, num_features: int, *, device=None,
                         dtype=jnp.float32, missing_as_nan: bool = True,
                         injector=None, retry_policy=None):
    """Timed sparse load: parse text -> CSR -> densify -> transfer.

    The densify step is the "conversion" the paper's Criteo/Bosch pipelines
    pay (sparse store format -> the dense blocks inference kernels want).
    This is the DENSE-FALLBACK baseline; ``load_libsvm_csr_external`` is
    the sparse data plane's path, which skips the densify entirely.
    """
    METRICS.counter("load.external_loads").inc()
    t0 = time.perf_counter()
    with TRACER.span("load.parse", format="libsvm"):
        indptr, indices, values, labels = _parse_libsvm(path)
        indptr_np = np.asarray(indptr, np.int64)
        indices_np = np.asarray(indices, np.int64)
        values_np = np.asarray(values, np.float32)
    t1 = time.perf_counter()
    with TRACER.span("load.convert", densify=True):
        n = len(labels)
        fill = np.nan if missing_as_nan else 0.0
        dense = np.full((n, num_features), fill, np.float32)
        rows = np.repeat(np.arange(n), np.diff(indptr_np))
        dense[rows, indices_np] = values_np
    t2 = time.perf_counter()
    with TRACER.span("load.transfer"):
        dev = _guarded_transfer(
            lambda: jax.device_put(jnp.asarray(dense, dtype), device),
            injector=injector, retry_policy=retry_policy)
        dev.block_until_ready()
    t3 = time.perf_counter()
    timing = LoadTiming(parse_s=t1 - t0, convert_s=t2 - t1,
                        transfer_s=t3 - t2, total_s=t3 - t0)
    return dev, np.asarray(labels, np.float32), timing


def load_libsvm_csr_external(path: str, num_features: int, *,
                             page_rows: int = 512, pages_multiple: int = 1,
                             tier: str = "device",
                             spill_dir: str | None = None,
                             injector=None, retry_policy=None):
    """Timed sparse load, SPARSE data plane: parse -> CSR pages -> transfer.

    Never materializes [N, F] on the host: parse builds host CSR lists,
    convert lays them out as fixed-capacity CSR page blocks
    (``db/sparse.paginate_csr`` — the layout the tensor-block store holds),
    and transfer ships indptr/indices/values only.  For criteo-density
    data that is a ~``1/density`` shrink of both the host working set and
    the host->device transfer, which is exactly the term the paper's
    sparse-storage claim is about.  Same LoadTiming contract as every
    other external loader.

    ``tier="host"`` skips the device transfer entirely (``transfer_s``
    records 0): criteo-scale files parse straight into page-aligned host
    CSR blocks, ready for ``store.put_sparse(pages=..., tier="host")``
    and the streaming scan executor — the out-of-core ingest path, with
    no device round-trip at load time.  ``tier="disk"`` goes one rung
    lower: the three page arrays are written to page-aligned
    memory-mapped files and handed back as lazy ``np.memmap`` views —
    ``store.put_sparse(pages=..., tier="disk")`` registers the maps
    zero-copy and the SCAN faults in only the pages each batch touches,
    so a file larger than both the device and host budgets streams
    through inference (the parse/convert stages themselves still hold
    the CSR arrays in host RAM once while writing the files).  The mmap
    writes are part of the CONVERT stage; ``transfer_s`` is 0 for both
    off-device tiers.

    Page-file lifecycle: the files are owned by the CALLER, not by the
    store (``put_sparse(pages=...)`` registers them zero-copy and will
    not delete them on ``drop``).  Pass ``spill_dir`` to control where
    they live; with ``spill_dir=None`` they land in a fresh
    ``tempfile.mkdtemp`` directory that persists until the OS cleans
    /tmp — each returned array's ``.filename`` attribute carries its
    path for manual cleanup.

    Returns (CSRPages on ``tier``, labels [N] np, LoadTiming).
    """
    from repro.db.sparse import CSRPages, paginate_csr
    from repro.db.store import mmap_array

    if tier not in ("device", "host", "disk"):
        raise ValueError(f"unknown tier {tier!r}")
    METRICS.counter("load.external_loads").inc()
    t0 = time.perf_counter()
    with TRACER.span("load.parse", format="libsvm-csr"):
        indptr, indices, values, labels = _parse_libsvm(path)
    t1 = time.perf_counter()
    with TRACER.span("load.convert", tier=tier):
        ip, ix, vl = paginate_csr(
            np.asarray(indptr, np.int64), np.asarray(indices, np.int32),
            np.asarray(values, np.float32), num_rows=len(labels),
            page_rows=page_rows, n_features=num_features,
            pages_multiple=pages_multiple)
        if tier == "disk":
            import tempfile
            d = spill_dir or tempfile.mkdtemp(prefix="libsvm-disk-")
            stem = os.path.splitext(os.path.basename(path))[0]
            ip, ix, vl = (mmap_array(os.path.join(d, f"{stem}.{lbl}.bin"), a)
                          for lbl, a in
                          (("indptr", ip), ("indices", ix), ("values", vl)))
    t2 = time.perf_counter()
    if tier in ("host", "disk"):
        pages = CSRPages(indptr=ip, indices=ix, values=vl,
                         n_features=int(num_features))
        t3 = t2               # no device transfer: transfer_s == 0
    else:
        with TRACER.span("load.transfer"):
            pages = _guarded_transfer(
                lambda: CSRPages(indptr=jnp.asarray(ip),
                                 indices=jnp.asarray(ix),
                                 values=jnp.asarray(vl),
                                 n_features=int(num_features)),
                injector=injector, retry_policy=retry_policy)
            jax.block_until_ready((pages.indptr, pages.indices,
                                   pages.values))
        t3 = time.perf_counter()
    timing = LoadTiming(parse_s=t1 - t0, convert_s=t2 - t1,
                        transfer_s=t3 - t2, total_s=t3 - t0)
    return pages, np.asarray(labels, np.float32), timing


# ---------------------------------------------------------------------------
# Array-typed rows (the Epsilon path: PostgreSQL array columns)
# ---------------------------------------------------------------------------


def write_array_rows(path: str, x: np.ndarray) -> None:
    """Each row as a '{v1,v2,...}' array literal — the PostgreSQL array-type
    storage the paper is forced into for >1600-column tables (Sec. 6.3.2)."""
    with open(path, "w") as fh:
        for row in x:
            fh.write("{" + ",".join(f"{v:.6g}" for v in row) + "}\n")


def load_array_rows_external(path: str, *, device=None, dtype=jnp.float32):
    """Timed array-column load; the expensive step is the per-row array
    parse + stack (the paper's 'converting a PostgreSQL array type back to
    a NumPy array ... becomes the bottleneck')."""
    METRICS.counter("load.external_loads").inc()
    t0 = time.perf_counter()
    with TRACER.span("load.parse", format="array-rows"):
        rows = []
        with open(path) as fh:
            for line in fh:
                rows.append(np.fromstring(line.strip()[1:-1], sep=","))
    t1 = time.perf_counter()
    with TRACER.span("load.convert"):
        host = np.stack(rows).astype(np.float32)
    t2 = time.perf_counter()
    with TRACER.span("load.transfer"):
        dev = jax.device_put(jnp.asarray(host, dtype), device)
        dev.block_until_ready()
    t3 = time.perf_counter()
    return dev, LoadTiming(parse_s=t1 - t0, convert_s=t2 - t1,
                           transfer_s=t3 - t2, total_s=t3 - t0)
