"""Roofline-term extraction from a compiled dry-run artifact.

Three terms per (arch × shape × mesh) cell, all in SECONDS (per step,
per chip — the compiled SPMD module is the per-device program, so
``cost_analysis`` FLOPs/bytes and the HLO collective operand sizes are
already per-chip quantities):

  compute    = HLO_FLOPs_per_chip / peak_FLOP/s          (197e12 bf16 v5e)
  memory     = HLO_bytes_per_chip / HBM_bandwidth        (819e9 B/s)
  collective = collective_operand_bytes_per_chip / ICI   (50e9 B/s/link)

collective bytes are parsed from ``compiled.as_text()``: the summed
operand sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute instruction (the convention the task
spec fixes; ring-algorithm wire amplification is NOT applied — it is a
constant ≈(n-1)/n ≈ 1 factor at n=16).
"""

from __future__ import annotations

import dataclasses
import re
import time
from typing import Any

from repro.launch.mesh import TPU_PEAKS, V5E

__all__ = ["CollectiveStats", "parse_collectives", "roofline_terms",
           "model_flops", "calibrate_peaks", "resolve_peaks"]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# a result shape: dtype[dims]{layout}?  e.g.  bf16[16,512]{1,0}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# instruction line:  %name = <shape or (tuple)> opcode(...)
_INSTR_RE = re.compile(
    r"=\s+(\([^)]*\)|[\w\[\]{},]+)\s+(" + "|".join(_COLLECTIVES) +
    r")(-start|-done)?\(")
# replica_groups={{0,1,..},{..}}  or iota form  replica_groups=[16,16]<=[256]
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _result_bytes(result_str: str) -> int:
    return sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(result_str))


def _group_size(line: str) -> int:
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return m.group(1).count(",") + 1
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))          # [n_groups, group_size]
    return 1


@dataclasses.dataclass
class CollectiveStats:
    """Per-device collective traffic, two conventions:

    operand bytes  — the spec's convention: what each device CONTRIBUTES
                     (all-gather: its shard; all-reduce: the full buffer;
                     reduce-scatter: the full input; all-to-all /
                     permute: the local buffer);
    wire bytes     — ring-algorithm estimate of what actually crosses each
                     device's links (all-reduce ≈ 2× buffer, etc.).
    """
    counts: dict[str, int]
    bytes_by_kind: dict[str, int]       # operand-bytes convention
    wire_bytes_by_kind: dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    @property
    def total_wire_bytes(self) -> int:
        return sum(self.wire_bytes_by_kind.values())

    @property
    def total_count(self) -> int:
        return sum(self.counts.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts: dict[str, int] = {}
    by_kind: dict[str, int] = {}
    wire: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m:
            continue
        result_str, kind, startdone = m.group(1), m.group(2), m.group(3)
        if startdone == "-done":
            continue                     # paired with its -start
        r = _result_bytes(result_str)    # per-device result buffer bytes
        g = max(_group_size(line), 1)
        if kind == "all-gather":
            operand, w = r // max(g, 1), r * (g - 1) // max(g, 1)
        elif kind == "all-reduce":
            operand, w = r, 2 * r * (g - 1) // max(g, 1)
        elif kind == "reduce-scatter":
            operand, w = r * g, r * (g - 1)
        elif kind == "all-to-all":
            operand, w = r, r * (g - 1) // max(g, 1)
        else:                            # collective-permute
            operand, w = r, r
        counts[kind] = counts.get(kind, 0) + 1
        by_kind[kind] = by_kind.get(kind, 0) + operand
        wire[kind] = wire.get(kind, 0) + w
    return CollectiveStats(counts, by_kind, wire)


def model_flops(cfg, shape) -> float:
    """6·N·D (dense) / 6·N_active·D (MoE) useful-model FLOPs per step.
    For decode shapes D = global_batch tokens (one step)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n_active * tokens          # forward only
    return 2.0 * n_active * shape.global_batch  # decode: 1 token/stream


# ---------------------------------------------------------------------------
# Backend-calibrated peaks.
#
# The v5e constants above the fold are the right model for the production
# TPU mesh the dry-run targets, but cost RANKING on the CI backend
# (XLA:CPU) is meaningless against 197 TFLOP/s: every candidate looks
# compute-free and memory ordering is off by ~two orders of magnitude.
# ``calibrate_peaks`` measures the *effective* peaks of the live backend
# once per process with a one-shot microbenchmark and caches the result;
# ``resolve_peaks`` is the lookup the optimizer uses (TPU → the published
# table of its device kind, anything else → the calibrated table).
# ---------------------------------------------------------------------------

_CALIBRATED: dict[str, dict[str, float]] = {}


def _time_best(fn, iters: int = 3) -> float:
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def calibrate_peaks(backend: str | None = None, *,
                    force: bool = False) -> dict[str, float]:
    """Measure effective peaks of the live jax backend (cached per process).

    Returns a dict with the three keys :func:`roofline_terms` consumes
    (``peak_flops_bf16``, ``hbm_bandwidth``, ``ici_bandwidth`` — the names
    keep the v5e spelling so the tables are interchangeable) plus extras
    the cost model uses directly:

    ``gather_bandwidth``  effective B/s of a row gather (the access
                          pattern of tree traversal — usually differs
                          from streaming bandwidth, in either direction,
                          which is exactly why it is measured);
    ``h2d_bandwidth``     host→device transfer B/s (``device_put``);
    ``dispatch_s``        fixed overhead of one jitted dispatch — the
                          per-stage / per-batch launch constant.

    All measurements are min-of-3 on deliberately small operands
    (~10-50 MiB, one matmul) so the whole calibration stays well under a
    second; the numbers are *effective* throughputs (what a real kernel
    sees), not datasheet peaks.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    backend = backend or jax.default_backend()
    if not force and backend in _CALIBRATED:
        return _CALIBRATED[backend]

    # FLOP/s: one f32 [N,N]@[N,N] matmul, 2*N^3 useful flops.
    n = 512
    a = jnp.ones((n, n), jnp.float32)
    mm = jax.jit(lambda x: x @ x)
    mm(a).block_until_ready()
    t = _time_best(lambda: mm(a).block_until_ready())
    peak_flops = 2.0 * n ** 3 / max(t, 1e-9)

    # Streaming bandwidth: elementwise add over 32 MiB (1 read + 1 write).
    big = jnp.ones((8 << 20,), jnp.float32)          # 32 MiB
    add = jax.jit(lambda x: x + 1.0)
    add(big).block_until_ready()
    t = _time_best(lambda: add(big).block_until_ready())
    stream_bw = 2.0 * big.nbytes / max(t, 1e-9)

    # Gather bandwidth: row gather of 16 MiB through random indices —
    # the memory access pattern of node/threshold lookups.
    rows = 1 << 16
    table = jnp.ones((rows, 64), jnp.float32)        # 16 MiB
    idx = jnp.asarray(np.random.default_rng(0).integers(0, rows, rows),
                      jnp.int32)
    gat = jax.jit(lambda tb, ix: jnp.take(tb, ix, axis=0))
    gat(table, idx).block_until_ready()
    t = _time_best(lambda: gat(table, idx).block_until_ready())
    gather_bw = 2.0 * table.nbytes / max(t, 1e-9)

    # Host→device transfer: device_put of a 16 MiB numpy array.
    host = np.ones((4 << 20,), np.float32)
    jax.device_put(host).block_until_ready()
    t = _time_best(lambda: jax.device_put(host).block_until_ready())
    h2d_bw = host.nbytes / max(t, 1e-9)

    # Dispatch overhead: one tiny jitted call, end to end.
    tiny = jnp.ones((8,), jnp.float32)
    t = _time_best(lambda: add(tiny).block_until_ready(), iters=5)
    dispatch_s = t

    peaks = {
        "peak_flops_bf16": peak_flops,
        "hbm_bandwidth": stream_bw,
        # Single-host loopback: inter-"chip" traffic moves at memory
        # speed; keeps collective terms finite and comparable.
        "ici_bandwidth": stream_bw,
        "gather_bandwidth": gather_bw,
        "h2d_bandwidth": h2d_bw,
        "dispatch_s": dispatch_s,
        "backend": backend,
        "measured": True,
    }
    _CALIBRATED[backend] = peaks
    return peaks


def resolve_peaks(backend: str | None = None) -> dict[str, float]:
    """Peaks table for cost ranking on the live backend.

    TPU backends get the published table of their ``device_kind``
    (``mesh.TPU_PEAKS``; a kind not in it is an error, not a default),
    augmented with ASSUMED gather/h2d/dispatch entries — the table says
    so with ``measured=False``.  Everything else gets the one-shot
    calibrated table from :func:`calibrate_peaks`.
    """
    import jax
    backend = backend or jax.default_backend()
    if backend == "tpu":
        kind = jax.devices()[0].device_kind
        if kind not in TPU_PEAKS:
            raise ValueError(
                f"no published peaks for TPU device kind {kind!r}; "
                f"known kinds: {sorted(TPU_PEAKS)}")
        peaks = dict(TPU_PEAKS[kind])
        peaks.setdefault("gather_bandwidth", peaks["hbm_bandwidth"] / 8)
        peaks.setdefault("h2d_bandwidth", 25e9)      # PCIe-class
        peaks.setdefault("dispatch_s", 5e-6)
        peaks["backend"] = "tpu"
        peaks["device_kind"] = kind
        peaks["measured"] = False
        return peaks
    return calibrate_peaks(backend)


def roofline_terms(*, flops_per_chip: float, bytes_per_chip: float,
                   coll_bytes_per_chip: float,
                   peak=V5E) -> dict[str, float]:
    compute_s = flops_per_chip / peak["peak_flops_bf16"]
    memory_s = bytes_per_chip / peak["hbm_bandwidth"]
    coll_s = coll_bytes_per_chip / peak["ici_bandwidth"]
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    terms["dominant"] = dominant
    terms["step_s_lower_bound"] = bound
    return terms
