"""The operations and bytes a forest query needs, from its shapes alone.

The count is what the query asks for, whatever implements it: each row
is read once (``features`` f32 values), its prediction is written once
(one f32), the tree arrays are read once, and each row walks each tree
from the root to a leaf, one comparison per level.  An implementation
that evaluates every node, re-reads the rows per tree partition or pads
its tiles does more work than this; the roofline share then says how far
it is from the least the chip could take.
"""

from __future__ import annotations

import dataclasses

from chipbench.peaks import Peaks

__all__ = ["Work", "forest_bytes", "query_work", "roofline_s"]

F32 = 4


@dataclasses.dataclass(frozen=True)
class Work:
    ops: float          # comparisons
    bytes: float        # bytes read and written

    def __add__(self, other: "Work") -> "Work":
        return Work(self.ops + other.ops, self.bytes + other.bytes)

    def __mul__(self, n: float) -> "Work":
        return Work(self.ops * n, self.bytes * n)


def forest_bytes(trees: int, depth: int) -> int:
    """A complete forest's arrays: per internal node a feature index
    (int32), a threshold (f32) and a default direction (one byte); per
    leaf a value (f32)."""
    internal, leaves = (1 << depth) - 1, 1 << depth
    return trees * (internal * (4 + F32 + 1) + leaves * F32)


def query_work(rows: int, features: int, trees: int, depth: int) -> Work:
    """Scoring ``rows`` rows of ``features`` f32 values with a complete
    forest of ``trees`` trees of depth ``depth``."""
    return Work(ops=float(rows) * trees * depth,
                bytes=float(rows) * (features * F32 + F32)
                + forest_bytes(trees, depth))


def roofline_s(work: Work, peaks: Peaks) -> tuple[float, str]:
    """The least time the chip could take for ``work``, and which bound
    sets it (``"compute"`` or ``"memory"``)."""
    compute = work.ops / peaks.flops
    memory = work.bytes / peaks.hbm_bytes_per_s
    return (memory, "memory") if memory >= compute else (compute, "compute")
