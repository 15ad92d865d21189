"""Published peaks of the chips the benchmark runs on, keyed by
``device_kind`` as JAX reports it.

A kind that is not in the table is an error: a roofline share against a
guessed peak would be a number with no meaning.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Peaks", "PEAKS", "peaks_for"]


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float              # operations per second (bf16 MXU peak)
    hbm_bytes_per_s: float    # device memory bandwidth
    hbm_bytes: float          # device memory
    source: str


PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM at 819 GB/s per chip.  JAX names the chip
    # "TPU v5 lite".
    "TPU v5 lite": Peaks(flops=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9,
                         source='Google Cloud, "TPU v5e" (per chip)'),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for a kind the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
