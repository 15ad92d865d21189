"""Tier transfer: the rate the window's scans asked of the host-to-device
link, in GB/s: the engine's ``scan.pinned_bytes`` counter (bytes copied
from page-locked host memory to the device) over the window's seconds.
It is the demanded rate, averaged over the window, not the link's own
rate while a copy runs: it moves with the scan rate, and what share of
the copies the kernel did not hide is ``scan_transfer_wait_ms``.  A
program without the counter, or a window that copied nothing from pinned
memory, reports nothing."""


def read(run):
    copied = run.window.counters.get("scan.pinned_bytes", 0)
    if not copied or run.window.seconds <= 0:
        return None
    return copied / run.window.seconds / 1e9
