"""Whole query on the device: the window's queries' share of the chip's
peak, in %.

The least time the chip could take for all the window's queries (the
same count and peaks as ``predicated_fused_roofline``) over the window's
wall time.  It bounds every kernel's share from above, so it still
speaks when a kernel leaves the path.
"""

from chipbench.work import roofline_s


def read(run):
    queries = run.window.queries
    if run.peaks is None or not queries:
        return None
    least, _ = roofline_s(run.query_work(), run.peaks)
    return least * len(queries) / run.window.seconds * 100
