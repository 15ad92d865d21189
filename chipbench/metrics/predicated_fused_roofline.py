"""Fused kernels: the predicated forest kernel's share of its roofline,
in %.

The least time the chip could take for one query (``chipbench.work``:
every row and the forest read once, every prediction written once, one
comparison per tree level; against ``chipbench.peaks``) over the
kernel's device time per query.  At these shapes the bytes set the
bound: the forest's work is bound by memory bandwidth.
"""

from chipbench.work import roofline_s


def read(run):
    queries = run.window.queries
    if run.device is None or run.peaks is None or not queries \
            or run.device.kernel_s <= 0:
        return None
    least, _ = roofline_s(run.query_work(), run.peaks)
    return least / (run.device.kernel_s / len(queries)) * 100
