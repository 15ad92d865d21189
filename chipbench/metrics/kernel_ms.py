"""Fused kernels: device time of the Pallas forest kernel per query, in
ms: the summed durations of its events (``tpu_custom_call`` in the
trace's ``XLA Ops`` line) over the window's queries."""


def read(run):
    queries = run.window.queries
    if run.device is None or not queries or run.device.kernel_s <= 0:
        return None
    return run.device.kernel_s / len(queries) * 1e3
