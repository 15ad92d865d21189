"""Serve plane: 99th percentile of the wait from a request's due time
to the tick that coalesced it (``ForestRequest.admitted_at``)."""

import numpy as np


def read(run):
    r = run.window.requests
    if r is None or not np.isfinite(r.admitted_s).any():
        return None
    wait = r.admitted_s - r.due_s
    return float(np.percentile(wait[np.isfinite(wait)], 99)) * 1e3
