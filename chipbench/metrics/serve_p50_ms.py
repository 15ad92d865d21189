"""Median latency of the served requests (host clock), measured as
``serve_p95_ms`` is."""

import numpy as np


def read(run):
    r = run.window.requests
    if r is None:
        return None
    return float(np.percentile(r.latency_s, 50)) * 1e3
