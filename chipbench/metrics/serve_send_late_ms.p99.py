"""Traffic generator: 99th percentile of how late a request was sent
after it was due (host clock).  A high value means the generator, which
shares the host with the serve plane, was starved: the latency it
records then includes its own delay."""

import numpy as np


def read(run):
    r = run.window.requests
    if r is None:
        return None
    return float(np.percentile(r.sent_s - r.due_s, 99)) * 1e3
