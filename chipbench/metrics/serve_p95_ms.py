"""95th percentile latency of the served requests (host clock).

Over every request sent in the window, from when it was due to when its
answer reached the client; a request never answered counts until the
wait for answers ended.
"""

import numpy as np


def read(run):
    r = run.window.requests
    if r is None:
        return None
    return float(np.percentile(r.latency_s, 95)) * 1e3
