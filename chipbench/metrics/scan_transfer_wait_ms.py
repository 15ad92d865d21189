"""Tier transfer: the time a query's compute thread waited for its next
batch of pages to reach the device (``ScanStats.transfer_wait_s``: the
copy that double-buffering did not hide), per query, in ms."""


def read(run):
    queries = run.window.queries
    if not queries:
        return None
    return sum(q.scan.transfer_wait_s for q in queries) / len(queries) * 1e3
