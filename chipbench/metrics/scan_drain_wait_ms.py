"""Scan executor: the time a query's compute thread was blocked on the
drain of predictions to the host (``ScanStats.drain_wait_s``), per
query, in ms."""


def read(run):
    queries = run.window.queries
    if not queries:
        return None
    return sum(q.scan.drain_wait_s for q in queries) / len(queries) * 1e3
