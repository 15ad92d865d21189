"""Rows scored and delivered per second (host clock).

Every real row, not padding, that the window's queries scored and
delivered, over the time from the window's start to the end of its last
query.
"""


def read(run):
    queries = run.window.queries
    if not queries:
        return None
    return sum(q.rows for q in queries) / queries[-1].end_s
