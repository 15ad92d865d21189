"""Serve plane (coalescer): the share of the window's coalesced rows that
were padding, in %: the sum over ``serve.tick`` spans of their bucket
less their real rows, over the sum of their buckets."""


def read(run):
    ticks = [s.attrs for s in run.spans if s.name == "serve.tick"
             and "bucket" in s.attrs and "rows" in s.attrs]
    bucket = sum(a["bucket"] for a in ticks)
    if not bucket:
        return None
    return sum(a["bucket"] - a["rows"] for a in ticks) / bucket * 100
