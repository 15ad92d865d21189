"""Serve plane: mean duration of one coalescer tick, the engine's
``serve.tick`` spans (coalesce, ``infer_rows``, hand-back), in ms."""


def read(run):
    ticks = [s.duration_s for s in run.spans if s.name == "serve.tick"]
    if not ticks:
        return None
    return sum(ticks) / len(ticks) * 1e3
