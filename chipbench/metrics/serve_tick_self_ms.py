"""Serve plane: a tick's own time, in ms: the mean ``serve.tick`` less
its upload, dispatch and result-wait phases, so coalescing, bookkeeping
and the hand-back of answers.  With ``serve_upload_ms``,
``serve_dispatch_ms`` and ``serve_result_wait_ms`` it adds up to
``serve_tick_ms``."""

from chipbench import ticks


def read(run):
    return ticks.split(run.spans).self_ms()
