"""Query engine, row path: the upload of a tick's padded rows and mask
to the device (the engine's ``query.rows_upload`` spans under
``serve.tick``), per tick, in ms."""

from chipbench import ticks


def read(run):
    return ticks.split(run.spans).mean_ms("query.rows_upload")
