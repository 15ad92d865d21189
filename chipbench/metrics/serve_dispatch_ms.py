"""Query engine, row path: the plan-cache lookup, the dispatch of the
plan's stages and of the padding mask's ``jnp.where`` (the engine's
``query.rows_dispatch`` spans under ``serve.tick``), per tick, in ms."""

from chipbench import ticks


def read(run):
    return ticks.split(run.spans).mean_ms("query.rows_dispatch")
