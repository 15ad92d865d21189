"""Serve plane: the wait for a tick's predictions to reach the host (the
engine's ``serve.result_wait`` spans around ``np.asarray``, which hold
the kernel's run), per tick, in ms."""

from chipbench import ticks


def read(run):
    return ticks.split(run.spans).mean_ms("serve.result_wait")
