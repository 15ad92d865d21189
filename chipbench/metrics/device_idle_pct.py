"""Device: the share of the traced window in which no operation ran on
the chip (1 - busy / window, from the profiler's trace), in %.  The
reader of every ``device_idle_pct.<cells>`` metric."""


def read(run):
    if run.device is None or run.device.window_s <= 0:
        return None
    return (1 - run.device.busy_s / run.device.window_s) * 100
