"""Device: the share of the traced window in which no operation ran on
the chip (1 - union of device op intervals / window), in percent."""


def read(run):
    if run.trace is None or not run.trace.ops or run.trace_window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace_busy_s / run.trace_window_s)
