"""device_idle_share: the part of the traced window in which no operation
ran on chip 0."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.chips[0].busy_ns / run.trace.window_ns)
