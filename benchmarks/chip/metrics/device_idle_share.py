"""1 - (union of the device's operation intervals / traced window), from
the profiler trace."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
