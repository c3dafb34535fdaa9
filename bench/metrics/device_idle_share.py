"""device_idle_share: 1 - (union of the device's program executions) /
(the traced window), from the profiler trace, in %."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
