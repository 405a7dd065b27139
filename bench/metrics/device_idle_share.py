"""Share of the traced steps' wall time in which no operation ran on the
device, in percent: ``100 * (1 - busy / window)`` from the profiler trace."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
