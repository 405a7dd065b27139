"""Median of the window's step times, in ms (host clock, as step_ms_p90)."""
import statistics


def read(run):
    return 1e3 * statistics.median(run.step_s)
