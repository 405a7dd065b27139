"""90th percentile (nearest rank) of the window's step times, in ms: host
clock from one step's feed call to the next, each step ended on the host
by the loop's ``block_until_ready``."""
import math


def read(run):
    times = sorted(run.step_s)
    return 1e3 * times[math.ceil(0.9 * len(times)) - 1]
