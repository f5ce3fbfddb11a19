"""95th percentile (nearest rank) over every request of the window, from
its first submission until its results are ready on the device."""
import math


def read(run):
    if not run.latencies_s:
        return None
    s = sorted(run.latencies_s)
    return 1e3 * s[max(0, math.ceil(0.95 * len(s)) - 1)]
