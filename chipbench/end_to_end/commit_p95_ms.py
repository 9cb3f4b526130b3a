"""95th percentile (nearest rank) of the calls' wall times, over every
call of the window; only where the window holds at least 200 calls, so
that ten samples lie beyond it."""

import math


def read(run):
    d = sorted(run["durations_ms"])
    if len(d) < 200:
        return None
    return d[math.ceil(0.95 * len(d)) - 1]
