"""Median wall time of one call, over every call of the window."""

import statistics


def read(run):
    if not run["durations_ms"]:
        return None
    return statistics.median(run["durations_ms"])
