"""Device milliseconds per call inside the programs (or operations)
whose names match, from the profiler's trace; averaged over the
devices, so on a mesh it is one device's share."""

from chipbench import tracefile


def kernel_seconds(ev, line, patterns):
    """(seconds summed over devices, events matched)."""
    lo, hi = tracefile.window(ev.trace)
    secs = count = 0
    for dev in ev.trace["devices"].values():
        events = dev[line]
        if line == "ops":
            events = tracefile.top_level(events)
        s, c = tracefile.matching_time(events, patterns, lo, hi)
        secs += s
        count += c
    return secs, count


def read(ev, line, patterns):
    if ev.trace is None or not ev.profiled_calls:
        return None
    secs, count = kernel_seconds(ev, line, patterns)
    if not count:
        return None
    n_dev = max(1, len(ev.trace["devices"]))
    return 1000.0 * secs / n_dev / len(ev.profiled_calls)
