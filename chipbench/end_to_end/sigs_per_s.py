"""Useful signatures verified per second: every call of the window,
from the first call's start to the last call's completion. The call in
flight when the window's time is up is completed and counted, so no
window edge quantises the rate; pad lanes do not count."""


def read(run):
    span_ns = run["last_end_ns"] - run["first_start_ns"]
    if span_ns <= 0:
        return None
    return run["lanes_per_call"] * len(run["durations_ms"]) / (span_ns / 1e9)
