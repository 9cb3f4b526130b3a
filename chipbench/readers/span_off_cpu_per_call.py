"""Milliseconds per call that threads with host work to do spent *off*
the CPU: over the spans that carry ``cpu_us`` (the tracer puts a
thread's CPU time beside the wall time of its outermost span: the
call's own, a worker thread's), the sum of ``dur`` less ``cpu_us``,
less the spans named in ``waits`` inside them on the same thread, in
which the thread waits by design (for the device, for another thread's
verdicts). What is left is time a thread was descheduled, or waited
for a lock or the GIL. Read it as a mean over a window: where the
machine's thread clock moves in ticks of 10 ms, one call's number says
little. A program whose spans carry no ``cpu_us`` gives nothing."""


def read(ev, waits):
    outer = [s for s in ev.spans if "cpu_us" in s["args"]]
    if not outer or not ev.calls:
        return None
    off_us = sum(s["dur"] - s["args"]["cpu_us"] for s in outer)
    held = {}  # thread -> its outermost spans' (start, end)
    for s in outer:
        held.setdefault(s.get("tid"), []).append((s["ts"], s["ts"] + s["dur"]))
    for s in ev.spans:
        if s["name"] in waits and any(
            lo <= s["ts"] and s["ts"] + s["dur"] <= hi for lo, hi in held.get(s.get("tid"), ())
        ):
            off_us -= s["dur"]
    return off_us / 1000.0 / len(ev.calls)
