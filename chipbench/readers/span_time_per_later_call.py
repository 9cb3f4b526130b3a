"""Milliseconds per call inside the named spans, over every call of
the window but the first. The first call's drain of the program's ring
also holds what ran between set-up and the window; for ``gc_pause``
that is the benchmark's own ``gc.collect()`` there, a pause of ~0.1 s
that no call waited for."""


def read(ev, spans):
    calls = ev.calls[1:]
    if not calls:
        return None
    us = sum(
        s["dur"] for call in calls for s in call["spans"] if s["name"] in spans
    )
    return us / 1000.0 / len(calls)
