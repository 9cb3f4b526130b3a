"""Milliseconds per call spent inside the named spans."""


def read(ev, spans):
    if not ev.calls:
        return None
    us = sum(s["dur"] for s in ev.spans if s["name"] in spans)
    return us / 1000.0 / len(ev.calls)
