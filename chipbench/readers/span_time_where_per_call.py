"""Milliseconds per call inside the named span, counting only spans
whose arguments reach the given minimums (``{"builds": 1}``: only the
table gathers that built something inside the window). A program that
never opens the span gives nothing; one that opens it and never reaches
the minimums gives 0."""


def read(ev, span, min_args):
    spans = [s for s in ev.spans if s["name"] == span]
    if not spans or not ev.calls:
        return None
    us = sum(
        s["dur"]
        for s in spans
        if all(s["args"].get(k, 0) >= v for k, v in min_args.items())
    )
    return us / 1000.0 / len(ev.calls)
