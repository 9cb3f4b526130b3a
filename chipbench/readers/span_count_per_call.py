"""How many spans of one name a call opens, over the window's calls:
the ``dispatch_chunk`` spans of a catch-up window (2 where the window
was planned into one sub-batch a key type, 32 where each of sixteen
blocks went out alone). A program that never opens the span gives
nothing."""


def read(ev, span):
    count = sum(1 for s in ev.spans if s["name"] == span)
    if not count or not ev.calls:
        return None
    return count / len(ev.calls)
