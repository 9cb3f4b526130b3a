"""Milliseconds per call of a span's self time: its duration less the
part its named child spans cover."""


def read(ev, span, children):
    parents = sorted(
        (s["ts"], s["ts"] + s["dur"]) for s in ev.spans if s["name"] == span
    )
    if not parents or not ev.calls:
        return None
    total = sum(hi - lo for lo, hi in parents)
    kids = sorted(
        (s["ts"], s["ts"] + s["dur"]) for s in ev.spans if s["name"] in children
    )
    covered, j = 0.0, 0
    for lo, hi in kids:
        while j < len(parents) and parents[j][1] <= lo:
            j += 1
        if j < len(parents) and parents[j][0] <= lo and hi <= parents[j][1]:
            covered += hi - lo
    return (total - covered) / 1000.0 / len(ev.calls)
