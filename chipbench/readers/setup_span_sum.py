"""Seconds of set-up spent inside the named span, counting only spans
whose arguments reach the given minimums (``{"builds": 1}``: only the
table gathers that built something)."""


def read(ev, span, min_args=None):
    picked = [
        s
        for s in ev.setup_spans
        if s["name"] == span
        and all(s["args"].get(k, 0) >= v for k, v in (min_args or {}).items())
    ]
    if not picked:
        return None
    return sum(s["dur"] for s in picked) / 1e6
