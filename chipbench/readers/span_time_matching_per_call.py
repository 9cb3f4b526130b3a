"""Milliseconds per call inside the named span, counting only spans
whose arguments equal the given ones (``{"engine": "sr25519"}``: one
engine's ``prep_chunk``). A program that opens no such span gives
nothing."""


def read(ev, span, where):
    picked = [
        s
        for s in ev.spans
        if s["name"] == span and all(s["args"].get(k) == v for k, v in where.items())
    ]
    if not picked or not ev.calls:
        return None
    return sum(s["dur"] for s in picked) / 1000.0 / len(ev.calls)
