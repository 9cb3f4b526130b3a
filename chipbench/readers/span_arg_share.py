"""A numeric argument summed over some spans as a share, in percent, of
an argument summed over others: the lanes of the ``host_lanes`` spans
over the lanes of ``build_lanes`` (the commit's signatures the host
verifies by design), or the lanes of the ``dispatch_chunk`` spans of
one ``kind`` over those of all. ``where`` holds the numerator's spans
to arguments they must equal. A program without the numerator's spans
gives nothing."""


def read(ev, span, arg, of_span, of_arg, where=None):
    top = [
        s["args"][arg]
        for s in ev.spans
        if s["name"] == span
        and arg in s["args"]
        and all(s["args"].get(k) == v for k, v in (where or {}).items())
    ]
    bottom = sum(
        s["args"][of_arg] for s in ev.spans if s["name"] == of_span and of_arg in s["args"]
    )
    if not top or not bottom:
        return None
    return 100.0 * sum(top) / bottom
