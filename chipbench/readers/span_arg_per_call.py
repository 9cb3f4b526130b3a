"""A numeric argument of the named span, summed over the window's
spans and divided by its calls: a phase total the program put on a
span (``build_lanes``'s ``sign_bytes_us``) or a count it carries
(``dispatch_chunk``'s ``h2d_bytes``). ``scale`` turns the unit
(0.001: microseconds to milliseconds). A program whose spans lack the
argument gives nothing."""


def read(ev, span, arg, scale=1.0):
    values = [
        s["args"][arg]
        for s in ev.spans
        if s["name"] == span and arg in s["args"]
    ]
    if not values or not ev.calls:
        return None
    return scale * sum(values) / len(ev.calls)
