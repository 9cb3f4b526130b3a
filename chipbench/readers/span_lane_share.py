"""Share of the lanes of one kind of span that sit in spans holding a
given child span (lanes of ``dispatch_chunk`` spans that went out
through a ``mesh_dispatch``)."""


def read(ev, span, child):
    kids = [s["ts"] for s in ev.spans if s["name"] == child]
    all_lanes = with_child = 0
    for s in ev.spans:
        if s["name"] != span:
            continue
        lanes = int(s["args"]["lanes"])
        all_lanes += lanes
        if any(s["ts"] <= ts <= s["ts"] + s["dur"] for ts in kids):
            with_child += lanes
    if not all_lanes:
        return None
    return 100.0 * with_child / all_lanes
