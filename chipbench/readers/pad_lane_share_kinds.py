"""``pad_lane_share`` for a cell whose chunks are of kinds that reader's
table does not hold: the share of the lanes the device was given that
nobody asked for, the padded width of a chunk read from the narrowest
compiled kernel (``kernel_compile`` spans) of the chunk's kind that
holds it, or from the ``mesh_dispatch`` span under it. Which kernel
serves which kind is the metric's to say (``kernel_of_kind``)."""


def read(ev, kernel_of_kind):
    widths = {}
    for s in ev.setup_spans + ev.spans:
        if s["name"] == "kernel_compile":
            widths.setdefault(s["args"].get("kernel"), set()).add(
                int(s["args"].get("lanes", 0))
            )
    mesh = sorted(
        (s["ts"], int(s["args"]["lanes"])) for s in ev.spans if s["name"] == "mesh_dispatch"
    )
    useful = padded = 0
    for s in ev.spans:
        if s["name"] != "dispatch_chunk":
            continue
        lanes = int(s["args"]["lanes"])
        inside = [m for ts, m in mesh if s["ts"] <= ts <= s["ts"] + s["dur"]]
        fits = [
            w for w in widths.get(kernel_of_kind.get(s["args"].get("kind")), ()) if w >= lanes
        ]
        if not inside and not fits:
            return None
        useful += lanes
        padded += inside[-1] if inside else min(fits)
    if not padded:
        return None
    return 100.0 * (1.0 - useful / padded)
