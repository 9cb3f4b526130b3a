"""Share of the lanes the device was given that nobody asked for.

A ``dispatch_chunk`` span names the useful lanes of its chunk. What the
chunk was padded to is read from what the program did, not from a copy
of its bucket rule: the lanes of the ``mesh_dispatch`` span under it
where the chunk went out sharded, else the narrowest kernel of the
chunk's kind that the program compiled (``kernel_compile`` spans, which
set-up's warm-up calls leave) that holds it."""

KERNEL_OF_KIND = {
    "legacy": "verify",
    "tables": "verify_tables",
    "resident": "verify_resident",
}


def read(ev):
    widths = {}
    for s in ev.setup_spans + ev.spans:
        if s["name"] == "kernel_compile":
            widths.setdefault(s["args"].get("kernel"), set()).add(
                int(s["args"].get("lanes", 0))
            )
    mesh = sorted(
        (s["ts"], int(s["args"]["lanes"]))
        for s in ev.spans
        if s["name"] == "mesh_dispatch"
    )
    useful = padded = 0
    for s in ev.spans:
        if s["name"] != "dispatch_chunk":
            continue
        lanes = int(s["args"]["lanes"])
        inside = [m for ts, m in mesh if s["ts"] <= ts <= s["ts"] + s["dur"]]
        if inside:
            width = inside[-1]
        else:
            fits = [
                w
                for w in widths.get(KERNEL_OF_KIND.get(s["args"].get("kind")), ())
                if w >= lanes
            ]
            if not fits:
                return None
            width = min(fits)
        useful += lanes
        padded += width
    if not padded:
        return None
    return 100.0 * (1.0 - useful / padded)
