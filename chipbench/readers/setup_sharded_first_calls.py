"""Seconds of set-up inside the first calls of a mesh's kernels: the
``kernel_compile`` spans that carry ``devices`` (parallel/sharding's
Pallas programs, one a kind and slab width: the fetch from the kernel
store and the compile or cache load of the sharded call). What the
store did for each (``stored``: ``hit``, the lowered program was read
back; ``miss``, the kernel body was walked) is said in the run's notes.
A program whose sharded first calls record no such span gives nothing."""


def read(ev):
    picked = [
        s for s in ev.setup_spans if s["name"] == "kernel_compile" and "devices" in s["args"]
    ]
    if not picked:
        return None
    for s in picked:
        a = s["args"]
        ev.note(
            "sharded first call: kernel %s, %s lanes a device over %s devices, stored %s, %.2f s"
            % (a.get("kernel"), a.get("lanes"), a["devices"], a.get("stored"), s["dur"] / 1e6)
        )
    return sum(s["dur"] for s in picked) / 1e6
