"""The verify kernels' share of their roofline, in percent: the least
time the chip could take for the useful lanes of the profiled calls
(``opcount.least_seconds`` against the published peaks of this
``device_kind``) over the device time their kernels took. Summed over
the devices on both sides, so a mesh reads as one chip does."""

from chipbench import opcount
from chipbench.readers import trace_kernel_time


def read(ev, line, patterns):
    if ev.trace is None or not ev.profiled_calls:
        return None
    secs, count = trace_kernel_time.kernel_seconds(ev, line, patterns)
    if not count or secs <= 0:
        return None
    lanes = {}
    for s in ev.profiled_spans:
        if s["name"] == "dispatch_chunk":
            kind = s["args"]["kind"]
            lanes[kind] = lanes.get(kind, 0) + int(s["args"]["lanes"])
    if not lanes:
        return None
    least = opcount.least_seconds(lanes, ev.peak)
    ev.note(
        "roofline: %s bound; %.4g ops and %d bytes for %s useful lanes; "
        "least %.6g s of %.6g s in %d kernel events"
        % (least["bound"], least["ops"], least["bytes"], lanes,
           least["seconds"], secs, count)
    )
    return 100.0 * least["seconds"] / secs
