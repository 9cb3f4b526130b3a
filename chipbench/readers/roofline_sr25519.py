"""The sr25519 kernel's share of its roofline, in percent: ``roofline``
for the lanes of one kind — the least time the chip could take for the
useful sr25519 lanes of the profiled calls (``opcount_sr25519`` against
the published peaks) over the device time of the sr25519 programs
alone. A program that dispatches no such chunk, or none under a name
the patterns match, gives nothing."""

from chipbench import opcount_sr25519
from chipbench.readers import trace_kernel_time


def read(ev, line, patterns):
    if ev.trace is None or not ev.profiled_calls:
        return None
    secs, count = trace_kernel_time.kernel_seconds(ev, line, patterns)
    lanes = sum(
        int(s["args"]["lanes"])
        for s in ev.profiled_spans
        if s["name"] == "dispatch_chunk" and s["args"].get("kind") == "sr25519"
    )
    if not count or secs <= 0 or not lanes:
        return None
    least = opcount_sr25519.least_seconds(lanes, ev.peak)
    ev.note(
        "sr25519 roofline: %s bound; %.4g ops and %d bytes for %d useful lanes; "
        "least %.6g s of %.6g s in %d kernel events"
        % (least["bound"], least["ops"], least["bytes"], lanes,
           least["seconds"], secs, count)
    )
    return 100.0 * least["seconds"] / secs
