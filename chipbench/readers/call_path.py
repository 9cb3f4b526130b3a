"""A call cut at two instants, milliseconds per call: the *start* of
its first ``dispatch_chunk`` (until then the device has been handed
nothing) and the end of its last ``collect_chunk`` (from then on the
host has every verdict). ``part`` = ``pre`` (call start -> first
dispatch), ``chain`` (between the two) or ``post`` (last collect ->
call end); the three sum to the call's wall time.

The call's start and end are the harness's ``perf_counter_ns``; the
spans are placed on that clock by the program's own word for its epoch
(``tracer.epoch_ns``), no probe span and no median. A program that does
not say its epoch gives nothing; a call that dispatched nothing is left
out of the mean."""

FIRST, LAST = "dispatch_chunk", "collect_chunk"


def tracer_epoch_ns():
    """perf_counter_ns of the tracer's ``ts = 0``, as the program says
    it; None from a program that does not."""
    from tendermint_tpu.libs import tracing

    return getattr(tracing.tracer, "epoch_ns", None)


def chain_ends(call):
    """(the first ``dispatch_chunk`` to open, the last ``collect_chunk``
    to close) of one call's spans; None where the call holds neither."""
    opened = [s for s in call["spans"] if s["name"] == FIRST]
    closed = [s for s in call["spans"] if s["name"] == LAST]
    if not opened or not closed:
        return None
    return (
        min(opened, key=lambda s: s["ts"]),
        max(closed, key=lambda s: s["ts"] + s["dur"]),
    )


def read(ev, part):
    epoch_ns = tracer_epoch_ns()
    if epoch_ns is None:
        return None
    parts = []
    for call in ev.calls:
        ends = chain_ends(call)
        if ends is None:
            continue
        lo = epoch_ns + ends[0]["ts"] * 1000.0
        hi = epoch_ns + (ends[1]["ts"] + ends[1]["dur"]) * 1000.0
        parts.append(
            {"pre": lo - call["start_ns"], "chain": hi - lo, "post": call["end_ns"] - hi}[part]
        )
    if not parts:
        return None
    return sum(parts) / len(parts) / 1e6
