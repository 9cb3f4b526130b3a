"""A profiled call as the device saw it, milliseconds per call, on the
trace's own clock. The call is its ``chipbench_call`` annotation (the
anchor); the verify programs are the device's programs ("XLA Modules")
inside it whose names match ``patterns``. ``part``:

- ``head``: anchor start -> the first verify program's start;
- ``busy``: first start -> last end, the union of *all* programs there;
- ``gap``: the rest of first start -> last end: the device idle between
  the launches of one call;
- ``tail``: the last verify program's end -> anchor end.
  The four sum to the anchor's duration.
- ``launch_lag``: the call's first ``dispatch_chunk`` opens -> the first
  verify program starts (the puts, the launch, and what the runtime
  adds); ``readback_lag``: the last verify program ends -> the last
  ``collect_chunk`` closes (the wake-up and the copy back). The spans
  are placed by ``tracer.epoch_ns`` and by *this call's own* anchor
  (anchor start less the call's start on the host clock), never a
  median over calls.

Mean over the devices that ran a verify program in the call, then over
the profiled calls.

The lags cross from the host's plane of the trace to the device's, and
the profiler lines the two up anew in every session: on a TPU v5 lite
the device's events sat in one of two positions 1.1 ms apart against
the host's, session by session, and in the earlier one a 0.9 ms kernel
started before the host had finished putting its inputs (PERF.md §6,
PR 34). So the lags move the device's events of the session by the
least shift that makes every profiled call possible: no verify program
starts before the puts of its call's first chunk are done
(``dispatch_chunk`` opens + ``h2d_us``), none ends after the host saw
its output ready (the last ``collect_chunk`` closes - ``d2h_us``).
Where the trace is possible as it stands nothing moves; the shift is
noted in the run's output. A lag is then never under what the host's
own clock proves."""

import fnmatch

from chipbench import tracefile
from chipbench.readers import call_path

LAGS = ("launch_lag", "readback_lag")


def verify_span(dev, a0, a1, patterns):
    """(first start, last end, busy between) of the verify programs of
    one device inside one anchor; None where it ran none."""
    inside = [(n, s, s + d) for n, s, d in dev["modules"] if a0 <= s < a1]
    verify = [(s, e) for n, s, e in inside if any(fnmatch.fnmatchcase(n, p) for p in patterns)]
    if not verify:
        return None
    first, last = min(s for s, _ in verify), max(e for _, e in verify)
    everything = tracefile.union([(s, e) for _, s, e in inside])
    return first, last, tracefile.total(tracefile.clip(everything, first, last))


def host_chain(call, a0, epoch_ns):
    """On the trace's clock, by this call's own anchor: (first dispatch
    opens, its puts are done, the host sees the last output ready, last
    collect closes); None for a call that dispatched nothing."""
    ends = call_path.chain_ends(call)
    if ends is None:
        return None
    first, last = ends
    opens = epoch_ns + first["ts"] * 1000.0 + a0 - call["start_ns"]
    closes = epoch_ns + (last["ts"] + last["dur"]) * 1000.0 + a0 - call["start_ns"]
    return (
        opens,
        opens + first["args"].get("h2d_us", 0.0) * 1000.0,
        closes - last["args"].get("d2h_us", 0.0) * 1000.0,
        closes,
    )


def least_shift(rows):
    """ns to move the device's events by (later is positive) so that
    in every row the first program starts no earlier than its inputs
    were put and the last ends no later than the host saw it ready;
    0.0 where that holds already."""
    too_early = max(host[1] - first for first, _, host in rows)
    if too_early > 0:
        return too_early
    too_late = max(last - host[2] for _, last, host in rows)
    return -max(too_late, 0.0)


def read(ev, part, patterns):
    if ev.trace is None or not ev.profiled_calls:
        return None
    anchors = ev.trace["anchors"]
    if len(anchors) != len(ev.profiled_calls):
        return None
    lag = part in LAGS
    epoch_ns = call_path.tracer_epoch_ns() if lag else None
    if lag and epoch_ns is None:
        return None
    calls = []  # one list a call: per device (first, last, busy), and what the host saw
    for (a0, dur), call in zip(anchors, ev.profiled_calls):
        host = host_chain(call, a0, epoch_ns) if lag else None
        if lag and host is None:
            continue
        seen = [verify_span(dev, a0, a0 + dur, patterns) for dev in ev.trace["devices"].values()]
        seen = [s for s in seen if s is not None]
        if seen:
            calls.append((a0, a0 + dur, seen, host))
    if not calls:
        return None
    shift = 0.0
    if lag:
        shift = least_shift([(first, last, host) for _, _, seen, host in calls for first, last, _ in seen])
        if shift:
            ev.note("device_call_path: %s read with the device's events moved %+.3f ms, the least "
                    "that makes every profiled call possible" % (part, shift / 1e6))
    total = 0.0
    for a0, a1, seen, host in calls:
        for first, last, busy in seen:
            if part == "launch_lag":
                ns = first + shift - host[0]
            elif part == "readback_lag":
                ns = host[3] - last - shift
            else:
                ns = {"head": first - a0, "busy": busy, "gap": last - first - busy, "tail": a1 - last}[part]
            total += ns / len(seen)
    return total / len(calls) / 1e6
