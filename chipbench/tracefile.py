"""From the profiler's trace to numbers: the one reduction every PR's
device metrics go through.

``load`` turns an ``.xplane.pb`` into plain lists (``reduced`` form):
for each device its operations and its programs ("modules") as
``[name, start_ns, duration_ns]``, and the host's ``chipbench_call``
annotations, which tie the trace's clock to the host's. Everything
else works on the reduced form, so the self-test can check it on a
small recorded trace (``testdata/trace_small.json``).
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

CALL_ANNOTATION = "chipbench_call"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> str:
    paths = sorted(
        glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not paths:
        raise RuntimeError("the profiler wrote no trace under %s" % log_dir)
    return paths[-1]


def short_name(name: str) -> str:
    """A TPU trace names an operation by its whole HLO text,
    ``%while.1948 = (s32[], ...) while(...)``: keep ``while.1948``."""
    if name.startswith("%"):
        return name[1:].split(" ", 1)[0]
    return name


def load(xplane_path: str) -> dict:
    """Reduced form of one trace. On a TPU each ``/device:TPU:n`` plane
    is a device, with its operations on the "XLA Ops" line and its
    programs on "XLA Modules". The CPU backend (the self-test's
    rehearsal only) has no device plane: its operations are the host
    plane's events that carry an ``hlo_op``, taken as one device (on
    several virtual devices their threads overlap; a rehearsal's
    numbers mean nothing anyway)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices: Dict[str, dict] = {}
    anchors: List[list] = []
    cpu_ops: List[list] = []
    cpu_modules: List[list] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    key = "ops"
                elif line.name == MODULES_LINE:
                    key = "modules"
                else:
                    continue
                dev[key] += [
                    [short_name(e.name), float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                ]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == CALL_ANNOTATION:
                        anchors.append([float(e.start_ns), float(e.duration_ns)])
                        continue
                    if e.duration_ns <= 0:
                        continue
                    stats = dict(e.stats)
                    if "hlo_op" not in stats:
                        continue
                    cpu_ops.append([e.name, float(e.start_ns), float(e.duration_ns)])
                    cpu_modules.append(
                        [str(stats.get("hlo_module")), float(e.start_ns), float(e.duration_ns)]
                    )
    if not devices and cpu_ops:
        # a rehearsal's "programs": each top-level operation under its
        # program's name
        devices["/host:CPU"] = {"ops": cpu_ops, "modules": top_level(cpu_modules)}
    for dev in devices.values():
        dev["ops"].sort(key=lambda ev: ev[1])
        dev["modules"].sort(key=lambda ev: ev[1])
    anchors.sort()
    return {"devices": devices, "anchors": anchors}


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Disjoint sorted intervals covering the same instants."""
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def top_level(events: List[list]) -> List[list]:
    """Events not nested inside an earlier one on the same line (a
    ``while`` and the operations of its body are all on "XLA Ops")."""
    out, end = [], -1.0
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        if ev[1] >= end:
            out.append(ev)
            end = ev[1] + ev[2]
    return out


def window(trace: dict) -> Tuple[float, float]:
    """The traced window on the trace's clock: from the start of the
    first annotated call to the end of the last."""
    if not trace["anchors"]:
        raise RuntimeError("no %s annotation in the trace" % CALL_ANNOTATION)
    lo = trace["anchors"][0][0]
    hi = max(a + d for a, d in trace["anchors"])
    return lo, hi


def busy(trace: dict) -> dict:
    """Per device, the disjoint intervals inside the window in which
    an operation ran; ``busy_s`` is their length averaged over the
    devices, ``window_s`` the window's."""
    lo, hi = window(trace)
    per_device = {}
    for name, dev in trace["devices"].items():
        ivs = [(s, s + d) for _, s, d in dev["ops"]]
        per_device[name] = clip(union(ivs), lo, hi)
    n = max(1, len(per_device))
    return {
        "window": (lo, hi),
        "per_device": per_device,
        "busy_s": sum(total(v) for v in per_device.values()) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
    }


def matching_time(events: List[list], patterns: List[str], lo: float, hi: float):
    """(seconds, count) of the events inside the window whose name
    matches one of ``patterns`` (``fnmatch`` globs)."""
    import fnmatch

    secs, count = 0.0, 0
    for name, start, dur in events:
        if start < lo or start >= hi:
            continue
        if any(fnmatch.fnmatchcase(name, p) for p in patterns):
            secs += dur / 1e9
            count += 1
    return secs, count


def device_ops(trace: dict, limit: int = 10) -> List[list]:
    """The top-level device operations that took most time inside the
    window, summed by name over the devices and divided by their
    number: ``[[name, seconds], ...]``."""
    lo, hi = window(trace)
    by_name: Dict[str, float] = {}
    for dev in trace["devices"].values():
        for name, start, dur in top_level(dev["ops"]):
            if start >= lo and start < hi:
                by_name[name] = by_name.get(name, 0.0) + dur
    n = max(1, len(trace["devices"]))
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, ns / n / 1e9] for name, ns in ranked]


def programs_by_time(trace: dict, limit: int = 6) -> List[list]:
    """The programs ("XLA Modules") that took most device time inside
    the window, summed over the devices: ``[[name, seconds], ...]``."""
    lo, hi = window(trace)
    by_name: Dict[str, float] = {}
    for dev in trace["devices"].values():
        for name, start, dur in dev["modules"]:
            if lo <= start < hi:
                by_name[name] = by_name.get(name, 0.0) + dur / 1e9
    return [list(kv) for kv in sorted(by_name.items(), key=lambda kv: -kv[1])[:limit]]


def innermost_segments(host_spans: List[list]) -> List[Tuple[float, float, str]]:
    """Flatten properly nested spans ``[name, start, end]`` of one
    thread into disjoint ``(start, end, name)`` segments, each named
    for the deepest span covering it."""
    out: List[Tuple[float, float, str]] = []
    stack: List[list] = []  # open spans, outermost first
    at = None

    def emit(upto: float) -> None:
        if stack and upto > at:
            out.append((at, upto, stack[-1][0]))

    for span in sorted(host_spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][2] <= span[1]:
            emit(stack[-1][2])
            at = max(at, stack[-1][2])
            stack.pop()
        if stack:
            emit(span[1])
        at = span[1]
        stack.append(span)
    while stack:
        emit(stack[-1][2])
        at = max(at, stack[-1][2])
        stack.pop()
    return out


def idle_gaps(trace: dict, host_spans: List[list], limit: int = 10) -> List[list]:
    """The device's idle time inside the window, by what the host was
    doing: ``host_spans`` are ``[name, start_ns, end_ns]`` on the
    trace's clock, from the one thread that drives the device, and
    every idle instant goes to the deepest span that covers it
    (``between_calls`` where none does). Idle means: no device was
    running an operation. ``[[name, seconds], ...]``."""
    b = busy(trace)
    lo, hi = b["window"]
    any_busy = union([iv for ivs in b["per_device"].values() for iv in ivs])
    gaps, at = [], lo
    for a, z in any_busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, z)
    if at < hi:
        gaps.append((at, hi))
    segs = innermost_segments(host_spans)
    by_name: Dict[str, float] = {}
    j = 0
    for a, z in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k, covered = j, 0.0
        while k < len(segs) and segs[k][0] < z:
            c = min(z, segs[k][1]) - max(a, segs[k][0])
            if c > 0:
                by_name[segs[k][2]] = by_name.get(segs[k][2], 0.0) + c
                covered += c
            k += 1
        if z - a > covered:
            by_name["between_calls"] = (
                by_name.get("between_calls", 0.0) + (z - a) - covered
            )
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, ns / 1e9] for name, ns in ranked]
