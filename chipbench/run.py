"""Run one cell of the benchmark once.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. Set-up (imports, keys, signed requests, the program's table
build and upload, compile or cache load, the untimed warm-up calls)
ends where the first timed call starts; then one caller drives the
cell's traffic for ``--seconds``; then the answers are checked. The
last line of standard output is the result, one JSON object; the lines
before it say what ran. ``--trace 0`` reports the cell's end-to-end
metrics with the program's tracer off; ``--trace 1`` turns the tracer
on, profiles a few calls inside the window and reports the cell's
per-layer metrics.

Exit code 0 only with a result. No accelerator, or another number of
chips than the cell states, is exit code 3 and no result: there is no
CPU fallback. (``--rehearse``, for ``selftest.py`` alone, skips that
look and marks every line of output as a rehearsal.)
"""

from __future__ import annotations

import time

T0_NS = time.perf_counter_ns()  # process start, as near as Python lets us

import argparse
import gc
import importlib
import json
import os
import shutil
import statistics
import sys

from chipbench import spec as spec_mod
from chipbench import tracefile

PROFILE_AFTER = 0.5  # of the window: where the profiled stretch starts
PROFILE_SECONDS = 2.0  # its length ...
PROFILE_MIN_CALLS = 3  # ... or this many calls, whichever is longer
PROFILE_MAX_CALLS = 8  # 10^5 device events a call: a trace of more is too large to read back
SAMPLE_LANES = 64  # lanes a run holds against the plain reference


def snapshot_counters() -> dict:
    """The program's own counters, flattened to ``group.name`` paths
    (``resident.hits``, ``results.hits``, ``health.failures`` ...): what
    ``layer_metrics/*.json`` name and the health checks read."""
    from tendermint_tpu.ops import hash512, precompute, resident
    from tendermint_tpu.ops.device_policy import shared as health
    from tendermint_tpu.parallel import mesh

    groups = {
        "health": health.snapshot(),
        "resident": resident.stats(),
        "hash": hash512.stats(),
        "results": precompute.results.stats(),
        "tables": precompute.tables.stats(),
        "mesh": mesh.manager.snapshot(),
    }
    groups["health"]["failures"] = sum(groups["health"]["failures"].values())
    return {
        "%s.%s" % (group, name): value
        for group, stats in groups.items()
        for name, value in stats.items()
        if isinstance(value, (int, float)) and not isinstance(value, bool)
    }


class Context:
    """What a generator gets: the cell's files and the seed."""

    def __init__(self, cell, config, traffic, seed, say):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.say = seed, say


class Results:
    """Every number the run compares, beside its limit. All of this
    benchmark's comparisons are exact: the limit is 0."""

    sample_lanes = SAMPLE_LANES

    def __init__(self, say):
        self.rows = []
        self._say = say

    def compare(self, name: str, value, limit) -> None:
        self.rows.append((name, value, limit))
        self._say(
            "compared: %s = %s (limit %s)%s"
            % (name, value, limit, "" if value <= limit else "  <-- over")
        )

    @property
    def correct(self) -> bool:
        return all(value <= limit for _, value, limit in self.rows)


class Evidence:
    """What the readers of per-layer metrics read."""

    def __init__(self):
        self.calls = []  # measured, unprofiled calls: {start_ns, end_ns}
        self.profiled_calls = []
        self.spans = []  # the program's spans inside ``calls`` (ts, dur in us)
        self.profiled_spans = []
        self.setup_spans = []
        self.before, self.after = {}, {}
        self.lanes_sent = 0  # useful lanes of every call of the window
        self.trace = None  # tracefile's reduced form
        self.busy = None
        self.peak = None  # this device_kind's entry of peaks.json
        self.notes = []

    def counter_delta(self, path: str):
        return self.after[path] - self.before[path]

    def note(self, text: str) -> None:
        self.notes.append(text)


def drain_spans() -> list:
    from tendermint_tpu.libs import tracing

    doc = tracing.tracer.export(clear=True)
    if doc["otherData"]["dropped"]:
        raise RuntimeError("the program's trace ring overflowed between drains")
    return [e for e in doc["traceEvents"] if e.get("ph") == "X"]


def tracer_epoch_ns() -> float:
    """perf_counter_ns of the instant the program's tracer counts its
    ``ts`` from, found with a span of our own (public API only)."""
    from tendermint_tpu.libs import tracing

    before = time.perf_counter_ns()
    with tracing.span("chipbench_clock"):
        pass
    mark = [e for e in drain_spans() if e["name"] == "chipbench_clock"][-1]
    return before - mark["ts"] * 1000.0


def watch_compiles() -> list:
    """Every backend compilation (or load from the persistent cache)
    lands in the returned list as perf_counter_ns."""
    import jax

    seen = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(time.perf_counter_ns())

    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen


def run_window(traffic, seconds: float, trace: bool, trace_dir: str):
    """The measured window: one caller, one call after another, until
    ``seconds`` have passed; the call in flight then is completed and
    counted. With ``trace`` the program's spans are drained after every
    call and a stretch in the middle runs under the profiler."""
    import jax

    calls, outcomes = [], []
    profiling = profiled = False
    n_profiled = 0
    prof_t0 = 0
    t0 = time.perf_counter_ns()
    t_end = t0 + int(seconds * 1e9)
    profile_from = t0 + int(PROFILE_AFTER * seconds * 1e9)
    i = 0
    while True:
        now = time.perf_counter_ns()
        if now >= t_end:
            break
        if trace and not profiling and not profiled and now >= profile_from:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            profiling = True
            prof_t0 = time.perf_counter_ns()
        if profiling:
            start = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation(tracefile.CALL_ANNOTATION):
                out = traffic.call(i)
            end = time.perf_counter_ns()
            n_profiled += 1
        else:
            start = time.perf_counter_ns()
            out = traffic.call(i)
            end = time.perf_counter_ns()
        call = {"start_ns": start, "end_ns": end, "profiled": profiling}
        if trace:
            call["spans"] = drain_spans()
        calls.append(call)
        outcomes.append(out)
        i += 1
        if profiling and (
            n_profiled >= PROFILE_MAX_CALLS
            or (
                n_profiled >= PROFILE_MIN_CALLS
                and end - prof_t0 >= PROFILE_SECONDS * 1e9
            )
        ):
            jax.profiler.stop_trace()
            profiling, profiled = False, True
    if profiling:
        jax.profiler.stop_trace()
    return calls, outcomes


def gather_evidence(ev: Evidence, calls, epoch_ns: float, trace_dir: str) -> list:
    """Sort the window's spans into measured and profiled, read the
    profiler's trace, and put the profiled calls' spans on its clock.
    Returns those spans as ``[name, start, end]`` for the idle gaps."""
    for call in calls:
        if call["profiled"]:
            ev.profiled_calls.append(call)
            ev.profiled_spans += call["spans"]
        else:
            ev.calls.append(call)
            ev.spans += call["spans"]
    if not ev.profiled_calls:
        return []
    ev.trace = tracefile.load(tracefile.find_xplane(trace_dir))
    anchors = ev.trace["anchors"]
    if len(anchors) != len(ev.profiled_calls):
        raise RuntimeError(
            "the trace holds %d call annotations for %d profiled calls"
            % (len(anchors), len(ev.profiled_calls))
        )
    offset = statistics.median(
        a[0] - c["start_ns"] for a, c in zip(anchors, ev.profiled_calls)
    )
    ev.busy = tracefile.busy(ev.trace)
    host = [
        ["caller", c["start_ns"] + offset, c["end_ns"] + offset]
        for c in ev.profiled_calls
    ]
    for s in ev.profiled_spans:
        lo = epoch_ns + s["ts"] * 1000.0 + offset
        host.append([s["name"], lo, lo + s["dur"] * 1000.0])
    return host


def device_alone(ev: Evidence, traced: bool) -> list:
    """Reasons, if any, to think the host answered lanes: a copy of
    ``chip_smoke.py``'s conditions. An empty list is a healthy run."""
    why = []
    for path in ("health.fallback_batches", "health.failures", "mesh.exclusions"):
        if ev.counter_delta(path):
            why.append("%s grew by %s" % (path, ev.counter_delta(path)))
    spans = ev.spans + ev.profiled_spans
    fallbacks = sum(1 for s in spans if s["name"] == "host_fallback")
    if fallbacks:
        why.append("%d host_fallback spans" % fallbacks)
    if traced:
        for stage in ("dispatch_chunk", "collect_chunk"):
            lanes = sum(int(s["args"]["lanes"]) for s in spans if s["name"] == stage)
            if lanes != ev.lanes_sent:
                why.append("%s spans carry %d lanes of %d sent" % (stage, lanes, ev.lanes_sent))
    return why


def published_peak(kind: str, rehearse: bool) -> dict:
    peaks = spec_mod.load_json(os.path.join(spec_mod.HERE, "peaks.json"))
    if kind in peaks:
        return peaks[kind]
    if rehearse:  # a rehearsal's roofline is arithmetic, not a reading
        return peaks["TPU v5 lite"]
    raise SystemExit("chipbench: no published peaks for device_kind %r in peaks.json" % kind)


def device_record(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    # for selftest.py and control.py alone; the driver passes neither
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--bench-file", default=os.path.join(spec_mod.ROOT, "BENCHMARK.json"))
    ap.add_argument("--break", dest="breaks", action="append", default=[])
    args = ap.parse_args(argv)

    spec = spec_mod.Spec(args.bench_file)
    cell = spec.cell(args.workload)
    config = spec.config(cell["config"])
    traffic_doc = spec.traffic(cell["traffic"])
    tag = "chipbench[rehearsal, not a measurement]: " if args.rehearse else "chipbench: "

    def say(text: str) -> None:
        print(tag + text, flush=True)

    # the deployment's operator settings, before the program is imported
    for key, value in config.get("env", {}).items():
        os.environ[key] = str(value)
    say("cell %s: config %s, traffic %s, seed %d, %.6g s, trace %d; env %s"
        % (cell["name"], cell["config"], cell["traffic"], args.seed,
           args.seconds, args.trace, json.dumps(config.get("env", {}))))

    import jax

    devs = jax.devices()
    say("device: platform %s, device_kind %s, count %d"
        % (devs[0].platform, devs[0].device_kind, len(devs)))
    if not args.rehearse and (devs[0].platform != "tpu" or len(devs) != cell["chips"]):
        print(
            "chipbench: cell %s needs %d TPU chip(s); JAX found %d %s device(s). "
            "No result." % (cell["name"], cell["chips"], len(devs), devs[0].platform),
            file=sys.stderr,
        )
        return 3
    compiles = watch_compiles()

    from tendermint_tpu.libs import tracing
    from tendermint_tpu.ops import autotune, ed25519_batch, field32

    ev = Evidence()
    epoch_ns = 0.0
    if args.trace:
        tracing.configure("ring")
        epoch_ns = tracer_epoch_ns()
    else:
        tracing.configure("off")
    breaks = None
    if args.breaks:
        breaks = importlib.import_module("chipbench.breaks")
        for name in args.breaks:
            breaks.before_setup(name, traffic_doc, say)

    # --- set-up ---------------------------------------------------------
    ctx = Context(cell, config, traffic_doc, args.seed, say)
    traffic = spec_mod.generator(traffic_doc["kind"]).build(ctx)
    traffic.warm()
    if args.trace:
        ev.setup_spans = drain_spans()
    for name in args.breaks:
        breaks.after_setup(name, say)
    trace_dir = os.path.join(spec_mod.ROOT, ".chipbench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    # set-up's ~10^5 keys, signatures and validators stop being
    # rescanned at every full collection; the collector stays on
    gc.collect()
    gc.freeze()
    ev.before = snapshot_counters()
    compiles_before = len(compiles)
    setup_s = (time.perf_counter_ns() - T0_NS) / 1e9

    # --- the window -------------------------------------------------------
    calls, outcomes = run_window(traffic, args.seconds, bool(args.trace), trace_dir)
    ev.after = snapshot_counters()
    compiles_in_window = len(compiles) - compiles_before
    ev.lanes_sent = traffic.lanes_per_call * len(calls)
    host_spans = gather_evidence(ev, calls, epoch_ns, trace_dir) if args.trace else []
    shutil.rmtree(trace_dir, ignore_errors=True)

    # --- what ran ---------------------------------------------------------
    say("verify implementation %s; field multiplier %s; tuner selections %s"
        % (ed25519_batch.active_impl(), field32.get_mul_impl(),
           json.dumps(autotune.stats()["selections"])))
    for s in ev.setup_spans:
        if s["name"] == "kernel_compile":
            a = s["args"]
            say("kernel first call: engine %s kernel %s lanes %s %.2f s"
                % (a.get("engine"), a.get("kernel"), a.get("lanes"), s["dur"] / 1e6))
    durations_ms = [(c["end_ns"] - c["start_ns"]) / 1e6 for c in calls]
    say("calls completed %d (%d useful lanes each); verdict-cache hits %d, misses %d"
        % (len(calls), traffic.lanes_per_call,
           ev.counter_delta("results.hits"), ev.counter_delta("results.misses")))

    # --- correct, failed ----------------------------------------------------
    results = Results(say)
    results.compare("verdict_cache_hits_in_window", ev.counter_delta("results.hits"), 0)
    results.compare("compilations_in_window", compiles_in_window, 0)
    traffic.check(outcomes, results)
    unhealthy = device_alone(ev, bool(args.trace))
    for why in unhealthy:
        say("not served by the device alone: " + why)
    failed = len(calls) if unhealthy else 0

    # --- metrics ------------------------------------------------------------
    devices = device_record(devs)
    metrics = {}
    out = {"correct": results.correct, "attempted": len(calls), "failed": failed}
    if args.trace:
        ev.peak = published_peak(devices["kind"], args.rehearse)
        for m in spec.metrics_for("per_layer", cell["name"]):
            doc = spec_mod.layer_metric(m["name"])
            value = spec_mod.reader(doc["reader"]).read(ev, **doc.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        for note in ev.notes:
            say(note)
        if ev.busy is not None:
            devices["busy_s"] = ev.busy["busy_s"]
            devices["window_s"] = ev.busy["window_s"]
            out["breakdown"] = {
                "device_ops": tracefile.device_ops(ev.trace),
                "idle_gaps": tracefile.idle_gaps(ev.trace, host_spans),
            }
            say("programs on the device, by time: %s" % json.dumps(tracefile.programs_by_time(ev.trace)))
    else:
        run = {
            "durations_ms": durations_ms,
            "setup_s": setup_s,
            "lanes_per_call": traffic.lanes_per_call,
            "first_start_ns": calls[0]["start_ns"] if calls else 0,
            "last_end_ns": calls[-1]["end_ns"] if calls else 0,
        }
        for m in spec.metrics_for("end_to_end", cell["name"]):
            read = importlib.import_module("chipbench.end_to_end." + m["name"]).read
            value = read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = devices
    if durations_ms:
        say("call wall time ms: median %.4f, min %.4f, max %.4f; set-up %.3f s"
            % (statistics.median(durations_ms), min(durations_ms), max(durations_ms), setup_s))
        slowest = sorted(range(len(durations_ms)), key=lambda i: -durations_ms[i])[:3]
        say("slowest calls: %s (a stall shows here; a rate feels it, a median does not)"
            % ", ".join("#%d %.1f ms" % (i, durations_ms[i]) for i in slowest))
        third = max(1, len(durations_ms) // 3)
        say("median by third of the window, ms: %s (a drift inside one run shows here)"
            % ", ".join("%.4f" % statistics.median(durations_ms[i:i + third])
                        for i in range(0, 3 * third, third)))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
