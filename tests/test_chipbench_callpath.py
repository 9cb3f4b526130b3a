"""The call-path readers of ISSUE 34 on hand-made evidence and their
metric files against ``BENCHMARK.json``. Their tiny twin is rehearsed
end to end, with every new metric in its result line, in
``tests/test_chipbench_rehearsals.py``.

Times: spans in microseconds from the tracer's epoch, as the tracer
exports them; calls in ``perf_counter_ns``, as ``run.py`` takes them;
anchors and device programs in nanoseconds on a clock of the trace's
own, 7 s ahead of the host's here.
"""

from __future__ import annotations

import collections
import json
import os

import pytest

from chipbench import selftest, spec
from chipbench.readers import call_path, device_call_path, span_off_cpu_per_call
from tendermint_tpu.libs import tracing
from tests.helpers import REAL_BENCH, definitions, read, span

EPOCH = tracing.tracer.epoch_ns
TRACE_AHEAD = 7_000_000_000  # trace clock less host clock, ns
PATTERNS = ["jit_run*", "jit__lambda*"]


def call(at_us, wall_us, spans, profiled=False):
    """A call that started ``at_us`` after the tracer's epoch."""
    return {
        "start_ns": EPOCH + at_us * 1000,
        "end_ns": EPOCH + (at_us + wall_us) * 1000,
        "profiled": profiled,
        "spans": spans,
    }


def chunked_call(at):
    """verify_commit at..at+1000: the first dispatch opens at +300, a
    second at +420; collects close at +700 and +900."""
    return [
        span("verify_commit", at, 1000),
        span("prep_chunk", at + 250, 50),
        span("dispatch_chunk", at + 300, 40, chunk=0, chunks=2, h2d_us=30.0, launch_us=8.0),
        span("prep_chunk", at + 345, 70),
        span("dispatch_chunk", at + 420, 30, chunk=1, chunks=2),
        span("collect_chunk", at + 460, 240, chunk=0),
        span("collect_chunk", at + 705, 195, chunk=1, wait_us=180.0, d2h_us=10.0),
        span("cache_store", at + 910, 60),
    ]


class Evidence:
    def __init__(self, calls=(), profiled=(), trace=None):
        self.calls = list(calls)
        self.profiled_calls = list(profiled)
        self.spans = [s for c in self.calls for s in c["spans"]]
        self.profiled_spans = [s for c in self.profiled_calls for s in c["spans"]]
        self.trace = trace


# --- call_path -----------------------------------------------------------------


@pytest.mark.parametrize("part,want", [("pre", 0.35), ("chain", 0.6), ("post", 0.15)])
def test_call_path_cuts_a_call_at_first_dispatch_and_last_collect(part, want):
    # the second call starts 100 us before its verify_commit span: the
    # caller's own head counts as ``pre``
    ev = Evidence([call(5000, 1000, chunked_call(5000)), call(8900, 1200, chunked_call(9000))])
    assert call_path.read(ev, part=part) == pytest.approx(want)


def test_call_path_parts_sum_to_each_calls_wall_time():
    calls = [call(5000, 1000, chunked_call(5000)), call(8900, 1200, chunked_call(9000))]
    for one in calls:
        ev = Evidence([one])
        total = sum(call_path.read(ev, part=p) for p in ("pre", "chain", "post"))
        assert total == pytest.approx((one["end_ns"] - one["start_ns"]) / 1e6)


def test_call_path_leaves_out_a_call_that_dispatched_nothing():
    refused = call(7000, 300, [span("verify_commit", 7000, 300)])
    ev = Evidence([call(5000, 1000, chunked_call(5000)), refused])
    assert call_path.read(ev, part="pre") == pytest.approx(0.3)
    assert call_path.read(Evidence([refused]), part="pre") is None


# --- device_call_path ------------------------------------------------------------


def profiled_evidence(clock_slip_us=(0.0, 0.0), devices=1, device_slip_us=0.0):
    """Two profiled calls at 5000 and 9000 us, each under an anchor as
    long as itself. On the device, relative to the call: a gather
    340..350, verify programs 360..560 and 580..880; so head 360, busy
    500 and gap 20 between first start and last end, tail 120. A second
    device starts 20 us later and ends 20 us earlier. The trace's clock
    runs ``clock_slip_us`` further ahead of the host's in each call;
    the device's plane of it sits ``device_slip_us`` later than the
    host's plane, all session long."""
    calls, anchors = [], []
    devs = {"/device:TPU:%d" % d: {"ops": [], "modules": []} for d in range(devices)}
    for at, slip in zip((5000, 9000), clock_slip_us):
        c = call(at, 1000, chunked_call(at), profiled=True)
        calls.append(c)
        base = c["start_ns"] + TRACE_AHEAD + slip * 1000
        anchors.append([base, 1_000_000])
        base += device_slip_us * 1000
        for d, dev in enumerate(devs.values()):
            skew = 20_000 * d
            dev["modules"] += [
                ["jit_take(7)", base + 340_000, 10_000],
                ["jit__lambda_(3)", base + 360_000 + skew, 200_000 - skew],
                ["jit_run(5)", base + 580_000, 300_000 - skew],
            ]
    return Evidence(profiled=calls, trace={"devices": devs, "anchors": anchors})


def test_device_call_path_parts_sum_to_the_anchor():
    ev = profiled_evidence()
    got = {p: device_call_path.read(ev, part=p, patterns=PATTERNS) for p in ("head", "busy", "gap", "tail")}
    assert got == pytest.approx({"head": 0.36, "busy": 0.5, "gap": 0.02, "tail": 0.12})
    assert sum(got.values()) == pytest.approx(1.0)  # the anchor


def test_device_call_path_gap_ignores_programs_outside_the_chain():
    """The gather before the first verify program is no part of first
    start -> last end: it shortens neither the head nor the gap."""
    ev = profiled_evidence()
    for dev in ev.trace["devices"].values():
        dev["modules"] = [m for m in dev["modules"] if not m[0].startswith("jit_take")]
    assert device_call_path.read(ev, part="gap", patterns=PATTERNS) == pytest.approx(0.02)
    assert device_call_path.read(ev, part="head", patterns=PATTERNS) == pytest.approx(0.36)


@pytest.mark.parametrize("clock_slip_us", [(0.0, 0.0), (0.0, 1000.0), (1000.0, 0.0), (-1000.0, 250.0)])
def test_lags_use_each_calls_own_anchor(clock_slip_us):
    """A call whose own anchor offset differs from the others' by a
    millisecond (the two clocks are not one: they drift, and one may be
    stepped) still reads its true lags: first dispatch +300 -> program
    +360, program end +880 -> last collect +900. A median offset would
    move that call's spans by the difference and read a negative lag."""
    ev = profiled_evidence(clock_slip_us)
    assert device_call_path.read(ev, part="launch_lag", patterns=PATTERNS) == pytest.approx(0.06)
    assert device_call_path.read(ev, part="readback_lag", patterns=PATTERNS) == pytest.approx(0.02)


@pytest.mark.parametrize(
    "device_slip_us,launch_lag,readback_lag,moved",
    [
        # possible as it stands: the first program +360 after its puts
        # were done at +330, the last end +880 before the host saw it
        # ready at +890 (the collect closes at +900, 10 of it the copy)
        (0.0, 0.06, 0.02, None),
        (-20.0, 0.04, 0.04, None),
        # the device's plane 300 us early: a program before its inputs.
        # Moved 270 later, and the lag reads what the host's own clock
        # proves, the puts; the two still sum to 80
        (-300.0, 0.03, 0.05, "+0.270"),
        # 100 us late: a program still running when the host had its
        # output. Moved 90 earlier: the copy is what is left
        (100.0, 0.07, 0.01, "-0.090"),
    ],
)
def test_lags_move_the_devices_plane_by_the_least_that_is_possible(device_slip_us, launch_lag, readback_lag, moved):
    ev = profiled_evidence(device_slip_us=device_slip_us)
    notes = []
    ev.note = notes.append
    assert device_call_path.read(ev, part="launch_lag", patterns=PATTERNS) == pytest.approx(launch_lag)
    assert device_call_path.read(ev, part="readback_lag", patterns=PATTERNS) == pytest.approx(readback_lag)
    assert len(notes) == (2 if moved else 0) and all(moved in n for n in notes)
    # what does not cross between the planes is read as it stands
    assert device_call_path.read(ev, part="gap", patterns=PATTERNS) == pytest.approx(0.02)
    assert device_call_path.read(ev, part="head", patterns=PATTERNS) == pytest.approx(0.36 + device_slip_us / 1000)


def test_device_call_path_is_a_mean_over_devices():
    ev = profiled_evidence(devices=2)
    assert device_call_path.read(ev, part="launch_lag", patterns=PATTERNS) == pytest.approx(0.07)
    assert device_call_path.read(ev, part="readback_lag", patterns=PATTERNS) == pytest.approx(0.03)
    assert device_call_path.read(ev, part="head", patterns=PATTERNS) == pytest.approx(0.37)


def test_device_call_path_reads_nothing_without_a_trace_or_a_verify_program():
    assert device_call_path.read(Evidence(), part="gap", patterns=PATTERNS) is None
    ev = profiled_evidence()
    assert device_call_path.read(ev, part="gap", patterns=["jit_other*"]) is None
    ev.trace["anchors"].pop()  # one annotation lost: nothing is guessed
    assert device_call_path.read(ev, part="gap", patterns=PATTERNS) is None


# --- span_off_cpu_per_call ----------------------------------------------------------


def test_off_cpu_is_the_outermost_spans_less_their_waits_by_design():
    spans = [
        # the caller's thread: 1000 of wall, 500 on the CPU, 400 of the
        # rest waiting for the device
        span("verify_commit", 0, 1000, cpu_us=500.0),
        span("build_lanes", 10, 300),  # no clock of its own: held by the outer span
        span("collect_chunk", 500, 400),
        # a worker thread's own outermost span, waiting 50 for the device
        span("scheduler_dispatch", 100, 200, tid=2, cpu_us=120.0),
        span("collect_chunk", 150, 50, tid=2),
        # a wait on a thread whose outermost span carries no clock: not taken off
        span("collect_chunk", 0, 900, tid=3),
    ]
    ev = Evidence([{"spans": spans}, {"spans": []}])
    got = span_off_cpu_per_call.read(ev, waits=["collect_chunk", "light_super_batch"])
    assert got == pytest.approx(((1000 - 500 - 400) + (200 - 120 - 50)) / 1000.0 / 2)
    # the waits by design are the file's to name: none named, none taken off
    assert span_off_cpu_per_call.read(ev, waits=[]) == pytest.approx((500 + 80) / 1000.0 / 2)


# --- a program that says none of it ---------------------------------------------------

BASES = ["dispatch_ms", "h2d_put_ms", "launch_ms", "d2h_ms", "pre_dispatch_ms", "chain_ms", "post_collect_ms",
         "device_chain_gap_ms", "launch_lag_ms", "readback_lag_ms", "off_cpu_ms", "engine_proc_cpu_ms"]
REAL = spec.Spec(REAL_BENCH)
CELLS = {w["name"]: w["chips"] for w in REAL.doc["workloads"]}
# the cells of PR 34; ``mixed10k`` reports four of the stems in copies of its own (``tests/test_chipbench_mixed.py``)
COMMIT_CELLS = ["hub150-warm", "big10k-warm", "big10k-x4"]
STREAM_CELLS = ["big10k-flood", "sync500-catchup", "light1k-chain", "sync500-rotation"]
COMMIT_ALONE = {"d2h_ms", "engine_proc_cpu_ms"}  # read inside the engine's own call: no stream cell reports them
# every entry under a call-path stem, named through each cell that reports it
NEW = [(cell, stem) for cell in CELLS for stem in BASES if definitions(REAL_BENCH, cell, [stem])]
# the two that read what the parent's program already had: a span's time, and the device's own trace
READ_ON_THE_PARENT = {"dispatch_ms", "device_chain_gap_ms"}


def test_the_new_metric_files_are_within_the_cap_and_agree_with_their_entries():
    assert len(REAL.doc["per_layer"]) <= 128  # the driver's cap
    assert {cell for cell, _ in NEW} >= set(COMMIT_CELLS + STREAM_CELLS) and {stem for _, stem in NEW} == set(BASES)
    selftest.test_files()


@pytest.mark.parametrize("cell,stem", NEW)
def test_a_program_without_the_new_arguments_reads_none_not_zero(cell, stem, monkeypatch):
    """The parent's program under this PR's benchmark files: spans without ``chunk``, phase totals
    or ``cpu_us``, a tracer that does not say its epoch. Every new metric but the two that read
    what the parent already had is left out of the result line; none raises."""
    monkeypatch.setattr(call_path, "tracer_epoch_ns", lambda: None)
    ev = profiled_evidence()
    ev.calls, ev.spans = ev.profiled_calls, ev.profiled_spans
    for s in ev.spans:
        s["args"] = {"lanes": 4}
    got = read(ev, REAL_BENCH, cell, stem)
    assert got > 0 if stem in READ_ON_THE_PARENT else got is None


@pytest.mark.parametrize("stem", BASES)
def test_a_call_path_stem_is_one_definition_in_every_cell_that_reports_it(stem):
    """Each commit cell under ``commit_p50_ms`` and, but for the engine's own two, each stream
    cell under ``sigs_per_s``: one reader on one set of arguments. A sharded call transfers
    inside itself: no h2d phase on the mesh."""
    want = {c: "commit_p50_ms" for c in COMMIT_CELLS if (stem, CELLS[c]) != ("h2d_put_ms", 4)}
    if stem not in COMMIT_ALONE:
        want.update((c, "sigs_per_s") for c in STREAM_CELLS)
    moves, measured = {}, set()
    for cell in COMMIT_CELLS + STREAM_CELLS:
        for text in definitions(REAL_BENCH, cell, [stem]).elements():
            *definition, moves[cell] = json.loads(text)
            measured.add(json.dumps(definition))
    assert moves == want and len(measured) == 1


# --- the tiny twin ------------------------------------------------------------------

BENCH = os.path.join(spec.HERE, "testdata", "tiny-path-benchmark.json")
# each tiny benchmark file: its cell, and what of which real cells it rehearses (stems kept, stems left out)
TWINS = {
    "tiny-path-benchmark.json": ("tiny-hub-warm", {"hub150-warm": (BASES, ()), "big10k-flood": (BASES, ())}),
    "tiny-sync-benchmark.json": ("tiny-sync-catchup", {"sync500-catchup": (None, BASES)}),
    "tiny-light-benchmark.json": ("tiny-light-chain", {"light1k-chain": (None, BASES)}),
    "tiny-rotation-benchmark.json": ("tiny-sync-rotation", {"sync500-rotation": (None, BASES)}),
    "tiny-mixed-benchmark.json": ("tiny-mixed", {"mixed10k": (None, ())}),
}


@pytest.mark.parametrize("bench", TWINS)
def test_a_tiny_twin_reports_what_its_real_cell_reports(bench):
    """Every definition of the real cell's but the call path's, which has
    a twin of its own; none else; under the names the real cell reports."""
    path = os.path.join(spec.HERE, "testdata", bench)
    cell, real_cells = TWINS[bench]
    want, names = collections.Counter(), set()
    for real_cell, (stems, but) in real_cells.items():
        want += definitions(REAL_BENCH, real_cell, stems, but)
        names |= {m["name"] for m in REAL.metrics_for("per_layer", real_cell)}
    got = definitions(path, cell)
    assert got == want, (sorted((want - got).elements()), sorted((got - want).elements()))
    assert {m["name"] for m in spec.Spec(path).metrics_for("per_layer", cell)} <= names
