"""Span tracer tests: nesting, concurrency, ring bounds, nop overhead,
and the end-to-end verify-pipeline acceptance capture.

The tracer under test is the process-global ``tendermint_tpu.libs.
tracing.tracer`` (instrumentation sites have no handle to pass one in),
so every test here configures it explicitly and restores ``off`` +
observer-free state on exit via the ``ring`` fixture.
"""

from __future__ import annotations

import json
import threading

import pytest

from tendermint_tpu.crypto import hashing
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.metrics import (
    ConsensusMetrics,
    OpsMetrics,
    Registry,
)

from tests.helpers import CHAIN_ID, make_block_id, make_commit, make_validators


@pytest.fixture(autouse=True, scope="module")
def sinks_of_a_fresh_process():
    """A node assembled earlier in this worker (tests/test_node.py, any
    in-process ``Node``) leaves the kernel profiler in the tracer's
    profile slot, and a daemon its flight recorder; with either set, no
    span is the NOP span, which this file's off-mode tests assert. The
    file is held to a process that has assembled neither, whatever ran
    before it, and the slots get back what they held."""
    profile, flight = tracing.tracer._profile, tracing.tracer._flight
    tracing.tracer.set_profile_sink(None)
    tracing.tracer.set_flight_sink(None)
    yield
    tracing.tracer.set_profile_sink(profile)
    tracing.tracer.set_flight_sink(flight)


@pytest.fixture
def ring(monkeypatch):
    """Global tracer in ring mode, restored to off/empty afterwards."""
    monkeypatch.delenv(tracing.CAP_ENV, raising=False)
    tracing.configure("ring")
    tracing.tracer.clear()
    tracing.tracer.set_metrics_observer(None)
    yield tracing.tracer
    tracing.tracer.set_metrics_observer(None)
    tracing.configure("off")
    tracing.tracer.clear()


def _complete_events(exported):
    return [e for e in exported["traceEvents"] if e.get("ph") == "X"]


# --- basic recording ---------------------------------------------------------


def test_nested_spans_record_parent_and_args(ring):
    with tracing.span("outer", height=7):
        with tracing.span("inner", stage="prep", engine="ed25519") as sp:
            sp.set(lanes=42)
    out = ring.export()
    events = {e["name"]: e for e in _complete_events(out)}
    assert set(events) == {"outer", "inner"}
    assert events["outer"]["args"]["height"] == 7
    assert "parent" not in events["outer"]["args"]
    assert events["inner"]["args"]["parent"] == "outer"
    assert events["inner"]["args"]["lanes"] == 42
    # inner completes first and sits inside outer's time window
    inner, outer = events["inner"], events["outer"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert out["displayTimeUnit"] == "ms"
    assert out["otherData"]["mode"] == "ring"


def test_tag_lands_on_the_innermost_open_span(ring):
    """``tracing.tag`` is for code that runs under a span it does not
    hold (the engine's challenge hashing under ``prep_chunk``)."""
    tracing.tag(hash="nowhere")  # no span open: nothing to tag, no error
    with tracing.span("outer"):
        with tracing.span("inner"):
            tracing.tag(hash="native")
        tracing.tag(lanes=3)
    with tracing.attach(tracing.TraceContext("ab" * 8, "cd" * 8, 1)):
        tracing.tag(hash="anchor")  # a remote anchor is not a span
    events = {e["name"]: e for e in _complete_events(ring.export())}
    assert events["inner"]["args"]["hash"] == "native"
    assert events["outer"]["args"]["lanes"] == 3
    assert "hash" not in events["outer"]["args"]
    tracing.configure("off")
    with tracing.span("unrecorded"):
        tracing.tag(hash="native")  # the shared no-op span stays clean
    assert len(ring) == 2


def test_instant_events(ring):
    tracing.instant("device_health_transition", from_state="healthy")
    (ev,) = ring.export()["traceEvents"][-1:]
    assert ev["ph"] == "i"
    assert ev["s"] == "p"
    assert ev["args"]["from_state"] == "healthy"


def test_export_is_valid_bounded_json(ring):
    for i in range(10):
        with tracing.span("s", i=i):
            pass
    out = ring.export(limit=4)
    assert len(_complete_events(out)) == 4
    # the wire form of /debug/traces round-trips through json
    assert json.loads(json.dumps(out)) == out


def test_export_clear_drains_ring(ring):
    with tracing.span("s"):
        pass
    assert len(ring) == 1
    ring.export(clear=True)
    assert len(ring) == 0


# --- concurrency -------------------------------------------------------------


def test_concurrent_threads_yield_well_nested_untorn_output(ring):
    """≥4 threads race nested spans; every event must carry intact args
    and per-thread parent attribution (no cross-thread tearing)."""
    n_threads, n_iters = 6, 25
    barrier = threading.Barrier(n_threads)
    errors = []

    def work(t):
        try:
            barrier.wait(timeout=10)
            for i in range(n_iters):
                with tracing.span(f"outer-{t}", t=t, i=i):
                    with tracing.span(f"inner-{t}", t=t, i=i):
                        pass
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    threads = [
        threading.Thread(target=work, args=(t,)) for t in range(n_threads)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
    assert not errors
    out = ring.export()
    # the threads' own spans: a collection of 1 ms or more while they ran is a
    # ``gc_pause`` span of the tracer's own in the same ring
    events = [e for e in _complete_events(out) if e["name"].startswith(("outer-", "inner-"))]
    assert len(events) == n_threads * n_iters * 2
    # untorn: the JSON form parses back identical
    assert json.loads(json.dumps(out)) == out
    for ev in events:
        t = ev["args"]["t"]
        assert ev["name"] in (f"outer-{t}", f"inner-{t}")
        if ev["name"].startswith("inner"):
            # nesting never crosses threads: the parent is this
            # thread's own outer span, regardless of interleaving
            assert ev["args"]["parent"] == f"outer-{t}"
        else:
            assert "parent" not in ev["args"]
    # each thread's events landed under its own tid
    by_tid = {}
    for ev in events:
        by_tid.setdefault(ev["tid"], set()).add(ev["args"]["t"])
    assert all(len(owners) == 1 for owners in by_tid.values())


# --- ring bound --------------------------------------------------------------


def test_ring_bound_enforced(ring, monkeypatch):
    monkeypatch.setenv(tracing.CAP_ENV, "8")
    tracing.configure("ring")
    tracing.tracer.clear()
    for i in range(20):
        with tracing.span("s", i=i):
            pass
    assert len(tracing.tracer) == 8
    out = tracing.tracer.export()
    events = _complete_events(out)
    # most recent events survive
    assert [e["args"]["i"] for e in events] == list(range(12, 20))
    assert out["otherData"]["dropped"] == 12


# --- nop path ----------------------------------------------------------------


def test_nop_tracer_adds_no_spans():
    tracing.tracer.set_metrics_observer(None)
    tracing.configure("off")
    tracing.tracer.clear()
    before = tracing.tracer.recorded
    for _ in range(100):
        with tracing.span("hot", lanes=1) as sp:
            sp.set(x=1)
        tracing.instant("tick")
    # counter-asserted, not timing-asserted: nothing was recorded and
    # the disabled span is the one shared nop instance
    assert tracing.tracer.recorded == before
    assert len(tracing.tracer) == 0
    assert tracing.span("hot") is tracing.NOP_SPAN


def test_off_mode_with_observer_times_spans_without_storing():
    seen = []
    tracing.configure("off")
    tracing.tracer.clear()
    tracing.tracer.set_metrics_observer(
        lambda name, args, sec: seen.append((name, dict(args), sec))
    )
    try:
        with tracing.span("stage_span", stage="prep", engine="ed25519"):
            pass
        assert len(tracing.tracer) == 0  # ring stays empty in off mode
        assert len(seen) == 1
        name, args, sec = seen[0]
        assert name == "stage_span"
        assert args["stage"] == "prep"
        assert sec >= 0.0
    finally:
        tracing.tracer.set_metrics_observer(None)


def test_broken_observer_never_fails_the_traced_op(ring):
    def boom(name, args, sec):
        raise RuntimeError("broken metrics binding")

    ring.set_metrics_observer(boom)
    with tracing.span("s"):
        pass
    assert len(ring) == 1


# --- summary -----------------------------------------------------------------


def test_summary_groups_by_stage_tag(ring):
    for _ in range(3):
        with tracing.span("prep_chunk", stage="prep", engine="ed25519"):
            pass
    with tracing.span("verify_batch", engine="ed25519"):
        pass
    s = ring.summary()
    assert s["prep"]["count"] == 3
    assert s["verify_batch"]["count"] == 1
    for row in s.values():
        assert row["p50_ms"] <= row["p95_ms"] or row["count"] == 1
        assert row["total_ms"] >= row["p50_ms"] >= 0


# --- metrics observer bridge -------------------------------------------------


def test_metrics_observer_feeds_both_histograms():
    reg = Registry()
    ops = OpsMetrics(reg)
    consensus = ConsensusMetrics(reg)
    obs = tracing.metrics_observer(ops=ops, consensus=consensus)
    obs("prep_chunk", {"stage": "prep", "engine": "ed25519"}, 0.001)
    obs("propose", {"step": "propose", "height": 1}, 0.002)
    obs("verify_batch", {"engine": "ed25519"}, 0.003)  # no stage: skipped
    text = reg.expose()
    assert (
        'tendermint_ops_verify_stage_seconds_count'
        '{engine="ed25519",stage="prep"} 1' in text
    )
    assert (
        'tendermint_consensus_step_duration_seconds_count'
        '{step="propose"} 1' in text
    )


# --- end-to-end: verify_commit under ring tracing ----------------------------


def _stage_counts_from_events(events):
    counts = {}
    for ev in events:
        stage = ev["args"].get("stage")
        engine = ev["args"].get("engine")
        if stage and engine:
            counts[(stage, engine)] = counts.get((stage, engine), 0) + 1
    return counts


def _histogram_counts(ops):
    hist = ops.verify_stage_seconds
    with hist._lock:
        return {
            (dict(k)["stage"], dict(k)["engine"]): n
            for k, (_c, _t, n) in hist._values.items()
        }


def test_verify_commit_traced_end_to_end(ring, monkeypatch):
    """The acceptance capture: a 24-validator commit verified with
    TENDERMINT_TPU_TRACE=ring records the nested pipeline (consensus
    span -> batch verify -> cache lookup / per-chunk prep+dispatch),
    and the stage histogram counts equal the traced stage-span counts."""
    from tendermint_tpu.ops import precompute
    from tendermint_tpu.types import validation

    monkeypatch.setenv("TENDERMINT_TPU_TRACE", "ring")
    monkeypatch.setenv(precompute._RESULT_ENV, "1")  # conftest turns it off
    precompute.reset()
    reg = Registry()
    ops = OpsMetrics(reg)
    consensus = ConsensusMetrics(reg)
    ring.set_metrics_observer(
        tracing.metrics_observer(ops=ops, consensus=consensus)
    )

    privs, vset = make_validators(24)
    block_id = make_block_id()
    height, round_ = 5, 1
    commit = make_commit(block_id, height, round_, vset, privs)
    validation.verify_commit(CHAIN_ID, vset, block_id, height, commit)
    # second pass: the digest-keyed result cache answers every lane
    validation.verify_commit(CHAIN_ID, vset, block_id, height, commit)

    events = _complete_events(ring.export())
    by_name = {}
    for ev in events:
        by_name.setdefault(ev["name"], []).append(ev)

    # consensus span tagged with height/round
    vc = by_name["verify_commit"]
    assert len(vc) == 2
    for ev in vc:
        assert ev["args"]["height"] == height
        assert ev["args"]["round"] == round_
        assert ev["args"]["sigs"] == 24

    # nested under it: the key type's batch verifier, the engine
    # batch, then the cache lookup
    assert all(
        ev["args"]["parent"] == "verify_commit"
        for ev in by_name["batch_verify"]
    )
    assert all(
        ev["args"]["parent"] == "batch_verify"
        for ev in by_name["verify_batch"]
    )
    lookups = by_name["cache_lookup"]
    assert len(lookups) == 2
    assert all(ev["args"]["parent"] == "verify_batch" for ev in lookups)
    assert lookups[0]["args"]["hits"] == 0
    assert lookups[1]["args"]["hits"] == 24  # warm pass: all cached

    # per-chunk device stages ran only on the cold pass
    assert len(by_name["prep_chunk"]) >= 1
    for ev in by_name["prep_chunk"]:
        assert ev["args"]["stage"] == "prep"
        assert ev["args"]["engine"] == "ed25519"
        assert ev["args"]["parent"] == "verify_batch"
        # which path made the challenge scalars (the CPU keeps device
        # hashing off; the C extension builds here)
        assert ev["args"]["hash"] == "native"
    dispatched = "dispatch_chunk" in by_name
    fell_back = "host_fallback" in by_name
    assert dispatched or fell_back  # every lane was answered somewhere

    # the histograms observed exactly the spans the trace recorded:
    # one clock, one count
    assert _histogram_counts(ops) == _stage_counts_from_events(events)

    ring.set_metrics_observer(None)


def test_scheduler_spans_nest_assembly_and_flush(ring):
    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.crypto.scheduler import VerifyScheduler
    from tendermint_tpu.ops import ed25519_batch

    priv = Ed25519PrivKey.from_seed(b"\x07" * 32)
    pk = priv.pub_key().bytes()
    msg = b"sched-traced"
    sig = priv.sign(msg)
    # the kernel's first call compiles for longer than verify()'s wait
    assert ed25519_batch.verify_batch([pk], [msg], [sig]) == [True]
    ring.clear()
    sched = VerifyScheduler(ed25519_batch.verify_batch, max_delay=0.01)
    sched.start()
    try:
        assert sched.verify(pk, msg, sig)
    finally:
        sched.stop()
    events = _complete_events(ring.export())
    names = [e["name"] for e in events]
    assert "sched_assemble" in names
    assert "sched_flush" in names
    flush = next(e for e in events if e["name"] == "sched_flush")
    assert flush["args"]["lanes"] == 1
    # the engine's own spans nest under the scheduler flush
    vb = next(e for e in events if e["name"] == "verify_batch")
    assert vb["args"]["parent"] == "sched_flush"


def test_tracing_off_changes_no_verify_results(monkeypatch):
    from tendermint_tpu.types import validation

    privs, vset = make_validators(8)
    block_id = make_block_id(b"off-mode")
    commit = make_commit(block_id, 3, 0, vset, privs)

    tracing.tracer.set_metrics_observer(None)
    monkeypatch.setenv("TENDERMINT_TPU_TRACE", "off")
    tracing.configure("off")
    tracing.tracer.clear()
    validation.verify_commit(CHAIN_ID, vset, block_id, 3, commit)  # no raise
    assert len(tracing.tracer) == 0

    tracing.configure("ring")
    try:
        validation.verify_commit(CHAIN_ID, vset, block_id, 3, commit)
        assert len(tracing.tracer) > 0
    finally:
        tracing.configure("off")
        tracing.tracer.clear()


def test_file_mode_flush_writes_chrome_trace(tmp_path):
    path = tmp_path / "trace.json"
    tracing.configure(str(path))
    try:
        with tracing.span("flushed", k="v"):
            pass
        written = tracing.tracer.flush()
        assert written == str(path)
        doc = json.loads(path.read_text())
        assert any(
            e.get("name") == "flushed" for e in doc["traceEvents"]
        )
    finally:
        tracing.configure("off")
        tracing.tracer.clear()


# --- ISSUE 24: the host time the spans stopped short of ---------------------

N_LANES = 64


@pytest.fixture(scope="module")
def commit_capture():
    """One warm 64-validator ``verify_commit`` recorded in ring mode:
    the events of that call alone, and what ``off`` leaves behind."""
    import gc

    from tendermint_tpu.ops import precompute
    from tendermint_tpu.ops import resident as resident_mod
    from tendermint_tpu.types import validation

    mp = pytest.MonkeyPatch()
    mp.setenv(precompute._RESULT_ENV, "1")
    # on a CPU the resident store is off unless asked for; the chip's
    # path is the one to show
    mp.setenv(resident_mod._ENV, "on")
    mp.delenv(tracing.CAP_ENV, raising=False)
    precompute.reset()
    try:
        privs, vset = make_validators(N_LANES)
        block_id = make_block_id(b"issue-24")
        warm = make_commit(block_id, 7, 0, vset, privs)
        validation.verify_commit(CHAIN_ID, vset, block_id, 7, warm)
        commit = make_commit(block_id, 8, 0, vset, privs)
        tracing.tracer.set_metrics_observer(None)
        tracing.configure("ring")
        tracing.tracer.clear()
        validation.verify_commit(CHAIN_ID, vset, block_id, 8, commit)
        events = _complete_events(tracing.tracer.export(clear=True))
        tracing.configure("off")
        recorded = tracing.tracer.recorded
        off_span = tracing.span("verify_commit", sigs=N_LANES)
        commit = make_commit(block_id, 9, 0, vset, privs)
        validation.verify_commit(CHAIN_ID, vset, block_id, 9, commit)
        off = {
            "span": off_span,
            "recorded_during": tracing.tracer.recorded - recorded,
            "ring": len(tracing.tracer),
            "gc_hooked": tracing.tracer._gc_hook in gc.callbacks,
            "jax_hooked": tracing.tracer._jax_hooked,
        }
    finally:
        tracing.configure("off")
        tracing.tracer.clear()
        precompute.reset()
        resident_mod.store.clear()
        mp.undo()
    return {"events": events, "off": off}


@pytest.mark.parametrize(
    "name,parent",
    [
        ("note_validator_set", "verify_commit"),
        ("build_lanes", "verify_commit"),
        ("batch_verify", "verify_commit"),
        ("merge_verdicts", "verify_commit"),
        ("verify_batch", "batch_verify"),
        ("route_lanes", "verify_batch"),
        ("resident_acquire", "route_lanes"),
        ("cache_store", "verify_batch"),
        ("merge_results", "verify_batch"),
    ],
)
def test_commit_yields_each_new_span_once_under_its_parent(
    commit_capture, name, parent
):
    found = [e for e in commit_capture["events"] if e["name"] == name]
    assert len(found) == 1, [e["name"] for e in commit_capture["events"]]
    (ev,) = found
    assert ev["args"]["parent"] == parent
    by_id = {e["span_id"]: e for e in commit_capture["events"]}
    up = by_id[ev["parent_span_id"]]
    assert up["name"] == parent
    # same thread, inside the parent's interval
    assert up["tid"] == ev["tid"]
    assert up["ts"] <= ev["ts"]
    assert ev["ts"] + ev["dur"] <= up["ts"] + up["dur"] + 1e-3


@pytest.mark.parametrize(
    "name,args",
    [
        ("note_validator_set", {"validators": N_LANES, "newly_active": False, "recognised": True}),
        ("build_lanes", {"lanes": N_LANES, "block_lanes": N_LANES, "sign_bytes_prefixes": 1}),
        ("batch_verify", {"key_type": "ed25519", "lanes": N_LANES, "route": "device"}),
        ("merge_verdicts", {"lanes": N_LANES}),
        ("route_lanes", {"lanes": N_LANES, "resident": N_LANES, "tables": 0, "legacy": 0, "jobs": 1}),
        ("resident_acquire", {"lanes": N_LANES, "hits": N_LANES, "misses": 0}),
        ("cache_store", {"lanes": N_LANES}),
        ("merge_results", {"lanes": N_LANES}),
        ("dispatch_chunk", {"lanes": N_LANES, "padded_lanes": 64, "kind": "resident", "impl": "xla"}),
        ("collect_chunk", {"lanes": N_LANES, "d2h_bytes": 64}),
    ],
)
def test_commit_span_arguments(commit_capture, name, args):
    (ev,) = [e for e in commit_capture["events"] if e["name"] == name]
    assert {k: ev["args"].get(k) for k in args} == args


def test_build_lanes_phase_totals(commit_capture):
    (ev,) = [e for e in commit_capture["events"] if e["name"] == "build_lanes"]
    a = ev["args"]
    # one timed call a block each (ISSUE 45): the block's sign-bytes, its add_many
    assert a["sign_bytes_n"] == a["batch_add_n"] == 1 and a["block_lanes"] == a["lanes"] == N_LANES
    assert a["sign_bytes_us"] > 0 and a["batch_add_us"] > 0
    assert a["sign_bytes_us"] + a["batch_add_us"] <= ev["dur"]
    (outer,) = [e for e in commit_capture["events"] if e["name"] == "verify_commit"]
    assert outer["args"]["blocks"] == 1 and outer["args"]["early_lanes"] == 0
    # the index path never looks a validator up by address
    assert "val_lookup_us" not in a and "val_lookup_n" not in a


def test_commit_emits_few_events(commit_capture):
    """No event per lane: the ring holds 4,096 and a 10,000-lane call
    must fit as this one does."""
    assert len(commit_capture["events"]) < 60
    per_lane = [
        e for e in commit_capture["events"]
        if e["name"] in ("sign_bytes", "batch_add", "val_lookup")
    ]
    assert per_lane == []


def test_off_mode_leaves_nothing_behind(commit_capture):
    off = commit_capture["off"]
    assert off["span"] is tracing.NOP_SPAN
    assert off["recorded_during"] == 0 and off["ring"] == 0
    assert off["gc_hooked"] is False and off["jax_hooked"] is False
    assert tracing.NOP_SPAN.live is False
    f = len
    assert tracing.NOP_SPAN.timed("sign_bytes", f) is f


@pytest.mark.parametrize("n", [12, 48])
def test_off_mode_the_lane_loop_calls_the_encoder_itself(monkeypatch, n):
    """Tracer off, ``build_lanes`` calls the commit encoder's and the
    verifier's own bound methods (no wrapper, no tracing call a lane or
    a block) and opens the same spans whatever the lanes."""
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.types import validation
    from tendermint_tpu.types.block import CommitSignBytes

    assert tracing.tracer.mode == "off"
    privs, vset = make_validators(n)
    block_id = make_block_id(b"issue-27-off")
    commit = make_commit(block_id, 3, 0, vset, privs, nil_votes={1})
    opened, phases = [], {}
    real_span = tracing.span

    def counting(name, *args, **kwargs):
        opened.append(name)
        return real_span(name, *args, **kwargs)

    def timed(self, phase, fn):
        phases[phase] = fn
        return fn

    monkeypatch.setattr(tracing, "span", counting)
    monkeypatch.setattr(tracing._NopSpan, "timed", timed)
    validation.verify_commit(CHAIN_ID, vset, block_id, 3, commit)
    assert phases["sign_bytes"].__func__ is CommitSignBytes.lanes
    assert phases["batch_add"].__func__ is crypto_batch.MultiBatchVerifier.add_many
    ours = ("note_validator_set", "build_lanes", "single_verify", "sign_bytes")
    assert [name for name in opened if name in ours] == ["note_validator_set", "build_lanes"]
    assert len(opened) < 40


def test_build_lanes_counts_a_second_prefix_where_a_nil_vote_is_sent():
    from tendermint_tpu.types import validation

    privs, vset = make_validators(N_LANES)
    block_id = make_block_id(b"issue-27-nil")
    commit = make_commit(block_id, 4, 0, vset, privs, absent={2}, nil_votes={1, 9})
    tracing.configure("ring")
    tracing.tracer.clear()
    try:
        validation.verify_commit(CHAIN_ID, vset, block_id, 4, commit)
        events = _complete_events(tracing.tracer.export(clear=True))
    finally:
        tracing.configure("off")
        tracing.tracer.clear()
    (loop,) = [e for e in events if e["name"] == "build_lanes"]
    a = loop["args"]
    assert (a["lanes"], a["block_lanes"], a["sign_bytes_n"], a["sign_bytes_prefixes"]) == (63, 63, 1, 2)


@pytest.mark.parametrize("entry", ["verify_commit", "verify_commit_light_trusting", "unknown_flag"])
def test_one_build_lanes_span_a_block_and_the_blocks_sum_to_the_call(monkeypatch, entry):
    """A commit of three jobs' lanes (ISSUE 45): one ``build_lanes`` a
    block, every ``batch_verify`` beside them, their ``lanes`` the
    call's, ``block_lanes`` the lanes a span handed over by its one
    ``add_many`` (0 where they went through ``add``: a block holding an
    entry of an unknown flag), the by-address lookups a phase of the
    span that chooses the lanes."""
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.ops import ed25519_batch, precompute
    from tendermint_tpu.types import validation
    from tests.helpers import traced

    monkeypatch.setattr(ed25519_batch, "job_lanes", lambda: 16)
    monkeypatch.setattr(ed25519_batch, "_ENGINES_WITH_A_JOB_RUN", {"ed25519", "sr25519"})
    privs, vset = make_validators(40)
    crypto_batch.note_validator_set(vset)
    precompute.tables.gather([v.pub_key.bytes() for v in vset.validators])
    block_id = make_block_id(b"issue-45-spans")
    commit = make_commit(block_id, 4, 0, vset, privs, absent={2})
    if entry == "verify_commit_light_trusting":
        call = lambda: validation.verify_commit_light_trusting(CHAIN_ID, vset, commit, validation.Fraction(9, 10))
    else:
        call = lambda: validation.verify_commit(CHAIN_ID, vset, block_id, 4, commit)
    if entry == "unknown_flag":
        commit.signatures[37].block_id_flag = 7
    raised, events = traced(call)
    assert (raised is None) == (entry != "unknown_flag")
    loops = [e for e in events if e["name"] == "build_lanes"]
    batches = [e for e in events if e["name"] == "batch_verify"]
    # 39 signed seats; nine tenths of 400 are passed at the 37th; the span an exception leaves carries no count
    want = {"verify_commit": [16, 16, 7], "verify_commit_light_trusting": [16, 16, 5], "unknown_flag": [16, 16, None]}[entry]
    assert [e["args"].get("lanes") for e in loops] == want
    assert [e["args"].get("block_lanes") for e in loops] == want  # all by add_many, or lane by lane up to the fault
    for b in batches:  # beside every loop span, inside none
        assert all(b["ts"] + b["dur"] <= l["ts"] or l["ts"] + l["dur"] <= b["ts"] for l in loops)
    assert [b["args"].get("early") for b in batches[:2]] == [1, 1]
    if entry == "verify_commit":
        (outer,) = [e for e in events if e["name"] == "verify_commit"]
        assert (outer["args"]["blocks"], outer["args"]["early_lanes"]) == (3, 32)
    if entry == "unknown_flag":
        assert str(raised) == "unknown BlockIDFlag: 7"
        assert "batch_add_n" not in loops[2]["args"]  # the lanes before the fault went through add, untimed
    assert [e["args"].get("sign_bytes_n") for e in loops] == [1, 1, 1 if raised is None else None]
    looked_up = [e["args"].get("val_lookup_n") for e in loops]
    assert looked_up == ([37, None, None] if entry == "verify_commit_light_trusting" else [None] * 3)


def test_off_mode_holds_no_jax_listener():
    import gc

    from jax._src import monitoring

    tracing.configure("ring")
    try:
        with tracing.span("hook"):
            pass
        assert tracing.tracer._gc_hook in gc.callbacks
        listeners = monitoring.get_event_duration_listeners()
        assert tracing.tracer._jax_hook in listeners
    finally:
        tracing.configure("off")
        tracing.tracer.clear()
    assert tracing.tracer._gc_hook not in gc.callbacks
    assert tracing.tracer._jax_hook not in monitoring.get_event_duration_listeners()
    assert tracing.tracer._annotate("x") is None


def test_timed_phase_accumulates_on_the_span(ring):
    with tracing.span("loop", lanes=3) as sp:
        step = sp.timed("step", lambda x: x + 1)
        assert [step(i) for i in range(3)] == [1, 2, 3]
        sp.timed("never", len)  # wrapped, not called: left out
    (ev,) = _complete_events(ring.export())
    assert ev["args"]["step_n"] == 3
    assert 0 <= ev["args"]["step_us"] <= ev["dur"]
    assert "never_n" not in ev["args"]


# --- CPU time beside wall time, one clock (ISSUE 34) ---------------------------


def test_cpu_us_of_a_sleeping_span_is_far_under_its_dur(ring):
    import time

    with tracing.span("asleep"):
        time.sleep(0.05)
    (ev,) = _complete_events(ring.export())
    assert ev["dur"] >= 50_000
    assert 0 <= ev["args"]["cpu_us"] < 0.2 * ev["dur"]
    assert "proc_cpu_us" not in ev["args"]  # only a span that asked


def test_cpu_us_of_a_busy_span_is_the_cpu_time_its_thread_used(ring):
    """A span that spins until its thread has used 50 ms of CPU, by the
    thread's own clock: ``cpu_us`` is those 50 ms (to the clock's 10-ms
    tick, twice) and no more than ``dur``, however long the thread was
    kept off its core meanwhile. What share of ``dur`` that is depends
    on the host, not on the tracer."""
    import time

    with tracing.span("busy"):
        until = time.thread_time() + 0.05
        while time.thread_time() < until:
            pass
    (ev,) = _complete_events(ring.export(clear=True))
    assert 30_000 <= ev["args"]["cpu_us"] <= ev["dur"]  # both read inside the wall interval


def test_only_a_threads_outermost_span_reads_the_cpu_clock(ring):
    """Two reads of the thread's clock a call, not two a span: nested
    spans carry no ``cpu_us``, a worker thread's own outermost span
    does, and a span opened under a remote parent is its thread's
    outermost."""
    def worker(ctx):
        with tracing.span("scheduler_dispatch", parent_ctx=ctx):
            with tracing.span("sched_flush"):
                pass

    with tracing.span("verify_commit") as outer:
        with tracing.span("verify_batch") as sp:
            sp.process_cpu()
            with tracing.span("prep_chunk"):
                pass
        t = threading.Thread(target=worker, args=(outer.context(),))
        t.start()
        t.join()
    events = {e["name"]: e["args"] for e in _complete_events(ring.export())}
    assert sorted(n for n, a in events.items() if "cpu_us" in a) == ["scheduler_dispatch", "verify_commit"]
    assert [n for n, a in events.items() if "proc_cpu_us" in a] == ["verify_batch"]


def test_process_cpu_counts_the_other_threads(ring):
    """``proc_cpu_us`` is the whole process's: a second thread spinning
    beside a sleeping caller shows there and not in ``cpu_us``."""
    import time

    stop = threading.Event()

    def spin():
        while not stop.is_set():
            pass

    worker = threading.Thread(target=spin)
    worker.start()
    try:
        with tracing.span("verify_batch") as sp:
            sp.process_cpu()
            time.sleep(0.05)
    finally:
        stop.set()
        worker.join()
    (ev,) = _complete_events(ring.export())
    assert ev["args"]["cpu_us"] < 0.2 * ev["dur"]
    assert ev["args"]["proc_cpu_us"] > 0.5 * ev["dur"]


def test_externally_timed_spans_carry_no_cpu_time(ring):
    ring._record_interval("gc_pause", 1.0, 1.5, {"generation": 2, "collected": 0})
    ring._record_interval("xla_compile", 2.0, 2.5, {"event": "x"})
    assert [sorted(e["args"]) for e in _complete_events(ring.export())] == [
        ["collected", "generation"], ["event"],
    ]


def test_observer_alone_reads_no_cpu_clock():
    """Off with a metrics observer bound: spans are timed for the
    histograms and nothing else is read or stored."""
    seen = []
    tracing.configure("off")
    tracing.tracer.set_metrics_observer(lambda name, args, secs: seen.append(dict(args)))
    try:
        with tracing.span("verify_batch", stage="x", engine="e") as sp:
            sp.process_cpu()
    finally:
        tracing.tracer.set_metrics_observer(None)
    assert seen == [{"stage": "x", "engine": "e"}]


def test_epoch_ns_places_a_span_on_the_perf_counter_ns_clock(ring):
    """What the benchmark's readers do: a call timed with
    ``perf_counter_ns`` around a span finds the span inside it."""
    import time

    before = time.perf_counter_ns()
    with tracing.span("inside"):
        pass
    after = time.perf_counter_ns()
    doc = ring.export()
    (ev,) = _complete_events(doc)
    start = ring.epoch_ns + ev["ts"] * 1000.0
    assert before - 1000 <= start <= start + ev["dur"] * 1000.0 <= after + 1000
    assert doc["otherData"]["epoch_perf_ns"] == ring.epoch_ns
    with pytest.raises(AttributeError):
        ring.epoch_ns = 0  # read-only


@pytest.mark.parametrize("mode", ["off", "ring"])
def test_collect_chunk_waits_apart_from_its_copy_only_when_live(mode, monkeypatch):
    """The untraced path keeps its one blocking line, ``np.asarray``;
    a live ``collect_chunk`` cuts it in two: ``block_until_ready``
    (``wait_us``), then the copy (``d2h_us``)."""
    import numpy as np

    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.ops import ed25519_batch

    waits, copies = [], []
    real_wait, real_copy = ed25519_batch.jax.block_until_ready, np.asarray

    def wait(x):
        waits.append(type(x).__name__)
        return real_wait(x)

    class CountingNumpy:
        """``np`` as the engine sees it, counting device arrays copied."""

        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(x, *args, **kwargs):
            if hasattr(x, "block_until_ready"):
                copies.append(type(x).__name__)
            return real_copy(x, *args, **kwargs)

    pks, msgs, sigs = _raw_lanes(3)
    assert ed25519_batch.verify_batch(pks, msgs, sigs) == [True] * 3  # compiled, tables noted
    monkeypatch.setattr(ed25519_batch.jax, "block_until_ready", wait)
    monkeypatch.setattr(ed25519_batch, "np", CountingNumpy())
    msgs = [m + b"-again" for m in msgs]
    sigs = [Ed25519PrivKey.from_seed(bytes([i + 1]) * 32).sign(m) for i, m in enumerate(msgs)]
    tracing.tracer.set_metrics_observer(None)
    tracing.configure(mode)
    tracing.tracer.clear()
    try:
        assert ed25519_batch.verify_batch(pks, msgs, sigs) == [True] * 3
        events = _complete_events(tracing.tracer.export(clear=True))
    finally:
        tracing.configure("off")
        tracing.tracer.clear()
    assert len(copies) == 1
    if mode == "off":
        assert waits == [] and events == []
        return
    assert len(waits) == 1
    (ev,) = [e for e in events if e["name"] == "collect_chunk"]
    assert ev["args"]["wait_n"] == ev["args"]["d2h_n"] == 1
    assert ev["args"]["wait_us"] + ev["args"]["d2h_us"] <= ev["dur"]


def test_gc_collect_inside_a_span_yields_nested_gc_pause(ring, monkeypatch):
    import gc

    monkeypatch.setattr(tracing, "EXTERNAL_SPAN_MIN_S", 0.0)
    with tracing.span("stalled") as sp:
        gc.collect()
    events = _complete_events(ring.export())
    pauses = [
        e for e in events
        if e["name"] == "gc_pause" and e["args"].get("parent") == "stalled"
    ]
    assert pauses, [e["name"] for e in events]
    full = [e for e in pauses if e["args"]["generation"] == 2]
    assert len(full) == 1
    assert full[0]["args"]["collected"] >= 0
    assert full[0]["parent_span_id"] == sp.span_id
    (outer,) = [e for e in events if e["name"] == "stalled"]
    assert outer["ts"] <= full[0]["ts"]
    assert full[0]["ts"] + full[0]["dur"] <= outer["ts"] + outer["dur"] + 1e-3


def test_short_external_intervals_are_dropped(ring):
    ring._record_interval("gc_pause", 1.0, 1.0 + tracing.EXTERNAL_SPAN_MIN_S / 2, {})
    assert len(ring) == 0
    ring._record_interval("gc_pause", 1.0, 1.0 + tracing.EXTERNAL_SPAN_MIN_S * 2, {})
    assert len(ring) == 1


def test_first_call_of_a_fresh_jit_yields_xla_compile_child(ring, monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(tracing, "EXTERNAL_SPAN_MIN_S", 0.0)
    fresh = jax.jit(lambda x: x * 3 + 1)
    with tracing.span("first_call") as sp:
        fresh(jnp.arange(5)).block_until_ready()
    with tracing.span("second_call"):
        fresh(jnp.arange(5)).block_until_ready()
    events = _complete_events(ring.export())
    compiles = [e for e in events if e["name"] == "xla_compile"]
    assert compiles
    assert {e["args"]["parent"] for e in compiles} == {"first_call"}
    assert all(e["parent_span_id"] == sp.span_id for e in compiles)
    kinds = {e["args"]["event"] for e in compiles}
    assert "/jax/core/compile/backend_compile_duration" in kinds
    assert "/jax/core/compile/jaxpr_trace_duration" in kinds


def _raw_lanes(n):
    from tendermint_tpu.crypto.keys import Ed25519PrivKey

    pks, msgs, sigs = [], [], []
    for i in range(n):
        priv = Ed25519PrivKey.from_seed(bytes([i + 1]) * 32)
        msg = b"h2d-%d" % i
        pks.append(priv.pub_key().bytes())
        msgs.append(msg)
        sigs.append(priv.sign(msg))
    return pks, msgs, sigs


@pytest.mark.parametrize("kind", ["legacy_dispatch", "resident_inputs"])
def test_h2d_bytes_is_the_summed_nbytes_of_the_chunk(ring, kind):
    import numpy as np

    from tendermint_tpu.ops import ed25519_batch

    if kind == "resident_inputs":
        # hand-built: what _prep_resident_chunk hands over, at 64 lanes
        inputs = dict(
            store=object(),  # on the device already: not counted
            mesh_key=None,
            idx=np.zeros(64, dtype=np.int32),
            ok=np.ones(64, dtype=np.uint8),
            r=np.zeros((64, 32), dtype=np.uint8),
            s=np.zeros((64, 32), dtype=np.uint8),
            k=np.zeros((64, 32), dtype=np.uint8),
        )
        resident = ed25519_batch.KINDS["resident"]
        assert resident.h2d_bytes(inputs) == 64 * 4 + 64 + 3 * 64 * 32
        return
    pks, msgs, sigs = _raw_lanes(3)
    inputs, _ = ed25519_batch.prepare_batch(pks, msgs, sigs)
    want = sum(a.nbytes for a in inputs.values())
    assert want == 4 * 64 * 32  # pk, r, s, k padded to the 64-lane bucket
    assert ed25519_batch.verify_batch(pks, msgs, sigs) == [True] * 3
    (ev,) = [e for e in _complete_events(ring.export()) if e["name"] == "dispatch_chunk"]
    assert ev["args"]["h2d_bytes"] == want
    assert ev["args"]["padded_lanes"] == 64 and ev["args"]["lanes"] == 3


@pytest.mark.parametrize("mode", ["ring", "off"])
def test_dispatch_chunk_names_the_implementation_only_when_live(mode, monkeypatch):
    """``impl`` is an argument of the live span, and it is what the
    runner says it handed the chunk to; with the tracer off nothing is
    recorded."""
    from tendermint_tpu.ops import ed25519_batch

    handed = []
    real = ed25519_batch._run_chunk

    def recording(kind, inputs, backend, plan=None, sp=tracing.NOP_SPAN):
        out = real(kind, inputs, backend, plan, sp)
        handed.append(out[2])
        return out

    monkeypatch.setattr(ed25519_batch, "_run_chunk", recording)
    pks, msgs, sigs = _raw_lanes(3)
    tracing.tracer.set_metrics_observer(None)
    tracing.configure(mode)
    tracing.tracer.clear()
    try:
        assert ed25519_batch.verify_batch(pks, msgs, sigs) == [True] * 3
        events = _complete_events(tracing.tracer.export(clear=True))
    finally:
        tracing.configure("off")
        tracing.tracer.clear()
    assert handed == ["xla"] == [ed25519_batch.active_impl()]
    if mode == "off":
        assert events == []
        return
    (ev,) = [e for e in events if e["name"] == "dispatch_chunk"]
    assert ev["args"]["impl"] == "xla"


@pytest.mark.parametrize(
    "active,sharded,want",
    [
        ("pallas", False, "pallas"),
        ("mxu", False, "mxu"),
        ("xla", False, "xla"),
        # a mesh has the implementations one device has (PR 36)
        ("pallas", True, "pallas"),
        ("mxu", True, "mxu"),
        ("xla", True, "xla"),
    ],
)
def test_chunk_impl_is_what_the_chunk_was_handed_to(monkeypatch, active, sharded, want):
    """The runner returns the implementation it chose; nothing derives
    it a second time."""
    from types import SimpleNamespace

    from tendermint_tpu.ops import ed25519_batch, pallas_verify
    from tendermint_tpu.parallel import sharding

    monkeypatch.setenv(ed25519_batch._IMPL_ENV, active)
    monkeypatch.setattr(
        pallas_verify, "compiled_verify", lambda n: lambda *args: "pallas"
    )
    monkeypatch.setattr(
        ed25519_batch,
        "_compiled_kernel",
        lambda kind, n, backend, mul_impl: lambda *args: mul_impl,
    )
    monkeypatch.setattr(
        sharding,
        "run_chunk_mesh",
        lambda kind, inputs, impl, mul_impl, plan, sp: ("mesh", plan),
    )
    pks, msgs, sigs = _raw_lanes(3)
    inputs, _ = ed25519_batch.prepare_batch(pks, msgs, sigs)
    plan = SimpleNamespace(device_ids=(0, 1)) if sharded else None
    out, used, impl = ed25519_batch._run_chunk(
        ed25519_batch.KINDS["legacy"], inputs, None, plan
    )
    assert impl == want and used is plan
    assert out == ("mesh" if sharded else {"pallas": "pallas", "mxu": "mxu"}.get(active, "vpu"))


def test_profiler_capture_holds_the_program_spans(ring, tmp_path):
    """One clock with the device trace: a jax.profiler capture taken
    while the tracer records shows the program's spans as host
    annotations, beside the operations XLA ran."""
    import jax
    from jax.profiler import ProfileData

    from tendermint_tpu.ops import ed25519_batch

    pks, msgs, sigs = _raw_lanes(3)
    ed25519_batch.verify_batch(pks, msgs, sigs)  # compile outside the capture
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert ed25519_batch.verify_batch(pks, msgs, sigs) == [True] * 3
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = set()
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                names.update(e.name for e in line.events)
    assert {"verify_batch", "prep_chunk", "dispatch_chunk", "collect_chunk"} <= names


# --- a committee of mixed key types: both engines under the same names --------


def test_a_mixed_commit_names_both_engines_alike(ring, monkeypatch):
    """What the benchmark's shared commit-cell entries read, present for
    the sr25519 engine as for ed25519's (ISSUE 40): ``verify_batch``
    with ``engine`` / ``lanes`` / ``proc_cpu_us``, ``prep_chunk``,
    ``dispatch_chunk`` (``kind``, ``padded_lanes``, ``h2d_bytes``,
    ``h2d_us``, ``launch_us``, ``impl``), ``collect_chunk`` (``wait_us``,
    ``d2h_us``, ``d2h_bytes``), ``merge_results``, and a
    ``kernel_compile`` (``engine``, ``kernel``, ``lanes``) at a shape's
    first call; and the spans of what is sr25519's or the host's alone:
    ``merlin_challenge`` (``lanes``) inside its ``prep_chunk``,
    ``host_lanes`` (``key_type``, ``lanes``, ``device_lanes_inflight``,
    and since PR 49 ``impl``: whose ECDSA answered) inside its
    ``batch_verify``. A device sub-batch of a mixed call
    opens ``batch_verify`` / ``verify_batch`` once a phase."""
    from tendermint_tpu.ops import ed25519_batch
    from tendermint_tpu.types import validation
    from tests.helpers import make_mixed_validators

    # an uncached factory, so that this call meets each shape first
    monkeypatch.setattr(ed25519_batch, "_compiled_kernel", ed25519_batch._compiled_kernel.__wrapped__)
    privs, vset = make_mixed_validators(17, 19, 2)
    block_id = make_block_id(b"issue-40-names")
    commit = make_commit(block_id, 6, 0, vset, privs)
    validation.verify_commit(CHAIN_ID, vset, block_id, 6, commit)
    events = _complete_events(ring.export())
    by_engine = {"ed25519": {}, "sr25519": {}}
    for e in events:
        engine = e["args"].get("engine")
        if engine in by_engine:
            by_engine[engine].setdefault(e["name"], []).append(e["args"])
    for engine, lanes in (("ed25519", 17), ("sr25519", 19)):
        spans = by_engine[engine]
        # one a phase of a mixed call (ISSUE 41), alike but for ``phase``
        assert sorted(b["phase"] for b in spans["verify_batch"]) == ["collect", "dispatch"]
        for batch in spans["verify_batch"]:
            assert batch["lanes"] == lanes and batch["proc_cpu_us"] >= 0
            assert batch["parent"] == "batch_verify"
        (prep,) = spans["prep_chunk"]
        assert prep["lanes"] == lanes and prep["parent"] == "verify_batch"
        (sent,) = spans["dispatch_chunk"]
        assert (sent["lanes"], sent["padded_lanes"], sent["impl"]) == (lanes, 64, "xla")
        assert sent["h2d_bytes"] > 0 and sent["h2d_n"] >= 4
        assert sent["h2d_us"] >= 0 and sent["launch_us"] > 0 and (sent["chunk"], sent["chunks"]) == (0, 1)
        (got,) = spans["collect_chunk"]
        assert got["lanes"] == lanes and got["d2h_bytes"] == 64
        assert got["wait_us"] >= 0 and got["d2h_us"] >= 0
        (first,) = spans["kernel_compile"]
        assert first["lanes"] == 64
    assert by_engine["sr25519"]["dispatch_chunk"][0]["kind"] == "sr25519"
    assert by_engine["sr25519"]["kernel_compile"][0]["kernel"] == "verify_sr"
    assert sum(1 for e in events if e["name"] == "merge_results") == 2
    (merlin,) = [e["args"] for e in events if e["name"] == "merlin_challenge"]
    assert (merlin["lanes"], merlin["parent"]) == (19, "prep_chunk")
    (host,) = [e["args"] for e in events if e["name"] == "host_lanes"]
    assert (host["key_type"], host["lanes"], host["parent"]) == ("secp256k1", 2, "batch_verify")
    assert host["device_lanes_inflight"] == 17 + 19
    assert host["impl"] == hashing.host_secp256k1_impl() == "native"
    outer = [e["args"] for e in events if e["name"] == "batch_verify"]
    assert sorted((o["key_type"], o["route"], o.get("phase", "")) for o in outer) == [
        ("ed25519", "device", "collect"), ("ed25519", "device", "dispatch"),
        ("secp256k1", "host", ""),
        ("sr25519", "device", "collect"), ("sr25519", "device", "dispatch"),
    ]
    names = {e["name"] for e in events}
    assert not names & {"single_verify", "host_fallback"}
    # one cpu_us a call, on the thread's outermost span, as before
    assert [e["name"] for e in events if "cpu_us" in e["args"]] == ["verify_commit"]
