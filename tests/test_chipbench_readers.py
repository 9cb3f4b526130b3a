"""The span readers ISSUE 24 adds to the benchmark, reduced on
hand-made spans (times in microseconds, as the tracer exports them).

One call: verify_commit 0..1000 holding note_validator_set 10..60,
build_lanes 100..400 (phase totals 200 + 40 of its 300), batch_verify
400..900 holding verify_batch 420..880, merge_verdicts 900..950.
"""

import pytest

from chipbench.readers import (
    span_arg_per_call,
    span_time_per_later_call,
    span_unnamed_per_call,
)
from tests.helpers import REAL_BENCH, Evidence, read, span


def one_call(at=0.0):
    return [
        span("verify_commit", at, 1000),
        span("note_validator_set", at + 10, 50),
        span(
            "build_lanes", at + 100, 300,
            lanes=4, sign_bytes_us=200.0, sign_bytes_n=4,
            batch_add_us=40.0, batch_add_n=4,
        ),
        span("batch_verify", at + 400, 500),
        span("verify_batch", at + 420, 460),
        span("dispatch_chunk", at + 500, 20, lanes=4, h2d_bytes=8192),
        span("dispatch_chunk", at + 600, 20, lanes=4, h2d_bytes=4096),
        span("merge_verdicts", at + 900, 50),
    ]


CHILDREN = ["note_validator_set", "build_lanes", "batch_verify", "merge_verdicts"]
PHASED = {"build_lanes": ["sign_bytes", "batch_add", "val_lookup"]}


@pytest.mark.parametrize(
    "arg,scale,want",
    [
        ("sign_bytes_us", 0.001, 0.2),
        ("batch_add_us", 0.001, 0.04),
        ("sign_bytes_n", 1.0, 4.0),
    ],
)
def test_span_arg_per_call_sums_a_phase_total(arg, scale, want):
    ev = Evidence(one_call() + one_call(at=5000.0), calls=2)
    got = span_arg_per_call.read(ev, span="build_lanes", arg=arg, scale=scale)
    assert got == pytest.approx(want)


def test_span_arg_per_call_sums_over_the_chunks_of_a_call():
    ev = Evidence(one_call())
    got = span_arg_per_call.read(ev, span="dispatch_chunk", arg="h2d_bytes")
    assert got == 8192 + 4096


@pytest.mark.parametrize(
    "spans",
    [
        [],  # nothing recorded
        [span("build_lanes", 0, 10, lanes=4)],  # the parent's program: no phases
        [span("other", 0, 10, sign_bytes_us=1.0)],  # the argument on another span
    ],
)
def test_span_arg_per_call_reads_nothing_where_nothing_is(spans):
    ev = Evidence(spans)
    assert span_arg_per_call.read(ev, span="build_lanes", arg="sign_bytes_us") is None


def test_span_unnamed_is_self_time_plus_the_loop_outside_its_phases():
    ev = Evidence(one_call())
    # verify_commit 1000 less its children 50 + 300 + 500 + 50 = 100;
    # build_lanes 300 less its phases 240 = 60
    got = span_unnamed_per_call.read(
        ev, span="verify_commit", children=CHILDREN, phased=PHASED
    )
    assert got == pytest.approx((100 + 60) / 1000.0)


def test_span_unnamed_divides_by_the_calls_of_the_window():
    ev = Evidence(one_call() + one_call(at=5000.0), calls=2)
    got = span_unnamed_per_call.read(
        ev, span="verify_commit", children=CHILDREN, phased=PHASED
    )
    assert got == pytest.approx(0.16)


@pytest.mark.parametrize(
    "spans",
    [
        [],
        # the parent's program: verify_commit and verify_batch alone
        [span("verify_commit", 0, 1000), span("verify_batch", 420, 460)],
        # a loop span with no verify_commit around it
        [span("build_lanes", 100, 300, sign_bytes_us=200.0)],
    ],
)
def test_span_unnamed_reads_nothing_without_the_new_spans(spans):
    ev = Evidence(spans)
    got = span_unnamed_per_call.read(
        ev, span="verify_commit", children=CHILDREN, phased=PHASED
    )
    assert got is None


def test_the_parts_of_the_entry_add_up_to_its_self_time():
    """ISSUE 24's identity: sign_bytes + batch_add + note_set + unnamed
    + merge_verdicts + batch_verify's own time = verify_commit less
    verify_batch (what entry_host_ms reads)."""
    from chipbench.readers import span_self_time_per_call, span_time_per_call

    ev = Evidence(one_call())
    parts = (
        span_arg_per_call.read(ev, "build_lanes", "sign_bytes_us", 0.001)
        + span_arg_per_call.read(ev, "build_lanes", "batch_add_us", 0.001)
        + span_time_per_call.read(ev, ["note_validator_set", "merge_verdicts"])
        + span_unnamed_per_call.read(ev, "verify_commit", CHILDREN, PHASED)
        + span_self_time_per_call.read(ev, "batch_verify", ["verify_batch"])
    )
    entry_host = span_self_time_per_call.read(ev, "verify_commit", ["verify_batch"])
    assert parts == pytest.approx(entry_host)


ENTRY = [("entry_host_ms", 0.54), ("sign_bytes_ms", 0.2), ("batch_add_ms", 0.04), ("note_set_ms", 0.05),
         ("entry_unnamed_ms", 0.16), ("engine_unnamed_ms", 0.42), ("h2d_bytes", 8192 + 4096)]


@pytest.mark.parametrize("cell", ["hub150-warm", "big10k-x4", "mixed10k"])
@pytest.mark.parametrize("stem,want", ENTRY)
def test_the_entrys_metrics_as_a_commit_cell_reports_them(cell, stem, want):
    """The same call through the files: every commit cell reads it with the readers and arguments above."""
    assert read(Evidence(one_call()), REAL_BENCH, cell, stem) == pytest.approx(want)


def test_later_calls_leave_out_what_ran_before_the_window():
    """run.py collects garbage itself between set-up and the window;
    that pause is drained with the first call and is no call's."""
    first = {"spans": [span("gc_pause", 0, 90000, generation=2), span("verify_commit", 100000, 1000)]}
    second = {"spans": [span("verify_commit", 200000, 1000), span("gc_pause", 200100, 300, generation=1)]}
    third = {"spans": [span("verify_commit", 300000, 1000)]}
    ev = Evidence([])
    ev.calls = [first, second, third]
    assert span_time_per_later_call.read(ev, ["gc_pause"]) == pytest.approx(0.15)
    ev.calls = [first]
    assert span_time_per_later_call.read(ev, ["gc_pause"]) is None


# --- the contract between the program and the benchmark's readers -----------


@pytest.mark.parametrize("kind", ["legacy", "tables", "resident"])
def test_program_still_emits_what_the_readers_match(kind, monkeypatch):
    """What ``chipbench`` reads from a verify call, held on one CPU ``verify_batch`` a chunk kind: the
    ``dispatch_chunk`` span names the kind and carries ``lanes``/``padded_lanes``/``h2d_bytes``/``impl``;
    the ``kernel_compile`` span names the kernel ``pad_lane_share.KERNEL_OF_KIND`` maps the kind to; and
    the jitted program is called ``run…``, which is what the kernel-time and roofline entries find on the
    device trace (``jit_run*``). And what ``sync500-rotation``'s metrics read of a set change (PR 32):
    ``route_lanes``' ``legacy``, ``gather_tables``' ``builds``, ``resident_upload``'s ``reason`` and
    ``width``, and, once eight newer sets have pushed a carried one out, ``note_validator_set``'s
    ``retired`` / ``tables_dropped`` around a ``valset_hash`` and the ``resident_drop`` span. And the
    chunk's life and the CPU clocks (ISSUE 34): ``chunk`` / ``chunks`` and the phase totals ``h2d_us`` /
    ``launch_us`` on ``dispatch_chunk``, ``chunk``, ``wait_us`` and ``d2h_us`` on ``collect_chunk``,
    ``hash_us`` on ``prep_chunk``, ``cpu_us`` on the call's outermost span alone and ``proc_cpu_us`` on
    ``verify_batch`` alone (here one span is both; ``tests/test_mesh.py`` holds the same on the sharded
    path). A refactor that renames any of these fails here, not on the chip."""
    from chipbench.readers.pad_lane_share import KERNEL_OF_KIND
    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.libs import tracing
    from tendermint_tpu.ops import ed25519_batch, precompute, resident

    pks, msgs, sigs = [], [], []
    for i in range(3):
        priv = Ed25519PrivKey.from_seed(bytes([i + 71]) * 32)
        msgs.append(b"contract-%d" % i)
        pks.append(priv.pub_key().bytes())
        sigs.append(priv.sign(msgs[-1]))

    # an uncached factory: every chunk builds (and names) its program anew,
    # without emptying the cache the rest of the suite is using
    made = []
    build = ed25519_batch._compiled_kernel.__wrapped__

    def factory(*key):
        made.append(build(*key))
        return made[-1]

    monkeypatch.setattr(ed25519_batch, "_compiled_kernel", factory)
    monkeypatch.setenv(precompute._RESULT_ENV, "0")
    if kind == "resident":
        monkeypatch.setenv("TENDERMINT_TPU_RESIDENT", "on")
    precompute.reset()
    resident.reset()
    if kind != "legacy":
        precompute.pin_pubkeys(set(pks))
    tracing.tracer.set_metrics_observer(None)
    tracing.configure("ring")
    tracing.tracer.clear()
    try:
        assert ed25519_batch.verify_batch(pks, msgs, sigs) == [True] * 3
        events = tracing.tracer.export(clear=True)["traceEvents"]
        if kind == "resident":
            rotation = _a_carried_set_pushed_out_by_eight_newer_ones()
    finally:
        tracing.configure("off")
        tracing.tracer.clear()
        precompute.reset()
        resident.reset()
    (chunk,) = [e["args"] for e in events if e.get("name") == "dispatch_chunk"]
    assert chunk["kind"] == kind and chunk["lanes"] == 3
    assert chunk["padded_lanes"] == 64 and chunk["impl"] == "xla"
    assert chunk["h2d_bytes"] > 0
    assert (chunk["chunk"], chunk["chunks"], chunk["launch_n"]) == (0, 1, 1)
    # one put an array that carries lanes: the store is on the device already
    assert chunk["h2d_n"] == sum(
        1 for i in ed25519_batch.KINDS[kind].inputs if i.lane_axis is not None
    )
    (dispatch,) = [e for e in events if e.get("name") == "dispatch_chunk"]
    assert 0 < chunk["h2d_us"] + chunk["launch_us"] <= dispatch["dur"]
    (collect,) = [e for e in events if e.get("name") == "collect_chunk"]
    assert (collect["args"]["chunk"], collect["args"]["wait_n"], collect["args"]["d2h_n"]) == (0, 1, 1)
    assert 0 < collect["args"]["wait_us"] + collect["args"]["d2h_us"] <= collect["dur"]
    (prep,) = [e for e in events if e.get("name") == "prep_chunk"]
    assert prep["args"]["hash_n"] == 1 and 0 < prep["args"]["hash_us"] <= prep["dur"]
    (outer,) = [e for e in events if "cpu_us" in e.get("args", {})]
    assert outer["name"] == "verify_batch" and 0 < outer["args"]["cpu_us"] <= outer["dur"]
    assert [e["name"] for e in events if "proc_cpu_us" in e.get("args", {})] == ["verify_batch"]
    (compiled,) = [e["args"] for e in events if e.get("name") == "kernel_compile"]
    assert compiled["kernel"] == KERNEL_OF_KIND[kind]
    assert (compiled["engine"], compiled["lanes"]) == ("ed25519", 64)
    (fn,) = made
    assert fn.__name__.startswith("run") and fn.__wrapped__.__name__ == fn.__name__
    (route,) = [e["args"] for e in events if e.get("name") == "route_lanes"]
    assert route["legacy"] == (3 if kind == "legacy" else 0) and route[kind] == 3
    (gather,) = [e["args"] for e in events if e.get("name") == "gather_tables"]
    assert gather["builds"] == (0 if kind == "legacy" else 3)
    uploads = [e["args"] for e in events if e.get("name") == "resident_upload"]
    assert [(u["reason"], u["width"], u["keys"]) for u in uploads] == (
        [("first", 64, 3)] if kind == "resident" else []
    )
    if kind != "resident":
        return
    notes = [e["args"] for e in rotation if e["name"] == "note_validator_set"]
    assert [n.get("retired") for n in notes] == [0] * 8 + [1]
    assert [n.get("tables_dropped") for n in notes] == [0] * 8 + [2]
    assert sum(1 for e in rotation if e["name"] == "valset_hash") == 9
    (drop,) = [e["args"] for e in rotation if e["name"] == "resident_drop"]
    assert (drop["reason"], drop["keys"], drop["departed"]) == ("rotation", 5, 2)
    (upload,) = [e["args"] for e in rotation if e["name"] == "resident_upload"]
    assert (upload["reason"], upload["keys"]) == ("joined", 5)


def _a_carried_set_pushed_out_by_eight_newer_ones() -> list:
    """The spans of: a two-validator set noted beside three pinned keys,
    its tables built and sent to the store, then eight other sets."""
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.libs import tracing
    from tendermint_tpu.ops import precompute, resident
    from tests.helpers import make_validators

    sets = [
        make_validators(
            2, key_factory=lambda i, o=o: Ed25519PrivKey.from_seed(bytes([101 + 2 * o + i]) * 32)
        )[1]
        for o in range(9)
    ]
    tracing.tracer.clear()
    crypto_batch.note_validator_set_traced(sets[0])
    keys = [v.pub_key.bytes() for v in sets[0].validators]
    has_table = precompute.tables.gather(keys)[1]  # the first set a cache meets: built at first sight
    assert has_table.all() and resident.acquire(keys, has_table) is not None
    for vset in sets[1:]:
        crypto_batch.note_validator_set_traced(vset)
    return [e for e in tracing.tracer.export(clear=True)["traceEvents"] if e.get("ph") == "X"]


def test_program_still_emits_the_cache_spans_the_readers_match(monkeypatch):
    """Every cell's ``prep_ms`` reads ``cache_lookup``, its ``cache_store_ms`` reads ``cache_store`` and its
    ``engine_unnamed_ms`` takes both and ``merge_results`` off ``verify_batch``: one span each a call, with
    ``hits`` on the lookup and ``lanes`` / ``evicted`` on the store. Held on two CPU calls whose batches carry two
    repeats: inside one batch a repeat misses (and is stored) like any lane, from an earlier call it hits."""
    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.libs import tracing
    from tendermint_tpu.ops import ed25519_batch, precompute

    pks, msgs, sigs = [], [], []
    for i in range(5):
        priv = Ed25519PrivKey.from_seed(bytes([i + 91]) * 32)
        msgs.append(b"cache-contract-%d" % i)
        pks.append(priv.pub_key().bytes())
        sigs.append(priv.sign(msgs[-1]))

    def lanes(*rows):
        return [[seq[i] for i in rows] for seq in (pks, msgs, sigs)]

    def spans_of(batch):
        tracing.tracer.clear()
        assert ed25519_batch.verify_batch(*batch) == [True] * len(batch[0])
        events = tracing.tracer.export(clear=True)["traceEvents"]
        named = {}
        for e in events:
            if e.get("name") in ("cache_lookup", "cache_store", "merge_results"):
                assert e["name"] not in named  # one a call
                named[e["name"]] = e["args"]
        return named

    monkeypatch.setenv(precompute._RESULT_ENV, "1")
    precompute.reset()
    tracing.tracer.set_metrics_observer(None)
    tracing.configure("ring")
    try:
        first = spans_of(lanes(0, 1, 2, 0, 1))
        monkeypatch.setenv(precompute._RESULT_CAP_ENV, "2")
        second = spans_of(lanes(3, 0, 4, 1))
        third = spans_of(lanes(3, 4))
        stats = precompute.results.stats()
    finally:
        tracing.configure("off")
        tracing.tracer.clear()
        precompute.reset()
    assert (first["cache_lookup"]["lanes"], first["cache_lookup"]["hits"]) == (5, 0)
    assert (first["cache_store"]["lanes"], first["cache_store"]["evicted"]) == (5, 0)
    assert first["merge_results"]["lanes"] == 5
    assert (second["cache_lookup"]["lanes"], second["cache_lookup"]["hits"]) == (4, 2)
    assert (second["cache_store"]["lanes"], second["cache_store"]["evicted"]) == (2, 3)
    assert third["cache_lookup"]["hits"] == 2 and "cache_store" not in third
    assert stats == {"entries": 2, "hits": 4, "misses": 7, "evictions": 3}
