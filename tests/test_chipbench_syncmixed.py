"""The benchmark's part of the ``syncmixed500`` deployment (PR 51),
without a chip: the plain light reference of three key types on
hand-made blocks, the configuration held to ``sync500``'s and
``mixed10k``'s, the cell's files and definitions, the readers of what
the cell brought on hand-made spans, and the generator's windows. The
cell's tiny twin is rehearsed end to end, sound and broken on purpose,
in ``tests/test_chipbench_rehearsals.py``; the pipeline itself is held
to the reference in ``tests/test_pipeline_mixed.py``."""

from __future__ import annotations

import os

import pytest

from chipbench import reference_light, reference_light_mixed, reference_mixed, selftest, spec
from chipbench.generators import commits_mixed, cycle_length
from chipbench.run import Context
from tests.helpers import REAL_BENCH, Evidence, definitions, metric, read, span, stem_of
from tests.test_chipbench_mixed import as_counted

BENCH = os.path.join(spec.HERE, "testdata", "tiny-syncmixed-benchmark.json")
CELL = "tiny-syncmixed-catchup"
SEED = 2**31 + 51
REAL = (REAL_BENCH, "syncmixed500-catchup")
KEY_TYPES = commits_mixed.KEY_TYPES

SPAN = ("program_span", "sigs_per_s")
# what the cell brought, as definitions (``tests/helpers.DEFINITION_KEYS``), under whatever names
OWN = [
    ["span_self_time_per_call", {"span": "verify_commits_pipelined", "children": ["batch_verify"]},
     "Pipeline", "ms", "lower", *SPAN],
    ["span_count_per_call", {"span": "dispatch_chunk"}, "Pipeline", "launches", "lower", *SPAN],
    ["span_arg_per_call", {"span": "host_lanes", "arg": "device_lanes_inflight"}, "Pipeline", "lanes", "higher", *SPAN],
    ["span_arg_per_call", {"span": "build_lanes", "arg": "group_lanes_us", "scale": 0.001}, "Pipeline", "ms", "lower", *SPAN],
]


# --- the plain reference ---------------------------------------------------------------------

ABSENT, COMMIT, NIL = 1, 2, 3


def block(flags, bad=(), powers=None, seed=7):
    """(validators, signatures) over seats that cycle through the three
    key types, signed by ``commits_mixed``'s signers; ``flags[i]`` is
    validator i's; signatures at the indices in ``bad`` are tampered the
    way only a canonicity rule refuses."""
    from chipbench import workload

    validators, signatures = [], []
    for i, flag in enumerate(flags):
        kt = KEY_TYPES[i % 3]
        seed32 = workload._digest("ref-light-mixed", seed, i)
        if kt == "sr25519":
            from tendermint_tpu.crypto.sr25519 import Sr25519PrivKey

            priv = Sr25519PrivKey(seed32)
            pub, sign = priv.pub_key().bytes(), priv.sign
        else:
            signer = (commits_mixed.EdSigner if kt == "ed25519" else commits_mixed.SecpSigner)(seed32)
            pub, sign = signer.pub, signer.sign
        validators.append((kt, pub, (powers or [10] * len(flags))[i]))
        if flag == ABSENT:
            signatures.append((ABSENT, b"", b""))
            continue
        msg = b"vote %d flag %d" % (i, flag)
        sig = sign(msg)
        if i in bad:
            sig = commits_mixed.tamper(kt, sig, commits_mixed.TAMPER_KINDS[kt][-1])
        signatures.append((flag, msg, sig))
    return validators, signatures


@pytest.mark.parametrize(
    "flags,bad,powers,want",
    [
        # 6 equal votes: needed 40, the fifth passes it; two seats of each key type
        ([COMMIT] * 6, (), None, reference_light_mixed.OK),
        # the sixth is past the early exit: never looked at
        ([COMMIT] * 6, (5,), None, reference_light_mixed.OK),
        # an included lane of each type names its index in the commit
        ([COMMIT] * 6, (3,), None, ("wrong signature", 3)),  # ed25519
        ([COMMIT] * 6, (4,), None, ("wrong signature", 4)),  # sr25519
        ([COMMIT] * 6, (2,), None, ("wrong signature", 2)),  # secp256k1
        # ... which an absent and a nil vote before it move off its lane
        ([ABSENT, COMMIT, NIL, COMMIT, COMMIT, COMMIT, COMMIT, COMMIT], (5,), None, ("wrong signature", 5)),
        # two bad lanes of different types: the lower index
        ([COMMIT] * 6, (1, 3), None, ("wrong signature", 1)),
        ([COMMIT] * 6, (2, 0), None, ("wrong signature", 0)),
        # exactly 2/3 is not more than 2/3, bad signature or not
        ([COMMIT] * 4 + [NIL, ABSENT], (), None, reference_light_mixed.INSUFFICIENT),
        ([COMMIT] * 4 + [NIL, ABSENT], (1,), None, reference_light_mixed.INSUFFICIENT),
        # unequal powers: 50 of 60 pass 2/3 at the first vote
        ([COMMIT] * 3, (1, 2), [50, 5, 5], reference_light_mixed.OK),
    ],
)
def test_reference_light_mixed_verify_block(flags, bad, powers, want):
    assert reference_light_mixed.verify_block(*block(flags, bad, powers)) == want


def test_reference_light_mixed_blocks_do_not_look_at_their_neighbours():
    good, bad, short = block([COMMIT] * 6), block([COMMIT] * 6, bad=(1,)), block([COMMIT] * 3 + [ABSENT] * 3)
    assert reference_light_mixed.verify_window([good, bad, short, good]) == [
        reference_light_mixed.OK, ("wrong signature", 1), reference_light_mixed.INSUFFICIENT, reference_light_mixed.OK,
    ]
    with pytest.raises(ValueError):
        reference_light_mixed.verify_block(good[0], good[1][:-1])


def test_reference_light_mixed_is_reference_lights_rule_over_ed25519_seats():
    """On seats of ed25519 keys alone it answers as ``reference_light``
    does, block for block: the rule is one, the lane's judge differs."""
    from tests.test_chipbench_sync import block as ed_block

    for flags, bad in (([COMMIT] * 6, ()), ([COMMIT] * 6, (4,)), ([COMMIT, ABSENT, NIL, COMMIT, COMMIT, COMMIT, COMMIT], (3,)),
                       ([COMMIT] * 4 + [NIL, ABSENT], ())):
        validators, signatures = ed_block(len(flags), flags, bad)
        mixed = [("ed25519", pub, power) for pub, power in validators]
        assert reference_light_mixed.verify_block(mixed, signatures) == reference_light.verify_block(validators, signatures)


def test_reference_light_mixed_imports_nothing_of_the_program():
    with open(reference_light_mixed.__file__, encoding="utf-8") as fh:
        source = fh.read()
    imports = [ln for ln in source.splitlines() if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "from chipbench import reference_mixed"]
    assert "tendermint_tpu" not in source.split('"""', 2)[2] and "jax" not in source


# --- the files ---------------------------------------------------------------------------------


def test_the_configuration_keeps_sync500s_keys_and_mixed10ks():
    real = spec.Spec(REAL_BENCH)
    config, sync, mixed = (real.config(name) for name in ("syncmixed500", "sync500", "mixed10k"))
    for key in ("validators", "voting_power", "verify_window", "absent_share", "nil_share", "sign_bytes", "chips",
                "env", "env_why"):
        assert config[key] == sync[key], key
    assert config["key_type_order"] == mixed["key_type_order"]
    assert config["assumed"]["sr25519_signing_context"] == mixed["assumed"]["sr25519_signing_context"]
    for key in ("absent_share", "nil_share", "verify_window", "height_seconds", "chain_id"):
        assert config["assumed"][key] == sync["assumed"][key], key
    assert config["key_types"] == {"ed25519": 248, "sr25519": 247, "secp256k1": 5}
    assert sum(config["key_types"].values()) == config["validators"] == 500
    # mixed10k's shares: halves for the two types that batch, 1% for the one that cannot
    assert config["key_types"]["secp256k1"] * 10000 == mixed["key_types"]["secp256k1"] * 500
    assert 0 <= config["key_types"]["ed25519"] - config["key_types"]["sr25519"] <= 1
    assert list(config["reduced"]) == ["blocks"] == list(sync["reduced"])
    # no guarantee of either deployment is weakened: the three lane rules, and sync500's five in their words
    # or with "of any type" / "ed25519 and sr25519" where a set of three key types needs it said
    assert mixed["guarantees"][:3] == config["guarantees"][:3] and mixed["guarantees"][5] in config["guarantees"]
    assert sync["guarantees"][3] in config["guarantees"] and sync["guarantees"][4] in config["guarantees"]
    assert len(config["guarantees"]) == 9


def test_benchmark_files_agree():
    selftest.test_files()
    real = spec.Spec(REAL_BENCH)
    assert len(real.doc["per_layer"]) <= 128 and len(real.doc["workloads"]) <= 24
    cell = real.cell("syncmixed500-catchup")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("syncmixed500", "mixed-catchup-windows", 1)
    assert len(cell["why"]) <= 200
    entry = [c for c in real.doc["configs"] if c["name"] == "syncmixed500"][0]
    config = real.config("syncmixed500")
    assert entry["reduced"] == list(config["reduced"]) == ["blocks"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert "config 4" in entry["source"] and "config 5" in entry["source"]
    assert "poolRoutine" in config["source_detail"] and "PubKeyTypes" in config["source_detail"]
    assert [m["name"] for m in real.metrics_for("end_to_end", "syncmixed500-catchup")] == ["sigs_per_s", "setup_s"]
    assert real.doc["workloads"][-1] is cell and real.doc["configs"][-1] is entry
    sigs = [m for m in real.doc["end_to_end"] if m["name"] == "sigs_per_s"][0]
    assert sigs["workloads"][-1] == "syncmixed500-catchup" and sigs["bound"] == 0.12
    traffic = real.traffic("mixed-catchup-windows")
    assert traffic["kind"] == "catchup_mixed" and traffic["cycle_over_verdict_cache"] == 1.05
    assert {k: v for k, v in traffic.items() if k not in ("kind", "doc")} == \
        {k: v for k, v in real.traffic("catchup-windows").items() if k not in ("kind", "doc")}
    # the cycle the configuration states: sized from the ed25519 lanes of a window
    assert cycle_length(traffic, 16 * 168, 65536) == 27 and "27 windows" in config["reduced"]["blocks"]


def test_the_cell_reports_what_it_brought_and_what_every_stream_cell_reports():
    """Its four definitions, and every definition ``sync500-catchup``
    reports from an entry that lists no cells (what a ``sigs_per_s``
    cell gets for nothing and has to print)."""
    real = spec.Spec(REAL_BENCH)
    unlisted = {m["name"] for m in real.doc["per_layer"] if "workloads" not in m and m["moves"] == "sigs_per_s"}
    mine = {m["name"] for m in real.metrics_for("per_layer", "syncmixed500-catchup")}
    assert unlisted and unlisted <= mine
    assert unlisted <= {m["name"] for m in real.metrics_for("per_layer", "sync500-catchup")}
    shared = definitions(REAL_BENCH, "sync500-catchup", {stem_of(n) for n in unlisted})
    listed_too = {stem_of(m["name"]) for m in real.metrics_for("per_layer", "sync500-catchup")
                  if m["name"] not in unlisted} & {stem_of(n) for n in unlisted}
    assert not listed_too  # no listed entry of sync500-catchup under one of these stems
    assert definitions(*REAL) == as_counted(OWN) + shared
    for stem in ("pipeline_host_ms", "device_launches", "host_inflight_lanes", "group_lanes_ms"):
        assert metric(*REAL, stem).startswith(stem)


@pytest.mark.parametrize(
    "cell", [w["name"] for w in spec.Spec(REAL_BENCH).doc["workloads"] if w["name"] != "syncmixed500-catchup"]
)
def test_no_other_cell_reports_what_is_new_with_syncmixed500(cell):
    """Definitions, never copies: none of the four is another entry's."""
    assert not definitions(REAL_BENCH, cell) & as_counted(OWN), cell


def test_the_tiny_twin_lists_every_entry_the_real_cell_is_held_to():
    real, tiny = spec.Spec(REAL_BENCH), spec.Spec(BENCH)
    want = [m["name"] for m in real.metrics_for("per_layer", "syncmixed500-catchup")]
    assert [m["name"] for m in tiny.metrics_for("per_layer", CELL)] == want
    assert [m["name"] for m in tiny.metrics_for("end_to_end", CELL)] == ["sigs_per_s", "setup_s"]
    cell, twin = real.cell("syncmixed500-catchup"), tiny.cell(CELL)
    assert (twin["traffic"], twin["chips"]) == (cell["traffic"], cell["chips"])
    config = tiny.config("syncmixed")
    assert set(config["key_types"]) == set(real.config("syncmixed500")["key_types"])
    assert (config["absent_share"], config["nil_share"]) == (0.05, 0.01) and config["verify_window"] == 4
    from tendermint_tpu.crypto.batch import DEVICE_THRESHOLD

    # a window reaches the device with each type that batches; a block alone, the parent's road, would not
    quorum = config["validators"] * 2 // 3 + 1
    assert quorum // 2 < DEVICE_THRESHOLD <= config["verify_window"] * (quorum // 3)


# --- the readers of what the cell brought, on hand-made spans -----------------------------------


def window(blocks=1, lanes=5264, host=80):
    """One call of ``verify_commits_pipelined`` 0..60,000 us: planned
    (``blocks`` = 1: one dispatch and one collect phase a device type
    around one host call) or block by block, the parent's road."""
    ED, SR = {"engine": "ed25519", "kind": "resident"}, {"engine": "sr25519", "kind": "sr25519"}
    spans = [span("verify_commits_pipelined", 0, 60000, tasks=16, lanes=lanes + host)]
    if blocks == 1:
        spans.append(span("build_lanes", 100, 16000, lanes=lanes + host, sign_bytes_us=9000.0, basic_checks_us=100.0,
                          group_lanes_us=2100.0, group_lanes_n=1))
    step = 40000 // blocks
    for b in range(blocks):
        at = 17000 + b * step
        part = step // 8
        if blocks > 1:
            spans.append(span("verify_commit", at, 8 * part))
            spans.append(span("build_lanes", at, part, lanes=(lanes + host) // blocks, sign_bytes_us=part / 2.0))
            at += part
            part = 7 * part // 8
        for j, tags in enumerate((ED, SR)):
            spans.append(span("batch_verify", at + j * part, part, phase="dispatch"))
            spans.append(span("dispatch_chunk", at + j * part + 10, part // 2, lanes=lanes // 2 // blocks, **tags))
        spans.append(span("batch_verify", at + 2 * part, part, route="host"))
        spans.append(span("host_lanes", at + 2 * part + 5, part - 10, key_type="secp256k1", lanes=host // blocks,
                          device_lanes_inflight=lanes // blocks))
        for j in (3, 4):
            spans.append(span("batch_verify", at + j * part, part, phase="collect"))
    spans.append(span("merge_verdicts", 58000, 1500, blocks=16))
    return Evidence(spans)


@pytest.mark.parametrize(
    "stem,planned,block_by_block",
    [
        # 60 ms less five phases of 5 ms | less 16 x 5 phases of 273 us
        ("pipeline_host_ms", 35.0, 60.0 - 16 * 5 * 0.273),
        ("device_launches", 2.0, 32.0),
        ("host_inflight_lanes", 5264.0, 5264.0),  # sixteen spans of 329
        ("group_lanes_ms", 2.1, None),  # a program that plans no window records no such phase
    ],
)
def test_syncmixed_metric_on_hand_made_spans(stem, planned, block_by_block):
    assert read(window(), *REAL, stem) == pytest.approx(planned)
    got = read(window(blocks=16), *REAL, stem)
    assert got is None if block_by_block is None else got == pytest.approx(block_by_block)


def test_syncmixed_metrics_give_nothing_where_the_program_lacks_the_spans():
    """The parent of a program with no such spans: nothing to read, no error."""
    ev = Evidence([span("verify_batch", 510, 390)])
    for stem in ("pipeline_host_ms", "device_launches", "host_inflight_lanes", "group_lanes_ms"):
        assert read(ev, *REAL, stem) is None, stem
    ev = Evidence([span("dispatch_chunk", 5, 10, lanes=8, kind="sr25519"), span("dispatch_chunk", 50, 10, lanes=8, kind="resident")], calls=2)
    from chipbench.readers import span_count_per_call

    assert span_count_per_call.read(ev, "dispatch_chunk") == 1.0
    assert span_count_per_call.read(ev, "collect_chunk") is None


# --- the generator --------------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    from chipbench.generators import catchup_mixed

    bench = spec.Spec(BENCH)
    cell = bench.cell(CELL)
    config = bench.config(cell["config"])
    os.environ["TENDERMINT_TPU_RESULT_CACHE_CAP"] = config["env"]["TENDERMINT_TPU_RESULT_CACHE_CAP"]
    try:
        from tendermint_tpu.ops import precompute

        precompute.reset()
        said = []
        traffic = catchup_mixed.build(Context(cell, config, bench.traffic(cell["traffic"]), SEED, said.append))
    finally:
        del os.environ["TENDERMINT_TPU_RESULT_CACHE_CAP"]
        precompute.reset()
    return traffic, said


def test_every_block_of_the_generators_windows_holds_the_same_lanes_of_each_key_type(tiny):
    traffic, said = tiny
    types = [s.key_type for s in traffic.signers]
    assert {kt: types.count(kt) for kt in KEY_TYPES} == {"ed25519": 18, "sr25519": 18, "secp256k1": 6}
    assert sum(traffic.by_type.values()) == traffic.quorum == 29 and min(traffic.by_type.values()) > 0
    assert traffic.lanes_per_call == 4 * (traffic.by_type["ed25519"] + traffic.by_type["sr25519"])
    # the cycle is sized from the lanes that enter the verdict cache
    per_window = 4 * traffic.by_type["ed25519"]
    assert (traffic.count - 1) * per_window >= 1.05 * 256 > (traffic.count - 2) * per_window
    exits = set()
    for tasks in traffic.windows:
        assert len(tasks) == 4 and len({id(t.vals) for t in tasks}) == 1  # one ValidatorSet object a window
        for task in tasks:
            flags = [cs.block_id_flag for cs in task.commit.signatures]
            assert (flags.count(ABSENT), flags.count(NIL)) == (3, 1)
            lanes = traffic._included(task.commit)
            assert {kt: sum(types[i] == kt for i in lanes) for kt in KEY_TYPES} == traffic.by_type
            exits.add(lanes[-1])
    assert len(exits) > 1  # the early exit still moves from block to block
    assert [t.height for tasks in traffic.windows for t in tasks] == list(range(1, 4 * traffic.count + 1))
    assert "drawn" in said[0] and "%d lanes a call sent to the device" % traffic.lanes_per_call in said[0]
    assert traffic._draws >= traffic._heights == 4 * traffic.count


def test_a_committee_with_no_key_of_a_type_before_the_early_exit_is_drawn_again():
    """With one secp256k1 key among 42 the seat falls past the early
    exit on a seed in three: the generator then takes the seed's next
    committee, so that every block's included lanes hold every type —
    the host call and the check's fault of that type have lanes to run
    on — and says which draw it took."""
    from chipbench.generators import catchup_mixed

    bench = spec.Spec(BENCH)
    cell = bench.cell(CELL)
    config = dict(bench.config(cell["config"]), key_types={"ed25519": 21, "sr25519": 20, "secp256k1": 1})
    traffic = dict(bench.traffic(cell["traffic"]), cycle_over_verdict_cache=0.0, _allow_cache_answers=True)
    drawn = []
    for seed in range(1, 9):
        said = []
        made = catchup_mixed.build(Context(cell, config, traffic, seed, said.append))
        (seat,) = made.committee.lanes_of["secp256k1"]
        assert seat < made.quorum and made.by_type["secp256k1"] == 1 and min(made.by_type.values()) > 0
        assert all(seat in made._included(t.commit) for tasks in made.windows for t in tasks)
        assert "the committee is the seed's draw number %d" % made.committees in said[0]
        drawn.append(made.committees)
    assert min(drawn) == 1 < max(drawn)


def test_the_generators_votes_are_ones_the_reference_and_the_program_accept(tiny):
    traffic, _ = tiny
    from chipbench import workload

    task = traffic.windows[1][2]
    validators, signatures = traffic._plain(task)
    assert validators == [(s.key_type, s.pub, 10) for s in traffic.signers]
    for i, (flag, msg, sig) in enumerate(signatures):
        if flag == ABSENT:
            assert (msg, sig) == (b"", b"")
            continue
        # a nil vote too is signed over its own sign-bytes, by whatever kind of key
        assert msg == task.commit.vote_sign_bytes(workload.CHAIN_ID, i)
        assert reference_mixed.verify(traffic.signers[i].key_type, traffic.signers[i].pub, msg, sig), (i, flag)
        assert traffic.vset.validators[i].pub_key.verify_signature(msg, sig), (i, flag)
    assert {traffic.signers[i].key_type for i, (flag, _, _) in enumerate(signatures) if flag == NIL}
    assert reference_light_mixed.verify_block(validators, signatures) == reference_light_mixed.OK


def test_the_generators_fault_window_is_what_the_reference_says_it_is(tiny):
    """One fresh window: a tampered included lane of each key type, a
    tampered lane past the early exit, a block left at 2/3 — on the
    host oracles (``use_device=False``), the plain reference and the
    generator's own expectation alike."""
    from chipbench.generators import catchup_mixed
    from tendermint_tpu.parallel.pipeline import verify_commits_pipelined

    traffic, _ = tiny
    tasks, want = traffic._faulted()
    assert len(tasks) == 4 and tasks[0].height == 4 * traffic.count + 1
    kinds = sorted(w[0] for w in want)
    assert kinds == ["insufficient power", "wrong signature", "wrong signature", "wrong signature"]
    blamed = {traffic.signers[w[1]].key_type for w in want if w[0] == "wrong signature"}
    assert blamed == set(KEY_TYPES)
    assert reference_light_mixed.verify_window([traffic._plain(t) for t in tasks]) == want
    assert [traffic._answer(v) for v in verify_commits_pipelined(tasks, use_device=False)] == want
    # a second drawing gives the same window: the faults come from the seed
    again, want_again = traffic._faulted()
    assert want_again == want and [t.commit.signatures[5].signature for t in again] == \
        [t.commit.signatures[5].signature for t in tasks]
    assert len(catchup_mixed.FAULTS) == 5
