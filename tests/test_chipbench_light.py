"""The benchmark's part of the ``light1k`` deployment, without a chip:
the plain skipping reference against the program's own hashes and
sign-bytes and on seeded chains, the program's client against it (walk,
store contents, the four faults), the Light and Scheduler metrics'
files reduced on hand-made spans and ``BENCHMARK.json`` against its
files. The cell's tiny twin is rehearsed end to end in
``tests/test_chipbench_rehearsals.py``.
"""

from __future__ import annotations

import hashlib
import os

import pytest

from chipbench import reference_lightclient as plain
from chipbench import selftest, spec, workload
from chipbench.generators import lightclient
from tests.helpers import REAL_BENCH, Evidence, read, span

BENCH = os.path.join(spec.HERE, "testdata", "tiny-light-benchmark.json")
CELL, SEED = "tiny-light-chain", 2**31 + 30
REAL = (REAL_BENCH, "light1k-chain")  # the cell a hand-made reading is named through
N, JUMP, SHORT = 40, 28, 27  # the tiny twin's: 27 pass 2/3, 14 pass 1/3


@pytest.fixture(scope="module")
def chain():
    """Heights 1..1+2*JUMP of a seeded chain of N validators, one
    replaced a height, light blocks at every height a test asks for."""
    c = lightclient.Chain(30, N, 2 * JUMP + 3)
    for h in (1, 1 + JUMP // 4, 1 + JUMP // 2, 2 + JUMP // 2, 1 + SHORT, 1 + SHORT // 2,
              1 + JUMP, 1 + JUMP + JUMP // 2, 1 + 2 * JUMP):
        c.build(h)
    return c


def params(chain, check_signatures=True):
    now_ns = workload.BASE_NS + (max(chain.blocks) + 1) * workload.SECOND_NS
    return plain.Params(workload.CHAIN_ID, 14 * 86400 * 10**9, now_ns, 10 * 10**9,
                        (1, 3), check_signatures)


# --- the plain reference against the program's encodings ----------------------


def test_merkle_root_is_rfc6962():
    assert plain.merkle_root([]) == hashlib.sha256(b"").digest()
    leaf = lambda b: hashlib.sha256(b"\x00" + b).digest()  # noqa: E731
    node = lambda l, r: hashlib.sha256(b"\x01" + l + r).digest()  # noqa: E731
    a, b, c, d, e = (bytes([i]) * 3 for i in range(5))
    assert plain.merkle_root([a]) == leaf(a)
    assert plain.merkle_root([a, b, c]) == node(node(leaf(a), leaf(b)), leaf(c))
    # five leaves split 4 + 1: the largest power of two below the count
    assert plain.merkle_root([a, b, c, d, e]) == node(
        node(node(leaf(a), leaf(b)), node(leaf(c), leaf(d))), leaf(e)
    )


def test_reference_hashes_and_sign_bytes_are_the_programs(chain):
    block = chain.blocks[1 + JUMP // 2]
    doc = chain.plain(1 + JUMP // 2)
    assert plain.validators_hash(doc["validators"]) == block.validator_set.hash()
    assert plain.header_hash(doc["header"]) == block.signed_header.header.hash()
    commit = block.signed_header.commit
    for idx in (0, 7, N - 1):
        assert plain.vote_sign_bytes(workload.CHAIN_ID, doc["commit"], idx) == (
            commit.vote_sign_bytes(workload.CHAIN_ID, idx)
        )


def test_generated_sets_are_in_the_programs_canonical_order(chain):
    from tendermint_tpu.types import ValidatorSet

    vset = chain.validator_set(5)
    rebuilt = ValidatorSet([v.copy() for v in vset.validators])
    assert [v.address for v in rebuilt.validators] == [v.address for v in vset.validators]
    # one validator a height: sets d heights apart share N - d keys
    a = {v.address for v in chain.validator_set(1).validators}
    b = {v.address for v in chain.validator_set(1 + JUMP).validators}
    assert len(a & b) == N - JUMP


# --- the walk ---------------------------------------------------------------------


def test_reference_walk_refuses_the_target_by_tally_then_takes_two_hops(chain):
    out = plain.verify_skipping(chain.plain(1), 1 + JUMP, chain.plain, params(chain))
    mid = 1 + JUMP // 2
    assert out["verdict"] == plain.OK
    assert out["fetched"] == [1 + JUMP, mid]
    assert out["refused"] == [1 + JUMP]
    assert out["accepted"] == [mid, 1 + JUMP]
    # each hop: the 2/3 lanes of its commit, the trusting lanes inside them
    assert len(out["checked"]) == 2 * 27
    assert len(set(out["checked"])) == len(out["checked"])
    assert {h for h, _, _ in out["checked"]} == {mid, 1 + JUMP}
    # tallies alone decide the walk: the same without a signature checked
    dry = plain.verify_skipping(chain.plain(1), 1 + JUMP, chain.plain, params(chain, False))
    assert dry == out


def test_reference_walk_one_short_of_a_third_bisects(chain):
    # 13 shared validators: 130 of 400 is not more than 133
    out = plain.verify_skipping(chain.plain(1), 1 + SHORT, chain.plain, params(chain, False))
    assert (out["verdict"], out["refused"]) == (plain.OK, [1 + SHORT])
    assert out["accepted"] == [1 + SHORT // 2, 1 + SHORT]
    # one more shared validator and the target is trusted at once
    out = plain.verify_skipping(chain.plain(2 + JUMP // 2), 1 + JUMP + JUMP // 2,
                                chain.plain, params(chain, False))
    assert out["verdict"] == plain.OK and out["refused"] == [1 + JUMP + JUMP // 2]
    near = plain.verify_skipping(chain.plain(1 + JUMP // 2), 1 + JUMP, chain.plain, params(chain, False))
    assert (near["refused"], near["accepted"]) == ([], [1 + JUMP])


def test_reference_walk_faults(chain):
    def tampered(height, idx, kind):
        doc = chain.plain(height)
        doc = dict(doc, commit=dict(doc["commit"], signatures=list(doc["commit"]["signatures"])))
        flag, addr, t, sig = doc["commit"]["signatures"][idx]
        doc["commit"]["signatures"][idx] = (flag, addr, t, workload.tamper_signature(sig, kind))
        return doc

    mid, target = 1 + JUMP // 2, 1 + JUMP
    sound = plain.verify_skipping(chain.plain(1), target, chain.plain, params(chain))
    first_mid = next(i for h, i, _ in sound["checked"] if h == mid)
    blocks = {mid: tampered(mid, first_mid, "s>=L"), target: chain.plain(target)}
    out = plain.verify_skipping(chain.plain(1), target, blocks.get, params(chain))
    assert (out["verdict"], out["detail"], out["accepted"]) == ("wrong signature", (mid, first_mid), [])
    # past the 2/3 exit nothing is looked at
    blocks = {mid: chain.plain(mid), target: tampered(target, N - 1, "R")}
    out = plain.verify_skipping(chain.plain(1), target, blocks.get, params(chain))
    assert out["verdict"] == plain.OK and out["accepted"] == [mid, target]
    # a header that names another set than the one supplied
    wrong = dict(chain.plain(mid), validators=chain.plain(target)["validators"])
    out = plain.verify_skipping(chain.plain(1), target, {mid: wrong, target: chain.plain(target)}.get,
                                params(chain))
    assert (out["verdict"], out["accepted"]) == ("validators_hash", [])
    # a provider without the midpoint
    out = plain.verify_skipping(chain.plain(1), target, {target: chain.plain(target)}.get, params(chain))
    assert (out["verdict"], out["detail"]) == ("no block", mid)
    # an expired trust root
    late = params(chain)
    late.now_ns = workload.BASE_NS + 10**9 + late.trusting_period_ns + 1
    assert plain.verify_skipping(chain.plain(1), target, chain.plain, late)["verdict"] == (
        "trusted header expired"
    )


# --- the files ----------------------------------------------------------------------


def test_benchmark_files_agree():
    selftest.test_files()
    real = spec.Spec(REAL_BENCH)
    cell = real.cell("light1k-chain")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("light1k", "skipping-updates", 1)
    config, traffic = real.config("light1k"), real.traffic("skipping-updates")
    assert config["validators"] == 1000 and config["trust_level"] == [1, 3]
    assert list(config["reduced"]) == ["signed_heights"]
    assert list(config["env"]) == ["TENDERMINT_TPU_FIELD_MUL"]
    quorum = config["validators"] * 2 // 3 + 1
    assert config["lanes_per_call"] == 2 * quorum == 1334
    assert (traffic["jump_heights"], traffic["short_of_trust_jump"], traffic["warm_up_calls"]) == (800, 667, 5)
    from chipbench.generators import cycle_length

    assert cycle_length(traffic, 1334, 65536) == 53
    assert [m["name"] for m in real.metrics_for("end_to_end", "light1k-chain")] == ["sigs_per_s", "setup_s"]
    # its own layers and, since PR 34, the call path's (Engine, Device)
    assert {m["layer"] for m in real.metrics_for("per_layer", "light1k-chain")} == {
        "Light", "Scheduler", "Tables", "Engine", "Kernels", "Device"}
    # the reference imports nothing of the program
    with open(os.path.join(spec.HERE, "reference_lightclient.py")) as fh:
        assert "tendermint_tpu" not in fh.read().replace("tendermint v0.35.9", "")


def one_update():
    """One call: light_verify 0..2000 holding the trusted block's load 10..60, the target's
    light_block_checks 60..95 and valset_hash 100..200 (the client's validate_basic), light_round
    300..1900 with light_plan 310..900 (phases 60 + 40 + 200; inside it a pivot's light_block_checks
    370..390 and valset_hash 400..480, and two note_validator_set of 30) and light_super_batch 910..1890,
    in which scheduler_dispatch 950..1850 holds sched_assemble 960..1000 and verify_batch 1010..1800; then
    the pivot's save 1900..1950, the detector 1950..1960 and the target's save 1960..1995."""
    return [
        span("light_verify", 0, 2000, target=801, hops=2), span("light_store_load", 10, 50, bytes=200000),
        span("light_block_checks", 60, 35), span("valset_hash", 100, 100),
        span("light_round", 300, 1600), span("light_block_checks", 370, 20),
        span("light_plan", 310, 590, header_checks_us=60.0, header_checks_n=3, valset_hash_us=80.0,
             valset_hash_n=1, tally_us=40.0, tally_n=5, sign_bytes_us=200.0, sign_bytes_n=54),
        span("valset_hash", 400, 80), span("note_validator_set", 500, 30), span("note_validator_set", 700, 30),
        span("light_super_batch", 910, 980, lanes=54), span("scheduler_dispatch", 950, 900, lanes=54),
        span("sched_assemble", 960, 40), span("sched_flush", 1005, 800), span("verify_batch", 1010, 790),
        span("gather_tables", 1020, 10, builds=0), span("gather_tables", 1040, 30, builds=2),
        span("light_store_save", 1900, 50, height=401), span("light_detect", 1950, 10, witnesses=1),
        span("light_store_save", 1960, 35, height=801),
    ]


# light_unnamed_ms: outside plan and super-batch 2000 - 590 - 980 = 430, less the hash, checks, load,
# saves and detector there (100 + 35 + 50 + 85 + 10) = 150; the plan's 590 less its phases 300 and the
# 160 of the spans inside it = 130
NAMED = [("valset_hash_ms", 0.180), ("note_set_ms", 0.060), ("sign_bytes_ms", 0.200), ("tally_ms", 0.040),
         ("header_checks_ms", 0.060), ("store_save_ms", 0.085), ("store_load_ms", 0.050),
         ("block_checks_ms", 0.055), ("detector_ms", 0.010)]
LIGHT = NAMED + [("light_host_ms", 1.210), ("sched_handoff_ms", 0.080), ("sched_assemble_ms", 0.040),
                 ("table_build_ms", 0.030), ("light_unnamed_ms", 0.280)]


@pytest.mark.parametrize("stem,want", LIGHT)
def test_light_metric_on_nested_spans(stem, want):
    assert read(Evidence(one_update()), *REAL, stem) == pytest.approx(want)


def test_light_metrics_add_up_on_nested_spans():
    """The call's host time is its named parts, the scheduler's share (the super-batch less the engine) and the rest."""
    got = {stem: read(Evidence(one_update()), *REAL, stem) for stem, _ in LIGHT}
    named = (980 - 790) / 1000.0 + sum(got[stem] for stem, _ in NAMED)
    assert named + got["light_unnamed_ms"] == pytest.approx(got["light_host_ms"])
    # a program without the light spans (the parent): nothing to read
    ev = Evidence([span("verify_batch", 1010, 790), span("gather_tables", 1020, 10, builds=0)])
    for stem in ("light_host_ms", "sign_bytes_ms", "tally_ms", "light_unnamed_ms", "sched_handoff_ms",
                 "store_save_ms", "store_load_ms", "block_checks_ms", "detector_ms"):
        assert read(ev, *REAL, stem) is None, stem
    assert read(ev, *REAL, "table_build_ms") == 0.0
