"""``parallel/pipeline.verify_commits_pipelined`` over a validator set of
three key types (``JAX_PLATFORMS=cpu``, the device path): a window's
included lanes planned by key type across its blocks — one ed25519 and
one sr25519 sub-batch a window, the secp256k1 lanes of every block in
one ``host_lanes`` call — held block by block against the plain
reference (``chipbench/reference_light_mixed.py``: big integers, nothing
of the program) and against ``verify_commit_light`` alone; then the
route, which the verdicts say nothing of.
"""

from __future__ import annotations

import pytest

from chipbench import reference_light_mixed
from chipbench.generators import commits_mixed
from tendermint_tpu.parallel import pipeline as pipeline_mod
from tendermint_tpu.types import BLOCK_ID_FLAG_COMMIT
from tests.helpers import make_commit, make_mixed_validators, traced
from tests.test_pipeline_reference import (
    INSUFFICIENT, OK, included, light_alone, make_window, pipelined,
)

N_ED, N_SR, N_SECP = 20, 18, 4  # a window of 4 sends each device type more than crypto.batch.DEVICE_THRESHOLD
N = N_ED + N_SR + N_SECP
KEY_TYPES = ("ed25519", "sr25519", "secp256k1")
TAMPER = {"ed25519": "s>=L", "sr25519": "s", "secp256k1": "high-s"}


@pytest.fixture(scope="module")
def mixed():
    return make_mixed_validators(N_ED, N_SR, N_SECP)


def window(mixed, size, seed, gaps=True, first=1):
    return make_window(*mixed, size, gaps=gaps, seed=seed, first=first)


def plain(task):
    """A task as the plain reference takes it: values only."""
    commit = task.commit
    validators = [(v.pub_key.type, v.pub_key.bytes(), v.voting_power) for v in task.vals.validators]
    signatures = [
        (cs.block_id_flag, commit.vote_sign_bytes(task.chain_id, i) if cs.signature else b"", cs.signature)
        for i, cs in enumerate(commit.signatures)
    ]
    return validators, signatures


def assert_all_agree(tasks, want=None):
    got = pipelined(tasks)
    assert got == reference_light_mixed.verify_window([plain(t) for t in tasks])
    assert got == [light_alone(t) for t in tasks]
    if want is not None:
        assert got == want
    return got


def included_of(task, key_type):
    """Commit indices light verification looks at whose key is ``key_type``'s."""
    return [i for i in included(task) if task.vals.validators[i].pub_key.type == key_type]


def tamper(task, idx, kind=None):
    key_type = task.vals.validators[idx].pub_key.type
    cs = task.commit.signatures[idx]
    cs.signature = commits_mixed.tamper(key_type, cs.signature, kind or TAMPER[key_type])
    return idx


@pytest.mark.parametrize("gaps", [False, True], ids=["all-sign", "absent-nil"])
@pytest.mark.parametrize("size", [1, 4])
def test_sound_mixed_windows_agree_with_reference_and_light(mixed, size, gaps):
    tasks = window(mixed, size, seed=510 + size + gaps, gaps=gaps)
    assert {v.pub_key.type for t in tasks for v in t.vals.validators} == set(KEY_TYPES)
    assert_all_agree(tasks, want=[OK] * size)


@pytest.mark.parametrize("key_type", KEY_TYPES)
@pytest.mark.parametrize("kind", ["bit", "canonicity"])
def test_tampered_included_lane_of_each_type_refuses_its_block_at_its_commit_index(mixed, key_type, kind):
    tasks = window(mixed, 4, seed=520)
    lanes = included_of(tasks[2], key_type)
    idx = lanes[-1]  # past an absent or nil vote the commit index runs ahead of the lane
    tamper(tasks[2], idx, commits_mixed.TAMPER_KINDS[key_type][0] if kind == "bit" else TAMPER[key_type])
    assert_all_agree(tasks, want=[OK, OK, ("wrong signature", idx), OK])


@pytest.mark.parametrize("first,second", [("sr25519", "ed25519"), ("secp256k1", "sr25519"), ("ed25519", "secp256k1")])
def test_two_bad_lanes_of_different_types_are_blamed_on_the_lower_index(mixed, first, second):
    tasks = window(mixed, 3, seed=530)
    low = included_of(tasks[1], first)[0]
    high = next(i for i in included_of(tasks[1], second) if i > low)
    tamper(tasks[1], high)
    tamper(tasks[1], low)
    assert_all_agree(tasks, want=[OK, ("wrong signature", low), OK])


@pytest.mark.parametrize("key_type", KEY_TYPES)
def test_tampered_lane_past_the_early_exit_is_never_looked_at(mixed, key_type):
    tasks = window(mixed, 2, seed=540, gaps=False)
    task = tasks[1]
    last = included(task)[-1]
    later = [
        i for i, cs in enumerate(task.commit.signatures)
        if i > last and cs.block_id_flag == BLOCK_ID_FLAG_COMMIT and task.vals.validators[i].pub_key.type == key_type
    ]
    tamper(task, later[0])
    assert_all_agree(tasks, want=[OK, OK])


def test_block_left_at_two_thirds_is_refused_for_power_and_sends_no_lane(mixed):
    privs, vset = mixed
    tasks = window(mixed, 3, seed=550)
    bid = tasks[1].block_id
    # 28 of 42 equal votes are exactly 2/3: not more than 2/3
    tasks[1].commit = make_commit(bid, 2, 0, vset, privs, absent=set(range(0, N, 3)))
    bad = tamper(tasks[2], included_of(tasks[2], "sr25519")[1])  # its slice must still be its own
    raised, events = traced(lambda: assert_all_agree(tasks, want=[OK, INSUFFICIENT, ("wrong signature", bad)]))
    assert raised is None
    outer = [e for e in events if e["name"] == "verify_commits_pipelined"][0]["args"]
    sent = len(included(tasks[0])) + len(included(tasks[2]))
    assert (outer["lanes"], outer["refused_early"]) == (sent, 1)
    assert outer["device_lanes_ed25519"] + outer["device_lanes_sr25519"] + outer["host_lanes"] == sent


def test_a_bad_block_beside_fifteen_sound_ones_changes_no_other_verdict(mixed):
    tasks = window(mixed, 16, seed=560)
    bad = tamper(tasks[9], included_of(tasks[9], "secp256k1")[0])
    got = pipelined(tasks)
    assert got == [OK] * 9 + [("wrong signature", bad)] + [OK] * 6
    assert got == [light_alone(t) for t in tasks]
    # the plain reference on the bad block and its two neighbours
    assert reference_light_mixed.verify_window([plain(t) for t in tasks[8:11]]) == got[8:11]


def test_block_holding_an_entry_its_verifier_refuses_gets_verify_commit_lights_verdict(mixed, monkeypatch):
    """A malformed ed25519 signature (63 bytes) is one ``add`` raises on:
    that block alone is given ``verify_commit_light``'s own verdict, as
    ``_verify_commit_batch`` sends such a commit to single verification,
    and the other blocks stay in the plan."""
    tasks = window(mixed, 4, seed=570)
    idx = included_of(tasks[1], "ed25519")[2]
    cs = tasks[1].commit.signatures[idx]
    cs.signature = cs.signature[:63]
    bad = tamper(tasks[3], included_of(tasks[3], "sr25519")[0])
    single = []
    monkeypatch.setattr(
        pipeline_mod, "_verify_light_single",
        lambda task, real=pipeline_mod._verify_light_single: single.append(task.height) or real(task),
    )
    raised, events = traced(lambda: assert_all_agree(
        tasks, want=[OK, ("wrong signature", idx), OK, ("wrong signature", bad)]
    ))
    assert raised is None
    assert single == [tasks[1].height]
    merge = [e for e in events if e["name"] == "merge_verdicts" and "blocks" in e["args"]][0]
    assert merge["args"]["blocks"] == 3


# --- the route ------------------------------------------------------------------


@pytest.mark.parametrize("blocks", [1, 4, 16])
def test_a_mixed_window_runs_one_sub_batch_a_key_type_whatever_its_blocks(mixed, blocks):
    """One ``batch_verify`` a phase a device key type and one
    ``host_lanes`` span a window, not one of each a block; every lane
    the device was sent in one ``dispatch_chunk`` a type; the ECDSA ran
    while both sub-batches were in flight."""
    tasks = window(mixed, blocks, seed=580 + blocks)
    raised, events = traced(lambda: pipelined(tasks))
    assert raised is None
    sent = {kt: sum(len(included_of(t, kt)) for t in tasks) for kt in KEY_TYPES}
    (outer,) = [e for e in events if e["name"] == "verify_commits_pipelined"]
    a = outer["args"]
    assert (a["tasks"], a["lanes"], a["sub_batches"]) == (blocks, sum(sent.values()), 3)
    assert (a["device_lanes_ed25519"], a["device_lanes_sr25519"], a["host_lanes"]) == tuple(sent[kt] for kt in KEY_TYPES)
    on_device = blocks > 1  # a lone block sends each device type under crypto.batch.DEVICE_THRESHOLD lanes
    routes = [(e["args"]["key_type"], e["args"]["lanes"], e["args"]["route"], e["args"].get("phase"))
              for e in events if e["name"] == "batch_verify"]
    if on_device:
        assert routes == [
            ("ed25519", sent["ed25519"], "device", "dispatch"), ("sr25519", sent["sr25519"], "device", "dispatch"),
            ("secp256k1", sent["secp256k1"], "host", None),
            ("ed25519", sent["ed25519"], "device", "collect"), ("sr25519", sent["sr25519"], "device", "collect"),
        ]
    else:
        assert routes == [(kt, sent[kt], "host", None) for kt in KEY_TYPES]
    (host,) = [e for e in events if e["name"] == "host_lanes"]
    assert (host["args"]["key_type"], host["args"]["lanes"]) == ("secp256k1", sent["secp256k1"])
    assert host["args"]["device_lanes_inflight"] == (sent["ed25519"] + sent["sr25519"]) * on_device
    chunks = [(e["args"]["kind"] == "sr25519", e["args"]["lanes"]) for e in events if e["name"] == "dispatch_chunk"]
    assert sorted(chunks) == ([(False, sent["ed25519"]), (True, sent["sr25519"])] if on_device else [])
    assert len([e for e in events if e["name"] == "merlin_challenge"]) == on_device
    # the loop's span: the grouping is a phase of it, timed once a window; one note a task
    (loop,) = [e for e in events if e["name"] == "build_lanes"]
    assert loop["args"]["group_lanes_n"] == 1 and 0 <= loop["args"]["group_lanes_us"] <= loop["dur"]
    assert loop["args"]["lanes"] == a["lanes"] == loop["args"]["sign_bytes_n"]
    assert len([e for e in events if e["name"] == "note_validator_set"]) == blocks
    merges = [e["args"] for e in events if e["name"] == "merge_verdicts"]
    assert [m.get("blocks") for m in merges] == [None, blocks]  # the verifier's merge, then the per-block scan
    assert not [e for e in events if e["name"] in ("verify_commit", "single_verify", "host_fallback")]


def test_the_key_types_of_a_set_are_read_once_a_set_object_not_once_a_block(mixed, monkeypatch):
    """The window's look at its sets reads each seat's key type once a
    ValidatorSet object, whatever the blocks that share it; the grouping
    itself goes by the keys' classes (``_seats_by_type``)."""
    from tendermint_tpu.crypto.keys import Ed25519PubKey

    tasks = window(mixed, 8, seed=590)
    reads = []
    real = Ed25519PubKey.type
    monkeypatch.setattr(Ed25519PubKey, "type", property(lambda self: reads.append(1) or real.fget(self)))
    seats, is_mixed = pipeline_mod._window_seats(tasks)
    assert is_mixed and len(reads) == N_ED
    assert list(seats) == [id(mixed[1])] and seats[id(mixed[1])] == [v.pub_key for v in mixed[1].validators]
    # a window of ed25519 sets alone is handed the raw keys the engine takes
    ed_only = make_window(*make_mixed_validators(6, 0, 0), 2, seed=591)
    seats, is_mixed = pipeline_mod._window_seats(ed_only)
    assert not is_mixed and all(isinstance(k, bytes) for ks in seats.values() for k in ks)


def test_use_device_false_sends_every_type_to_its_host_oracle(mixed):
    tasks = window(mixed, 4, seed=600)
    bad = tamper(tasks[0], included_of(tasks[0], "sr25519")[3])
    got = []
    raised, events = traced(lambda: got.extend(pipeline_mod.verify_commits_pipelined(tasks, use_device=False)))
    assert raised is None
    assert [v.ok for v in got] == [False, True, True, True] and "(#%d)" % bad in str(got[0].error)
    routes = {(e["args"]["key_type"], e["args"]["route"]) for e in events if e["name"] == "batch_verify"}
    assert routes == {(kt, "host") for kt in KEY_TYPES}
    assert not [e for e in events if e["name"] in ("dispatch_chunk", "verify_batch")]
