"""VerifyCommit family tests (mirrors types/validation_test.go).

Covers the batch path (>=16 sigs routes to the device kernel) and the
single-verify path, absent/nil handling, fault attribution, and the
trusting variant's by-address lookup with double-sign detection.
"""

import pytest

from tendermint_tpu.types import (
    Fraction,
    InvalidCommitError,
    NotEnoughVotingPowerError,
    verify_commit,
    verify_commit_light,
    verify_commit_light_trusting,
)
from tendermint_tpu.types.validation import _verify_commit_single, _verify_commit_batch
from tests.helpers import CHAIN_ID, make_block_id, make_commit, make_validators


@pytest.fixture(scope="module")
def small_net():
    privs, vset = make_validators(4)
    return privs, vset


class TestVerifyCommit:
    def test_valid(self, small_net):
        privs, vset = small_net
        bid = make_block_id()
        commit = make_commit(bid, 5, 0, vset, privs)
        verify_commit(CHAIN_ID, vset, bid, 5, commit)

    def test_wrong_height(self, small_net):
        privs, vset = small_net
        bid = make_block_id()
        commit = make_commit(bid, 5, 0, vset, privs)
        with pytest.raises(InvalidCommitError, match="height"):
            verify_commit(CHAIN_ID, vset, bid, 6, commit)

    def test_wrong_block_id(self, small_net):
        privs, vset = small_net
        bid = make_block_id()
        commit = make_commit(bid, 5, 0, vset, privs)
        with pytest.raises(InvalidCommitError, match="block ID"):
            verify_commit(CHAIN_ID, vset, make_block_id(b"other"), 5, commit)

    def test_wrong_set_size(self, small_net):
        privs, vset = small_net
        bid = make_block_id()
        commit = make_commit(bid, 5, 0, vset, privs)
        commit.signatures = commit.signatures[:-1]
        with pytest.raises(InvalidCommitError, match="set size"):
            verify_commit(CHAIN_ID, vset, bid, 5, commit)

    def test_insufficient_power(self, small_net):
        privs, vset = small_net
        bid = make_block_id()
        # 2 of 4 absent: 20/40 power < 2/3
        commit = make_commit(bid, 5, 0, vset, privs, absent={0, 1})
        with pytest.raises(NotEnoughVotingPowerError):
            verify_commit(CHAIN_ID, vset, bid, 5, commit)

    def test_bad_signature_attributed(self, small_net):
        privs, vset = small_net
        bid = make_block_id()
        commit = make_commit(bid, 5, 0, vset, privs)
        commit.signatures[2].signature = b"\x01" * 64
        with pytest.raises(InvalidCommitError, match=r"#2"):
            verify_commit(CHAIN_ID, vset, bid, 5, commit)

    def test_nil_votes_counted_but_not_tallied(self, small_net):
        privs, vset = small_net
        bid = make_block_id()
        # 3 commit votes (30/40 > 2/3) + 1 nil vote — still valid, and the
        # nil vote's signature is still checked (flag != absent).
        commit = make_commit(bid, 5, 0, vset, privs, nil_votes={3})
        verify_commit(CHAIN_ID, vset, bid, 5, commit)
        commit.signatures[3].signature = b"\x02" * 64
        with pytest.raises(InvalidCommitError, match=r"#3"):
            verify_commit(CHAIN_ID, vset, bid, 5, commit)

    def test_large_batch_path(self):
        # 20 validators -> routed through the device kernel (threshold 16).
        privs, vset = make_validators(20)
        bid = make_block_id()
        commit = make_commit(bid, 9, 0, vset, privs)
        verify_commit(CHAIN_ID, vset, bid, 9, commit)
        commit.signatures[17].signature = bytes(64)
        with pytest.raises(InvalidCommitError, match=r"#17"):
            verify_commit(CHAIN_ID, vset, bid, 9, commit)


class TestVerifyCommitLight:
    def test_ignores_nil_votes(self, small_net):
        privs, vset = small_net
        bid = make_block_id()
        commit = make_commit(bid, 5, 0, vset, privs, nil_votes={3})
        # Corrupt the nil vote signature: light verification ignores it.
        commit.signatures[3].signature = b"\x02" * 64
        verify_commit_light(CHAIN_ID, vset, bid, 5, commit)

    def test_insufficient(self, small_net):
        privs, vset = small_net
        bid = make_block_id()
        commit = make_commit(bid, 5, 0, vset, privs, nil_votes={0, 1})
        with pytest.raises(NotEnoughVotingPowerError):
            verify_commit_light(CHAIN_ID, vset, bid, 5, commit)


class TestVerifyCommitLightTrusting:
    def test_same_valset(self, small_net):
        privs, vset = small_net
        bid = make_block_id()
        commit = make_commit(bid, 5, 0, vset, privs)
        verify_commit_light_trusting(CHAIN_ID, vset, commit, Fraction(1, 3))

    def test_overlapping_valset(self):
        # Trusted set = first 6 of 8 signers; 6*10 > 80/3.
        privs, vset = make_validators(8)
        bid = make_block_id()
        commit = make_commit(bid, 5, 0, vset, privs)
        from tendermint_tpu.types import Validator, ValidatorSet

        subset = ValidatorSet([v.copy() for v in vset.validators[:6]])
        verify_commit_light_trusting(CHAIN_ID, subset, commit, Fraction(1, 3))

    def test_disjoint_valset_fails(self, small_net):
        privs, vset = small_net
        bid = make_block_id()
        commit = make_commit(bid, 5, 0, vset, privs)
        other_privs, other_vset = make_validators(4, power=7)
        # same addresses? No: same seeds produce same keys — use offset seeds
        from tendermint_tpu.crypto.keys import Ed25519PrivKey
        from tendermint_tpu.types import Validator, ValidatorSet

        vals = [
            Validator(Ed25519PrivKey.from_seed(bytes([99 + i]) * 32).pub_key(), 10)
            for i in range(4)
        ]
        disjoint = ValidatorSet(vals)
        with pytest.raises(NotEnoughVotingPowerError):
            verify_commit_light_trusting(CHAIN_ID, disjoint, commit, Fraction(1, 3))

    def test_zero_denominator(self, small_net):
        privs, vset = small_net
        commit = make_commit(make_block_id(), 5, 0, vset, privs)
        with pytest.raises(InvalidCommitError, match="Denominator"):
            verify_commit_light_trusting(CHAIN_ID, vset, commit, Fraction(1, 0))


class TestBatchSingleEquivalence:
    """The batch path must agree with the single path on every input."""

    def test_agreement_on_valid_and_invalid(self):
        privs, vset = make_validators(6)
        bid = make_block_id()
        for corrupt in (None, 0, 5):
            commit = make_commit(bid, 3, 0, vset, privs, absent={2})
            if corrupt is not None and corrupt != 2:
                commit.signatures[corrupt].signature = b"\x03" * 64
            needed = vset.total_voting_power() * 2 // 3
            ignore = lambda c: c.block_id_flag == 1
            count = lambda c: c.block_id_flag == 2
            results = []
            for fn in (_verify_commit_single, _verify_commit_batch):
                try:
                    fn(CHAIN_ID, vset, commit, needed, ignore, count, True, True)
                    results.append(None)
                except Exception as e:
                    results.append(type(e).__name__)
            assert results[0] == results[1], f"corrupt={corrupt}: {results}"


# --- the block-wise loop against the lane-by-lane one it replaced (ISSUE 45) -----------

import copy

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.ops import ed25519_batch, precompute
from tendermint_tpu.types import Validator, ValidatorSet, validation
from tests.helpers import lane_by_lane_commit_batch, outcome, record_verifier

JOB = 16  # lanes an engine job, stood in: a 40-lane commit is three blocks
N_VALS = 40


@pytest.fixture(scope="module")
def net():
    privs, vset = make_validators(N_VALS)
    block_id = make_block_id(b"issue-45")
    return privs, vset, block_id, make_commit(block_id, 7, 0, vset, privs, absent={2, 17}, nil_votes={5, 30})


@pytest.fixture
def blocks(monkeypatch, net):
    """A node that has verified a commit of the set, at a job of 16
    lanes: every full job is begun early, as on the chip at 4,096."""
    monkeypatch.setattr(ed25519_batch, "job_lanes", lambda: JOB)
    monkeypatch.setattr(ed25519_batch, "_ENGINES_WITH_A_JOB_RUN", {"ed25519", "sr25519"})
    crypto_batch.note_validator_set(net[1])
    precompute.tables.gather([v.pub_key.bytes() for v in net[1].validators])


def _tampered(commit, idx):
    sig = bytearray(commit.signatures[idx].signature)
    sig[33] ^= 0x04
    commit.signatures[idx].signature = bytes(sig)


def _malformed(commit, idx):
    commit.signatures[idx].signature = commit.signatures[idx].signature[:63]


def _double_vote(commit, idx, of):
    commit.signatures[idx] = commit.signatures[of]


def _unknown_flag(commit, idx):
    commit.signatures[idx].block_id_flag = 7


# name -> what is done to a copy of the sound commit (lanes: every entry but 2 and 17)
SCENARIOS = {
    "sound": [],
    "bad_first_lane": [(_tampered, 0)],
    "bad_last_lane_of_block_1": [(_tampered, 16)],  # lane 15: entries 2 is absent
    "bad_first_lane_of_block_2": [(_tampered, 18)],  # lane 16: entries 2 and 17 are absent
    "bad_last_lane": [(_tampered, 39)],
    "two_bad_lanes": [(_tampered, 33), (_tampered, 9)],
    "malformed": [(_malformed, 21)],
    "malformed_in_block_1_bad_before_it": [(_malformed, 12), (_tampered, 4)],
    "double_vote": [(_double_vote, 20, 3)],
    "double_vote_then_malformed": [(_double_vote, 12, 3), (_malformed, 31)],
    "malformed_then_double_vote": [(_malformed, 10), (_double_vote, 20, 3)],
    "unknown_flag": [(_unknown_flag, 25)],
    "malformed_then_unknown_flag": [(_malformed, 10), (_unknown_flag, 25)],
    "unknown_flag_then_malformed": [(_unknown_flag, 10), (_malformed, 25)],
    "bad_then_unknown_flag": [(_tampered, 3), (_unknown_flag, 25)],
    "unknown_flag_in_a_nil_vote_seat": [(_unknown_flag, 5), (_double_vote, 36, 1)],
}
# what is done to the set the commit is held against
SETS = {
    "its_own": lambda vset: vset,
    "a_third_of_it": lambda vset: ValidatorSet([v.copy() for v in vset.validators[4::3]]),
    "heavy_seats": lambda vset: ValidatorSet(
        [Validator(v.pub_key, 1000 if i < 2 else 1) for i, v in enumerate(vset.validators)]  # the order stays
    ),
}


def _entry(name, vset, block_id, commit):
    if name == "verify_commit":
        return lambda: verify_commit(CHAIN_ID, vset, block_id, 7, commit)
    if name == "verify_commit_light":
        return lambda: verify_commit_light(CHAIN_ID, vset, block_id, 7, commit)
    return lambda: verify_commit_light_trusting(CHAIN_ID, vset, commit, Fraction(1, 3))


ENTRIES = ["verify_commit", "verify_commit_light", "verify_commit_light_trusting"]


def _both_ways(monkeypatch, call):
    """``call`` through the block-wise loop and through the lane-by-lane
    one: what each raised and what each handed its verifier."""
    said = record_verifier(monkeypatch)
    new = outcome(call), list(said)
    del said[:]
    monkeypatch.setattr(validation, "_verify_commit_batch", lane_by_lane_commit_batch)
    old = outcome(call), list(said)
    return new, old


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("entry", ENTRIES)
def test_the_blockwise_loop_hands_over_what_the_lane_by_lane_loop_did(monkeypatch, net, blocks, entry, scenario):
    """Same lanes in the same order, the same jobs begun after the same
    lanes, the same way out: error by type and message, whichever of two
    faults comes first, ``verify()`` reached or not, ``close()`` last."""
    _, vset, block_id, sound = net
    commit = copy.deepcopy(sound)
    for fault, *at in SCENARIOS[scenario]:
        fault(commit, *at)
    (raised, said), (raised_old, said_old) = _both_ways(monkeypatch, _entry(entry, vset, block_id, commit))
    assert raised == raised_old
    if said != said_old:
        # only where the verifier refused a lane: it was handed that lane's block whole, the
        # lanes behind the refused one too, and the commit went to single verification all the same
        assert "malformed" in scenario and ("verify",) not in said + said_old
        lanes, lanes_old = ([step for step in steps if step[0] == "lane"] for steps in (said, said_old))
        assert lanes[: len(lanes_old)] == lanes_old and len(lanes[len(lanes_old):]) < JOB
        assert len(lanes_old[-1][3]) == 63  # the last lane the old loop handed over is the refused one
        assert [step for step in said if step[0] != "lane"] == [step for step in said_old if step[0] != "lane"]
    assert said[-1] == ("close",)
    if scenario == "sound":
        lanes = [step for step in said if step[0] == "lane"]
        # every entry but the absent two; 27 of 40 seats pass 2/3, 14 a third (the nil votes lie among them)
        want = {"verify_commit": 38, "verify_commit_light": 27, "verify_commit_light_trusting": 14}
        assert raised is None and len(lanes) == want[entry]
        assert (("begun", JOB) in said) == (want[entry] > JOB)


@pytest.mark.parametrize("of", list(SETS)[1:])
@pytest.mark.parametrize("entry", ENTRIES)
def test_the_blockwise_loop_tallies_and_skips_as_the_lane_by_lane_loop_did(monkeypatch, net, blocks, entry, of):
    """Against another set than the commit's own: seats of unknown
    address are skipped on the by-address path (the other two refuse the
    set's size before any lane), and two heavy seats end a light rule's
    lanes at once."""
    _, vset, block_id, commit = net
    (raised, said), (raised_old, said_old) = _both_ways(monkeypatch, _entry(entry, SETS[of](vset), block_id, commit))
    assert (raised, said) == (raised_old, said_old)
    lanes = [step for step in said if step[0] == "lane"]
    if of == "heavy_seats":  # 2,038 of power: seat 0 alone passes a third, seats 0 and 1 two thirds
        want = {"verify_commit": 38, "verify_commit_light": 2, "verify_commit_light_trusting": 1}
        assert raised is None and len(lanes) == want[entry]
    if of == "a_third_of_it" and entry == "verify_commit_light_trusting":
        assert raised is None and 0 < len(lanes) <= 12


@pytest.mark.parametrize("entry", ENTRIES)
def test_not_enough_power_is_raised_before_any_verify(monkeypatch, net, blocks, entry):
    privs, vset, block_id, _ = net
    commit = make_commit(block_id, 7, 0, vset, privs, nil_votes=set(range(0, N_VALS, 2)))
    if entry == "verify_commit_light_trusting":  # a third is there: ask for more
        call = lambda: verify_commit_light_trusting(CHAIN_ID, vset, commit, Fraction(2, 3))
    else:
        call = _entry(entry, vset, block_id, commit)
    (raised, said), old = _both_ways(monkeypatch, call)
    assert (raised, said) == old
    assert raised[0] == "NotEnoughVotingPowerError" and "got 200, needed more than 266" in raised[1]
    assert ("verify",) not in said and said[-1] == ("close",)
    assert ("begun", JOB) in said  # a job was on the device by then: collected by close()
