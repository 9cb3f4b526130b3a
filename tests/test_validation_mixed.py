"""``verify_commit`` over a validator set that holds all three key
types (BASELINE config 5): the ed25519 and sr25519 lanes each on their
device sub-batch, the secp256k1 lanes on the host under ``host_lanes``,
the commit never sent to single verification; verdicts and blame those
of the three host oracles, lane for lane."""

import pytest

from tendermint_tpu.crypto.ed25519_ref import verify_zip215
from tendermint_tpu.crypto.sr25519 import verify as verify_sr
from tendermint_tpu.libs import tracing
from tendermint_tpu.types import validation
from tests.helpers import CHAIN_ID, make_block_id, make_commit, make_mixed_validators

N_ED, N_SR, N_SECP = 20, 18, 3


@pytest.fixture(scope="module")
def mixed():
    privs, vset = make_mixed_validators(N_ED, N_SR, N_SECP)
    block_id = make_block_id(b"mixed-committee")
    return privs, vset, block_id


def traced(fn):
    tracing.tracer.set_metrics_observer(None)
    tracing.configure("ring")
    tracing.tracer.clear()
    try:
        raised = None
        try:
            fn()
        except validation.InvalidCommitError as exc:
            raised = exc
        events = [e for e in tracing.tracer.export(clear=True)["traceEvents"] if e.get("ph") == "X"]
    finally:
        tracing.configure("off")
        tracing.tracer.clear()
    return raised, events


def lanes_of(vset, key_type):
    return [i for i, v in enumerate(vset.validators) if v.pub_key.type == key_type]


def test_a_mixed_commit_is_accepted_on_the_batch_path(mixed):
    privs, vset, block_id = mixed
    commit = make_commit(block_id, 7, 0, vset, privs)
    raised, events = traced(lambda: validation.verify_commit(CHAIN_ID, vset, block_id, 7, commit))
    assert raised is None
    names = [e["name"] for e in events]
    assert "single_verify" not in names and "host_fallback" not in names
    (host,) = [e for e in events if e["name"] == "host_lanes"]
    assert (host["args"]["key_type"], host["args"]["lanes"]) == ("secp256k1", N_SECP)
    assert host["args"]["parent"] == "batch_verify"
    routes = {e["args"]["key_type"]: (e["args"]["lanes"], e["args"]["route"])
              for e in events if e["name"] == "batch_verify"}
    assert routes == {
        "ed25519": (N_ED, "device"), "sr25519": (N_SR, "device"), "secp256k1": (N_SECP, "host"),
    }
    engines = {e["args"]["engine"]: e["args"]["lanes"] for e in events if e["name"] == "verify_batch"}
    assert engines == {"ed25519": N_ED, "sr25519": N_SR}
    dispatched = sum(e["args"]["lanes"] for e in events if e["name"] == "dispatch_chunk")
    assert dispatched == N_ED + N_SR


@pytest.mark.parametrize("key_type", ["ed25519", "sr25519", "secp256k1"])
def test_a_bad_signature_of_each_type_is_blamed_on_its_lane(mixed, key_type):
    privs, vset, block_id = mixed
    commit = make_commit(block_id, 8, 0, vset, privs)
    lane = lanes_of(vset, key_type)[1]
    sig = bytearray(commit.signatures[lane].signature)
    sig[40] ^= 0x01
    commit.signatures[lane].signature = bytes(sig)
    raised, events = traced(lambda: validation.verify_commit(CHAIN_ID, vset, block_id, 8, commit))
    assert raised is not None and "wrong signature (#%d)" % lane in str(raised)
    names = {e["name"] for e in events}
    assert "host_lanes" in names and not names & {"single_verify", "host_fallback"}
    # every lane's verdict is its own oracle's
    oracle = {
        "ed25519": verify_zip215,
        "sr25519": verify_sr,
        "secp256k1": lambda pub, msg, sig: vset.validators[lane].pub_key.verify_signature(msg, sig),
    }[key_type]
    pub = vset.validators[lane].pub_key.bytes()
    assert not oracle(pub, commit.vote_sign_bytes(CHAIN_ID, lane), commit.signatures[lane].signature)


def test_a_secp256k1_proposer_does_not_send_the_commit_to_single_verification(mixed):
    privs, vset, block_id = mixed
    other = vset.copy()
    other.proposer = other.validators[lanes_of(other, "secp256k1")[0]]
    commit = make_commit(block_id, 9, 0, other, privs)
    raised, events = traced(lambda: validation.verify_commit(CHAIN_ID, other, block_id, 9, commit))
    assert raised is None
    assert "single_verify" not in {e["name"] for e in events}


def test_light_verification_stops_at_two_thirds_over_a_mixed_set(mixed):
    privs, vset, block_id = mixed
    commit = make_commit(block_id, 10, 0, vset, privs)
    raised, events = traced(
        lambda: validation.verify_commit_light(CHAIN_ID, vset, block_id, 10, commit)
    )
    assert raised is None
    (loop,) = [e for e in events if e["name"] == "build_lanes"]
    assert loop["args"]["lanes"] == (N_ED + N_SR + N_SECP) * 2 // 3 + 1


def test_a_set_of_secp256k1_keys_alone_is_verified_as_host_lanes():
    privs, vset = make_mixed_validators(0, 0, 4)
    block_id = make_block_id(b"secp-only")
    commit = make_commit(block_id, 3, 0, vset, privs)
    validation.verify_commit(CHAIN_ID, vset, block_id, 3, commit)
    sig = bytearray(commit.signatures[2].signature)
    sig[5] ^= 0x01
    commit.signatures[2].signature = bytes(sig)
    with pytest.raises(validation.InvalidCommitError, match=r"wrong signature \(#2\)"):
        validation.verify_commit(CHAIN_ID, vset, block_id, 3, commit)


def test_a_malformed_ed25519_entry_still_takes_the_single_path(mixed):
    """What is left of the fallback: an entry its own verifier refuses
    to take (a 63-byte ed25519 signature) sends the commit to single
    verification, which blames that lane."""
    privs, vset, block_id = mixed
    commit = make_commit(block_id, 11, 0, vset, privs)
    lane = lanes_of(vset, "ed25519")[0]
    commit.signatures[lane].signature = commit.signatures[lane].signature[:63]
    raised, events = traced(lambda: validation.verify_commit(CHAIN_ID, vset, block_id, 11, commit))
    assert raised is not None and "#%d" % lane in str(raised)
    assert "single_verify" in {e["name"] for e in events}
