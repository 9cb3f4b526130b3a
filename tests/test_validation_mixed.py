"""``verify_commit`` over a validator set that holds all three key
types (BASELINE config 5): the ed25519 and sr25519 lanes each on their
device sub-batch, the secp256k1 lanes on the host under ``host_lanes``,
the commit never sent to single verification; verdicts and blame those
of the three host oracles, lane for lane."""

import pytest

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto import hashing
from tendermint_tpu.crypto.ed25519_ref import verify_zip215
from tendermint_tpu.crypto.keys import Secp256k1PubKey
from tendermint_tpu.crypto.sr25519 import Sr25519BatchVerifier, verify as verify_sr
from tendermint_tpu.libs.metrics import OpsMetrics, Registry
from tendermint_tpu.ops import device_policy, precompute
from tendermint_tpu.types import validation
from tests.helpers import CHAIN_ID, make_block_id, make_commit, make_mixed_validators
from tests.helpers import traced as traced_catching

N_ED, N_SR, N_SECP = 20, 18, 3


@pytest.fixture(scope="module")
def mixed():
    privs, vset = make_mixed_validators(N_ED, N_SR, N_SECP)
    block_id = make_block_id(b"mixed-committee")
    return privs, vset, block_id


def traced(fn):
    return traced_catching(fn, catch=validation.InvalidCommitError)


def in_order(events):
    """By start, an enclosing span before what it holds."""
    return sorted(events, key=lambda e: (e["ts"], -e["dur"]))


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
    assert host["args"]["impl"] == hashing.host_secp256k1_impl()
    # one batch_verify / verify_batch a phase for a device sub-batch, in
    # the order the phases run; the host lanes have no second phase
    routes = [(e["args"]["key_type"], e["args"]["lanes"], e["args"]["route"], e["args"].get("phase"))
              for e in in_order(events) if e["name"] == "batch_verify"]
    assert routes == [
        ("ed25519", N_ED, "device", "dispatch"), ("sr25519", N_SR, "device", "dispatch"),
        ("secp256k1", N_SECP, "host", None),
        ("ed25519", N_ED, "device", "collect"), ("sr25519", N_SR, "device", "collect"),
    ]
    engines = [(e["args"]["engine"], e["args"]["lanes"], e["args"]["phase"], e["args"]["parent"])
               for e in in_order(events) if e["name"] == "verify_batch"]
    assert engines == [
        ("ed25519", N_ED, "dispatch", "batch_verify"), ("sr25519", N_SR, "dispatch", "batch_verify"),
        ("ed25519", N_ED, "collect", "batch_verify"), ("sr25519", N_SR, "collect", "batch_verify"),
    ]
    assert all(e["args"]["proc_cpu_us"] >= 0 for e in events if e["name"] == "verify_batch")
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
    raised, events = traced(lambda: validation.verify_commit(CHAIN_ID, vset, block_id, 3, commit))
    assert raised is None
    (host,) = [e for e in events if e["name"] == "host_lanes"]
    assert (host["args"]["lanes"], host["args"]["device_lanes_inflight"]) == (4, 0)
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


# --- a mixed batch in phases (ISSUE 41) ---------------------------------------


@pytest.fixture
def verdict_cache(monkeypatch):
    """On, as a node runs it (the suite pins it off: conftest.py)."""
    monkeypatch.setenv(precompute._RESULT_ENV, "1")
    return precompute.results


def test_a_mixed_commit_dispatches_both_sub_batches_then_verifies_the_host_lanes_then_collects(
    mixed, verdict_cache
):
    privs, vset, block_id = mixed
    commit = make_commit(block_id, 12, 0, vset, privs)
    raised, events = traced(lambda: validation.verify_commit(CHAIN_ID, vset, block_id, 12, commit))
    assert raised is None
    (host,) = [e for e in events if e["name"] == "host_lanes"]
    sent = [e for e in events if e["name"] == "dispatch_chunk"]
    got = [e for e in events if e["name"] == "collect_chunk"]
    assert {e["args"]["engine"] for e in sent} == {e["args"]["engine"] for e in got} == {"ed25519", "sr25519"}
    assert all(e["ts"] + e["dur"] <= host["ts"] for e in sent)
    assert all(host["ts"] + host["dur"] <= e["ts"] for e in got)
    assert host["args"]["device_lanes_inflight"] == N_ED + N_SR
    assert sum(e["args"]["lanes"] for e in got) == N_ED + N_SR
    # no span of a phase holds the host lanes, and none overlaps another's
    phases = [e for e in events if e["name"] in ("batch_verify", "verify_batch") and "phase" in e["args"]]
    assert all(e["ts"] + e["dur"] <= host["ts"] or host["ts"] + host["dur"] <= e["ts"] for e in phases)
    outer = in_order(e for e in events if e["name"] == "batch_verify")
    assert all(a["ts"] + a["dur"] <= b["ts"] for a, b in zip(outer, outer[1:]))
    # the store stays behind the device's answer, the merges behind the store
    names = [e["name"] for e in in_order(events)]
    assert names.index("host_lanes") < names.index("collect_chunk") < names.index("cache_store")
    assert names.index("cache_store") < names.index("merge_results") < names.index("merge_verdicts")


def commit_lanes(vset, commit):
    return [
        (val.pub_key, commit.vote_sign_bytes(CHAIN_ID, i), commit.signatures[i].signature)
        for i, val in enumerate(vset.validators)
    ]


def multi_of(lanes):
    bv = crypto_batch.MultiBatchVerifier()
    for lane in lanes:
        bv.add(*lane)
    return bv


def back_to_back(bv):
    """Each sub-verifier's ``begin().finish()`` one after the other, in
    the order a mixed call begins them, merged as ``verify()`` merges:
    the serial order, made of the phased call's own two steps."""
    order = sorted(bv._subs, key=lambda kt: (isinstance(bv._subs[kt], crypto_batch.HostLanesVerifier), kt))
    results = {kt: iter(bv._subs[kt].begin().finish()[1]) for kt in order}
    return [next(results[kt]) for kt in bv._order]


@pytest.mark.parametrize("tampered", [None, "ed25519", "sr25519", "secp256k1"])
def test_phased_and_back_to_back_give_the_same_verdicts_and_the_same_verdict_cache(
    mixed, verdict_cache, tampered
):
    privs, vset, block_id = mixed
    commit = make_commit(block_id, 13, 0, vset, privs)
    lane = None
    if tampered is not None:
        lane = lanes_of(vset, tampered)[2]
        sig = bytearray(commit.signatures[lane].signature)
        sig[33] ^= 0x04
        commit.signatures[lane].signature = bytes(sig)
    lanes = commit_lanes(vset, commit)
    left = {}
    for name, run in (("phased", lambda bv: bv.verify()[1]), ("back_to_back", back_to_back)):
        verdict_cache.clear()
        verdicts = run(multi_of(lanes))
        left[name] = (verdicts, list(verdict_cache._entries.items()))
    assert left["phased"] == left["back_to_back"]
    verdicts, cached = left["phased"]
    assert verdicts == [i != lane for i in range(len(lanes))]
    # ed25519 verdicts alone enter the cache, the tampered lane's as False
    assert len(cached) == N_ED
    assert [v for _, v in cached].count(False) == (1 if tampered == "ed25519" else 0)


class Boom(Exception):
    pass


@pytest.mark.parametrize("health_state", ["healthy", "half_open"])
@pytest.mark.parametrize("fault", ["host_lane_raises", "second_begin_raises"])
def test_a_phase_that_raises_leaves_nothing_in_flight_and_no_probe_latched(
    mixed, monkeypatch, fault, health_state
):
    """The handles already begun are finished on the way out: the
    in-flight gauge is back at 0, the one-prober latch is free (with the
    device half open the ed25519 sub-batch holds it, and the sr25519
    sub-batch is the host oracle's, as a second caller's would be), and
    the caller sees the first exception."""
    privs, vset, block_id = mixed
    commit = make_commit(block_id, 14, 0, vset, privs)
    now = [1000.0]
    health = device_policy.DeviceHealth(retry_budget=1, cooldown_base=1.0, clock=lambda: now[0])
    ops_metrics = OpsMetrics(Registry())
    health.bind_metrics(ops_metrics)
    monkeypatch.setattr(device_policy, "shared", health)
    if health_state == "half_open":
        health.record_failure(RuntimeError("UNAVAILABLE: planted"), health.begin_attempt("ed25519"))
        assert health.state == device_policy.COOLDOWN
        now[0] += 5.0
    if fault == "host_lane_raises":
        def verify_many(pub_keys, msgs, sigs):  # where the host lanes go since PR 49
            raise Boom("host lane")
        monkeypatch.setattr(Secp256k1PubKey, "verify_many", staticmethod(verify_many))
    else:
        def begin(self):
            raise Boom("second begin")
        monkeypatch.setattr(Sr25519BatchVerifier, "begin", begin)
    began = []
    begin_ed = crypto_batch.Ed25519BatchVerifier.begin
    monkeypatch.setattr(
        crypto_batch.Ed25519BatchVerifier, "begin",
        lambda self: began.append(health.snapshot()["probe_inflight"]) or begin_ed(self),
    )
    bv = multi_of(commit_lanes(vset, commit))
    with pytest.raises(Boom):
        bv.verify()
    assert began == [False]  # the ed25519 sub-batch was begun, once
    assert health.snapshot()["probe_inflight"] is False
    # the gauge went up, and came back
    assert ops_metrics.inflight_lanes.collect()[0] == 'tendermint_ops_inflight_lanes{engine="ed25519"} 0'
    assert all(line.endswith(" 0") for line in ops_metrics.inflight_lanes.collect())
    assert health.state == device_policy.HEALTHY  # the ed25519 chunk round-tripped


PINNED = {  # the parent's (PR 40) list for a warm commit of one key type, by start
    "ed25519": [
        "verify_commit", "note_validator_set", "build_lanes", "batch_verify", "verify_batch",
        "cache_lookup", "gather_tables", "route_lanes", "prep_chunk", "dispatch_chunk",
        "collect_chunk", "cache_store", "merge_results", "merge_verdicts",
    ],
    "sr25519": [
        "verify_commit", "note_validator_set", "build_lanes", "batch_verify", "verify_batch",
        "prep_chunk", "merlin_challenge", "dispatch_chunk", "collect_chunk", "merge_results",
        "merge_verdicts",
    ],
    "secp256k1": [
        "verify_commit", "note_validator_set", "build_lanes", "batch_verify", "host_lanes",
        "merge_verdicts",
    ],
}


@pytest.mark.parametrize(
    "key_type,shape", [("ed25519", (20, 0, 0)), ("sr25519", (0, 18, 0)), ("secp256k1", (0, 0, 3))]
)
def test_a_commit_of_one_key_type_records_the_spans_it_always_did(verdict_cache, key_type, shape):
    """The control for the cells that build one sub-verifier: their call
    is that sub-verifier's ``verify()``, one ``batch_verify`` and one
    ``verify_batch`` with no ``phase``."""
    privs, vset = make_mixed_validators(*shape)
    block_id = make_block_id(b"pinned-" + key_type.encode())
    warm = make_commit(block_id, 7, 0, vset, privs)
    validation.verify_commit(CHAIN_ID, vset, block_id, 7, warm)  # a shape's first call compiles
    commit = make_commit(block_id, 8, 0, vset, privs)
    raised, events = traced(lambda: validation.verify_commit(CHAIN_ID, vset, block_id, 8, commit))
    assert raised is None
    assert [e["name"] for e in in_order(events)] == PINNED[key_type]
    assert not any("phase" in e["args"] for e in events)


# --- a mixed commit built a block at a time (ISSUE 45) ---------------------------

import copy  # noqa: E402

from tendermint_tpu.ops import ed25519_batch  # noqa: E402
from tests.helpers import lane_by_lane_commit_batch, outcome, record_verifier  # noqa: E402

JOB = 16  # lanes an engine job, stood in: 20 ed25519 and 18 sr25519 lanes each fill one


@pytest.fixture
def blocks(monkeypatch, mixed):
    monkeypatch.setattr(ed25519_batch, "job_lanes", lambda: JOB)
    monkeypatch.setattr(ed25519_batch, "_ENGINES_WITH_A_JOB_RUN", {"ed25519", "sr25519"})
    crypto_batch.note_validator_set(mixed[1])
    precompute.tables.gather([v.pub_key.bytes() for v in mixed[1].validators if v.pub_key.type == "ed25519"])


def _entries(vset, block_id, commit):
    return {
        "verify_commit": lambda: validation.verify_commit(CHAIN_ID, vset, block_id, 14, commit),
        "verify_commit_light": lambda: validation.verify_commit_light(CHAIN_ID, vset, block_id, 14, commit),
        "verify_commit_light_trusting": lambda: validation.verify_commit_light_trusting(
            CHAIN_ID, vset, commit, validation.Fraction(9, 10)
        ),
    }


@pytest.fixture(scope="module")
def mixed_commit(mixed):
    privs, vset, block_id = mixed
    return make_commit(block_id, 14, 0, vset, privs, absent={4}, nil_votes={11})


@pytest.mark.parametrize("bad", [None, ("ed25519", 1), ("ed25519", -1), ("sr25519", 0), ("sr25519", -1),
                                 ("secp256k1", 0), ("secp256k1", -1), "one_of_each"], ids=str)
@pytest.mark.parametrize("entry", ["verify_commit", "verify_commit_light", "verify_commit_light_trusting"])
def test_a_mixed_commit_in_blocks_is_the_lane_by_lane_commit(monkeypatch, mixed, mixed_commit, blocks, entry, bad):
    """The three key types interleave by address: which sub-verifier
    fills its job first, and after which lane, is the verifier's to
    say. The block-wise loop hands over the same lanes, begins the
    same jobs after the same lanes and names the same lane as the
    lane-by-lane loop, whichever type the refused signature is of."""
    _, vset, block_id = mixed
    commit = copy.deepcopy(mixed_commit)
    picks = [(kt, 1) for kt in ("ed25519", "sr25519", "secp256k1")] if bad == "one_of_each" else [bad] if bad else []
    lanes = [[i for i in lanes_of(vset, kt) if i not in (4, 11)][at] for kt, at in picks]
    for lane in lanes:
        sig = bytearray(commit.signatures[lane].signature)
        sig[40] ^= 0x01
        commit.signatures[lane].signature = bytes(sig)
    call = _entries(vset, block_id, commit)[entry]
    said = record_verifier(monkeypatch)
    raised, new = outcome(call), list(said)
    del said[:]
    monkeypatch.setattr(validation, "_verify_commit_batch", lane_by_lane_commit_batch)
    assert (outcome(call), list(said)) == (raised, new)
    handed = [step for step in new if step[0] == "lane"]
    if entry == "verify_commit":
        assert len(handed) == N_ED + N_SR + N_SECP - 1
        assert [step for step in new if step[0] == "begun"] == [("begun", JOB), ("begun", JOB)]
        if lanes:
            assert raised == ("InvalidCommitError", "wrong signature (#%d): %s" % (
                min(lanes), commit.signatures[min(lanes)].signature.hex().upper()))
    if not lanes:
        assert raised is None


def test_room_says_which_sub_verifier_fills_its_job_first(monkeypatch, mixed, blocks):
    """``room`` over the keys to come: the lane that gives a device
    sub-verifier its sixteenth, however the types interleave, and more
    than there are where no job fills; after ``add_many`` of that
    block the verifier is ready, and not a lane sooner."""
    privs, vset, block_id = mixed
    keys = [v.pub_key for v in vset.validators]
    types = [key.type for key in keys]
    bv = crypto_batch.MultiBatchVerifier()
    first = {kt: [i for i, t in enumerate(types) if t == kt][JOB - 1] + 1 for kt in ("ed25519", "sr25519")}
    assert bv.room(keys) == min(first.values())
    assert bv.room(keys[: min(first.values()) - 1]) > len(keys)  # one lane short: no job fills
    assert bv.room([k for k in keys if k.type == "secp256k1"]) > len(keys)  # host lanes fill none
    assert bv.room([]) > len(keys)
    lanes = commit_lanes(vset, make_commit(block_id, 15, 0, vset, privs))
    cut = bv.room(keys)
    bv.add_many(*zip(*lanes[: cut - 1]))
    assert not bv.ready and bv.room(keys[cut - 1:]) == 1
    bv.add_many(*zip(*lanes[cut - 1: cut]))
    assert bv.ready and bv.begin_ready() == JOB
    # the other type's job fills next, counted from where the block ended
    assert cut + bv.room(keys[cut:]) == max(first.values())
    bv.close()


# --- the host lanes in one native call (ISSUE 49) ------------------------------


@pytest.fixture(params=["native", "openssl"])
def secp_impl(request, monkeypatch):
    """The process with the native library, and as it is on a machine
    with no C compiler: no library, OpenSSL lane by lane."""
    if request.param == "openssl":
        monkeypatch.setattr(hashing, "_LIB", None)
        monkeypatch.setattr(hashing, "_LIB_TRIED", True)
    assert hashing.host_secp256k1_impl() == request.param
    return request.param


def _tamper(commit, lanes):
    for lane in lanes:
        sig = bytearray(commit.signatures[lane].signature)
        sig[7] ^= 0x10
        commit.signatures[lane].signature = bytes(sig)


@pytest.mark.parametrize("bad", [(), (0,), (1, 2), (0, 1, 2)], ids=["none", "first", "last_two", "all"])
def test_the_host_lanes_give_one_verdict_list_whoever_verifies(mixed, secp_impl, bad):
    """``HostLanesVerifier.verify``: the verdict of every lane is its
    key's own ``verify_signature`` and OpenSSL's, with the library and
    without, and the span says which answered."""
    privs, vset, block_id = mixed
    commit = make_commit(block_id, 16, 0, vset, privs)
    secp = lanes_of(vset, "secp256k1")
    _tamper(commit, [secp[i] for i in bad])
    lanes = [lane for lane in commit_lanes(vset, commit) if lane[0].type == "secp256k1"]
    bv = crypto_batch.HostLanesVerifier("secp256k1")
    bv.add_many(*zip(*lanes))
    got, events = traced(lambda: bv.verify(device_lanes_inflight=5))
    assert got is None  # traced() returns what was raised
    (host,) = [e["args"] for e in events if e["name"] == "host_lanes"]
    assert host == dict(host, key_type="secp256k1", lanes=N_SECP, device_lanes_inflight=5, impl=secp_impl)
    want = [i not in bad for i in range(N_SECP)]
    assert bv.verify() == (not bad, want)
    assert [pk.verify_signature(msg, sig) for pk, msg, sig in lanes] == want
    assert [pk._verify_openssl(msg, sig) for pk, msg, sig in lanes] == want


@pytest.mark.parametrize("tampered", [(), ("secp256k1",), ("secp256k1", "ed25519"), ("sr25519", "secp256k1")],
                         ids=lambda t: "+".join(t) or "none")
def test_a_mixed_commit_names_the_same_first_bad_lane_whoever_verifies_the_host_lanes(
    mixed, secp_impl, tampered
):
    privs, vset, block_id = mixed
    commit = make_commit(block_id, 17, 0, vset, privs)
    bad = sorted(lanes_of(vset, key_type)[-1] for key_type in tampered)
    _tamper(commit, bad)
    lanes = commit_lanes(vset, commit)
    assert multi_of(lanes).verify() == (not bad, [i not in bad for i in range(len(lanes))])
    raised, events = traced(lambda: validation.verify_commit(CHAIN_ID, vset, block_id, 17, commit))
    if bad:
        assert "wrong signature (#%d)" % bad[0] in str(raised)
    else:
        assert raised is None
    (host,) = [e["args"] for e in events if e["name"] == "host_lanes"]
    assert (host["impl"], host["lanes"], host["device_lanes_inflight"]) == (secp_impl, N_SECP, N_ED + N_SR)


class _LoopKey(Secp256k1PubKey):
    """A key type with no batch form, as every type was before PR 49."""

    verify_many = None

    def verify_signature(self, msg, sig):
        return self._verify_openssl(msg, sig)


def test_a_key_class_without_a_batch_form_is_verified_lane_by_lane(mixed):
    privs, vset, block_id = mixed
    commit = make_commit(block_id, 18, 0, vset, privs)
    _tamper(commit, [lanes_of(vset, "secp256k1")[1]])
    lanes = [(_LoopKey(pk.bytes()), msg, sig) for pk, msg, sig in commit_lanes(vset, commit) if pk.type == "secp256k1"]
    calls = []
    bv = crypto_batch.HostLanesVerifier("secp256k1")
    bv.add_many(*zip(*lanes))
    got, events = traced(lambda: calls.append(bv.verify()))
    assert calls == [(False, [True, False, True])]
    (host,) = [e["args"] for e in events if e["name"] == "host_lanes"]
    assert "impl" not in host and host["lanes"] == N_SECP
    # and two classes under one type name are nobody's batch: lane by lane too
    both = crypto_batch.HostLanesVerifier("secp256k1")
    both.add_many(*zip(*(lanes[:1] + [lane for lane in commit_lanes(vset, commit) if lane[0].type == "secp256k1"][1:])))
    assert both.verify() == (False, [True, False, True])
