"""The early begin (ISSUE 44): a device batch verifier hands each full
engine job of lanes to the engine as soon as it holds them, and
``verify()`` begins the rest and finishes every block in the order
begun. The answer is one ``verify()``'s, lane for lane; what changes is
when the device starts. The job is the engine's accessor's
(``ops.ed25519_batch.job_lanes``: 4,096 lanes a device), stood in here
at 16 so that the CPU's 64-lane kernels serve."""

import gc
import re
import time

import numpy as np
import pytest

from chipbench.readers import call_path
from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto.keys import Ed25519PrivKey, Secp256k1PrivKey
from tendermint_tpu.crypto.sr25519 import Sr25519BatchVerifier, Sr25519PrivKey
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.metrics import OpsMetrics, Registry
from tendermint_tpu.ops import device_policy, ed25519_batch, fault_injection, precompute
from tendermint_tpu.parallel import mesh
from tendermint_tpu.types import validation
from tests.helpers import (
    CHAIN_ID,
    REAL_BENCH,
    lane_by_lane_commit_batch,
    make_block_id,
    make_commit,
    make_validators,
    outcome,
    read,
    record_verifier,
    traced,
)

JOB = 16  # lanes a job, stood in; crypto.batch.DEVICE_THRESHOLD, so the route says device by then
SIZES = [1, JOB - 1, JOB, JOB + 1, 2 * JOB + 5]
MOST = max(SIZES)
NO_JOB = 1 << 30  # a job no batch fills: the one-shot verify() of before


def jobs_have_run(monkeypatch):
    """Both engines as in a process whose first full job is behind it
    (until then a batch goes whole: the first launch compiles)."""
    monkeypatch.setattr(ed25519_batch, "_ENGINES_WITH_A_JOB_RUN", {"ed25519", "sr25519"})


@pytest.fixture
def job(monkeypatch):
    monkeypatch.setattr(ed25519_batch, "job_lanes", lambda: JOB)
    jobs_have_run(monkeypatch)
    return JOB


def seams(n):
    """The lanes on both sides of every block seam of an ``n``-lane
    batch, and its two ends."""
    return sorted({i for i in (0, n - 1, *(s + d for s in range(JOB, n, JOB) for d in (-1, 0))) if 0 <= i < n})


def _signed(privs, tag):
    lanes = []
    for i, priv in enumerate(privs):
        msg = b"%s lane %d" % (tag, i) + b"." * (i % 3)
        lanes.append((priv.pub_key(), msg, priv.sign(msg)))
    return lanes


@pytest.fixture(scope="module")
def signed():
    """``MOST`` signed lanes of each key type, signed once."""
    return {
        "ed25519": _signed([Ed25519PrivKey.from_seed(b"early-%026d" % i) for i in range(MOST)], b"ed"),
        "sr25519": _signed([Sr25519PrivKey.from_secret(b"early-sr %d" % i) for i in range(MOST)], b"sr"),
        "secp256k1": _signed([Secp256k1PrivKey(bytes([7, i + 1]) * 16) for i in range(3)], b"secp"),
    }


def flip(lanes, picks):
    out = list(lanes)
    for i in picks:
        pub, msg, sig = out[i]
        out[i] = (pub, msg, sig[:33] + bytes([sig[33] ^ 0x04]) + sig[34:])
    return out


def batch_of(kind, signed, n):
    """``(verifier factory, lanes)`` of an ``n``-lane batch of ``kind``,
    tampered on both sides of every seam; a mixed batch holds ``n``
    such lanes of each device type, interleaved, and three secp256k1
    lanes among them, the second tampered."""
    if kind != "mixed":
        factory = crypto_batch.Ed25519BatchVerifier if kind == "ed25519" else Sr25519BatchVerifier
        return factory, flip(signed[kind][:n], seams(n))
    ed, sr = flip(signed["ed25519"][:n], seams(n)), flip(signed["sr25519"][:n], seams(n))
    lanes = [lane for pair in zip(ed, sr) for lane in pair]
    for at, lane in zip((0, n, 2 * n + 2), flip(signed["secp256k1"], [1])):
        lanes.insert(at, lane)
    return crypto_batch.MultiBatchVerifier, lanes


def drive(bv, lanes, looks):
    """Add every lane; a caller that ``looks`` begins what is ready
    after each ``add``, as ``_verify_commit_batch`` does."""
    begun = 0
    for lane in lanes:
        bv.add(*lane)
        if looks and bv.ready:
            begun += bv.begin_ready()
    return begun


def blocks_of(bv):
    """Blocks begun and not finished: a device verifier's, or a mixed
    batch's ed25519 and sr25519 sub-verifiers' (its host lanes have none)."""
    if not isinstance(bv, crypto_batch.MultiBatchVerifier):
        return [len(bv._blocks)]
    assert not hasattr(bv._subs["secp256k1"], "_blocks")
    return [len(bv._subs[kt]._blocks) for kt in ("ed25519", "sr25519")]


def oracle(lane):
    pub, msg, sig = lane
    return bool(pub.verify_signature(msg, sig))


@pytest.mark.parametrize("looks", [False, True], ids=["adds", "looks"])
@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("kind", ["ed25519", "sr25519", "mixed"])
def test_verdicts_are_those_of_one_verify_whatever_was_begun_early(monkeypatch, signed, kind, n, looks):
    factory, lanes = batch_of(kind, signed, n)
    jobs_have_run(monkeypatch)
    monkeypatch.setattr(ed25519_batch, "job_lanes", lambda: NO_JOB)
    whole = factory()
    assert drive(whole, lanes, looks) == 0 and not any(blocks_of(whole))
    want = whole.verify()
    assert want[1] == [oracle(lane) for lane in lanes] and want[0] == all(want[1])
    assert want[1].count(False) == len(seams(n)) * (2 if kind == "mixed" else 1) + (kind == "mixed")

    monkeypatch.setattr(ed25519_batch, "job_lanes", lambda: JOB)
    bv = factory()
    begun = drive(bv, lanes, looks)
    # a caller that looks begins a job with its last lane, add alone one lane later
    early = (n if looks else n - 1) // JOB if n >= JOB else 0
    subs = 2 if kind == "mixed" else 1
    assert blocks_of(bv) == [early] * subs
    if looks:
        assert begun == early * JOB * subs
    assert bv.verify() == want
    assert not any(blocks_of(bv))
    assert bv.verify() == want  # asked again: the whole batch, as before


def test_a_batch_under_one_job_begins_nothing_before_verify(job, signed):
    bv = crypto_batch.Ed25519BatchVerifier()
    lanes = signed["ed25519"][: JOB - 1]
    raised, events = traced(lambda: drive(bv, lanes, looks=True))
    assert raised is None and events == [] and not bv.ready and bv._blocks == []
    # and a full job is ready with its last lane, begun by nobody until asked
    bv.add(*signed["ed25519"][JOB - 1])
    assert bv.ready and bv._blocks == []


class Engine:
    """The engine's seam stood in: ``ops.begin_verify_batch`` and
    ``ops.verify_batch`` answer every lane True and record what they
    were handed; a begun batch counts as in flight until finished."""

    def __init__(self, monkeypatch, nap=0.0):
        from tendermint_tpu import ops

        self.begun, self.whole, self.unfinished, self.nap = [], [], 0, nap
        monkeypatch.setattr(ops, "begin_verify_batch", self.begin)
        monkeypatch.setattr(ops, "verify_batch", self.verify)

    def begin(self, pks, msgs, sigs, backend=None, early=False):
        with tracing.span("verify_batch", engine="ed25519", lanes=len(pks), phase="dispatch"):
            time.sleep(self.nap)
            with tracing.span("dispatch_chunk", lanes=len(pks)):
                pass
        self.begun.append((len(pks), early))
        self.unfinished += 1
        engine = self

        class Pending:
            lanes_inflight = len(pks)

            def finish(self):
                engine.unfinished -= 1
                with tracing.span("verify_batch", engine="ed25519", lanes=len(pks), phase="collect"):
                    with tracing.span("collect_chunk", lanes=len(pks)):
                        pass
                return [True] * len(pks)

        return Pending()

    def verify(self, pks, msgs, sigs, backend=None):
        self.whole.append(len(pks))
        return [True] * len(pks)


def warm(vset):
    """The committee as a node that has verified a commit of it knows
    it: live, every key's table built, so that no call here builds one
    (a block that would is not begun early)."""
    crypto_batch.note_validator_set(vset)
    precompute.tables.gather([v.pub_key.bytes() for v in vset.validators])


def add_many(bv, lane, n):
    for _ in range(n):
        bv.add(*lane)


@pytest.mark.parametrize("route", ["remote", "host", "use_device_false"])
def test_a_batch_that_is_not_this_processes_devices_never_begins_early(monkeypatch, job, signed, route):
    engine = Engine(monkeypatch)
    sent = []
    if route == "remote":
        monkeypatch.setattr(
            crypto_batch, "remote_verify_backend",
            lambda: lambda pks, msgs, sigs: sent.append(len(pks)) or [True] * len(pks),
        )
        bv = crypto_batch.Ed25519BatchVerifier()
    elif route == "host":
        monkeypatch.setattr(
            crypto_batch, "host_verify_ed25519", lambda pks, m, s: sent.append(len(pks)) or [True] * len(pks)
        )
        bv = crypto_batch.Ed25519BatchVerifier(device_threshold=1000)
    else:
        monkeypatch.setattr(
            crypto_batch, "host_verify_ed25519", lambda pks, m, s: sent.append(len(pks)) or [True] * len(pks)
        )
        bv = crypto_batch.Ed25519BatchVerifier(use_device=False)
    for lane in signed["ed25519"]:
        bv.add(*lane)
        assert not bv.ready and bv.begin_ready() == 0
    assert bv.verify() == (True, [True] * MOST)
    assert sent == [MOST] and engine.begun == [] and engine.whole == []


@pytest.mark.parametrize("devices,want", [(1, [(4096, True), (4096, True)]), (4, [])])
def test_the_jobs_lanes_follow_the_engines_accessor(monkeypatch, signed, devices, want):
    """10,000 lanes: two jobs begun early on one device, none where a
    plan would span four (a job is 16,384 lanes there). The accessor
    reserves no probe: it never asks the manager for a plan."""
    engine = Engine(monkeypatch)
    jobs_have_run(monkeypatch)
    monkeypatch.setattr(mesh.manager, "device_count", lambda: devices)
    monkeypatch.setattr(mesh.manager, "plan", lambda: pytest.fail("job_lanes() asked for a plan"))
    assert ed25519_batch.job_lanes() == ed25519_batch.CHUNK * devices
    bv = crypto_batch.Ed25519BatchVerifier()
    add_many(bv, signed["ed25519"][0], 10_000)
    assert engine.begun == want
    assert bv.verify() == (True, [True] * 10_000)
    if want:
        assert engine.begun == want + [(10_000 - 2 * 4096, False)] and engine.whole == []
    else:
        assert engine.begun == [] and engine.whole == [10_000]
    assert engine.unfinished == 0


def test_the_accessor_counts_a_forced_meshes_devices(monkeypatch):
    class Forced:
        devices = np.empty((2, 3), dtype=object)

    with mesh.manager.forced(Forced()):
        assert ed25519_batch.job_lanes() == 6 * ed25519_batch.CHUNK
    monkeypatch.setattr(mesh.manager, "device_count", lambda: 1 / 0)
    assert ed25519_batch.job_lanes() == ed25519_batch.CHUNK  # any trouble: unsharded


def test_an_engines_first_full_job_goes_to_it_whole(monkeypatch, signed):
    """A job's first launch compiles, or loads, its kernel: until an
    engine has dispatched one full job in the process a batch is not
    begun early, so that a process's first calls come in the order they
    always did; the next batch is."""
    monkeypatch.setattr(ed25519_batch, "job_lanes", lambda: JOB)
    monkeypatch.setattr(ed25519_batch, "_ENGINES_WITH_A_JOB_RUN", set())
    lanes = signed["ed25519"]
    begun = []
    for _ in range(2):
        bv = crypto_batch.Ed25519BatchVerifier()
        drive(bv, lanes, looks=False)
        begun.append(len(bv._blocks))
        assert bv.verify() == (True, [True] * MOST)
    assert begun == [0, MOST // JOB]
    assert ed25519_batch._ENGINES_WITH_A_JOB_RUN == {"ed25519"}
    assert ed25519_batch.job_has_run("ed25519") and not ed25519_batch.job_has_run("sr25519")


def test_a_block_that_would_build_tables_waits_for_verify(monkeypatch, job):
    """A committee's first commit builds its tables in the gather: sent
    in blocks, the device store would be uploaded at the width of the
    first block and a kernel compiled for it. Such a block is not begun
    early, and the commit after it is."""
    engine = Engine(monkeypatch)
    privs, vset = make_validators(2 * JOB + 5)
    block_id = make_block_id(b"early-cold")
    for height, early in ((1, []), (2, [(JOB, True), (JOB, True)])):
        commit = make_commit(block_id, height, 0, vset, privs)
        del engine.begun[:], engine.whole[:]
        if height == 2:  # what the first commit's gather does, the engine being stood in
            precompute.tables.gather([v.pub_key.bytes() for v in vset.validators])
        validation.verify_commit(CHAIN_ID, vset, block_id, height, commit)
        assert engine.begun == early + ([(5, False)] if early else [])
        assert engine.whole == ([] if early else [2 * JOB + 5])
    keys = [v.pub_key.bytes() for v in vset.validators]
    assert not precompute.tables.would_build(keys)
    assert not precompute.tables.would_build([b"\x07" * 32])  # of no set: never built
    precompute.tables.pin([b"\x07" * 32])
    assert precompute.tables.would_build(keys[:3] + [b"\x07" * 32])


# --- every way out of _verify_commit_batch that does not reach verify() ---------


class Boom(Exception):
    pass


@pytest.fixture
def health(monkeypatch):
    now = [1000.0]
    machine = device_policy.DeviceHealth(retry_budget=1, cooldown_base=1.0, clock=lambda: now[0])
    machine.now = now
    machine.ops_metrics = OpsMetrics(Registry())
    machine.bind_metrics(machine.ops_metrics)
    monkeypatch.setattr(device_policy, "shared", machine)
    return machine


def half_open(health):
    health.record_failure(RuntimeError("UNAVAILABLE: planted"), health.begin_attempt("ed25519"))
    assert health.state == device_policy.COOLDOWN
    health.now[0] += 5.0


def nothing_in_flight(health):
    assert health.snapshot()["probe_inflight"] is False
    lines = health.ops_metrics.inflight_lanes.collect()
    assert lines and all(line.endswith(" 0") for line in lines), lines


N_VALS = 40


@pytest.fixture(scope="module")
def committee():
    privs, vset = make_validators(N_VALS)
    return privs, vset, make_block_id(b"early-abort")


def unbatchable(privs, vset, block_id):
    commit = make_commit(block_id, 5, 0, vset, privs)
    commit.signatures[30].signature = commit.signatures[30].signature[:63]
    return (lambda: validation.verify_commit(CHAIN_ID, vset, block_id, 5, commit)), (
        validation.InvalidCommitError, r"wrong signature \(#30\)")


def not_enough_power(privs, vset, block_id):
    commit = make_commit(block_id, 5, 0, vset, privs, nil_votes=set(range(0, N_VALS, 2)))
    return (lambda: validation.verify_commit(CHAIN_ID, vset, block_id, 5, commit)), (
        validation.NotEnoughVotingPowerError, "insufficient voting power")


def double_vote(privs, vset, block_id):
    commit = make_commit(block_id, 5, 0, vset, privs)
    commit.signatures[20] = commit.signatures[3]
    return (lambda: validation.verify_commit_light_trusting(
        CHAIN_ID, vset, commit, validation.Fraction(9, 10))), (
        validation.InvalidCommitError, r"double vote from validator 3 \(3 and 20\)")


def add_raises(privs, vset, block_id, monkeypatch):
    commit = make_commit(block_id, 5, 0, vset, privs)
    add_many = crypto_batch.MultiBatchVerifier.add_many

    def failing(self, pub_keys, msgs, sigs):
        if len(self) == JOB:  # the second block
            raise Boom("add")
        return add_many(self, pub_keys, msgs, sigs)

    monkeypatch.setattr(crypto_batch.MultiBatchVerifier, "add_many", failing)
    return (lambda: validation.verify_commit(CHAIN_ID, vset, block_id, 5, commit)), (Boom, "add")


@pytest.mark.parametrize("health_state", ["healthy", "half_open"])
@pytest.mark.parametrize("way_out", ["unbatchable", "not_enough_power", "double_vote", "add_raises"])
def test_leaving_before_verify_raises_what_it_did_and_leaves_nothing_in_flight(
    monkeypatch, job, committee, health, way_out, health_state
):
    """Each is reached with a block on the device (the first job's 16
    lanes, begun by the loop): the error and its precedence are those of
    a verifier that waits for ``verify()``, the block is collected on
    the way out, and with the device half open the probe it held is
    settled."""
    warm(committee[1])
    if health_state == "half_open":
        half_open(health)
    if way_out == "add_raises":
        call, (error, match) = add_raises(*committee, monkeypatch)
    else:
        call, (error, match) = {"unbatchable": unbatchable, "not_enough_power": not_enough_power,
                                "double_vote": double_vote}[way_out](*committee)
    raised, events = traced(call)
    assert isinstance(raised, error) and re.search(match, str(raised)), raised
    early = [e for e in events if e["name"] == "batch_verify" and e["args"].get("early")]
    assert len(early) >= 1 and all(e["args"]["lanes"] == JOB for e in early)
    sent = sum(e["args"]["lanes"] for e in events if e["name"] == "dispatch_chunk")
    got = sum(e["args"]["lanes"] for e in events if e["name"] == "collect_chunk")
    assert sent == got and sent >= JOB  # (half open: the second block is the oracle's, as a second caller's)
    nothing_in_flight(health)
    assert health.state == device_policy.HEALTHY  # the block round-tripped


def test_a_verifier_dropped_with_a_block_in_flight_collects_it(job, signed, health):
    half_open(health)
    bv = crypto_batch.Ed25519BatchVerifier()
    for lane in signed["ed25519"][: JOB + 1]:
        bv.add(*lane)
    assert len(bv._blocks) == 1 and health.snapshot()["probe_inflight"] is True
    assert 'engine="ed25519"} %d' % JOB in health.ops_metrics.inflight_lanes.collect()[0]
    del bv
    gc.collect()
    nothing_in_flight(health)
    assert health.state == device_policy.HEALTHY


def test_close_is_idempotent_and_verify_after_it_verifies_the_whole_batch(job, signed, health):
    lanes = flip(signed["ed25519"], [JOB])
    bv = crypto_batch.Ed25519BatchVerifier()
    drive(bv, lanes, looks=True)
    assert len(bv._blocks) == 2
    bv.close()
    bv.close()
    nothing_in_flight(health)
    assert bv._blocks == []
    multi = crypto_batch.MultiBatchVerifier()
    drive(multi, lanes, looks=True)
    multi.close()
    nothing_in_flight(health)


def test_a_fault_at_the_collect_of_an_early_block_sends_that_block_to_the_oracle(job, signed, health):
    lanes = flip(signed["ed25519"], [3, JOB, MOST - 1])
    want = [i not in (3, JOB, MOST - 1) for i in range(MOST)]
    bv = crypto_batch.Ed25519BatchVerifier()
    drive(bv, lanes, looks=False)
    assert len(bv._blocks) == 2
    got = []
    with fault_injection.inject(site="ed25519.collect", fail_calls=(1,)), pytest.warns(
        UserWarning, match="failed at collect"
    ):
        raised, events = traced(lambda: got.append(bv.verify()))
    assert raised is None and got == [(False, want)]
    (fallback,) = [e for e in events if e["name"] == "host_fallback"]
    assert fallback["args"]["lanes"] == JOB  # the first block alone
    assert sum(e["args"]["lanes"] for e in events if e["name"] == "collect_chunk") == MOST
    nothing_in_flight(health)


# --- a block of lanes in one call (ISSUE 45) -------------------------------------


def state_of(bv):
    """What a verifier holds, column by column, a mixed one's by key type."""
    if isinstance(bv, crypto_batch.MultiBatchVerifier):
        return list(bv._order), {kt: state_of(sub) for kt, sub in bv._subs.items()}, bv.ready
    columns = [getattr(bv, name) for name in ("_pks", "_msgs", "_sigs", "_entries", "_lanes") if hasattr(bv, name)]
    return [list(column) for column in columns], len(bv), bv.ready


def in_blocks(bv, lanes):
    """Hand ``lanes`` over as ``_verify_commit_batch`` does: a block up
    to where ``room`` says, begin what is ready, the next."""
    keys = [lane[0] for lane in lanes]
    at = begun = 0
    ends = []
    while at < len(lanes):
        if at:
            begun += bv.begin_ready()
        end = min(at + bv.room(keys[at:]), len(lanes))
        bv.add_many(*zip(*lanes[at:end]))
        ends.append(end)
        at = end
    return begun, ends


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("kind", ["ed25519", "sr25519", "secp256k1", "mixed"])
def test_add_many_is_add_of_each_lane(monkeypatch, signed, kind, n):
    """One ``add_many`` of a batch leaves each of the four verifiers
    holding what ``add`` of each lane does, in the same order, and
    gives the same verdicts; so do the blocks ``room`` cuts, which are
    begun where a caller that looks after each ``add`` begins them."""
    if kind == "secp256k1":
        factory, lanes = (lambda: crypto_batch.HostLanesVerifier("secp256k1")), flip(signed[kind] * n, [0])[: max(n, 3)]
    else:
        factory, lanes = batch_of(kind, signed, n)
    jobs_have_run(monkeypatch)
    monkeypatch.setattr(ed25519_batch, "job_lanes", lambda: NO_JOB)
    one_by_one, at_once = factory(), factory()
    for lane in lanes:
        one_by_one.add(*lane)
    at_once.add_many(*zip(*lanes))
    assert state_of(at_once) == state_of(one_by_one) and len(at_once) == len(lanes)
    want = one_by_one.verify()
    assert at_once.verify() == want and want[1] == [oracle(lane) for lane in lanes]

    monkeypatch.setattr(ed25519_batch, "job_lanes", lambda: JOB)
    looked, cut = factory(), factory()
    begun = 0
    for at, lane in enumerate(lanes):  # the lane-by-lane loop: a look after each add while lanes are left
        looked.add(*lane)
        if looked.ready and at + 1 < len(lanes):
            begun += looked.begin_ready()
    got, ends = in_blocks(cut, lanes)
    assert got == begun and state_of(cut) == state_of(looked)
    if kind != "secp256k1":
        assert blocks_of(cut) == blocks_of(looked)
    if kind in ("ed25519", "sr25519"):
        assert ends == sorted({*range(JOB, n, JOB), n})  # a block a job, the rest: never a lane a block
    assert len(ends) <= 2 * (n // JOB) + 1
    assert cut.verify() == want


@pytest.mark.parametrize("kind", ["ed25519", "sr25519", "mixed"])
def test_add_after_add_many_and_the_reverse_begin_a_ready_job(job, signed, kind):
    factory, lanes = batch_of(kind, signed, MOST)
    subs = 2 if kind == "mixed" else 1
    full = JOB * subs + (2 if kind == "mixed" else 0)  # the lanes that give every device type a job (two host lanes among them)
    bv = factory()
    bv.add_many(*zip(*lanes[:full]))
    assert bv.ready and not any(blocks_of(bv))
    bv.add(*lanes[full])  # the next lane begins what was left ready, as after JOB adds
    assert sum(blocks_of(bv)) >= 1
    other = factory()
    for lane in lanes[:full]:
        other.add(*lane)
    assert other.ready
    other.add_many(*zip(*lanes[full:]))
    # what was ready is begun, with the jobs the block filled behind it (a sub-verifier
    # whose job an add had already begun is left ready once more)
    assert all(1 <= begun <= MOST // JOB for begun in blocks_of(other)) and MOST // JOB in blocks_of(other)
    want = [oracle(lane) for lane in lanes]
    bv.add_many(*zip(*lanes[full + 1:]))
    assert bv.verify()[1] == want and other.verify()[1] == want


@pytest.mark.parametrize("kind", ["ed25519", "sr25519", "secp256k1", "mixed"])
def test_add_many_refuses_what_add_refuses(signed, kind):
    """A key of another type, a short signature or key, columns of
    unequal length: ``ValueError``, as lane by lane."""
    if kind == "secp256k1":
        factory, lanes = (lambda: crypto_batch.HostLanesVerifier("secp256k1")), list(signed[kind])
    else:
        factory, lanes = batch_of(kind, signed, JOB)
    keys, msgs, sigs = (list(column) for column in zip(*lanes))
    with pytest.raises(ValueError):
        factory().add_many(keys, msgs[:-1], sigs)
    assert len(factory()) == 0
    if kind == "mixed":
        return
    stranger = signed["sr25519" if kind == "ed25519" else "ed25519"][0]
    for bad in ([stranger] + lanes[1:], lanes[:2] + [stranger]):
        by_block, by_lane = factory(), factory()
        with pytest.raises(ValueError) as refused:
            by_block.add_many(*zip(*bad))
        with pytest.raises(ValueError) as refused_by_lane:
            for lane in bad:
                by_lane.add(*lane)
        assert str(refused.value) == str(refused_by_lane.value)
        assert state_of(by_block) == state_of(by_lane)  # the lanes before it taken, as by add
    if kind == "ed25519":
        short = [(keys[0], msgs[0], sigs[0][:63])]
        with pytest.raises(ValueError, match="malformed ed25519 entry"):
            factory().add_many(*zip(*(lanes[:3] + short + lanes[3:])))


class SrEngine:
    """``Engine`` for the sr25519 seam."""

    def __init__(self, monkeypatch):
        from tendermint_tpu.ops import sr25519_batch

        self.begun = []
        monkeypatch.setattr(sr25519_batch, "begin_verify_batch_sr", self.begin)
        monkeypatch.setattr(sr25519_batch, "verify_batch_sr", lambda pks, msgs, sigs, backend=None: [True] * len(pks))

    def begin(self, pks, msgs, sigs, backend=None, early=False):
        self.begun.append((len(pks), early))
        return type("Pending", (), {"lanes_inflight": len(pks), "finish": lambda self: [True] * len(pks)})()


def big_commit(n_ed, n_sr=0, n_secp=0):
    """A commit of that many validators of each type, signed by nobody
    (the engines are stood in): keys and signatures from a seed."""
    from tendermint_tpu.crypto.keys import Ed25519PubKey
    from tendermint_tpu.crypto.sr25519 import Sr25519PubKey
    from tendermint_tpu.encoding.canonical import Timestamp
    from tendermint_tpu.types import BLOCK_ID_FLAG_COMMIT, Commit, CommitSig, Validator, ValidatorSet

    rng = np.random.default_rng(45)
    secp = Secp256k1PrivKey(bytes([7, 1]) * 16).pub_key()
    vals = [Validator(Ed25519PubKey(rng.bytes(32)), 10) for _ in range(n_ed)]
    vals += [Validator(Sr25519PubKey(rng.bytes(32)), 10) for _ in range(n_sr)]
    vals += [Validator(secp, 10, address=rng.bytes(20)) for _ in range(n_secp)]
    vset = ValidatorSet(vals)
    block_id = make_block_id(b"early-10k")
    commit = Commit(height=3, round=0, block_id=block_id)
    commit.signatures = [
        CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, Timestamp(1_700_000_000, 1 + 99_991 * i), rng.bytes(64))
        for i, v in enumerate(vset.validators)
    ]
    return vset, block_id, commit


@pytest.mark.parametrize(
    "devices,shape,blocks,early",
    [
        (1, (10_000, 0, 0), [4096, 4096, 1808], {"ed25519": [(4096, True), (4096, True), (1808, False)]}),
        (4, (10_000, 0, 0), [10_000], {"ed25519": []}),
        (1, (4950, 4950, 100), None, {"ed25519": [(4096, True), (854, False)], "sr25519": [(4096, True), (854, False)]}),
    ],
    ids=["one_chip", "four_chips", "mixed_one_chip"],
)
def test_a_10000_lane_commit_is_begun_where_the_lane_by_lane_loop_began_it(monkeypatch, devices, shape, blocks, early):
    """The chip's own job (4,096 lanes a device), the engines stood in:
    4,096 and 8,192 of 10,000 lanes are begun early on one device, none
    where a plan spans four, 4,096 of each device type in a mixed set;
    lane for lane and begin for begin what the lane-by-lane loop did."""
    engine, sr_engine = Engine(monkeypatch), SrEngine(monkeypatch)
    jobs_have_run(monkeypatch)
    monkeypatch.setattr(mesh.manager, "device_count", lambda: devices)
    monkeypatch.setattr(precompute.tables, "would_build", lambda keys: False)
    monkeypatch.setattr(
        crypto_batch.HostLanesVerifier, "verify", lambda self, device_lanes_inflight=0: (True, [True] * len(self))
    )
    vset, block_id, commit = big_commit(*shape)
    call = lambda: validation.verify_commit(CHAIN_ID, vset, block_id, 3, commit)
    said = record_verifier(monkeypatch)
    raised, events = traced(call)
    assert raised is None
    assert engine.begun == early["ed25519"] and sr_engine.begun == early.get("sr25519", [])
    assert engine.whole == ([10_000] if devices == 4 else [])
    loops = [e["args"] for e in events if e["name"] == "build_lanes"]
    (outer,) = [e["args"] for e in events if e["name"] == "verify_commit"]
    assert outer["blocks"] == len(loops) <= 4  # a handful, however the types interleave
    assert outer["early_lanes"] == sum(lanes for begun in early.values() for lanes, is_early in begun if is_early)
    assert all(a["block_lanes"] == a["lanes"] for a in loops) and sum(a["lanes"] for a in loops) == 10_000
    if blocks is not None:
        assert [a["lanes"] for a in loops] == blocks
    new = list(said)
    del said[:], engine.begun[:], sr_engine.begun[:], engine.whole[:]
    monkeypatch.setattr(validation, "_verify_commit_batch", lane_by_lane_commit_batch)
    assert outcome(call) is None
    assert said == new
    assert engine.begun == early["ed25519"] and sr_engine.begun == early.get("sr25519", [])


# --- span hygiene ---------------------------------------------------------------


def test_the_spans_of_a_two_job_commit_keep_the_engine_out_of_the_entrys_names(monkeypatch, job):
    """A traced commit of two jobs and five lanes, the engine stood in by
    one that sleeps 20 ms a begin: ``batch_verify`` lies beside
    ``build_lanes`` under ``verify_commit``, never inside it, so the
    loop's phase totals hold no engine time and the readers that
    subtract both names subtract each millisecond once."""
    nap = 0.02
    engine = Engine(monkeypatch, nap=nap)
    n = 2 * JOB + 5
    privs, vset = make_validators(n)
    block_id = make_block_id(b"early-spans")
    warm(vset)
    commit = make_commit(block_id, 3, 0, vset, privs)
    t0 = time.perf_counter_ns()
    raised, events = traced(lambda: validation.verify_commit(CHAIN_ID, vset, block_id, 3, commit))
    t1 = time.perf_counter_ns()
    assert raised is None and engine.begun == [(JOB, True), (JOB, True), (5, False)]
    by = lambda name: [e for e in events if e["name"] == name]
    (outer,) = by("verify_commit")
    assert outer["args"]["early_lanes"] == 2 * JOB and outer["args"]["sigs"] == n
    loops, batches = by("build_lanes"), by("batch_verify")
    assert [e["args"]["lanes"] for e in loops] == [JOB, JOB, 5] and outer["args"]["blocks"] == 3
    # every lane handed over by its block's one add_many, its sign-bytes made in one call
    assert [e["args"]["block_lanes"] for e in loops] == [JOB, JOB, 5]
    assert [(e["args"]["sign_bytes_n"], e["args"]["batch_add_n"]) for e in loops] == [(1, 1)] * 3
    assert all(e["args"]["sign_bytes_us"] > 0 and e["args"]["batch_add_us"] > 0 for e in loops)
    assert all(e["args"]["sign_bytes_us"] + e["args"]["batch_add_us"] <= e["dur"] for e in loops)
    assert [(e["args"]["lanes"], e["args"]["phase"], e["args"].get("early")) for e in batches] == [
        (JOB, "dispatch", 1), (JOB, "dispatch", 1), (5, "dispatch", None),
        (JOB, "collect", None), (JOB, "collect", None), (5, "collect", None),
    ]
    assert {e["args"]["parent"] for e in loops + batches} == {"verify_commit"}
    for b in batches:  # beside every loop span, inside none
        assert all(b["ts"] + b["dur"] <= l["ts"] or l["ts"] + l["dur"] <= b["ts"] for l in loops)
    # three begins slept 60 ms; the loops and their batch_add phase hold none of it
    assert sum(e["dur"] for e in batches) >= 3 * nap * 1e6
    assert sum(e["dur"] for e in loops) < nap * 1e6
    assert sum(e["args"]["batch_add_us"] for e in loops) < nap * 1e6
    # chipbench's readers on this evidence: big10k-warm's entries, found by what they measure
    call = {"start_ns": t0, "end_ns": t1, "spans": events}
    ev = type("Evidence", (), {"calls": [call], "spans": events})()
    unnamed = read(ev, REAL_BENCH, "big10k-warm", "entry_unnamed_ms")
    assert 0 <= unnamed < nap * 1e3
    entry = read(ev, REAL_BENCH, "big10k-warm", "entry_host_ms")
    assert unnamed <= entry < outer["dur"] / 1e3 - 3 * nap * 1e3 + 1e-6
    assert read(ev, REAL_BENCH, "big10k-warm", "batch_add_ms") < nap * 1e3
    parts = [call_path.read(ev, part=part) for part in ("pre", "chain", "post")]
    assert all(p >= 0 for p in parts) and sum(parts) == pytest.approx((t1 - t0) / 1e6)
    # the first dispatch is the first block's: the chain holds the building of the other two
    first = by("dispatch_chunk")[0]
    assert first["ts"] < loops[1]["ts"] and parts[1] > 2 * nap * 1e3
