"""``parallel/pipeline.verify_commits_pipelined`` on the device path
(``JAX_PLATFORMS=cpu``) held against the plain light reference
(``chipbench/reference_light.py``, big integers, nothing of the program)
and against ``verify_commit_light`` block by block: verdict kind and
commit index, on seeded keys. Then the cases a flat batch can get wrong,
the spans the benchmark's Pipeline metrics read, and the normal path
(``BlockSyncer``) reaching the function the cell ``sync500-catchup``
calls.
"""

from __future__ import annotations

import math
import random
import re

import pytest

from chipbench import reference_light, workload
from tendermint_tpu.crypto.keys import Ed25519PrivKey
from tendermint_tpu.libs import tracing
from tendermint_tpu.parallel import pipeline as pipeline_mod
from tendermint_tpu.parallel.pipeline import CommitTask, verify_commits_pipelined
from tendermint_tpu.types import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    Validator,
    ValidatorSet,
)
from tendermint_tpu.types.validation import (
    InvalidCommitError,
    NotEnoughVotingPowerError,
    verify_commit_light,
)
from tests.helpers import CHAIN_ID, make_block_id, make_commit

OK = reference_light.OK
INSUFFICIENT = reference_light.INSUFFICIENT


def make_set(n, unequal=False, key_factory=None, tag=0):
    """(private keys in the set's order, ValidatorSet); unequal powers
    run 1..13, so the set's order is by power first."""
    if key_factory is None:
        key_factory = lambda i: Ed25519PrivKey.from_seed(bytes([tag]) + i.to_bytes(31, "big"))
    privs = [key_factory(i) for i in range(n)]
    vset = ValidatorSet(
        [Validator(p.pub_key(), 1 + (i * 7) % 13 if unequal else 10) for i, p in enumerate(privs)]
    )
    by_addr = {p.pub_key().address(): p for p in privs}
    return [by_addr[v.address] for v in vset.validators], vset


def make_window(privs, vset, window, gaps=False, seed=0, first=1):
    """Tasks of consecutive heights; with ``gaps`` 5% of the validators
    are absent and 1% vote nil at each height, drawn from the seed."""
    n = len(privs)
    rng = random.Random(seed)
    tasks = []
    for h in range(first, first + window):
        absent, nil = set(), set()
        if gaps:
            order = rng.sample(range(n), n)
            n_absent = math.ceil(0.05 * n)
            absent = set(order[:n_absent])
            nil = set(order[n_absent : n_absent + math.ceil(0.01 * n)])
        bid = make_block_id(b"seed%d-blk%d" % (seed, h))
        commit = make_commit(bid, h, 0, vset, privs, absent=absent, nil_votes=nil)
        tasks.append(CommitTask(CHAIN_ID, vset, bid, h, commit))
    return tasks


def plain(task):
    """A task as the plain reference takes it: values only."""
    commit = task.commit
    validators = [(v.pub_key.bytes(), v.voting_power) for v in task.vals.validators]
    signatures = [
        (
            cs.block_id_flag,
            b"" if cs.block_id_flag == BLOCK_ID_FLAG_ABSENT else commit.vote_sign_bytes(task.chain_id, i),
            cs.signature,
        )
        for i, cs in enumerate(commit.signatures)
    ]
    return validators, signatures


def answer_of(error):
    if error is None:
        return OK
    if isinstance(error, NotEnoughVotingPowerError):
        return INSUFFICIENT
    m = re.search(r"wrong signature \(#(\d+)\)", str(error))
    return ("wrong signature", int(m.group(1))) if m else ("refused", str(error))


def pipelined(tasks):
    return [answer_of(v.error) for v in verify_commits_pipelined(tasks)]


def light_alone(task):
    try:
        verify_commit_light(task.chain_id, task.vals, task.block_id, task.height, task.commit)
    except (InvalidCommitError, NotEnoughVotingPowerError) as exc:
        return answer_of(exc)
    return OK


def assert_all_agree(tasks, want=None):
    got = pipelined(tasks)
    assert got == reference_light.verify_window([plain(t) for t in tasks])
    assert got == [light_alone(t) for t in tasks]
    if want is not None:
        assert got == want
    return got


def included(task):
    """Commit indices light verification looks at."""
    needed = task.vals.total_voting_power() * 2 // 3
    tallied, out = 0, []
    for i, cs in enumerate(task.commit.signatures):
        if cs.block_id_flag != BLOCK_ID_FLAG_COMMIT:
            continue
        out.append(i)
        tallied += task.vals.validators[i].voting_power
        if tallied > needed:
            break
    return out


def tamper(task, idx, kind):
    cs = task.commit.signatures[idx]
    cs.signature = workload.tamper_signature(cs.signature, kind)


@pytest.fixture(scope="module")
def sets():
    made = {}

    def get(n, unequal):
        if (n, unequal) not in made:
            made[n, unequal] = make_set(n, unequal)
        return made[n, unequal]

    return get


@pytest.mark.parametrize("unequal", [False, True], ids=["equal", "unequal"])
@pytest.mark.parametrize("gaps", [False, True], ids=["all-sign", "absent-nil"])
@pytest.mark.parametrize("window", [1, 4, 16])
@pytest.mark.parametrize("n", [7, 64, 100])
def test_sound_windows_agree_with_reference_and_light(sets, n, window, gaps, unequal):
    privs, vset = sets(n, unequal)
    seed = n * 1000 + window * 10 + gaps * 2 + unequal
    tasks = make_window(privs, vset, window, gaps=gaps, seed=seed)
    got = assert_all_agree(tasks)
    # seven unequal powers less an absent and a nil vote can fall to 2/3
    # or under: such a block is refused for power by all three alike
    assert set(got) <= ({OK, INSUFFICIENT} if n == 7 and gaps and unequal else {OK})


@pytest.fixture
def small(sets):
    """Twelve validators, 4-block windows with absent and nil votes."""
    privs, vset = sets(12, False)

    def window(seed, size=4):
        return make_window(privs, vset, size, gaps=True, seed=seed)

    return window


@pytest.mark.parametrize("kind", workload.TAMPER_KINDS)
def test_tampered_included_lane_refuses_its_block_at_its_commit_index(small, kind):
    tasks = small(seed=31)
    lanes = included(tasks[2])
    # past an absent or nil vote the commit index runs ahead of the lane
    lane = next(k for k, idx in enumerate(lanes) if idx != k)
    tamper(tasks[2], lanes[lane], kind)
    assert_all_agree(tasks, want=[OK, OK, ("wrong signature", lanes[lane]), OK])


def test_tampered_signature_after_the_early_exit_is_never_looked_at(small):
    tasks = small(seed=32)
    last = included(tasks[1])[-1]
    later = [
        i for i, cs in enumerate(tasks[1].commit.signatures)
        if i > last and cs.block_id_flag == BLOCK_ID_FLAG_COMMIT
    ]
    tamper(tasks[1], later[0], "R")
    assert_all_agree(tasks, want=[OK] * 4)


def test_two_bad_blocks_in_one_window_each_name_their_own_first_bad(small):
    tasks = small(seed=33)
    first, third = included(tasks[0]), included(tasks[3])
    tamper(tasks[0], first[4], "s")
    tamper(tasks[0], first[6], "R")  # a later bad lane of the same block
    tamper(tasks[3], third[0], "R")
    assert_all_agree(
        tasks, want=[("wrong signature", first[4]), OK, OK, ("wrong signature", third[0])]
    )


def test_block_at_two_thirds_is_refused_sends_no_lane_and_moves_no_neighbour(sets, ring):
    privs, vset = sets(12, False)
    tasks = make_window(privs, vset, 3, seed=34)
    bid = tasks[1].block_id
    # 8 of 12 equal votes are exactly 2/3: not more than 2/3
    tasks[1].commit = make_commit(bid, 2, 0, vset, privs, absent={0, 5, 7, 11})
    # a bad lane after the dropped block: its slice must still be its own
    tamper(tasks[2], included(tasks[2])[1], "s")
    got = assert_all_agree(tasks)
    assert got == [OK, INSUFFICIENT, ("wrong signature", included(tasks[2])[1])]
    outer = spans_named(ring, "verify_commits_pipelined")[0]
    assert outer["args"]["lanes"] == 2 * 9 and outer["args"]["refused_early"] == 1
    sent = [s for s in spans_named(ring, "verify_batch")]
    assert sent and all(s["args"]["lanes"] <= 18 for s in sent)


def test_task_that_fails_the_basic_checks_is_refused_alone(small):
    tasks = small(seed=35)
    tasks[1].height += 1  # wrong height
    got = pipelined(tasks)
    assert [g[0] for g in got] == ["ok", "refused", "ok", "ok"]
    assert "wrong height" in got[1][1]
    assert got == [light_alone(t) for t in tasks]


@pytest.mark.parametrize("bad", [False, True], ids=["sound", "tampered"])
def test_window_mixing_an_ed25519_only_set_and_a_mixed_set_is_planned_by_key_type(bad, ring):
    """A window that meets one set holding an sr25519 key goes to
    MultiBatchVerifier whole, the ed25519-only blocks' lanes too: one
    sub-batch a key type, no block verified alone, each block's verdict
    verify_commit_light's."""
    from tendermint_tpu.crypto.sr25519 import Sr25519PrivKey

    def factory(i):
        if i == 0:
            return Sr25519PrivKey(b"\x05" * 32)
        return Ed25519PrivKey.from_seed(bytes([9]) + i.to_bytes(31, "big"))

    mixed_privs, mixed = make_set(6, key_factory=factory)
    ed_privs, ed_only = make_set(6, tag=9)
    tasks = make_window(ed_privs, ed_only, 1, seed=36)
    tasks += make_window(mixed_privs, mixed, 1, seed=36, first=2)
    tasks += make_window(ed_privs, ed_only, 1, seed=36, first=3)
    sr_idx = next(i for i, v in enumerate(mixed.validators) if v.pub_key.type == "sr25519")
    assert sr_idx in included(tasks[1])  # else the flat batch never meets the key
    want = [OK, OK, OK]
    if bad:
        idx = included(tasks[1])[-1]
        cs = tasks[1].commit.signatures[idx]
        cs.signature = cs.signature[:5] + bytes([cs.signature[5] ^ 1]) + cs.signature[6:]
        want[1] = ("wrong signature", idx)
    got = pipelined(tasks)
    assert got == want
    outer = spans_named(ring, "verify_commits_pipelined")[0]["args"]
    assert (outer["sub_batches"], outer["device_lanes_sr25519"], outer["host_lanes"]) == (2, 1, 0)
    assert outer["device_lanes_ed25519"] == outer["lanes"] - 1 == sum(len(included(t)) for t in tasks) - 1
    assert sorted(s["args"]["key_type"] for s in spans_named(ring, "batch_verify")) == ["ed25519", "sr25519"]
    assert not spans_named(ring, "verify_commit")  # verify_commit_light's span: no block went alone
    ring.clear()
    assert got == [light_alone(t) for t in tasks]


# --- spans ------------------------------------------------------------------


@pytest.fixture
def ring():
    tracing.configure("ring")
    tracing.tracer.clear()
    yield tracing.tracer
    tracing.configure("off")
    tracing.tracer.clear()


def spans_named(tracer, name):
    events = tracer.export()["traceEvents"]
    return [e for e in events if e.get("ph") == "X" and e["name"] == name]


def test_pipeline_spans_nest_and_count_what_was_sent(sets, ring):
    privs, vset = sets(64, False)
    tasks = make_window(privs, vset, 4, gaps=True, seed=41)
    assert pipelined(tasks) == [OK] * 4
    events = [e for e in ring.export()["traceEvents"] if e.get("ph") == "X"]
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    (outer,) = by_name["verify_commits_pipelined"]
    (loop,) = by_name["build_lanes"]
    (merge,) = by_name["merge_verdicts"]
    notes = by_name["note_validator_set"]

    def inside(child, parent):
        return parent["ts"] <= child["ts"] and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 0.5

    assert inside(loop, outer) and inside(merge, outer)
    assert all(inside(b, outer) for b in by_name["verify_batch"])
    assert len(notes) == 4 and all(inside(s, loop) for s in notes)
    assert all(s["args"]["validators"] == 64 for s in notes)
    assert [s["args"]["newly_active"] for s in notes][1:] == [False] * 3
    assert [s["args"]["recognised"] for s in notes][1:] == [True] * 3
    assert all(s["args"]["recognised"] != s["args"]["newly_active"] for s in notes)
    # phase totals lie inside the loop's span
    a = loop["args"]
    assert a["basic_checks_n"] == 4 and a["sign_bytes_n"] == a["lanes"] == outer["args"]["lanes"]
    # one encoder a task; its nil votes are skipped before they are encoded
    assert a["sign_bytes_prefixes"] == 4
    assert a["sign_bytes_us"] + a["basic_checks_us"] + sum(s["dur"] for s in notes) <= loop["dur"]
    # every vote for a block was either sent or skipped past the early exit
    present = sum(
        1 for t in tasks for cs in t.commit.signatures if cs.block_id_flag == BLOCK_ID_FLAG_COMMIT
    )
    assert outer["args"]["lanes"] == 4 * 43
    assert outer["args"]["lanes"] + outer["args"]["skipped"] == present
    assert outer["args"]["tasks"] == 4 and outer["args"]["refused_early"] == 0
    m = merge["args"]
    assert (m["lanes"], m["blocks"], m["scan"]) == (4 * 43, 4, "first_bad_per_block")


@pytest.mark.parametrize(
    "carried, live_before, recognised",
    [
        ("one_object", False, 15),
        ("one_object", True, 16),
        ("a_copy_a_height", False, 15),
        ("a_copy_a_height", True, 16),
    ],
)
def test_a_window_over_one_set_hashes_it_at_most_once(sets, ring, carried, live_before, recognised):
    """Sixteen tasks over one validator set, as a blocksync window
    carries them (a node's state copies its set at every height): the
    set is hashed by the first task that finds it unknown, and every
    other task recognises it by its keys."""
    from tendermint_tpu.ops import precompute

    privs, vset = sets(12, False)
    tasks = make_window(privs, vset, 16, seed=61)
    if carried == "a_copy_a_height":
        for task in tasks:
            task.vals = task.vals.copy()
    precompute.reset()
    try:
        if live_before:
            assert precompute.activate_validator_set(vset) == (True, False)
        before = precompute.tables.stats()
        assert pipelined(tasks) == [OK] * 16
        after = precompute.tables.stats()
    finally:
        precompute.reset()
    notes = [s["args"] for s in spans_named(ring, "note_validator_set")]
    assert len(notes) == 16
    assert sum(a["recognised"] for a in notes) == recognised
    assert sum(a["newly_active"] for a in notes) == 16 - recognised
    assert after["active_set_hashed"] - before["active_set_hashed"] == 16 - recognised <= 1
    assert after["active_set_recognised"] - before["active_set_recognised"] == recognised


def test_blocksyncer_reaches_the_pipeline_with_windows_of_at_most_16(ring):
    from tendermint_tpu.blocksync import BlockSyncer
    from tests.test_blocksync import FakePeer, build_source_chain
    from tests.test_execution import make_chain_env

    source_exec, _ = build_source_chain(20)
    follower_exec, follower_state, *_ = make_chain_env(4)
    syncer = BlockSyncer(
        follower_state, follower_exec, follower_exec.block_store,
        transport=None, verify_window=16,
    )
    syncer.transport = FakePeer(syncer.pool, source_exec.block_store)
    syncer.pool.set_peer_range("p1", 1, source_exec.block_store.height())
    for _ in range(50):
        syncer.step()
        if syncer.state.last_block_height >= 19:
            break
    assert syncer.state.last_block_height >= 19
    calls = spans_named(ring, "verify_commits_pipelined")
    assert calls and all(1 <= c["args"]["tasks"] <= 16 for c in calls)
    assert max(c["args"]["tasks"] for c in calls) > 1
    assert sum(c["args"]["tasks"] - c["args"]["refused_early"] for c in calls) >= 19


@pytest.mark.parametrize("n,window", [(12, 2), (64, 4)])
def test_tracer_off_the_pipeline_makes_three_tracing_calls_whatever_the_lanes(
    sets, monkeypatch, n, window
):
    assert tracing.tracer.mode == "off"
    privs, vset = sets(n, False)
    tasks = make_window(privs, vset, window, seed=51)
    opened = []
    real = tracing.span

    def counting(name, *args, **kwargs):
        opened.append(name)
        return real(name, *args, **kwargs)

    def no_wrapper(*args):
        raise AssertionError("tracer off: the lane loop calls the encoder itself")

    monkeypatch.setattr(tracing, "span", counting)
    monkeypatch.setattr(pipeline_mod, "partial", no_wrapper)
    assert pipelined(tasks) == [OK] * window
    ours = ("verify_commits_pipelined", "build_lanes", "note_validator_set", "merge_verdicts")
    assert [name for name in opened if name in ours] == [
        "verify_commits_pipelined", "build_lanes", "merge_verdicts",
    ]


def test_validator_first_carried_heights_later_joins_the_store_without_a_compile(monkeypatch, ring):
    """What the chip showed in ``sync500-catchup`` (PR 26): light
    verification never carries the validators past 2/3, so the resident
    store first holds only those before it; when absences move the early
    exit, a new key joins the store, and a store one column wider used
    to recompile the kernel inside the call (15-20 s on a TPU v5e)."""
    from tendermint_tpu.ops import precompute, resident

    monkeypatch.setenv("TENDERMINT_TPU_RESIDENT", "on")
    precompute.reset()
    resident.reset()
    try:
        privs, vset = make_set(12, tag=7)
        assert pipelined(make_window(privs, vset, 2, seed=61)) == [OK, OK]  # carries 0..8
        assert resident.stats()["resident_keys"] == 9
        ring.clear()
        bid = make_block_id(b"later")
        commit = make_commit(bid, 3, 0, vset, privs, absent={0, 1})  # carries 2..10
        assert pipelined([CommitTask(CHAIN_ID, vset, bid, 3, commit)]) == [OK]
        s = resident.stats()
        assert (s["uploads"], s["resident_keys"], s["misses"]) == (2, 11, 0)
        assert not spans_named(ring, "xla_compile") and not spans_named(ring, "kernel_compile")
    finally:
        precompute.reset()
        resident.reset()
