"""The benchmark's part of the ``sync500rot`` deployment, without a chip:
the plain rotation reference on hand-made sets, the cell's files, the
generator's windows against the syncer's own on a real chain, the
old-key-in-the-new-seat fault and the cell's metrics reduced on
hand-made spans. The cell's tiny twin is rehearsed end to end in
``tests/test_chipbench_rehearsals.py``.
"""

from __future__ import annotations

import base64
import os

import pytest

from chipbench import reference, reference_light, reference_rotation, selftest, spec, workload
from chipbench.run import Context
from tests.helpers import REAL_BENCH, Evidence, read, span

BENCH = os.path.join(spec.HERE, "testdata", "tiny-rotation-benchmark.json")
CELL = "tiny-sync-rotation"
SEED = 2**31 + 26
REAL = (REAL_BENCH, "sync500-rotation")  # the cell a hand-made reading is named through


# --- the plain reference ------------------------------------------------------


def key(i: int) -> bytes:
    return workload.Signer(bytes([i + 1]) * 32).pub


def by_address(keys):
    return sorted(keys, key=reference_rotation.address)


@pytest.mark.parametrize(
    "genesis,updates,want",
    [
        # one leaves, one joins, equal power: address order decides the seat
        ([(key(i), 10) for i in range(4)], [(key(1), 0), (key(7), 10)],
         [(k, 10) for k in by_address([key(0), key(2), key(3), key(7)])]),
        # more power sorts first, whatever the address
        ([(key(i), 10) for i in range(3)], [(key(5), 30)],
         [(key(5), 30)] + [(k, 10) for k in by_address([key(0), key(1), key(2)])]),
        # a power changed in place
        ([(key(0), 10), (key(1), 10)], [(key(1), 5)], [(key(0), 10), (key(1), 5)]),
        # removal and addition of one key in one change set is a duplicate
        ([(key(0), 10), (key(1), 10)], [(key(1), 0), (key(1), 10)], ValueError),
        ([(key(0), 10)], [(key(3), 0)], ValueError),  # not in the set
        ([(key(0), 10)], [(key(0), 0)], ValueError),  # would leave no validator
        ([(key(0), 10)], [(key(1), -1)], ValueError),
    ],
)
def test_reference_rotation_apply_updates(genesis, updates, want):
    if want is ValueError:
        with pytest.raises(ValueError):
            reference_rotation.apply_updates(genesis, updates)
    else:
        assert reference_rotation.apply_updates(genesis, updates) == want


def test_reference_rotation_chain_answers_the_set_of_each_height():
    genesis = [(key(i), 10) for i in range(4)]
    chain = reference_rotation.Chain(
        genesis, [(3, [(key(0), 0), (key(4), 10)]), (7, [(key(1), 0), (key(5), 10)])]
    )
    first = [(k, 10) for k in by_address([key(i) for i in range(4)])]
    second = [(k, 10) for k in by_address([key(1), key(2), key(3), key(4)])]
    third = [(k, 10) for k in by_address([key(2), key(3), key(4), key(5)])]
    assert [chain.validators_at(h) for h in (1, 2, 3, 6, 7, 90)] == [
        first, first, second, second, third, third,
    ]
    assert [chain.first_height_of_set_at(h) for h in (2, 3, 6, 7)] == [1, 3, 3, 7]
    with pytest.raises(ValueError):
        reference_rotation.Chain(genesis, [(5, []), (5, [])])
    with pytest.raises(ValueError):
        chain.validators_at(0)


def test_reference_rotation_imports_nothing_of_the_program():
    with open(os.path.join(spec.HERE, "reference_rotation.py")) as fh:
        text = fh.read()
    assert "tendermint_tpu" not in text.split('"""', 2)[2]


# --- the files ------------------------------------------------------------------


def test_benchmark_files_agree():
    selftest.test_files()
    real = spec.Spec(REAL_BENCH)
    cell = real.cell("sync500-rotation")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("sync500rot", "rotating-windows", 1)
    config, control = real.config("sync500rot"), real.config("sync500")
    # everything sync500 fixes is kept letter for letter
    for kept in ("validators", "key_type", "voting_power", "verify_window", "absent_share",
                 "nil_share", "lanes_per_call", "signing", "sign_bytes", "chips", "env"):
        assert config[kept] == control[kept], kept
    assert config["guarantees"][: len(control["guarantees"])] == control["guarantees"]
    assert list(config["reduced"]) == ["blocks"]
    assert config["rotate_every"] == config["verify_window"] == 16
    assert (config["first_change_height"] - 1) % 16  # the first change cuts a window
    # away for more than the live sets the program remembers
    from tendermint_tpu.ops import precompute

    assert config["ring_candidates"] - config["ring_seats"] > precompute._ACTIVE_SETS_CAP
    assert config["ring_seats"] >= precompute.BUILD_AT_SIGHTING
    traffic = real.traffic("rotating-windows")
    assert traffic["warm_up_sets"] >= precompute._ACTIVE_SETS_CAP + precompute.BUILD_AT_SIGHTING
    from chipbench.generators import cycle_length

    windows = max(cycle_length(traffic, 5344, 65536), config["ring_candidates"])
    assert windows == 18
    assert config["blocks"] == config["first_change_height"] - 1 + windows * 16
    assert [m["name"] for m in real.metrics_for("end_to_end", "sync500-rotation")] == ["sigs_per_s", "setup_s"]


# --- the generator, in this process -----------------------------------------------


@pytest.fixture
def tiny(monkeypatch):
    """The tiny twin's traffic, built as ``run.py`` builds it."""
    from tendermint_tpu.ops import precompute, resident

    bench = spec.Spec(BENCH)
    cell = bench.cell(CELL)
    config = bench.config(cell["config"])
    for name, value in config["env"].items():
        monkeypatch.setenv(name, str(value))
    precompute.reset()
    resident.reset()
    ctx = Context(cell, config, bench.traffic(cell["traffic"]), SEED, lambda text: None)
    yield spec.generator(ctx.traffic["kind"]).build(ctx)
    precompute.reset()
    resident.reset()


def test_the_generators_sets_are_the_plain_references(tiny):
    assert tiny.n_sets == tiny.count == 12 and tiny.warm_windows == 11
    assert len(tiny.opening) == 2 and {len(w) for w in tiny.windows} == {4}
    members = []
    for tasks in [tiny.opening] + tiny.windows:
        vset = tasks[0].vals
        assert all(t.vals is vset for t in tasks)  # one object a window, as the syncer's
        for t in tasks:
            assert [(v.pub_key.bytes(), v.voting_power) for v in vset.validators] == \
                tiny.chain.validators_at(t.height)
            assert len(tiny._for_block(t.commit)) >= tiny.quorum
        members.append(frozenset(v.pub_key.bytes() for v in vset.validators))
    # consecutive windows differ in one seat, and the cycle closes on itself
    for before, after in zip(members, members[1:] + members[1:2]):
        assert len(before - after) == len(after - before) == 1
    assert members[0] == members[-1]
    # a key that left is away for more than the eight live sets
    gone = next(iter(members[1] - members[2]))
    away = [gone in m for m in members[2:]]
    assert away.index(True) == tiny.ring_size - tiny.seats > 8


def test_old_key_in_the_new_seat_is_refused_at_that_seat(tiny):
    """After a change the newcomer's seat carries a signature by the
    key that just left: a sound signature, by a key the block's own set
    does not seat there."""
    from tendermint_tpu.parallel.pipeline import verify_commits_pipelined

    tasks, want = tiny._faulted(tiny.warm_windows, "old_key_in_new_seat")
    s = (tasks[0].height - tiny.first_change) // tiny.every + 1
    leaves, joins = tiny._leaver(s), tiny._newcomer(s)
    faults = [(b, w) for b, w in enumerate(want) if w != reference_light.OK]
    assert len(faults) == 1
    block, (kind, idx) = faults[0]
    commit = tasks[block].commit
    assert kind == "wrong signature"
    assert tasks[block].vals.validators[idx].pub_key.bytes() == joins.pub
    assert idx in tiny._for_block(commit)[: tiny.quorum]  # before the 2/3 exit
    msg, sig = commit.vote_sign_bytes(workload.CHAIN_ID, idx), commit.signatures[idx].signature
    assert reference.verify(leaves.pub, msg, sig) and not reference.verify(joins.pub, msg, sig)
    got = [tiny._answer(v) for v in verify_commits_pipelined(tasks, use_device=False)]
    assert got == want
    assert [reference_light.verify_block(*tiny._plain(t)) for t in tasks] == want


def test_the_generators_windows_are_the_syncers_on_the_same_chain(tiny, monkeypatch):
    """A real chain with the tiny twin's genesis and schedule (kvstore
    ``val:`` transactions two heights ahead, as ``state/execution``
    delays them), caught up by a ``BlockSyncer``: every list of tasks it
    hands to the verifier is the generator's window of that place — the
    same heights under the same set."""
    from tendermint_tpu.blocksync import BlockSyncer, syncer as syncer_mod
    from tendermint_tpu.types import ExtendedCommit
    from tests.test_blocksync import FakePeer
    from tests.test_execution import advance_one_height, make_chain_env

    windows = [tiny.opening] + tiny.windows[:5]
    top = windows[-1][-1].height + 2
    genesis = tiny.sets[0]
    changes = {}
    for height, updates in tiny.schedule:
        changes[height - 2] = [
            ("val:%s!%d" % (base64.b64encode(pub).decode(), power)).encode()
            for pub, power in updates
        ]
    executor, state, _, _, _ = make_chain_env(validators=genesis)
    ec = ExtendedCommit()
    for h in range(1, top + 1):
        vset = state.validators
        privs = [tiny._by_pub[v.pub_key.bytes()] for v in vset.validators]
        state, ec = advance_one_height(executor, state, privs, vset, changes.get(h, []), ec)

    seen = []
    real_verify = syncer_mod.verify_commits_pipelined

    def recording(tasks, mesh=None, use_device=None):
        seen.append([(t.height, t.vals.hash(), id(t.vals)) for t in tasks])
        return real_verify(tasks, mesh=mesh, use_device=use_device)

    monkeypatch.setattr(syncer_mod, "verify_commits_pipelined", recording)
    follower_exec, follower_state, _, _, _ = make_chain_env(validators=genesis)
    sync = BlockSyncer(
        follower_state, follower_exec, follower_exec.block_store, transport=None,
        verify_window=tiny.verify_window, use_device=False,
    )
    sync.transport = FakePeer(sync.pool, executor.block_store)
    sync.pool.set_peer_range("p1", 1, executor.block_store.height())
    for _ in range(60):
        sync.step()
    assert sync.state.last_block_height >= windows[-1][-1].height
    for ours, theirs in zip(windows, seen):
        assert [(t.height, t.vals.hash()) for t in ours] == [(h, vh) for h, vh, _ in theirs]
        assert len({obj for _, _, obj in theirs}) == 1


# --- the cell's metrics on hand-made spans ----------------------------------------------


def one_window():
    """One call: verify_commits_pipelined 0..1000 holding build_lanes 10..500 (phases 300 + 20; two
    note_validator_set spans of 40 inside it, the first holding a valset_hash of 25), verify_batch 510..900 (a
    building gather_tables, route_lanes holding resident_upload, the drop inside the first note), merge_verdicts 910..950."""
    return [
        span("verify_commits_pipelined", 0, 1000, tasks=2, lanes=8),
        span("build_lanes", 10, 490, lanes=8, sign_bytes_us=300.0, sign_bytes_n=8, basic_checks_us=20.0, basic_checks_n=2),
        span("note_validator_set", 20, 40, recognised=False, retired=1, tables_dropped=1),
        span("valset_hash", 22, 25, validators=12), span("resident_drop", 50, 5, keys=11, departed=1, reason="rotation"),
        span("note_validator_set", 260, 40, recognised=True), span("verify_batch", 510, 390),
        span("gather_tables", 520, 60, builds=1, deferred=2),
        span("route_lanes", 590, 50, resident=6, tables=0, legacy=2, jobs=2),
        span("resident_upload", 600, 30, keys=11, width=64, reason="dropped"), span("merge_verdicts", 910, 40),
    ]


ROTATION = [("pipeline_host_ms", 0.610), ("sign_bytes_ms", 0.300), ("note_set_ms", 0.080), ("valset_hash_ms", 0.025),
            ("table_build_ms", 0.060), ("resident_upload_ms", 0.030), ("resident_drop_ms", 0.005),
            ("legacy_lanes", 2), ("tables_dropped", 1)]


@pytest.mark.parametrize("stem,want", ROTATION)
def test_rotation_metric_on_nested_spans(stem, want):
    assert read(Evidence(one_window()), *REAL, stem) == pytest.approx(want)


def test_rotation_metrics_add_up_on_nested_spans():
    ev = Evidence(one_window())
    named = 0.300 + 0.020 + 0.080 + 0.040  # sign-bytes, basic checks, note, merge
    assert read(ev, *REAL, "pipeline_unnamed_ms") + named == pytest.approx(read(ev, *REAL, "pipeline_host_ms"))
    # a program without the new span and arguments (the parent): nothing to read, or zero, and no error
    ev.spans = [s for s in ev.spans if s["name"] != "resident_drop"]
    for s in ev.spans:
        s["args"].pop("tables_dropped", None)
    assert read(ev, *REAL, "tables_dropped") is None
    assert read(ev, *REAL, "resident_drop_ms") == 0.0
