"""Validator-set precompute cache + digest-keyed result cache
(ops/precompute.py) and their wiring into the verify hot path.

Covers: host table builds vs the big-int oracle, auto-mode eligibility
gating, LRU eviction, rotation invalidation, thread safety, result-cache
verdict caching, and the headline amortization property — the second
verification of the same commit builds ZERO tables (counter-asserted).
"""

import hashlib
import random
import sys
import threading
from collections import OrderedDict

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519_ref as ref
from tendermint_tpu.crypto.keys import Ed25519PrivKey
from tendermint_tpu.ops import ed25519_batch, precompute, verify_batch
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet
from tests.helpers import CHAIN_ID, make_block_id, make_commit, make_validators


@pytest.fixture(autouse=True)
def fresh_caches():
    precompute.reset()
    yield
    precompute.reset()


def keypair(i):
    return ref.keypair_from_seed(bytes([i + 1]) * 32)


def make_batch(n, start=0):
    pks, msgs, sigs = [], [], []
    for i in range(start, start + n):
        priv, pub = keypair(i)
        msg = b"precompute msg %d" % i
        pks.append(pub)
        msgs.append(msg)
        sigs.append(ref.sign(priv, msg))
    return pks, msgs, sigs


def _vset(offset, n=3):
    """Validator set with keys disjoint from every other offset."""
    return make_validators(
        n,
        key_factory=lambda i: Ed25519PrivKey.from_seed(
            (100_000 * offset + i).to_bytes(32, "big")
        ),
    )


# --- host-side table builder ------------------------------------------------


def test_build_table_matches_oracle_multiples():
    pk = keypair(1)[1]
    tab, ok = precompute.build_table(pk)
    assert ok and tab.shape == (8, 4, 32) and tab.dtype == np.uint8
    p = ref.P
    neg_a = ref.pt_neg(ref.pt_decompress_liberal(pk))
    for i in range(8):
        m = ref.pt_mul(i + 1, neg_a)
        zinv = pow(m[2], p - 2, p)
        x, y = m[0] * zinv % p, m[1] * zinv % p
        assert int.from_bytes(tab[i, 0].tobytes(), "little") == (y + x) % p
        assert int.from_bytes(tab[i, 1].tobytes(), "little") == (y - x) % p
        assert int.from_bytes(tab[i, 2].tobytes(), "little") == 1
        assert (
            int.from_bytes(tab[i, 3].tobytes(), "little")
            == 2 * ref.D * x * y % p
        )


def test_build_table_invalid_pubkey_masks_lane():
    for bad in (bytes([2] + [0] * 31), b"short", b""):  # off-curve / malformed
        tab, ok = precompute.build_table(bad)
        assert not ok
        assert (tab == precompute._identity_table()).all()


# --- eligibility + lifecycle ------------------------------------------------


def test_auto_mode_gates_on_eligibility(monkeypatch):
    monkeypatch.delenv(precompute._MODE_ENV, raising=False)
    pk = keypair(1)[1]
    entries, has = precompute.tables.gather([pk])
    assert entries is None and not has.any()
    assert precompute.tables.stats()["builds"] == 0
    precompute.pin_pubkeys([pk])
    entries, has = precompute.tables.gather([pk])
    assert has.all() and entries[0][1] is True
    assert precompute.tables.stats()["builds"] == 1
    precompute.tables.gather([pk])
    s = precompute.tables.stats()
    assert s["builds"] == 1 and s["hits"] == 1


def test_activate_validator_set_makes_keys_eligible():
    privs, vset = _vset(1)
    assert precompute.activate_validator_set(vset) == (True, False)
    assert precompute.activate_validator_set(vset) == (False, True)  # LRU touch
    pks = [v.pub_key.bytes() for v in vset.validators]
    entries, has = precompute.tables.gather(pks)
    assert has.all()
    assert precompute.tables.stats()["builds"] == len(pks)


def test_rotation_invalidates_dropped_keys():
    _, v0 = _vset(1)
    precompute.activate_validator_set(v0)
    pk0 = v0.validators[0].pub_key.bytes()
    precompute.tables.gather([pk0])
    assert len(precompute.tables) == 1
    # Enough newer sets to retire v0 from the live window; its cached
    # table must drop with it (committee rotation).
    for off in range(2, 2 + precompute._ACTIVE_SETS_CAP):
        precompute.activate_validator_set(_vset(off)[1])
    assert len(precompute.tables) == 0
    assert precompute.tables.stats()["invalidations"] == 1
    assert precompute.tables.lookup(pk0) is None


# --- when an eligible key gets its table (PR 30) ------------------------------


def _keys(vset):
    return [v.pub_key.bytes() for v in vset.validators]


def test_the_first_set_is_built_at_first_sight_and_later_keys_in_their_third_batch():
    """The set the cache meets first (a node's own committee) is built
    the first time a batch carries it. A key that becomes eligible
    later goes table-less until the ``BUILD_AT_SIGHTING``-th batch that
    carries it: a light client's pivot, met once or twice, never pays
    for a table; a validator that joined the committee does, once."""
    _, first = _vset(1, n=16)
    assert precompute.activate_validator_set(first) == (True, False)
    assert precompute.tables.gather(_keys(first))[1].all()
    assert precompute.tables.stats()["builds"] == 16
    # a committee that replaced one validator, and a set of another
    # chain altogether: their new keys wait, the known ones still hit
    rotated = first.copy()
    gone = rotated.validators[3].copy()
    gone.voting_power = 0
    rotated.update_with_change_set([gone, Validator(_other_key(1), 10)])
    _, other_chain = _vset(3, n=5)
    known = set(_keys(first))
    for vset, new in ((rotated, 1), (other_chain, 5)):
        assert precompute.activate_validator_set(vset) == (True, False)
        before = precompute.tables.stats()
        for sighting in range(1, precompute.BUILD_AT_SIGHTING):
            has = precompute.tables.gather(_keys(vset))[1]
            assert [bool(h) for h in has] == [pk in known for pk in _keys(vset)]
            s = precompute.tables.stats()
            assert s["builds"] == before["builds"]
            assert s["builds_deferred"] == before["builds_deferred"] + new * sighting
        assert precompute.tables.gather(_keys(vset))[1].all()
        assert precompute.tables.stats()["builds"] == before["builds"] + new
        known.update(_keys(vset))
    assert not precompute.tables._sightings
    # a duplicate signer in one batch is one sighting, and in the batch
    # that builds it one build with every lane served
    _, pivot = _vset(2, n=4)
    precompute.activate_validator_set(pivot)
    dup = _keys(first)[:6] + [_keys(pivot)[0]] * 2
    builds = precompute.tables.stats()["builds"]
    for _ in range(1, precompute.BUILD_AT_SIGHTING):
        assert list(precompute.tables.gather(dup)[1]) == [True] * 6 + [False] * 2
    assert precompute.tables.gather(dup)[1].all()
    assert precompute.tables.stats()["builds"] == builds + 1
    # a key that leaves every live set takes its count with it
    assert set(precompute.tables._sightings) == set()
    precompute.tables.gather(_keys(pivot))
    assert set(precompute.tables._sightings) == set(_keys(pivot)[1:])
    for off in range(10, 10 + precompute._ACTIVE_SETS_CAP):
        precompute.activate_validator_set(_vset(off)[1])
    assert not precompute.tables._sightings


def test_a_set_registered_with_its_hash_is_not_hashed_again(monkeypatch):
    _, first = _vset(1)
    _, second = _vset(2)
    vhash = second.hash()
    precompute.activate_validator_set(first)
    monkeypatch.setattr(
        ValidatorSet, "hash", lambda self: pytest.fail("hashed a set whose hash was handed in")
    )
    assert precompute.activate_validator_set(second, vhash) == (True, False)
    assert precompute.activate_validator_set(second, vhash) == (False, True)
    assert precompute.activate_validator_set(second.copy(), b"ignored: recognised") == (False, True)
    assert precompute.tables.stats()["active_set_hashed"] == 2


def test_sets_retired_and_tables_dropped_are_counted():
    _, first = _vset(1)
    precompute.activate_validator_set(first)
    precompute.tables.gather(_keys(first))
    for off in range(2, 3 + precompute._ACTIVE_SETS_CAP):
        precompute.activate_validator_set(_vset(off)[1])
    s = precompute.tables.stats()
    assert (s["sets_retired"], s["invalidations"], s["entries"]) == (2, 3, 0)
    assert s["active_sets"] == precompute._ACTIVE_SETS_CAP


# --- a live set is recognised by its keys, never by hashing it (PR 29) ------


def _other_key(i=0):
    return Ed25519PrivKey.from_seed((7_000_000 + i).to_bytes(32, "big")).pub_key()


def _reprioritised(v):
    out = v.copy()
    out.increment_proposer_priority(3)
    return out


def _other_powers(v):
    out = v.copy()
    for i, val in enumerate(out.validators):
        val.voting_power += 1 + i
    return out


def _one_replaced(v):
    out = v.copy()
    gone = out.validators[1].copy()
    gone.voting_power = 0
    out.update_with_change_set([gone, Validator(_other_key(), 10)])
    return out


def _appended(v):
    v.validators.append(Validator(_other_key(), 10))
    return v


def _popped(v):
    v.validators.pop()
    return v


def _key_reassigned(v):
    v.validators[0].pub_key = _other_key()
    return v


def _cleared(v):
    precompute.tables.clear()
    return v


def _pushed_out_by_eight_newer_sets(v):
    for off in range(2, 2 + precompute._ACTIVE_SETS_CAP):
        assert precompute.activate_validator_set(_vset(off)[1]) == (True, False)
    return v


@pytest.fixture
def cache_events():
    events = []

    def observer(kind, payload):
        events.append((kind, payload))

    precompute.register_observer(observer)
    yield events
    precompute.unregister_observer(observer)


@pytest.mark.parametrize(
    "derive, recognised",
    [
        pytest.param(lambda v: v, True, id="same_object"),
        pytest.param(lambda v: v.copy(), True, id="copy"),
        pytest.param(_reprioritised, True, id="copy_other_priorities"),
        pytest.param(
            lambda v: ValidatorSet.from_proto_bytes(v.to_proto_bytes()),
            True,
            id="decoded_anew_equal_keys_fresh_objects",
        ),
        pytest.param(_other_powers, True, id="same_ordered_keys_other_powers"),
        pytest.param(_one_replaced, False, id="one_validator_replaced"),
        pytest.param(_appended, False, id="validators_append_in_place"),
        pytest.param(_popped, False, id="validators_pop_in_place"),
        pytest.param(_key_reassigned, False, id="pub_key_reassigned"),
        pytest.param(_cleared, False, id="clear_then_same_set"),
        pytest.param(
            _pushed_out_by_eight_newer_sets, False, id="ninth_set_evicts_the_first"
        ),
    ],
)
def test_live_set_is_recognised_by_its_current_keys(
    derive, recognised, monkeypatch, cache_events
):
    """A set whose ordered keys are those of a live set is an LRU touch
    that never calls ``ValidatorSet.hash``; a set whose keys changed in
    any way, or that left the live window, is hashed and newly active
    on the first call that carries it."""
    _, vset = _vset(1, n=4)
    assert precompute.activate_validator_set(vset) == (True, False)
    pk0 = vset.validators[0].pub_key.bytes()
    precompute.tables.gather([pk0])
    assert precompute.tables.lookup(pk0) is not None
    evicting = derive is _pushed_out_by_eight_newer_sets

    candidate = derive(vset)

    if evicting:  # tuple and key set went together, tables with them
        assert ("rotation", (pk0,)) in cache_events
        assert precompute.tables.lookup(pk0) is None
        assert precompute.tables.stats()["active_sets"] == precompute._ACTIVE_SETS_CAP
    hashed = []
    real_hash = ValidatorSet.hash
    monkeypatch.setattr(
        ValidatorSet, "hash", lambda self: hashed.append(1) or real_hash(self)
    )
    before = precompute.tables.stats()
    newly_active, was_recognised = precompute.activate_validator_set(candidate)
    after = precompute.tables.stats()
    if recognised:
        assert (newly_active, was_recognised) == (False, True)
        assert hashed == []
        assert after["active_sets"] == before["active_sets"] == 1  # no new slot
        assert after["active_set_recognised"] == before["active_set_recognised"] + 1
        assert after["active_set_hashed"] == before["active_set_hashed"]
        assert precompute.tables.lookup(pk0) is not None
    else:
        assert (newly_active, was_recognised) == (True, False)
        assert hashed == [1]
        assert after["active_set_hashed"] == before["active_set_hashed"] + 1
        assert after["active_set_recognised"] == before["active_set_recognised"]
        # and from now on it is the live set it says it is
        assert precompute.activate_validator_set(candidate) == (False, True)
        assert hashed == [1]
        # its keys are eligible: each has its table by the batch that
        # is due to build it
        keys = [v.pub_key.bytes() for v in candidate.validators]
        for _ in range(precompute.BUILD_AT_SIGHTING):
            has_table = precompute.tables.gather(keys)[1]
        assert has_table.all()


def test_recognition_touches_the_entry_it_found():
    """A recognised set moves to the newest end of the live window, as a
    hashed hit always did: seven more sets do not push it out."""
    _, first = _vset(1)
    _, second = _vset(2)
    precompute.activate_validator_set(first)
    precompute.activate_validator_set(second)
    assert precompute.activate_validator_set(first.copy()) == (False, True)
    for off in range(3, 2 + precompute._ACTIVE_SETS_CAP):
        precompute.activate_validator_set(_vset(off)[1])
    assert precompute.activate_validator_set(first) == (False, True)
    assert precompute.activate_validator_set(second) == (True, False)


def test_unreadable_set_takes_the_hash_path():
    """An object with a ``hash()`` but no ``validators`` list is known
    by that hash alone, and never recognised."""

    class Opaque:
        def hash(self):
            return b"\x07" * 32

    assert precompute.activate_validator_set(Opaque()) == (True, False)
    assert precompute.activate_validator_set(Opaque()) == (False, False)
    s = precompute.tables.stats()
    assert s["active_set_hashed"] == 2 and s["active_set_recognised"] == 0
    assert precompute.activate_validator_set(object()) == (False, False)


def test_lru_eviction_bound(monkeypatch):
    monkeypatch.setenv(precompute._MODE_ENV, "all")
    monkeypatch.setenv(precompute._CAP_ENV, "4")
    pks = [keypair(i)[1] for i in range(6)]
    for pk in pks:
        precompute.tables.gather([pk])
    assert len(precompute.tables) == 4
    assert precompute.tables.stats()["evictions"] == 2
    assert precompute.tables.lookup(pks[0]) is None
    assert precompute.tables.lookup(pks[5]) is not None


def test_gather_duplicate_lanes_one_build(monkeypatch):
    monkeypatch.setenv(precompute._MODE_ENV, "all")
    pk = keypair(1)[1]
    entries, has = precompute.tables.gather([pk, pk, pk])
    assert has.all()
    s = precompute.tables.stats()
    assert s["builds"] == 1
    assert all(e is not None for e in entries)


def test_concurrent_gather_is_threadsafe(monkeypatch):
    monkeypatch.setenv(precompute._MODE_ENV, "all")
    pks = [keypair(i)[1] for i in range(8)]
    errors = []

    def worker():
        try:
            for _ in range(10):
                entries, has = precompute.tables.gather(pks)
                assert has.all()
                assert all(e is not None and e[1] for e in entries)
        except Exception as exc:  # pragma: no cover - failure path
            errors.append(exc)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors
    # every key built exactly once, ever (gather serializes on the lock)
    assert precompute.tables.stats()["builds"] == len(pks)


def test_concurrent_activation_counts_every_call_and_keeps_the_window():
    """Sixteen threads activate copies of two live sets while two rotate
    ten new ones through the window: every call is counted once, as
    recognised or as hashed, and the window never outgrows its cap."""
    import sys

    live = [_vset(off)[1] for off in (1, 2)]
    rotating = [_vset(off)[1] for off in range(3, 13)]
    for v in live:
        precompute.activate_validator_set(v)
    precompute.tables.reset_stats()
    results, errors = [], []

    def reader(v):
        try:
            for _ in range(200):
                results.append(precompute.activate_validator_set(v.copy()))
                assert precompute.tables.stats()["active_sets"] <= precompute._ACTIVE_SETS_CAP
        except Exception as exc:  # pragma: no cover - failure path
            errors.append(exc)

    def rotator(sets):
        try:
            for v in sets:
                results.append(precompute.activate_validator_set(v))
        except Exception as exc:  # pragma: no cover - failure path
            errors.append(exc)

    threads = [threading.Thread(target=reader, args=(live[i % 2],)) for i in range(16)]
    threads += [threading.Thread(target=rotator, args=(rotating[i::2],)) for i in range(2)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    s = precompute.tables.stats()
    assert len(results) == 16 * 200 + 10
    assert s["active_set_recognised"] + s["active_set_hashed"] == len(results)
    assert s["active_set_recognised"] == sum(r == (False, True) for r in results)
    # a hashed set was unknown (newly active: each rotating set once, and
    # a live one again if the rotation pushed it out between two reads)
    # or registered by another thread in between
    newly = sum(r == (True, False) for r in results)
    assert newly >= 10
    assert s["active_set_hashed"] == newly + sum(r == (False, False) for r in results)
    assert s["active_sets"] == precompute._ACTIVE_SETS_CAP


# --- result cache -----------------------------------------------------------


def test_result_cache_caches_both_verdicts(monkeypatch):
    monkeypatch.setenv(precompute._RESULT_ENV, "1")
    rc = precompute.results
    pk, msg = b"k" * 32, b"msg"
    assert rc.get(pk, msg, b"s" * 64) is None
    rc.put(pk, msg, b"s" * 64, True)
    rc.put(pk, msg, b"t" * 64, False)
    assert rc.get(pk, msg, b"s" * 64) is True
    assert rc.get(pk, msg, b"t" * 64) is False
    s = rc.stats()
    assert s["hits"] == 2 and s["misses"] == 1


def test_result_cache_respects_cap(monkeypatch):
    monkeypatch.setenv(precompute._RESULT_ENV, "1")
    monkeypatch.setenv(precompute._RESULT_CAP_ENV, "3")
    rc = precompute.results
    for i in range(5):
        rc.put(b"k" * 32, b"m%d" % i, b"s" * 64, True)
    assert len(rc) == 3


def test_result_cache_disabled_is_inert(monkeypatch):
    monkeypatch.setenv(precompute._RESULT_ENV, "0")
    rc = precompute.results
    rc.put(b"k" * 32, b"m", b"s" * 64, True)
    assert len(rc) == 0
    assert rc.get(b"k" * 32, b"m", b"s" * 64) is None
    assert rc.stats()["misses"] == 0  # disabled lookups don't count


def test_verify_batch_answers_repeats_from_result_cache(monkeypatch):
    monkeypatch.setenv(precompute._RESULT_ENV, "1")
    pks, msgs, sigs = make_batch(20)
    sigs[4] = sigs[4][:33] + bytes([sigs[4][33] ^ 1]) + sigs[4][34:]
    want = [ref.verify_zip215(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    assert verify_batch(pks, msgs, sigs) == want

    calls = []
    orig = ed25519_batch._verify_uncached

    def spy(pks_, msgs_, sigs_, backend=None):
        calls.append(len(pks_))
        return orig(pks_, msgs_, sigs_, backend)

    monkeypatch.setattr(ed25519_batch, "_verify_uncached", spy)
    assert verify_batch(pks, msgs, sigs) == want  # all 20 lanes cached
    assert calls == []
    # a new lane among repeats only re-verifies the new lane
    pks2, msgs2, sigs2 = make_batch(1, start=40)
    assert verify_batch(pks + pks2, msgs + msgs2, sigs + sigs2) == want + [True]
    assert calls == [1]


# --- the result cache a batch at a time --------------------------------------


class LaneByLaneCache:
    """The per-lane ``get`` / ``put`` the batch forms replace, written
    out: what ``get_many`` / ``put_many`` must leave behind, entry for
    entry and count for count."""

    def __init__(self):
        self.entries = OrderedDict()
        self.hits = self.misses = self.evictions = 0

    @staticmethod
    def key(pk, msg, sig):
        return b"".join((pk, hashlib.sha256(msg).digest(), sig))

    def get(self, pk, msg, sig):
        key = self.key(pk, msg, sig)
        if key in self.entries:
            self.entries.move_to_end(key)
            self.hits += 1
            return self.entries[key]
        self.misses += 1
        return None

    def put(self, pk, msg, sig, verdict, cap):
        key = self.key(pk, msg, sig)
        self.entries[key] = bool(verdict)
        self.entries.move_to_end(key)
        while len(self.entries) > cap:
            self.entries.popitem(last=False)
            self.evictions += 1

    def batch(self, lanes, verdict_of, cap):
        """One ``verify_batch`` of the parent: look every lane up, then
        store the verdict of every lane that missed."""
        pending = [lane for lane in lanes if self.get(*lane) is None]
        for lane in pending:
            self.put(*lane, verdict_of(lane), cap)
        return len(lanes) - len(pending)


class TotalsOnly:
    """A bound ``metrics``: counters that refuse an empty increment."""

    class Counter:
        def __init__(self):
            self.total = 0

        def inc(self, n=1):
            assert n > 0
            self.total += n

    def __init__(self):
        self.result_cache_hits = self.Counter()
        self.result_cache_misses = self.Counter()


@pytest.mark.parametrize("seed", range(6))
def test_batch_forms_leave_what_the_lane_by_lane_loop_leaves(seed, monkeypatch):
    """Random batches over a dozen triples and a cap of a few entries:
    hits mixed in, repeats inside a batch, both verdicts, batches larger
    than the cap. After every batch the cache's contents, their order
    and the counters equal the per-lane model's."""
    monkeypatch.setenv(precompute._RESULT_ENV, "1")
    rng = random.Random(seed)
    cap = rng.choice([1, 3, 4, 7])
    monkeypatch.setenv(precompute._RESULT_CAP_ENV, str(cap))
    universe = [
        (bytes([i]) * 32, b"m" * (i % 4) + bytes([i // 2]), bytes([i % 5]) * 64)
        for i in range(12)
    ]

    def verdict_of(lane):
        return lane[0][0] % 3 != 0

    rc, model, metrics = precompute.results, LaneByLaneCache(), TotalsOnly()
    rc.bind_metrics(metrics)
    try:
        for _ in range(300):
            lanes = rng.choices(universe, k=rng.choice([0, 1, 2, 3, 5, 9]))
            want_hits = model.batch(lanes, verdict_of, cap)

            keys, cached = rc.get_many(*map(list, zip(*lanes))) if lanes else ([], None)
            assert keys == [model.key(*lane) for lane in lanes]
            if cached is None:
                pending = list(range(len(lanes)))
            else:
                assert [c for c in cached if c is not None] == [
                    verdict_of(lane) for lane, c in zip(lanes, cached) if c is not None
                ]
                pending = [i for i, c in enumerate(cached) if c is None]
            assert len(lanes) - len(pending) == want_hits
            rc.put_many(
                [keys[i] for i in pending],
                np.array([verdict_of(lanes[i]) for i in pending], dtype=bool),
            )

            assert list(rc._entries.items()) == list(model.entries.items())
            got = rc.stats()
            assert (got["entries"], got["hits"], got["misses"], got["evictions"]) == (
                len(model.entries), model.hits, model.misses, model.evictions,
            )
            assert metrics.result_cache_hits.total == model.hits
            assert metrics.result_cache_misses.total == model.misses
    finally:
        rc.bind_metrics(None)
    assert model.hits and model.evictions  # the sequence reached both


class CountingLock:
    def __init__(self):
        self.acquired = 0
        self._lock = threading.Lock()

    def __enter__(self):
        self.acquired += 1
        return self._lock.__enter__()

    def __exit__(self, *exc):
        return self._lock.__exit__(*exc)


@pytest.fixture
def stubbed_engine(monkeypatch):
    """``verify_batch`` with the device taken out (``_verify_uncached``
    answers from the lane's first byte), SHA-256 counted and the verdict
    cache's lock counted: ``seen`` holds, for each engine call, (lanes,
    digests so far, lock acquisitions so far)."""
    monkeypatch.setenv(precompute._RESULT_ENV, "1")
    counts = {"sha256": 0}
    sha256 = hashlib.sha256

    def counted(*args, **kwargs):
        counts["sha256"] += 1
        return sha256(*args, **kwargs)

    lock = CountingLock()
    seen = []

    def engine(pks, msgs, sigs, backend=None):
        seen.append((len(pks), counts["sha256"], lock.acquired))
        return np.array([pk[0] % 2 == 0 for pk in pks], dtype=bool)

    monkeypatch.setattr(hashlib, "sha256", counted)
    monkeypatch.setattr(precompute.results, "_lock", lock)
    monkeypatch.setattr(ed25519_batch, "_verify_uncached", engine)
    return counts, lock, seen


def random_lanes(n, seed):
    rng = random.Random(seed)
    return (
        [rng.randbytes(32) for _ in range(n)],
        [rng.randbytes(112 + (i & 1)) for i in range(n)],
        [rng.randbytes(64) for _ in range(n)],
    )


def test_verify_batch_digests_once_a_lane_and_locks_once_an_operation(stubbed_engine):
    counts, lock, seen = stubbed_engine
    n = 3000
    pks, msgs, sigs = random_lanes(n, 31)
    want = [pk[0] % 2 == 0 for pk in pks]

    def call(*lanes):
        """(verdicts, digests taken, holds of the lock) of one call."""
        before = counts["sha256"], lock.acquired
        verdicts = verify_batch(*lanes)
        return verdicts, counts["sha256"] - before[0], lock.acquired - before[1]

    # unseen lanes: one digest a lane and one hold of the lock before the
    # engine runs, one more hold and no digest to store its answers
    assert call(pks, msgs, sigs) == (want, n, 2)
    assert seen == [(n, n, 1)]
    assert precompute.results.stats() == {
        "entries": n, "hits": 0, "misses": n, "evictions": 0,
    }

    # the same lanes again: answered under one hold, nothing stored
    assert call(pks, msgs, sigs) == (want, n, 1)
    assert len(seen) == 1

    # hits and misses mixed, a fresh triple carried twice: the engine gets
    # the misses only (the repeat twice), and the cache one entry for it
    new = random_lanes(500, 32)
    mixed = [a[:1000] + b + b[:1] for a, b in zip((pks, msgs, sigs), new)]
    want_mixed = want[:1000] + [pk[0] % 2 == 0 for pk in mixed[0][1000:]]
    held = lock.acquired
    assert call(*mixed) == (want_mixed, 1501, 2)
    assert seen[1] == (501, 2 * n + 1501, held + 1)
    assert precompute.results.stats() == {
        "entries": n + 500, "hits": n + 1000, "misses": n + 501, "evictions": 0,
    }


def test_switch_and_cap_are_read_again_every_batch(stubbed_engine, monkeypatch):
    counts, lock, seen = stubbed_engine
    pks, msgs, sigs = random_lanes(40, 33)
    want = [pk[0] % 2 == 0 for pk in pks]
    assert verify_batch(pks, msgs, sigs) == want
    stats = precompute.results.stats()
    before = (counts["sha256"], lock.acquired)

    # switched off between two calls: the second neither asks nor fills
    monkeypatch.setenv(precompute._RESULT_ENV, "0")
    assert verify_batch(pks, msgs, sigs) == want
    assert [lanes for lanes, _, _ in seen] == [40, 40]
    assert (counts["sha256"], lock.acquired) == before
    assert precompute.results.stats() == stats
    assert precompute.results.get_many(pks, msgs, sigs) == (None, None)
    assert precompute.results.put_many([b"k"], [True]) == 0

    # the cap lowered between two stores: the second evicts down to it,
    # oldest first
    monkeypatch.setenv(precompute._RESULT_ENV, "1")
    monkeypatch.setenv(precompute._RESULT_CAP_ENV, "3")
    newest = list(precompute.results._entries)[-2:]
    assert precompute.results.put_many([b"one more"], [False]) == 38
    assert list(precompute.results._entries.items()) == [
        (newest[0], want[-2]), (newest[1], want[-1]), (b"one more", False),
    ]
    assert precompute.results.stats()["evictions"] == 38


def test_concurrent_batches_lose_no_count(monkeypatch):
    """More threads than cores, each looking up and storing batches of
    keys no other thread sends: a lost update would break ``misses`` =
    lanes asked, ``entries`` + ``evictions`` = lanes stored."""
    monkeypatch.setenv(precompute._RESULT_ENV, "1")
    monkeypatch.setenv(precompute._RESULT_CAP_ENV, "64")
    rc = precompute.results
    threads, batches, lanes = 12, 150, 20
    errors = []

    def work(t):
        try:
            for b in range(batches):
                pks = [bytes([t, b, i]) + bytes(29) for i in range(lanes)]
                keys, cached = rc.get_many(pks, [b"m"] * lanes, [bytes(64)] * lanes)
                assert cached is None
                rc.put_many(keys, [True] * lanes)
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=work, args=(t,)) for t in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not errors and not any(w.is_alive() for w in workers)
    s = rc.stats()
    assert (s["hits"], s["misses"]) == (0, threads * batches * lanes)
    assert s["entries"] == 64
    assert s["entries"] + s["evictions"] == threads * batches * lanes


# --- the headline amortization property -------------------------------------


def test_second_commit_verification_builds_zero_tables(monkeypatch):
    """ISSUE acceptance: a 100-validator commit verified twice performs
    zero table builds on the second call — every lane gathers its
    precomputed column. Result cache disabled so the kernel path (not a
    verdict replay) is what's exercised twice."""
    monkeypatch.setenv(precompute._RESULT_ENV, "0")
    from tendermint_tpu.types import validation

    privs, vset = make_validators(100)
    block_id = make_block_id()
    commit = make_commit(block_id, 5, 0, vset, privs)

    validation.verify_commit(CHAIN_ID, vset, block_id, 5, commit)
    s1 = precompute.tables.stats()
    assert s1["builds"] == 100  # one host build per distinct validator

    validation.verify_commit(CHAIN_ID, vset, block_id, 5, commit)
    s2 = precompute.tables.stats()
    assert s2["builds"] == s1["builds"]  # ZERO builds on the 2nd pass
    assert s2["hits"] >= s1["hits"] + 100


def test_table_path_agrees_with_oracle(monkeypatch):
    """XLA table kernel vs oracle, including a masked-invalid-key lane
    and a corrupted signature, with every lane eligible."""
    monkeypatch.setenv(precompute._MODE_ENV, "all")
    monkeypatch.setenv(precompute._RESULT_ENV, "0")
    pks, msgs, sigs = make_batch(20)
    pks[0] = bytes([2] + [0] * 31)  # off-curve: identity table + ok=False
    pks[1] = (ref.P + 1).to_bytes(32, "little")  # non-canonical encoding
    sigs[2] = sigs[2][:32] + bytes(32)  # zeroed s
    sigs[3] = bytes(32) + sigs[3][32:]  # R replaced (y=0 IS on curve)
    want = [ref.verify_zip215(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    got = verify_batch(pks, msgs, sigs)
    assert got == want
    # all lanes rode the table path (eligible in "all" mode)
    s = precompute.tables.stats()
    assert s["builds"] == len(set(pks))


# --- a committee that replaces one validator a step (PR 32) -------------------


def _ring_key(i):
    return Ed25519PrivKey.from_seed((7_000_000 + i).to_bytes(32, "big")).pub_key()


def _one_seat_changed(vset, leaves, joins):
    """``vset`` after one validator left and one joined at its power, as
    ``state/execution`` produces the next set."""
    out = vset.copy()
    gone = next(v for v in out.validators if v.pub_key.bytes() == leaves).copy()
    gone.voting_power = 0
    out.update_with_change_set([gone, Validator(joins, 10)])
    return out


def _last_note(tracer):
    notes = [
        e["args"] for e in tracer.export(clear=True)["traceEvents"]
        if e.get("ph") == "X" and e["name"] == "note_validator_set"
    ]
    assert len(notes) == 1
    return notes[0]


@pytest.mark.parametrize("seed", range(4))
def test_random_one_seat_changes_keep_tables_and_sightings_for_live_keys_only(seed, ring_tracer):
    """Thirty sets, each the last with one validator replaced by a new
    key or by one that left earlier. After every batch: a key has a host
    table exactly if it was built at first sight (the first set's keys,
    while that set is live) or carried by three batches; a key outside
    every live set has no table and no count; and the
    ``note_validator_set`` span says what the counters say."""
    from tendermint_tpu.crypto import batch as crypto_batch

    rng = random.Random(seed)
    pool = [_ring_key(100 * seed + i) for i in range(14)]
    vset = ValidatorSet([Validator(k, 10) for k in pool[:8]])
    first = set(_keys(vset))
    live = []  # key sets of the live sets, least lately met first
    carried, tabled, eligible = {}, set(), set()
    for step in range(30):
        if step:
            seated = _keys(vset)
            away = [k for k in pool if k.bytes() not in seated]
            vset = _one_seat_changed(vset, rng.choice(seated), rng.choice(away))
        before = precompute.tables.stats()
        members = set(_keys(vset))
        if members in live:  # a change that undid an earlier one: the set is still live
            assert crypto_batch.note_validator_set_traced(vset) == (False, True)
            live.remove(members)
            live.append(members)
            assert "retired" not in _last_note(ring_tracer)
            assert precompute.tables.stats()["sets_retired"] == before["sets_retired"]
        else:
            assert crypto_batch.note_validator_set_traced(vset) == (True, False)
            retired = len(live) == precompute._ACTIVE_SETS_CAP
            live = (live + [members])[-precompute._ACTIVE_SETS_CAP:]
            eligible = set().union(*live)
            dropped = {pk for pk in tabled if pk not in eligible}
            tabled -= dropped
            carried = {pk: n for pk, n in carried.items() if pk in eligible}
            note, after = _last_note(ring_tracer), precompute.tables.stats()
            assert note["recognised"] is False and note["newly_active"] is True
            assert note["retired"] == after["sets_retired"] - before["sets_retired"] == retired
            assert note["tables_dropped"] == after["invalidations"] - before["invalidations"] == len(dropped)
        founders = first if first in live else set()
        has = precompute.tables.gather(_keys(vset))[1]
        for pk in _keys(vset):
            if pk in tabled:
                continue
            carried[pk] = carried.get(pk, 0) + 1
            if pk in founders or carried[pk] >= precompute.BUILD_AT_SIGHTING:
                tabled.add(pk)
                del carried[pk]
        assert [bool(h) for h in has] == [pk in tabled for pk in _keys(vset)]
        assert set(precompute.tables._entries) == tabled
        assert precompute.tables._sightings == carried
        for k in pool:
            if k.bytes() not in eligible:
                assert precompute.tables.lookup(k.bytes()) is None
                assert k.bytes() not in precompute.tables._sightings
    # a recognised set says nothing of retirement: nothing was pushed out
    assert crypto_batch.note_validator_set_traced(vset) == (False, True)
    assert "retired" not in _last_note(ring_tracer)


@pytest.mark.parametrize(
    "away, known",
    [
        pytest.param(7, True, id="back_inside_the_eight_live_sets"),
        pytest.param(8, False, id="back_as_the_ninth_set"),
        pytest.param(9, False, id="back_after_nine_sets"),
    ],
)
def test_a_key_that_returns_after_the_live_sets_forgot_it_is_built_at_its_third_batch_again(away, known):
    """A key seated for three sets (table-less twice, built in its third
    batch), then away for ``away`` sets that each replace another
    validator. Back inside the live window it still has its table; once
    the last set that held it was retired it has neither a table nor a
    count, and waits for its third batch again."""
    fill = [_ring_key(i) for i in range(40)]
    vset = ValidatorSet([Validator(k, 10) for k in fill[:6]])
    precompute.activate_validator_set(vset)
    precompute.tables.gather(_keys(vset))
    ours, nxt = fill[39], 6

    def step(leaves, joins):
        nonlocal vset
        vset = _one_seat_changed(vset, leaves, joins)
        assert precompute.activate_validator_set(vset) == (True, False)
        return list(precompute.tables.gather(_keys(vset))[1])

    seat = lambda: _keys(vset).index(ours.bytes())
    assert step(fill[0].bytes(), ours)[seat()] is np.False_
    for _ in range(2):  # two more sets carry it: the third batch builds
        had = step(fill[nxt - 5].bytes(), fill[nxt])
        nxt += 1
    assert had[seat()] and precompute.tables.stats()["builds"] == 6 + 1
    step(ours.bytes(), fill[nxt])  # it leaves: the first of the sets without it
    nxt += 1
    for _ in range(away - 1):
        step(fill[nxt - 5].bytes(), fill[nxt])
        nxt += 1
    assert (precompute.tables.lookup(ours.bytes()) is not None) is known
    builds = precompute.tables.stats()["builds"]
    back = step(fill[nxt - 5].bytes(), ours)
    assert bool(back[seat()]) is known
    if not known:
        assert precompute.tables._sightings[ours.bytes()] == 1
        assert not step(fill[nxt - 4].bytes(), fill[nxt])[seat()]
        assert step(fill[nxt - 3].bytes(), fill[nxt + 1])[seat()]
        # ours, and the two other newcomers whose third batch fell here
        assert precompute.tables.lookup(ours.bytes()) is not None
        assert precompute.tables.stats()["builds"] > builds
