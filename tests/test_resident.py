"""Device-resident precompute table store (ops/resident.py).

The perf contract under test: a validator set's tables ship to the
device ONCE, steady-state batches carry only (N,) int32 gather indices,
and the device copy is invalidated in lockstep with the host cache on
rotation/eviction — a stale device tensor must never verify a
rotated-out key. H2D accounting (``ops_table_h2d_bytes_total``) covers
both the resident uploads and the legacy gathered-tensor path, so the
acceptance assertion is simply: the counter is FLAT across second and
later batches of the same committee.
"""

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519_ref as ref
from tendermint_tpu.crypto.keys import Ed25519PrivKey
from tendermint_tpu.libs.metrics import OpsMetrics, Registry
from tendermint_tpu.ops import ed25519_batch, precompute, resident
from tests.helpers import CHAIN_ID, make_validators


@pytest.fixture(autouse=True)
def _resident_on(monkeypatch):
    """Force the store on (auto keeps CPU off), isolate cache + store
    state per test."""
    monkeypatch.setenv("TENDERMINT_TPU_RESIDENT", "on")
    precompute.reset()
    resident.reset()
    yield
    precompute.reset()
    resident.reset()


def _batch(n, seed=50):
    pks, msgs, sigs = [], [], []
    for i in range(n):
        sk, pk = ref.keypair_from_seed(bytes([seed + i]) * 32)
        m = b"resident lane %03d" % i
        pks.append(pk)
        msgs.append(m)
        sigs.append(ref.sign(sk, m))
    return pks, msgs, sigs


def _h2d_total():
    s = resident.stats()
    return int(s["h2d_bytes"]) + int(s["gathered_h2d_bytes"])


# --- steady state: one upload, then index-only batches ----------------------


def test_second_batch_ships_zero_table_bytes():
    """Acceptance: ops_table_h2d_bytes_total is flat across 2nd+
    batches of a pinned committee, verdicts exact with a bad lane."""
    reg = Registry()
    ops = OpsMetrics(reg)
    resident.bind_metrics(ops)
    pks, msgs, sigs = _batch(16)
    precompute.pin_pubkeys(pks)
    sigs[3] = bytes(64)

    oks = ed25519_batch.verify_batch(pks, msgs, sigs)
    assert not oks[3] and sum(oks) == 15
    after_first = _h2d_total()
    metric_first = ops.table_h2d_bytes._values.get((), 0.0)
    assert after_first > 0, "first batch must pay the table upload"
    assert metric_first == after_first

    for _ in range(2):  # 2nd and 3rd batches: zero table H2D
        oks = ed25519_batch.verify_batch(pks, msgs, sigs)
        assert not oks[3] and sum(oks) == 15
    assert _h2d_total() == after_first
    assert ops.table_h2d_bytes._values.get((), 0.0) == metric_first
    s = resident.stats()
    assert s["uploads"] == 1 and s["hits"] >= 32 and s["misses"] == 0


def test_resident_hit_miss_metrics_wired():
    reg = Registry()
    ops = OpsMetrics(reg)
    resident.bind_metrics(ops)
    pks, msgs, sigs = _batch(4)
    precompute.pin_pubkeys(pks)
    ed25519_batch.verify_batch(pks, msgs, sigs)
    assert ops.table_resident_hits._values.get((), 0.0) == 4
    # Un-pinned fresh keys verify legacy: no resident lookups at all.
    p2, m2, s2 = _batch(2, seed=90)
    ed25519_batch.verify_batch(p2, m2, s2)
    assert ops.table_resident_hits._values.get((), 0.0) == 4


def test_committee_growth_refreshes_store_once():
    """A new pinned key joining the committee triggers ONE refresh
    upload; the grown store then serves every lane index-only."""
    pks, msgs, sigs = _batch(6)
    precompute.pin_pubkeys(pks[:4])
    ed25519_batch.verify_batch(pks[:4], msgs[:4], sigs[:4])
    assert resident.stats()["uploads"] == 1
    precompute.pin_pubkeys(pks)  # two newcomers
    oks = ed25519_batch.verify_batch(pks, msgs, sigs)
    assert all(oks)
    s = resident.stats()
    assert s["uploads"] == 2 and s["resident_keys"] == 6
    before = _h2d_total()
    ed25519_batch.verify_batch(pks, msgs, sigs)
    assert _h2d_total() == before


@pytest.mark.parametrize(
    "first,then,widths",
    [(4, 6, (64, 64)), (62, 63, (64, 64)), (63, 64, (64, 128))],
    ids=["few", "fills-the-width", "crosses-the-width"],
)
def test_store_width_is_a_power_of_two_so_growth_rarely_changes_its_shape(first, then, widths):
    """The kernels compile for the store's width. A key that joins the
    store (a validator first carried heights after its set was seen: a
    light commit stops at 2/3) must not change that width, or the next
    batch waits for a compile; the width holds the pad column, doubles
    when full, and the columns behind the real keys are pad tables."""
    pks = [ref.keypair_from_seed(i.to_bytes(2, "big") * 16)[1] for i in range(then)]
    has_table = np.ones(then, dtype=bool)
    precompute.pin_pubkeys(pks[:first])
    precompute.tables.gather(pks[:first])
    got = resident.acquire(pks[:first], has_table[:first])
    assert got is not None and got[3].shape == (8, 4, 32, widths[0])
    precompute.pin_pubkeys(pks)
    precompute.tables.gather(pks)
    res_mask, idx, ok, tab_dev, _ = resident.acquire(pks, has_table)
    assert tab_dev.shape == (8, 4, 32, widths[1]) and len(ok) == widths[1]
    assert res_mask.all() and sorted(idx) == list(range(1, then + 1))
    s = resident.stats()
    assert s["uploads"] == 2 and s["resident_keys"] == then
    pad = ed25519_batch._pad_table()
    host = np.asarray(tab_dev)
    assert (host[..., then + 1:] == pad[..., None]).all() and (host[..., 0] == pad).all()


# --- invalidation in lockstep with the host cache ---------------------------


def _vset(offset, n=3):
    return make_validators(
        n,
        key_factory=lambda i: Ed25519PrivKey.from_seed(
            (200_000 * offset + i).to_bytes(32, "big")
        ),
    )


def test_rotation_invalidates_device_copy():
    """Regression: validator rotation must drop the device tensor — the
    rotated-out keys disappear from the store and their next batch does
    NOT ride a stale resident gather."""
    privs, vset1 = _vset(1)
    precompute.activate_validator_set(vset1)
    pks = [v.pub_key.bytes() for v in vset1.validators]
    msgs = [b"rotation msg %d" % i for i in range(len(pks))]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    assert all(ed25519_batch.verify_batch(pks, msgs, sigs))
    assert resident.stats()["resident_keys"] == len(pks)

    # Push vset1 out of the live-set window (8 deep): true rotation.
    for off in range(2, 11):
        _, nxt = _vset(off)
        precompute.activate_validator_set(nxt)
    s = resident.stats()
    assert s["invalidations"] >= 1 and s["resident_keys"] == 0
    # Rotated-out keys still verify correctly (host-ineligible path).
    bad = list(sigs)
    bad[1] = bytes(64)
    oks = ed25519_batch.verify_batch(pks, msgs, bad)
    assert not oks[1] and sum(oks) == len(pks) - 1
    assert resident.stats()["resident_keys"] == 0


def test_cache_clear_clears_store():
    pks, msgs, sigs = _batch(4)
    precompute.pin_pubkeys(pks)
    ed25519_batch.verify_batch(pks, msgs, sigs)
    assert resident.stats()["resident_keys"] == 4
    precompute.reset()
    assert resident.stats()["resident_keys"] == 0


def test_lru_eviction_invalidates_device_copy(monkeypatch):
    """An LRU eviction on the host cache must invalidate the device
    store (the evicted column would otherwise verify stale)."""
    monkeypatch.setenv("TENDERMINT_TPU_PRECOMPUTE_CAP", "4")
    pks, msgs, sigs = _batch(4)
    precompute.pin_pubkeys(pks)
    ed25519_batch.verify_batch(pks, msgs, sigs)
    assert resident.stats()["resident_keys"] == 4
    inval_before = resident.stats()["invalidations"]
    # Two more pinned keys overflow the cap: their builds evict the two
    # LRU columns, which must drop the device tensor mid-batch (the
    # store then re-uploads the surviving committee).
    extra_p, extra_m, extra_s = _batch(2, seed=120)
    precompute.pin_pubkeys(extra_p)
    oks = ed25519_batch.verify_batch(extra_p, extra_m, extra_s)
    assert all(oks)
    assert resident.stats()["invalidations"] > inval_before
    oks = ed25519_batch.verify_batch(pks + extra_p, msgs + extra_m, sigs + extra_s)
    assert all(oks)


# --- result-cache interaction: hits skip the gather entirely ----------------


def test_cached_batch_skips_table_gather(monkeypatch):
    """Regression (ISSUE 8 satellite): a repeat batch answered by the
    digest-keyed result cache must do NO table gather and ship NO table
    bytes — cache-hit lanes never touch the table machinery."""
    monkeypatch.setenv("TENDERMINT_TPU_RESULT_CACHE", "1")
    pks, msgs, sigs = _batch(8)
    precompute.pin_pubkeys(pks)
    assert all(ed25519_batch.verify_batch(pks, msgs, sigs))

    calls = []
    orig = precompute.tables.gather

    def spy(pubkeys):
        calls.append(len(pubkeys))
        return orig(pubkeys)

    monkeypatch.setattr(precompute.tables, "gather", spy)
    before = _h2d_total()
    assert all(ed25519_batch.verify_batch(pks, msgs, sigs))
    assert calls == [], "cache-hit batch must not gather tables"
    assert _h2d_total() == before


# --- fallback ladder --------------------------------------------------------


def test_off_mode_disables_acquire(monkeypatch):
    monkeypatch.setenv("TENDERMINT_TPU_RESIDENT", "off")
    pks, msgs, sigs = _batch(4)
    precompute.pin_pubkeys(pks)
    oks = ed25519_batch.verify_batch(pks, msgs, sigs)
    assert all(oks)
    s = resident.stats()
    assert s["uploads"] == 0 and s["resident_keys"] == 0
    # Gathered path still pays per-batch table bytes — and counts them.
    assert s["gathered_h2d_bytes"] > 0


def test_acquire_failure_never_gates_verification(monkeypatch):
    def boom(pubkeys, has_table, plan=None, backend=None):
        raise RuntimeError("injected store failure")

    monkeypatch.setattr(resident, "acquire", boom)
    pks, msgs, sigs = _batch(4)
    precompute.pin_pubkeys(pks)
    sigs[0] = bytes(64)
    oks = ed25519_batch.verify_batch(pks, msgs, sigs)
    assert not oks[0] and sum(oks) == 3


def test_hot_keys_promote_to_pinned():
    """verifyd flush notifications promote repeat offenders into the
    pinned set so their tables go (and stay) device-resident."""
    pks, _, _ = _batch(3, seed=150)
    resident.note_hot_keys(pks)
    resident.note_hot_keys(pks)  # threshold 2 -> pin
    entries, has_table = precompute.tables.gather(pks)
    assert entries is not None and has_table.all()


def test_tenant_pin_quota_caps_one_namespace():
    """A tenant over its pin quota stops accumulating pins (counted as
    denials), while other tenants keep their full quota."""
    a_pks, _, _ = _batch(3, seed=160)
    b_pks, _, _ = _batch(2, seed=170)
    for _ in range(2):  # threshold 2 -> pin attempts
        resident.note_hot_keys(a_pks, tenant="chain-a", quota=2)
    for _ in range(2):
        resident.note_hot_keys(b_pks, tenant="chain-b", quota=2)
    pins = resident.store.tenant_pins()
    assert pins["chain-a"] == 2  # third key denied at the quota
    assert pins["chain-b"] == 2  # isolated: unaffected by a's denial
    assert resident.stats()["pin_quota_denials"] >= 1
    # the denied key was NOT pinned: only a's first two made the store
    _, has_table = precompute.tables.gather(a_pks)
    assert has_table[:2].all() and not has_table[2]


# --- a committee that replaces one validator a step (PR 32) -------------------


def _spans(tracer, *names):
    return [
        (e["name"], e["args"]) for e in tracer.export(clear=True)["traceEvents"]
        if e.get("ph") == "X" and e["name"] in names
    ]


def _acquire(pks, backend=None):
    """What ``verify_batch`` does before it builds its jobs."""
    _, has_table = precompute.tables.gather(pks)
    return resident.acquire(pks, has_table, backend=backend)


def _evict(monkeypatch):
    monkeypatch.setenv("TENDERMINT_TPU_PRECOMPUTE_CAP", "4")
    more, _, _ = _batch(1, seed=90)
    precompute.pin_pubkeys(more)
    precompute.tables.gather(more)  # the fifth table pushes the oldest out


def _rotate(monkeypatch):
    for off in range(2, 2 + precompute._ACTIVE_SETS_CAP):
        precompute.activate_validator_set(_vset(off)[1])


@pytest.mark.parametrize(
    "drop, reason",
    [
        pytest.param(_rotate, "rotation", id="rotation"),
        pytest.param(_evict, "evict", id="evict"),
        pytest.param(lambda monkeypatch: precompute.tables.clear(), "clear", id="clear"),
    ],
)
def test_drop_and_upload_spans_say_why_and_what_the_counters_say(drop, reason, ring_tracer, monkeypatch):
    """The device copy's life in spans: ``resident_upload`` carries why
    it was sent (``first``, ``joined``, ``context``, ``dropped``) and
    how wide, ``resident_drop`` why it was forgotten and how many
    columns went; each is counted by ``stats()`` once."""
    _, vset = _vset(1, n=4)
    pks = [v.pub_key.bytes() for v in vset.validators]
    precompute.activate_validator_set(vset)
    assert _acquire(pks[:3]) is not None
    assert _acquire(pks) is not None  # the fourth key joins
    assert _acquire(pks) is not None  # nothing to send
    assert _acquire(pks, backend="cpu") is not None  # asked for by name: another context
    ups = _spans(ring_tracer, "resident_upload", "resident_drop")
    assert [(n, a["reason"], a["keys"], a["width"]) for n, a in ups] == [
        ("resident_upload", "first", 3, 64),
        ("resident_upload", "joined", 4, 64),
        ("resident_upload", "context", 4, 64),
    ]
    assert resident.stats()["uploads"] == 3 and resident.stats()["invalidations"] == 0
    assert resident.store._tab_dev.shape[-1] == 64

    drop(monkeypatch)
    drops = _spans(ring_tracer, "resident_upload", "resident_drop")
    assert [(n, a["reason"], a["keys"]) for n, a in drops] == [("resident_drop", reason, 4)]
    if reason != "clear":
        assert 1 <= drops[0][1]["departed"] <= 4
    s = resident.stats()
    assert s["invalidations"] == 1 and s["resident_keys"] == 0
    # a second event finds no copy: no span, no count
    precompute.tables.clear()
    assert _spans(ring_tracer, "resident_drop") == [] and resident.stats()["invalidations"] == 1

    precompute.pin_pubkeys(pks[:2])
    assert _acquire(pks[:2]) is not None
    again = _spans(ring_tracer, "resident_upload")
    assert [(a["reason"], a["keys"]) for _, a in again] == [("dropped", 2)]
    assert resident.stats()["uploads"] == 4
    resident.reset()
    precompute.pin_pubkeys(pks[:2])
    assert _acquire(pks[:2]) is not None
    assert [a["reason"] for _, a in _spans(ring_tracer, "resident_upload")] == ["first"]


def _plain_block(vset_plain, commit):
    return vset_plain, [
        (cs.block_id_flag, commit.vote_sign_bytes(CHAIN_ID, i) if cs.signature else b"", cs.signature)
        for i, cs in enumerate(commit.signatures)
    ]


@pytest.mark.parametrize("seed", range(3))
def test_random_one_seat_changes_agree_with_the_plain_reference_block_by_block(seed):
    """Twelve validators, one replaced a step by a new key or one that
    left earlier; a window of two commits a step through
    ``verify_commits_pipelined`` on the device path (resident, gathered
    and legacy lanes in one call). Every block's verdict is the plain
    light reference's over the plain rotation reference's own set, a
    signature by the key that just left in the newcomer's seat included;
    a key outside every live set holds no column; the store is never
    wider than the next power of two over the keys still remembered."""
    import random

    from chipbench import reference_light, reference_rotation
    from tendermint_tpu.parallel.pipeline import CommitTask, verify_commits_pipelined
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet
    from tests.helpers import make_block_id, make_commit

    rng = random.Random(seed)
    privs = {}
    for i in range(20):
        p = Ed25519PrivKey.from_seed((9_000_000 + 100 * seed + i).to_bytes(32, "big"))
        privs[p.pub_key().bytes()] = p
    pool = list(privs)
    vset = ValidatorSet([Validator(privs[pk].pub_key(), 10) for pk in pool[:12]])
    plain = [(v.pub_key.bytes(), 10) for v in vset.validators]
    live, height = [], 1
    for step in range(14):
        leaves = joins = None
        if step:
            seated = [v.pub_key.bytes() for v in vset.validators]
            leaves = rng.choice(seated)
            joins = rng.choice([pk for pk in pool if pk not in seated])
            nxt = vset.copy()
            gone = next(v for v in nxt.validators if v.pub_key.bytes() == leaves).copy()
            gone.voting_power = 0
            nxt.update_with_change_set([gone, Validator(privs[joins].pub_key(), 10)])
            vset = nxt
            plain = reference_rotation.apply_updates(plain, [(leaves, 0), (joins, 10)])
        assert [(v.pub_key.bytes(), v.voting_power) for v in vset.validators] == plain
        order = [privs[pk] for pk, _ in plain]
        tasks = []
        for b in range(2):
            bid = make_block_id(b"rot %d %d" % (seed, height))
            commit = make_commit(
                bid, height, 0, vset, order,
                absent={rng.randrange(12)}, nil_votes={rng.randrange(12)},
            )
            if joins and b == 1 and step % 3 == 0:
                # the newcomer's seat, signed by the key that just left
                idx = [pk for pk, _ in plain].index(joins)
                if commit.signatures[idx].signature:
                    commit.signatures[idx].signature = privs[leaves].sign(
                        commit.vote_sign_bytes(CHAIN_ID, idx)
                    )
            tasks.append(CommitTask(CHAIN_ID, vset, bid, height, commit))
            height += 1
        got = []
        for v in verify_commits_pipelined(tasks):
            if v.ok:
                got.append(reference_light.OK)
            elif "wrong signature" in str(v.error):
                got.append(("wrong signature", int(str(v.error).split("(#")[1].split(")")[0])))
            else:
                got.append(reference_light.INSUFFICIENT)
        assert got == [reference_light.verify_block(*_plain_block(plain, t.commit)) for t in tasks]
        members = {pk for pk, _ in plain}
        if members in live:
            live.remove(members)
        live = (live + [members])[-precompute._ACTIVE_SETS_CAP:]
        remembered = set().union(*live)
        index = resident.store._index
        assert set(index) <= remembered
        for pk in pool:
            if pk not in remembered:
                assert precompute.tables.lookup(pk) is None and pk not in index
        width = resident.store._tab_dev.shape[-1]
        assert width == max(64, 1 << len(index).bit_length()) <= max(64, 1 << len(remembered).bit_length())
    s = resident.stats()
    # every drop was followed by an upload, and no lane with a table missed the store
    assert s["uploads"] > s["invalidations"] and s["misses"] == 0 and s["hits"] > 0
