"""PR 9 serving-tier tests: batched-vs-sequential skipping parity over
a rotating validator set, the one-super-batch-per-round pin (since PR 30
one round a walk), the
verified-header cache (LRU + divergence invalidation), lightd serving
semantics, provider retry/backoff, and the scheduler super-batch entry
points."""

import hashlib
import threading

import pytest

from tendermint_tpu.crypto.keys import Ed25519PrivKey
from tendermint_tpu.crypto.scheduler import (
    SchedulerSaturatedError,
    VerifyScheduler,
)
from tendermint_tpu.encoding.canonical import Timestamp
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.metrics import LightMetrics, Registry
from tendermint_tpu.light import batch as light_batch
from tendermint_tpu.light import (
    DEFAULT_TRUST_LEVEL,
    InvalidHeaderError,
    LightClient,
    MemoryProvider,
    NewValSetCantBeTrustedError,
    TrustOptions,
)
from tendermint_tpu.light.cache import CacheEntry, HeaderCache
from tendermint_tpu.light.client import DivergedHeaderError
from tendermint_tpu.light.lightd import LightServer
from tendermint_tpu.light.provider import (
    HeightTooHighError,
    LightBlockNotFoundError,
    ProviderBudgetExhaustedError,
    ProviderError,
    RetryingProvider,
)
from tendermint_tpu.rpc.server import RPCError
from tendermint_tpu.types import (
    BlockID,
    Consensus,
    Fraction,
    Header,
    LightBlock,
    PartSetHeader,
    SignedHeader,
    Validator,
    ValidatorSet,
)
from tests.helpers import CHAIN_ID, make_commit
from tests.test_light import build_light_chain, now_at

BASE_NS = 1_700_000_000_000_000_000
HOUR = 3600.0


def build_rotating_chain(n_heights, window=6, power=10, chain_id=CHAIN_ID):
    """Signed-header chain whose valset slides one validator per height:
    heights h and h+k overlap in (window-k) validators, so at trust
    level 1/3 a skipping jump of more than window//2 steps cannot be
    trusted and the client must bisect through REAL intermediate
    pivots (the constant-valset fixture verifies any span in one hop)."""
    pool = [
        Ed25519PrivKey.from_seed((7000 + i).to_bytes(32, "big"))
        for i in range(n_heights + window + 1)
    ]
    vsets, privss = [], []
    for h in range(1, n_heights + 2):
        keys = pool[h - 1 : h - 1 + window]
        vset = ValidatorSet([Validator(k.pub_key(), power) for k in keys])
        by_addr = {k.pub_key().address(): k for k in keys}
        privss.append([by_addr[v.address] for v in vset.validators])
        vsets.append(vset)
    blocks = []
    last_bid = BlockID()
    for h in range(1, n_heights + 1):
        vset, privs = vsets[h - 1], privss[h - 1]
        header = Header(
            version=Consensus(block=11),
            chain_id=chain_id,
            height=h,
            time=Timestamp.from_unix_ns(BASE_NS + h * 1_000_000_000),
            last_block_id=last_bid,
            last_commit_hash=hashlib.sha256(b"lc%d" % h).digest(),
            data_hash=hashlib.sha256(b"d%d" % h).digest(),
            validators_hash=vset.hash(),
            next_validators_hash=vsets[h].hash(),
            consensus_hash=hashlib.sha256(b"cp").digest(),
            app_hash=hashlib.sha256(b"app%d" % h).digest(),
            last_results_hash=b"",
            evidence_hash=b"",
            proposer_address=vset.validators[0].address,
        )
        bid = BlockID(
            header.hash(),
            PartSetHeader(1, hashlib.sha256(b"parts%d" % h).digest()),
        )
        commit = make_commit(
            bid, h, 0, vset, privs, chain_id=chain_id,
            time_ns=BASE_NS + h * 1_000_000_000,
        )
        blocks.append(
            LightBlock(
                signed_header=SignedHeader(header=header, commit=commit),
                validator_set=vset.copy(),
            )
        )
        last_bid = bid
    return blocks


def make_client(blocks, batching, height=1, witness_blocks=None):
    witnesses = (
        [MemoryProvider(CHAIN_ID, witness_blocks)]
        if witness_blocks is not None
        else []
    )
    return LightClient(
        CHAIN_ID,
        TrustOptions(
            period=10 * HOUR, height=height, hash=blocks[height - 1].hash()
        ),
        MemoryProvider(CHAIN_ID, blocks),
        witnesses,
        bisect_batching=batching,
        now=now_at,
    )


class TestBatchParity:
    """The batched super-batch rounds must be outcome-identical to the
    sequential one-call-per-pivot descent."""

    def test_rotating_chain_stores_identical_pivots(self):
        blocks = build_rotating_chain(17)
        stored = {}
        for batching in (False, True):
            client = make_client(blocks, batching)
            lb = client.verify_light_block_at_height(17)
            assert lb.height == 17
            stored[batching] = client.store.heights()
        # Same bisection descent -> byte-identical trust path.
        assert stored[True] == stored[False]
        assert len(stored[True]) > 3  # real multi-pivot bisection

    def test_constant_chain_parity(self):
        blocks, _, _ = build_light_chain(20)
        for batching in (False, True):
            client = make_client(blocks, batching)
            assert client.verify_light_block_at_height(20).height == 20

    def test_forged_target_commit_same_error_both_modes(self):
        errors = {}
        for batching in (False, True):
            blocks = build_rotating_chain(17)
            sh = blocks[16].signed_header
            sh.commit.signatures[0].signature = bytes(64)
            client = make_client(blocks, batching)
            with pytest.raises(InvalidHeaderError) as exc:
                client.verify_light_block_at_height(17)
            errors[batching] = str(exc.value)
        assert errors[True] == errors[False]
        assert "wrong signature" in errors[True]

    def test_forged_commit_below_accepted_pivot_ignored(self):
        """The batched ladder evaluates deeper candidates than the one
        it accepts; a forged commit BELOW the accepted pivot must not
        poison the round (sequential descent never visits it)."""
        blocks = build_rotating_chain(17)
        # Ladder for base=1 target=17 descends 17,9,5,3,2; overlap math
        # accepts 3 (first candidate within trust range). Forge height 2.
        blocks[1].signed_header.commit.signatures[0].signature = bytes(64)
        for batching in (False, True):
            client = make_client(blocks, batching)
            lb = client.verify_light_block_at_height(17)
            assert lb.height == 17
            assert 2 not in client.store.heights()

    def test_trust_level_edge_exact_third_bisects(self):
        """tallied == needed is NOT enough (needs strictly more): a jump
        whose overlap lands exactly on the trust threshold must BISECT,
        one step closer must verify."""
        blocks = build_rotating_chain(8)
        base = blocks[0]
        # window=6 power=10: needed = 60//3 = 20. Height 5 overlaps in
        # 2 validators (tallied 20), height 4 in 3 (tallied 30).
        outcomes = light_batch.evaluate_candidates(
            CHAIN_ID, base, [blocks[4], blocks[3]],
            10 * HOUR, now_at(), 10.0, DEFAULT_TRUST_LEVEL,
        )
        assert outcomes[0].kind == light_batch.BISECT
        assert isinstance(outcomes[0].error, NewValSetCantBeTrustedError)
        assert outcomes[1].kind == light_batch.OK

    def test_one_super_batch_per_round(self):
        """Acceptance pin: a round = at most ONE scheduler super-batch
        (one device call), and since PR 30 a walk the planner can
        express is one round however many hops it takes."""
        blocks = build_rotating_chain(17)
        client = make_client(blocks, batching=True)
        tracing.configure("ring")
        tracing.tracer.clear()
        try:
            client.verify_light_block_at_height(17)
            events = tracing.tracer.export()["traceEvents"]
        finally:
            tracing.configure("off")
        rounds = [e for e in events if e.get("name") == "light_round"]
        batches = [e for e in events if e.get("name") == "light_super_batch"]
        walks = [e for e in events if e.get("name") == "light_verify"]
        assert len(rounds) == 1 and len(batches) == 1 and len(walks) == 1
        # rotation forces a real multi-hop walk
        assert walks[0]["args"]["hops"] >= 3
        assert walks[0]["args"]["refused_by_tally"] >= 2
        assert batches[0]["args"]["lanes"] == walks[0]["args"]["lanes"] > 0


def _traced_walk(blocks, target, batching=True, height=1):
    """(client, light_verify span args, lanes in dispatch-side spans)
    of one ``verify_light_block_at_height(target)`` from ``height``."""
    client = make_client(blocks, batching=batching, height=height)
    tracing.configure("ring")
    tracing.tracer.clear()
    try:
        client.verify_light_block_at_height(target)
        events = tracing.tracer.export()["traceEvents"]
    finally:
        tracing.configure("off")
    walk = [e for e in events if e.get("name") == "light_verify"][-1]["args"]
    flushed = sum(
        e["args"]["lanes"] for e in events if e.get("name") == "sched_flush"
    )
    return client, walk, flushed


def _reference_walk(blocks, target, height=1):
    from chipbench import reference_lightclient as plain
    from chipbench.generators.lightclient import plain_block

    by_height = {b.height: plain_block(b) for b in blocks}
    params = plain.Params(
        CHAIN_ID, int(10 * HOUR * 1e9), now_at().to_unix_ns(), int(10e9),
        (1, 3), check_signatures=False,
    )
    return plain.verify_skipping(by_height[height], target, by_height.get, params)


class TestWalkSendsWhatUpstreamChecks:
    """PR 30: a call sends exactly the signatures upstream's walk
    checks, each once. The reference is the benchmark's plain
    ``verifySkipping`` (``chipbench/reference_lightclient.py``, no import
    of the program) over the same blocks."""

    # window 6, trust level 1/3: a jump of up to 3 heights is covered
    @pytest.mark.parametrize(
        "target, hops, refused, last_hop_adjacent",
        [
            (2, 1, 0, True),  # a walk that ends adjacent: the 2/3 rule alone
            (3, 1, 0, False),  # one hop, both rules, their lanes merged
            (6, 2, 1, False),  # the target refused by tally, its midpoint taken
            (10, 4, 4, False),
            (17, 7, 11, False),
        ],
    )
    def test_lanes_dispatched_are_the_references_checked_signatures(
        self, target, hops, refused, last_hop_adjacent
    ):
        blocks = build_rotating_chain(17)
        want = _reference_walk(blocks, target)
        assert want["verdict"] == "ok"
        assert (len(want["accepted"]), len(want["refused"])) == (hops, refused)
        trail = [1] + want["accepted"]
        assert (trail[-1] - trail[-2] == 1) == last_hop_adjacent
        client, walk, flushed = _traced_walk(blocks, target)
        assert (walk["hops"], walk["refused_by_tally"]) == (hops, refused)
        assert walk["lanes"] == flushed == len(want["checked"])
        assert client.store.heights() == [1] + want["accepted"]
        # the sequential loop leaves the same store and checks the
        # merged signatures twice over
        seq_client, _, _ = _traced_walk(blocks, target, batching=False)
        assert seq_client.store.heights() == client.store.heights()

    def test_a_signature_both_rules_ask_for_is_one_lane(self):
        blocks = build_rotating_chain(8)
        want = _reference_walk(blocks, 3)
        _, walk, flushed = _traced_walk(blocks, 3)
        # 1 -> 3: four shared validators; the trusting rule takes the
        # first three it finds, the 2/3 rule the first five by position
        assert walk["lanes"] == flushed == len(want["checked"]) == 5
        assert walk["merged"] >= 2
        assert walk["lanes"] + walk["merged"] == 3 + 5

    def test_no_lane_for_a_pivot_the_walk_never_visits(self):
        """The descending ladder [target, mid, mid-of-mid, ...] holds
        pivots upstream's walk never fetches; a forged commit at one
        neither fails the call nor costs a lane."""
        blocks = build_rotating_chain(17)
        want = _reference_walk(blocks, 17)
        unvisited = sorted(set(range(2, 17)) - set(want["fetched"]))
        assert unvisited
        fetched = []
        client = make_client(blocks, batching=True)
        inner = client.primary.light_block
        client.primary.light_block = lambda h: fetched.append(h) or inner(h)
        client.verify_light_block_at_height(17)
        assert fetched == want["fetched"]

    def test_a_verdict_that_never_came_is_a_timeout_not_a_wrong_signature(self):
        blocks = build_rotating_chain(8)
        release = threading.Event()

        def slow(pks, msgs, sigs):
            release.wait(5)
            return [True] * len(pks)

        sched = VerifyScheduler(slow, max_delay=0.001)
        sched.start()
        try:
            batch = light_batch.SuperBatch()
            plan = light_batch._plan_candidate(
                CHAIN_ID, blocks[0], blocks[2], 10 * HOUR, now_at(), 10.0,
                DEFAULT_TRUST_LEVEL, batch,
            )
            assert batch.lanes and plan.outcome is None
            with pytest.raises(TimeoutError, match="no verdict for 5 of 5"):
                batch.send(sched, timeout=0.05)
        finally:
            release.set()
            sched.stop()

    def test_bad_signature_in_a_later_hop_keeps_the_hops_before_it(self):
        blocks = build_rotating_chain(17)
        want = _reference_walk(blocks, 17)
        third = want["accepted"][2]
        idx = next(i for h, i, _ in want["checked"] if h == third)
        blocks[third - 1].signed_header.commit.signatures[idx].signature = bytes(64)
        stores = {}
        for batching in (False, True):
            client = make_client(blocks, batching)
            with pytest.raises(InvalidHeaderError, match=r"wrong signature \(#%d\)" % idx):
                client.verify_light_block_at_height(17)
            stores[batching] = client.store.heights()
        assert stores[True] == stores[False] == [1] + want["accepted"][:2]


def _refusal_cases():
    """(name, mutate(blocks) -> (base, cand, now, trusting period,
    trust level)): every way ``verifier.verify`` refuses a candidate."""
    from copy import deepcopy

    def case(name, mutate, base=0, cand=2, **kw):
        return pytest.param(mutate, base, cand, kw, id=name)

    def keep(blocks):
        return None

    def not_after(blocks):
        # the trusted side is not held to a commit: set its clock forward
        blocks[0].signed_header.header.time = blocks[2].signed_header.header.time

    def other_set(blocks):
        blocks[2].validator_set = deepcopy(blocks[3].validator_set)

    def other_chain(blocks):
        blocks[2].signed_header.header.chain_id = "another-chain"

    def bad_trusting_sig(blocks):
        # a validator both sets hold: the trusting rule looks at it first
        shared = {v.address for v in blocks[0].validator_set.validators}
        commit = blocks[2].signed_header.commit
        idx = next(i for i, cs in enumerate(commit.signatures) if cs.validator_address in shared)
        commit.signatures[idx].signature = bytes(64)

    def bad_full_sig(blocks):
        shared = {v.address for v in blocks[0].validator_set.validators}
        commit = blocks[2].signed_header.commit
        idx = next(i for i, cs in enumerate(commit.signatures) if cs.validator_address not in shared)
        commit.signatures[idx].signature = bytes(64)

    def short_of_two_thirds(blocks):
        from tendermint_tpu.types import CommitSig

        # absent votes from validators the trusted set does not hold: the
        # trusting rule passes, the 2/3 rule runs out of power
        shared = {v.address for v in blocks[0].validator_set.validators}
        commit = blocks[2].signed_header.commit
        for i, cs in enumerate(commit.signatures):
            if cs.validator_address not in shared:
                commit.signatures[i] = CommitSig.absent()

    def double_vote(blocks):
        commit = blocks[2].signed_header.commit
        shared = {v.address for v in blocks[0].validator_set.validators}
        idxs = [i for i, cs in enumerate(commit.signatures) if cs.validator_address in shared]
        commit.signatures[idxs[1]].validator_address = commit.signatures[idxs[0]].validator_address

    def wrong_next_set(blocks):
        blocks[0].signed_header.header.next_validators_hash = (
            blocks[2].signed_header.header.validators_hash
        )

    def commit_for_another_block(blocks):
        blocks[2].signed_header.commit.block_id = blocks[3].signed_header.commit.block_id

    return [
        case("sound", keep, sound=True),
        case("sound_adjacent", keep, cand=1, sound=True),
        case("cannot_be_trusted", keep, cand=5),
        case("exactly_a_third", keep, cand=4),
        case("trusted_header_expired", keep, period=1.0),
        case("trust_level_below_a_third", keep, level=Fraction(1, 4)),
        case("height_not_above", keep, base=2, cand=2),
        case("header_from_the_future", keep, now=Timestamp.from_unix_ns(BASE_NS - 8_000_000_000)),
        case("time_not_after", not_after),
        case("supplied_set_is_not_the_headers", other_set),
        case("another_chain", other_chain),
        case("bad_signature_the_trusting_rule_sees", bad_trusting_sig),
        case("bad_signature_only_the_full_rule_sees", bad_full_sig),
        case("short_of_two_thirds", short_of_two_thirds),
        case("double_vote", double_vote),
        case("adjacent_next_validators_mismatch", wrong_next_set, cand=1),
        case("commit_for_another_block", commit_for_another_block),
    ]


class TestPlannerParity:
    """``light/batch``'s contract: a planned candidate's outcome is
    exactly ``verifier.verify``'s, type and message, whatever refuses
    it."""

    @pytest.mark.parametrize("mutate, base, cand, kw", _refusal_cases())
    def test_outcome_is_verifier_verify_s(self, mutate, base, cand, kw):
        from tendermint_tpu.light import verifier

        blocks = build_rotating_chain(8)
        mutate(blocks)
        period = kw.get("period", 10 * HOUR)
        level = kw.get("level", DEFAULT_TRUST_LEVEL)
        now = kw.get("now", now_at())
        b, c = blocks[base], blocks[cand]
        want = None
        try:
            verifier.verify(
                b.signed_header, b.validator_set, c.signed_header, c.validator_set,
                period, now, 10.0, level,
            )
        except Exception as exc:
            want = exc
        (got,) = light_batch.evaluate_candidates(
            CHAIN_ID, b, [c], period, now, 10.0, level
        )
        assert (want is None) == kw.get("sound", False)
        if want is None:
            assert got.kind == light_batch.OK and got.error is None
        else:
            assert type(got.error) is type(want) and str(got.error) == str(want)
            cant_trust = isinstance(want, NewValSetCantBeTrustedError)
            assert got.kind == (light_batch.BISECT if cant_trust else light_batch.ERROR)
        # and so is a walk that meets it as its first candidate
        walk = light_batch.Walk(CHAIN_ID, period, now, 10.0, level)
        walk.plan(b, c, c, lambda base, current: (_ for _ in ()).throw(LookupError("no pivot")))
        if isinstance(want, NewValSetCantBeTrustedError):
            assert walk.refused == 1 and not walk.hops and isinstance(walk.stop, LookupError)
        else:
            (out,) = walk.verify()
            assert (out.kind == light_batch.OK) == (want is None)
            if want is not None:
                assert type(out.error) is type(want) and str(out.error) == str(want)


class TestHeaderCache:
    def test_lru_eviction_order(self):
        cache = HeaderCache(capacity=2)
        blocks, _, _ = build_light_chain(3)
        cache.put(CHAIN_ID, blocks[0])
        cache.put(CHAIN_ID, blocks[1])
        assert cache.get(CHAIN_ID, 1) is not None  # refresh height 1
        cache.put(CHAIN_ID, blocks[2])  # evicts height 2 (LRU)
        assert cache.get(CHAIN_ID, 2) is None
        assert cache.get(CHAIN_ID, 1) is not None
        assert cache.get(CHAIN_ID, 3) is not None
        assert cache.evictions == 1

    def test_header_hash_pinned_get(self):
        cache = HeaderCache()
        blocks, _, _ = build_light_chain(2)
        cache.put(CHAIN_ID, blocks[0])
        assert cache.get(CHAIN_ID, 1, header_hash=blocks[0].hash())
        assert cache.get(CHAIN_ID, 1, header_hash=b"\x01" * 32) is None

    def test_invalidate_chain_scoped(self):
        cache = HeaderCache()
        blocks, _, _ = build_light_chain(2)
        cache.put(CHAIN_ID, blocks[0])
        cache.put("other-chain", blocks[1])
        assert cache.invalidate_chain(CHAIN_ID) == 1
        assert cache.get(CHAIN_ID, 1) is None
        assert cache.get("other-chain", 2) is not None

    def test_metrics_wired(self):
        reg = Registry()
        cache = HeaderCache(capacity=1, metrics=LightMetrics(reg))
        blocks, _, _ = build_light_chain(2)
        cache.get(CHAIN_ID, 1)  # miss
        cache.put(CHAIN_ID, blocks[0])
        cache.get(CHAIN_ID, 1)  # hit
        cache.put(CHAIN_ID, blocks[1])  # evicts
        text = reg.expose()
        assert "tendermint_light_cache_hits_total 1" in text
        assert "tendermint_light_cache_misses_total 1" in text
        assert "tendermint_light_cache_evictions_total 1" in text

    def test_entry_holds_memoized_proof(self):
        blocks, _, _ = build_light_chain(2)
        e = CacheEntry(CHAIN_ID, 1, blocks[0].hash(), blocks[0],
                       trust_path=(1,), payload={"height": "1"})
        assert e.trust_path == (1,) and e.payload["height"] == "1"


class TestLightServer:
    def make_server(self, blocks, witness_blocks=None, **kw):
        client = make_client(blocks, batching=True,
                             witness_blocks=witness_blocks)
        return LightServer(client, **kw)

    def test_miss_then_hit_same_payload(self):
        blocks, _, _ = build_light_chain(10)
        srv = self.make_server(blocks)
        first = srv.light_header(height=10)
        assert first["height"] == "10"
        assert first["trust_path"]  # memoized proof rides the entry
        assert srv.light_header(height=10) is first  # memoized dict
        assert srv.cache.hits == 1 and srv.cache.misses == 1

    def test_divergence_invalidates_cache(self):
        blocks, _, _ = build_light_chain(10)
        forked, _, _ = build_light_chain(10, fork_at=6)
        srv = self.make_server(blocks, witness_blocks=forked)
        srv.light_header(height=3)  # below the fork: witness agrees
        assert len(srv.cache) == 1
        with pytest.raises(RPCError) as exc:
            srv.light_header(height=10)
        assert "attack" in exc.value.message
        assert len(srv.cache) == 0  # every memoized proof dropped

    def test_bad_height_params(self):
        blocks, _, _ = build_light_chain(3)
        srv = self.make_server(blocks)
        for bad in (None, "x", 0, -4):
            with pytest.raises(RPCError):
                srv.light_header(height=bad)

    def test_status_reports_cache(self):
        blocks, _, _ = build_light_chain(5)
        srv = self.make_server(blocks)
        srv.light_header(height=5)
        st = srv.light_status()
        assert st["trusted_height"] == "5"
        assert st["cache"]["entries"] == 1

    def test_single_flight_one_verification(self):
        blocks, _, _ = build_light_chain(12)
        client = make_client(blocks, batching=True)
        srv = LightServer(client)
        calls = []
        calls_mtx = threading.Lock()
        inner = client.verify_light_block_at_height

        def counting(height, now=None):
            with calls_mtx:
                calls.append(height)
            return inner(height, now)

        client.verify_light_block_at_height = counting
        results = []
        threads = [
            threading.Thread(
                target=lambda: results.append(srv.light_header(height=12))
            )
            for _ in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 8
        assert all(r["height"] == "12" for r in results)
        assert len(calls) == 1  # herd collapsed to one verification


class FlakyProvider(MemoryProvider):
    def __init__(self, chain_id, blocks, fail_times):
        super().__init__(chain_id, blocks)
        self.fail_times = fail_times
        self.calls = 0

    def light_block(self, height):
        self.calls += 1
        if self.calls <= self.fail_times:
            raise ProviderError("transient network flap")
        return super().light_block(height)


class TestRetryingProvider:
    def test_retries_transient_then_succeeds(self):
        blocks, _, _ = build_light_chain(3)
        slept = []
        p = RetryingProvider(
            FlakyProvider(CHAIN_ID, blocks, fail_times=2),
            retries=3, base_delay=0.05, sleep=slept.append,
        )
        assert p.light_block(2).height == 2
        assert slept == [0.05, 0.1]  # exponential backoff
        assert p.retries_total == 2

    def test_exhausted_retries_raise_last_error(self):
        blocks, _, _ = build_light_chain(3)
        p = RetryingProvider(
            FlakyProvider(CHAIN_ID, blocks, fail_times=99),
            retries=2, sleep=lambda s: None,
        )
        with pytest.raises(ProviderError, match="flap"):
            p.light_block(2)

    def test_definitive_answers_not_retried(self):
        blocks, _, _ = build_light_chain(3)
        inner = FlakyProvider(CHAIN_ID, blocks, fail_times=0)
        p = RetryingProvider(inner, retries=3, sleep=lambda s: None)
        with pytest.raises(HeightTooHighError):
            p.light_block(50)
        with pytest.raises(LightBlockNotFoundError):
            RetryingProvider(
                MemoryProvider(CHAIN_ID, []), sleep=lambda s: None
            ).light_block(1)
        assert inner.calls == 1  # single attempt, no retry burn

    def test_failure_budget_fails_fast_then_recovers(self):
        blocks, _, _ = build_light_chain(3)
        clock = [0.0]
        p = RetryingProvider(
            FlakyProvider(CHAIN_ID, blocks, fail_times=4),
            retries=0, failure_budget=4, budget_window=60.0,
            sleep=lambda s: None, clock=lambda: clock[0],
        )
        for _ in range(4):
            with pytest.raises(ProviderError):
                p.light_block(2)
        with pytest.raises(ProviderBudgetExhaustedError):
            p.light_block(2)
        assert p.fast_fails_total == 1
        clock[0] = 61.0  # window slides: budget restored
        assert p.light_block(2).height == 2


class TestSubmitMany:
    def make_sched(self, **kw):
        sched = VerifyScheduler(
            verify_fn=lambda pks, msgs, sigs: [s == b"ok" for s in sigs],
            max_delay=0.001,
            **kw,
        )
        sched.start()
        return sched

    def test_atomic_group_one_wait(self):
        sched = self.make_sched()
        try:
            lanes = [
                (b"p", b"m", b"ok"), (b"p", b"m", b"bad"), (b"p", b"m", b"ok"),
            ]
            entries = sched.submit_many(lanes, priority=1, tag="t")
            assert sched.wait_many(entries, timeout=5.0) == [
                True, False, True,
            ]
        finally:
            sched.stop()

    def test_all_or_nothing_on_saturation(self):
        sched = self.make_sched(max_pending=2)
        try:
            with pytest.raises(SchedulerSaturatedError):
                sched.submit_many(
                    [(b"p", b"m", b"ok")] * 3, flush_by=None
                )
            # The rejected group admitted NOTHING: a full group that
            # fits still goes through untouched.
            entries = sched.submit_many([(b"p", b"m", b"ok")] * 2)
            assert sched.wait_many(entries, timeout=5.0) == [True, True]
            assert sched.submit_rejections == 1
        finally:
            sched.stop()

    def test_submit_many_rejected_after_stop(self):
        sched = self.make_sched()
        sched.stop()
        with pytest.raises(RuntimeError):
            sched.submit_many([(b"p", b"m", b"ok")])
