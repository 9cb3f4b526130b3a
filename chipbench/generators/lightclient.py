"""A light client that comes back some hundreds of heights behind and
updates by skipping verification, over a chain whose validator set
replaces one validator a height: every call is
``LightClient.verify_light_block_at_height(next target)`` on one client,
as an IBC relayer, a statesync bootstrap or a wallet's light node calls
it (upstream ``light/client_benchmark_test.go BenchmarkBisection``:
primary and witness the same in-process provider).

The set at height h is ``pool[h-1 : h-1+n]``: the longest-serving
validator leaves and a fresh key joins at every height, so two sets d
heights apart share exactly n - d validators; addresses, hence positions
in the set, are the keys' hashes. Every validator signs for the block.
Light blocks are built in set-up, only at the heights the traffic file's
jumps can make a walk visit, and the provider answers "no such block"
elsewhere; it hands out the objects, as ``light/provider`` does after
decoding. What a call must check is taken from the plain reference's
walk over the same blocks (``reference_lightclient``), which also says
how many distinct signatures a call sends: the generator refuses a seed
where that differs between calls (``run.py`` needs it constant).
"""

from __future__ import annotations

import hashlib
import re
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context

from chipbench import reference
from chipbench import reference_lightclient as plain
from chipbench import workload

FAULTS = ("tampered_trusting", "tampered_skipped", "short_of_trust", "wrong_valset_hash")
POWER = 10
REFERENCE_WORKERS = 4


def _digest(*parts) -> bytes:
    return hashlib.sha256("/".join(str(p) for p in parts).encode()).digest()


def _reference_walk(args) -> dict:
    """One call of the plain reference, in a process of its own: a
    fresh interpreter that imports the plain references and nothing
    else (no jax: the chip stays the parent's)."""
    trusted, target, blocks, params = args
    return plain.verify_skipping(trusted, target, blocks.get, plain.Params(*params))


class Chain:
    """The seeded chain: keys, sets and signed light blocks."""

    def __init__(self, seed: int, n: int, heights_needed: int):
        self.seed, self.n = seed, n
        self.signers = workload.make_signers(seed, "light", n + heights_needed)
        self.addresses = [hashlib.sha256(s.pub).digest()[:20] for s in self.signers]
        self.blocks = {}  # height -> LightBlock (the program's type)
        self._plain = {}  # height -> the plain reference's dict
        self._order = {}  # height -> pool indices in the set's order

    def order(self, height: int) -> list:
        """Pool indices of the set at ``height``, in its canonical
        order: equal powers, so by address."""
        got = self._order.get(height)
        if got is None:
            pool = range(height - 1, height - 1 + self.n)
            got = self._order[height] = sorted(pool, key=self.addresses.__getitem__)
        return got

    def validator_set(self, height: int):
        from tendermint_tpu.crypto.keys import Ed25519PubKey
        from tendermint_tpu.types import Validator, ValidatorSet

        vset = ValidatorSet()
        vset.validators = [
            Validator(Ed25519PubKey(self.signers[k].pub), POWER, 0, self.addresses[k])
            for k in self.order(height)
        ]
        vset.get_proposer()
        return vset

    def plain_validators(self, height: int) -> list:
        return [(self.addresses[k], self.signers[k].pub, POWER) for k in self.order(height)]

    def build(self, height: int, wrong_valset_hash: bool = False):
        """The light block of ``height``, signed by every validator of
        its set over the header's hash. ``wrong_valset_hash``: the
        header names the next height's set and is signed as such."""
        from tendermint_tpu.encoding.canonical import Timestamp
        from tendermint_tpu.types import (
            BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig, Consensus, Header,
            LightBlock, PartSetHeader, SignedHeader,
        )

        vset = self.validator_set(height)
        next_hash = plain.validators_hash(self.plain_validators(height + 1))
        header = Header(
            version=Consensus(block=11),
            chain_id=workload.CHAIN_ID,
            height=height,
            time=Timestamp.from_unix_ns(workload.BASE_NS + height * workload.SECOND_NS),
            last_block_id=BlockID(
                _digest("last", self.seed, height), PartSetHeader(1, _digest("lastparts", self.seed, height))
            ),
            last_commit_hash=_digest("lc", self.seed, height),
            data_hash=_digest("data", self.seed, height),
            validators_hash=next_hash if wrong_valset_hash else vset.hash(),
            next_validators_hash=next_hash,
            consensus_hash=_digest("consensus", self.seed),
            app_hash=_digest("app", self.seed, height),
            last_results_hash=b"",
            evidence_hash=b"",
            proposer_address=vset.validators[0].address,
        )
        commit = Commit(
            height=height, round=0,
            block_id=BlockID(header.hash(), PartSetHeader(1, _digest("parts", self.seed, height))),
        )
        times = workload.vote_times(self.seed, "light", height, self.n)
        commit.signatures = [
            CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, Timestamp.from_unix_ns(int(t)), b"")
            for v, t in zip(vset.validators, times)
        ]
        lane = commit.sign_bytes_encoder(workload.CHAIN_ID).lane
        for i, k in enumerate(self.order(height)):
            commit.signatures[i].signature = self.signers[k].sign(lane(i))
        block = LightBlock(SignedHeader(header, commit), vset)
        self.blocks[height] = block
        self._plain.pop(height, None)
        return block

    def plain(self, height: int):
        """The block of ``height`` as the plain reference takes it, or
        None where the provider has none."""
        block = self.blocks.get(height)
        if block is None:
            return None
        got = self._plain.get(height)
        if got is None:
            got = self._plain[height] = plain_block(block)
        return got


def plain_block(block) -> dict:
    """One of the program's ``LightBlock``s as the plain values
    ``reference_lightclient`` takes."""
    h, c = block.signed_header.header, block.signed_header.commit

    def bid(b):
        return (b.hash, b.part_set_header.total, b.part_set_header.hash)

    return {
        "header": {
            "version_block": h.version.block, "version_app": h.version.app,
            "chain_id": h.chain_id, "height": h.height,
            "time_ns": h.time.to_unix_ns(), "last_block_id": bid(h.last_block_id),
            "last_commit_hash": h.last_commit_hash, "data_hash": h.data_hash,
            "validators_hash": h.validators_hash,
            "next_validators_hash": h.next_validators_hash,
            "consensus_hash": h.consensus_hash, "app_hash": h.app_hash,
            "last_results_hash": h.last_results_hash,
            "evidence_hash": h.evidence_hash,
            "proposer_address": h.proposer_address,
        },
        "validators": [
            (v.address, v.pub_key.bytes(), v.voting_power)
            for v in block.validator_set.validators
        ],
        "commit": {
            "height": c.height, "round": c.round, "block_id": bid(c.block_id),
            "signatures": [
                (cs.block_id_flag, cs.validator_address,
                 cs.timestamp.to_unix_ns(), cs.signature)
                for cs in c.signatures
            ],
        },
    }


class Provider:
    """``light/provider.Provider`` over the chain's blocks: hands out
    the objects and knows no block it was not given."""

    def __init__(self, chain: Chain):
        self._chain = chain

    def chain_id(self) -> str:
        return workload.CHAIN_ID

    def light_block(self, height: int):
        from tendermint_tpu.light.provider import HeightTooHighError, LightBlockNotFoundError

        blocks = self._chain.blocks
        if height == 0:
            return blocks[max(blocks)]
        if height > max(blocks):
            raise HeightTooHighError("height %d > latest %d" % (height, max(blocks)))
        if height not in blocks:
            raise LightBlockNotFoundError("no light block at height %d" % height)
        return blocks[height]

    def report_evidence(self, evidence) -> None:
        raise RuntimeError("chipbench: the client reported evidence against its own provider")


class LightTraffic:
    def __init__(self, ctx):
        from tendermint_tpu.ops import precompute

        from chipbench.generators import cycle_length

        cfg, traffic = ctx.config, ctx.traffic
        self.seed = ctx.seed
        self.n = int(cfg["validators"])
        self.jump = int(traffic["jump_heights"])
        self.short_jump = int(traffic["short_of_trust_jump"])
        self.warm_up_calls = int(traffic["warm_up_calls"])
        self.trust_level = tuple(cfg["trust_level"])
        self.trusting_period_s = float(cfg["trusting_period_s"])
        self.max_clock_drift_s = float(cfg["max_clock_drift_s"])
        quorum = self.n * 2 // 3 + 1  # equal powers
        # what a sound call of this deployment sends: two accepted hops,
        # each the 2/3 lanes of its commit with the trusting lanes inside
        self.lanes_per_call = 2 * quorum
        self.count = cycle_length(traffic, self.lanes_per_call, precompute.results.cap)
        last_cycle_height = 1 + self.jump * self.count
        self.fault_bases = [
            last_cycle_height + self.jump * (2 * j + 1) for j in range(len(FAULTS))
        ]
        self.chain = Chain(ctx.seed, self.n, self.fault_bases[-1] + self.jump + 1)
        self.provider = Provider(self.chain)
        for k in range(self.count):
            for h in self._heights_of_call(1 + self.jump * k, self.jump):
                if h not in self.chain.blocks:
                    self.chain.build(h)
        self._build_faults()
        self.now_ns = workload.BASE_NS + (max(self.chain.blocks) + 1) * workload.SECOND_NS
        self.walks = [self._reference(1 + self.jump * k, self.jump, False) for k in range(self.count)]
        for k, walk in enumerate(self.walks):
            if (
                walk["verdict"] != plain.OK
                or len(walk["checked"]) != self.lanes_per_call
                or len(walk["refused"]) != 1
                or len(walk["accepted"]) != 2
            ):
                raise SystemExit(
                    "chipbench: seed %d: the plain walk of call %d is not one refusal, "
                    "two hops and %d signatures (%s, refused %s, accepted %s, %d checked): "
                    "take another seed" % (
                        ctx.seed, k, self.lanes_per_call, walk["verdict"], walk["refused"],
                        walk["accepted"], len(walk["checked"]))
                )
        self.clients = []  # (client, [call indices it served])
        self._calls_made = 0
        ctx.say(
            "traffic: %d validators, one replaced a height; %d calls of %d heights cycled "
            "over heights 1-%d, %d light blocks signed; a call = 1 refusal by tally + 2 hops "
            "= %d signatures (%d between two visits of one call; verdict cache holds %d)"
            % (self.n, self.count, self.jump, last_cycle_height, len(self.chain.blocks),
               self.lanes_per_call, (self.count - 1) * self.lanes_per_call,
               precompute.results.cap)
        )

    # --- generation -------------------------------------------------------

    @staticmethod
    def _heights_of_call(base: int, jump: int) -> list:
        """Where a provider must hold a block for a call from ``base``
        over ``jump`` heights: the base, the target, the midpoint the
        walk takes, and one bisection level under each hop, which
        upstream's walk never asks for and a program that verifies
        ahead of its walk would."""
        target = base + jump
        mid = (base + target) // 2
        return [base, (base + mid) // 2, mid, (mid + target) // 2, target]

    def _build_faults(self) -> None:
        rng = workload.rng_for(self.seed, "light-faults")
        quorum = self.lanes_per_call // 2
        self.fault_calls = []  # (fault, base, target, what was tampered)
        for fault, base in zip(FAULTS, self.fault_bases):
            jump = self.short_jump if fault == "short_of_trust" else self.jump
            target = base + jump
            mid = (base + target) // 2
            for h in self._heights_of_call(base, jump):
                self.chain.build(h, wrong_valset_hash=(fault == "wrong_valset_hash" and h == mid))
            detail = None
            if fault == "tampered_trusting":
                # a signature the trusting rule looks at: s + L, which
                # only ``s < L`` refuses
                trusted = {a for a, _, _ in self.chain.plain_validators(base)}
                commit = self.chain.blocks[mid].signed_header.commit
                hits = [i for i, cs in enumerate(commit.signatures) if cs.validator_address in trusted]
                need = self.n * self.trust_level[0] // self.trust_level[1] + 1
                idx = hits[int(rng.integers(need))]
                commit.signatures[idx].signature = workload.tamper_signature(
                    commit.signatures[idx].signature, "s>=L")
                detail = (mid, idx)
            elif fault == "tampered_skipped":
                commit = self.chain.blocks[target].signed_header.commit
                idx = int(rng.integers(quorum, self.n))
                kind = workload.TAMPER_KINDS[int(rng.integers(len(workload.TAMPER_KINDS)))]
                commit.signatures[idx].signature = workload.tamper_signature(
                    commit.signatures[idx].signature, kind)
                detail = (target, idx)
            self.fault_calls.append((fault, base, target, detail))

    def _params(self, check_signatures: bool) -> tuple:
        return (
            workload.CHAIN_ID, int(self.trusting_period_s * 1e9), self.now_ns,
            int(self.max_clock_drift_s * 1e9), self.trust_level, check_signatures,
        )

    def _reference(self, base: int, jump: int, check_signatures: bool) -> dict:
        return plain.verify_skipping(
            self.chain.plain(base), base + jump, self.chain.plain,
            plain.Params(*self._params(check_signatures)),
        )

    # --- the program's side ---------------------------------------------------

    def _client(self, trusted_height: int, restarted: bool):
        """A client that trusts ``trusted_height``. ``restarted``: over
        a store that already holds that block, as a node that comes back
        with its persisted trust root (it verifies nothing to start)."""
        from tendermint_tpu.encoding.canonical import Timestamp
        from tendermint_tpu.light.client import LightClient, TrustOptions
        from tendermint_tpu.light.store import LightStore
        from tendermint_tpu.types import Fraction

        anchor = self.chain.blocks[trusted_height]
        store = LightStore()
        if restarted:
            store.save_light_block(anchor)
        now = Timestamp.from_unix_ns(self.now_ns)
        return LightClient(
            workload.CHAIN_ID,
            TrustOptions(period=self.trusting_period_s, height=trusted_height, hash=anchor.hash()),
            self.provider,
            [self.provider],
            store=store,
            trust_level=Fraction(*self.trust_level),
            max_clock_drift=self.max_clock_drift_s,
            now=lambda: now,
        )

    def _next(self):
        """The next call of the cycle on the client that serves it; a
        new client where the cycle starts over."""
        k = self._calls_made % self.count
        if k == 0:
            self.clients.append((self._client(1, restarted=self._calls_made > 0), []))
        self._calls_made += 1
        client, served = self.clients[-1]
        served.append(k)
        try:
            return client.verify_light_block_at_height(1 + self.jump * (k + 1))
        except Exception as exc:  # what the program answered
            return exc

    def warm(self) -> None:
        """The cycle's first calls, as a client's first updates: more
        accepted hops than the program keeps live validator sets, so
        the tables and the store have the size they keep."""
        for _ in range(self.warm_up_calls):
            out = self._next()
            if isinstance(out, Exception):
                raise RuntimeError("warm-up call refused: %r" % out)

    def call(self, i: int):
        return self._next()

    def _answer(self, out) -> tuple:
        """The program's answer to a call in the plain reference's words."""
        if not isinstance(out, Exception):
            return (plain.OK, None)
        text = str(out)
        m = re.search(r"wrong signature \(#(\d+)\)", text)
        if m:
            return ("wrong signature", int(m.group(1)))
        if "validator hash of header" in text or "validators to match those that were supplied" in text:
            return ("validators_hash", None)
        return ("refused", "%s: %s" % (type(out).__name__, text))

    def check(self, outcomes, results) -> None:
        first = self.warm_up_calls
        refused = wrong = 0
        for i, out in enumerate(outcomes):
            k = (first + i) % self.count
            target = 1 + self.jump * (k + 1)
            if isinstance(out, Exception):
                refused += 1
            elif out.height != target or out.hash() != self.chain.blocks[target].hash():
                wrong += 1
        results.compare("timed_calls_refused", refused, 0)
        # every client's store holds its anchor and what the reference's
        # walks of the calls it served accepted, no more and no less
        for client, served in self.clients:
            want = {1}
            for k in served:
                want.update(self.walks[k]["accepted"])
            if sorted(want) != client.store.heights():
                wrong += 1
        results.compare("calls_with_a_wrong_walk", wrong, 0)

        # four fresh clients past the cycle, one fault each
        jobs, answers, bad_faults = [], [], 0
        for fault, base, target, detail in self.fault_calls:
            client = self._client(base, restarted=True)
            try:
                got = client.verify_light_block_at_height(target)
            except Exception as exc:
                got = exc
            answer = self._answer(got)
            stored = client.store.heights()
            mid = (base + target) // 2
            if fault == "tampered_trusting":
                want, want_stored = ("wrong signature", detail[1]), [base]
            elif fault == "wrong_valset_hash":
                want, want_stored = ("validators_hash", None), [base]
            else:
                want, want_stored = (plain.OK, None), [base, mid, target]
            if answer != want or stored != want_stored:
                bad_faults += 1
            answers.append((answer, stored))
            heights = self._heights_of_call(base, target - base)
            jobs.append((
                self.chain.plain(base), target,
                {h: self.chain.plain(h) for h in heights}, self._params(True),
            ))
        results.compare("fault_calls_with_a_wrong_verdict", bad_faults, 0)

        # the plain reference on those four calls in full, and on a
        # seeded sample of the lanes the timed calls sent
        bad = 0
        workers = min(REFERENCE_WORKERS, len(jobs))
        with ProcessPoolExecutor(workers, mp_context=get_context("spawn")) as pool:
            walks = list(pool.map(_reference_walk, jobs))
        for (fault, base, _, _), walk, (answer, stored) in zip(self.fault_calls, walks, answers):
            ref = (walk["verdict"], walk["detail"][1] if walk["verdict"] == "wrong signature" else None)
            if ref != answer or [base] + walk["accepted"] != stored:
                bad += 1
        rng = workload.rng_for(self.seed, "sample", "light")
        for _ in range(results.sample_lanes if outcomes else 0):
            i = int(rng.integers(min(len(outcomes), self.count)))
            k = (first + i) % self.count
            checked = self.walks[k]["checked"]
            height, idx, pub = checked[int(rng.integers(len(checked)))]
            commit = self.chain.plain(height)["commit"]
            valid = reference.verify(
                pub, plain.vote_sign_bytes(workload.CHAIN_ID, commit, idx),
                commit["signatures"][idx][3],
            )
            if valid == isinstance(outcomes[i], Exception):
                bad += 1
        results.compare("lanes_where_reference_disagrees", bad, 0)


def build(ctx):
    return LightTraffic(ctx)
