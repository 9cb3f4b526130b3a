"""Light client with trusted store, bisection, and fork detection.

Mirrors light/client.go: trust options anchor the first block (height +
hash from a social-consensus source); VerifyLightBlockAtHeight then walks
forward sequentially or by skipping (bisection against the trust level),
or backwards via the hash chain. Skipping verification decides its walk
on tallies and sends the signatures of the hops it took, each once, in
one scheduler super-batch a round (light/batch.py): exactly those
upstream's loop checks. After verification the new block is
cross-checked against witness providers (light/detector.go); a
conflicting header yields LightClientAttackEvidence reported to all
providers.
"""

from __future__ import annotations

import time as _time
from dataclasses import dataclass, field as dc_field
from typing import Callable, List, Optional

from tendermint_tpu.encoding.canonical import Timestamp
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.metrics import LightMetrics
from tendermint_tpu.light import batch as light_batch
from tendermint_tpu.light import verifier
from tendermint_tpu.light.provider import (
    HeightTooHighError,
    LightBlockNotFoundError,
    Provider,
    ProviderError,
)
from tendermint_tpu.light.store import LightStore
from tendermint_tpu.types import Fraction
from tendermint_tpu.types.evidence import LightClientAttackEvidence
from tendermint_tpu.types.light import LightBlock

DEFAULT_PRUNING_SIZE = 1000
DEFAULT_MAX_CLOCK_DRIFT = 10.0  # seconds
DEFAULT_MAX_BLOCK_LAG = 10.0


class LightClientError(Exception):
    pass


class DivergedHeaderError(LightClientError):
    """A witness returned a conflicting verified header."""

    def __init__(self, evidence: LightClientAttackEvidence, witness_index: int):
        self.evidence = evidence
        self.witness_index = witness_index
        super().__init__("conflicting headers detected: light client attack")


@dataclass
class TrustOptions:
    """light.TrustOptions: period + (height, hash) root of trust."""

    period: float  # trusting period, seconds
    height: int
    hash: bytes

    def validate(self) -> None:
        if self.period <= 0:
            raise ValueError("negative or zero trusting period")
        if self.height <= 0:
            raise ValueError("negative or zero height")
        if len(self.hash) != 32:
            raise ValueError(f"expected hash size 32, got {len(self.hash)}")


class LightClient:
    def __init__(
        self,
        chain_id: str,
        trust_options: TrustOptions,
        primary: Provider,
        witnesses: List[Provider],
        store: Optional[LightStore] = None,
        trust_level: Fraction = verifier.DEFAULT_TRUST_LEVEL,
        max_clock_drift: float = DEFAULT_MAX_CLOCK_DRIFT,
        sequential: bool = False,
        pruning_size: int = DEFAULT_PRUNING_SIZE,
        now: Optional[Callable[[], Timestamp]] = None,
        bisect_batching: bool = True,
        metrics: Optional[LightMetrics] = None,
    ):
        trust_options.validate()
        verifier.validate_trust_level(trust_level)
        self.chain_id = chain_id
        self.trusting_period = trust_options.period
        self.trust_level = trust_level
        self.max_clock_drift = max_clock_drift
        self.primary = primary
        self.witnesses = list(witnesses)
        self.store = store or LightStore()
        self.sequential = sequential
        self.pruning_size = pruning_size
        # the walk planned on tallies, one super-batch a round
        # (light/batch.py); False keeps upstream's one-call-per-pivot
        # loop, the reference the parity tests hold the planner to
        self.bisect_batching = bisect_batching
        self.metrics = metrics or LightMetrics.nop()
        self._now = now or (lambda: Timestamp.from_unix_ns(_time.time_ns()))
        # what the last forward verification did (the light_verify span)
        self._last_walk = dict.fromkeys(
            ("hops", "refused_by_tally", "lanes", "merged"), 0
        )
        self._set_hashes: dict = {}
        self._initialize(trust_options)

    # --- initialization ------------------------------------------------------

    def _initialize(self, opts: TrustOptions) -> None:
        """light/client.go initializeWithTrustOptions: fetch the anchor
        block from the primary, check hash + self-consistency."""
        existing = self.store.light_block(opts.height)
        if existing is not None and existing.hash() == opts.hash:
            return
        lb = self.primary.light_block(opts.height)
        if lb.hash() != opts.hash:
            raise LightClientError(
                f"expected header's hash {opts.hash.hex()}, but got "
                f"{lb.hash().hex()}"
            )
        lb.validate_basic(self.chain_id)
        # 1/3+ of the valset must have signed (we can't check 2/3 of the
        # *previous* set without trusting more).
        from tendermint_tpu.types.validation import verify_commit_light_trusting

        verify_commit_light_trusting(
            self.chain_id, lb.validator_set, lb.signed_header.commit, Fraction(1, 3)
        )
        self.store.save_light_block(lb)

    # --- public API ----------------------------------------------------------

    def trusted_light_block(self, height: int) -> Optional[LightBlock]:
        return self.store.light_block(height)

    def latest_trusted(self) -> Optional[LightBlock]:
        return self.store.latest_light_block()

    def update(self, now: Optional[Timestamp] = None) -> Optional[LightBlock]:
        """Verify the primary's latest block (client.go Update)."""
        latest = self.primary.light_block(0)
        trusted = self.store.latest_light_block()
        if trusted is not None and latest.height <= trusted.height:
            return None
        return self.verify_light_block_at_height(latest.height, now)

    def verify_light_block_at_height(
        self, height: int, now: Optional[Timestamp] = None
    ) -> LightBlock:
        """client.go VerifyLightBlockAtHeight:413."""
        if height <= 0:
            raise ValueError("height must be positive")
        with tracing.span("light_verify", target=height) as sp:
            now = now or self._now()
            existing = self.store.light_block(height)
            if existing is not None:
                return existing
            latest = self.store.latest_light_block()
            if latest is None:
                raise LightClientError("no trusted state; initialize first")
            sp.set(base=latest.height)
            if height < latest.height:
                return self._backwards(latest, height)
            target = self._fetch_from_primary(height)
            try:
                self._verify_header_from(latest, target, now)
            finally:
                sp.set(**self._last_walk)
            return target

    def verify_header(self, new_block: LightBlock, now: Timestamp) -> None:
        """client.go VerifyHeader: forward verification + detector."""
        trusted = self.store.latest_light_block()
        if trusted is None:
            raise LightClientError("no trusted state")
        self._verify_header_from(trusted, new_block, now)

    def _verify_header_from(
        self, trusted: LightBlock, new_block: LightBlock, now: Timestamp
    ) -> None:
        """``verify_header`` from the latest trusted block, which the
        caller has read from the store (decoding one costs as much as
        planning a hop)."""
        if new_block.height <= trusted.height:
            raise LightClientError(
                f"height {new_block.height} is not above trusted "
                f"{trusted.height}"
            )
        # id(validator set) -> (the set, its hash) for the blocks
        # validated in this call (light/batch.SuperBatch)
        self._set_hashes = {}
        self._note_validated(new_block)
        for key in self._last_walk:
            self._last_walk[key] = 0
        try:
            if self.sequential:
                self._verify_sequential(trusted, new_block, now)
            else:
                self._verify_skipping(trusted, new_block, now)
        finally:
            self._set_hashes = {}
        self._detect_divergence(new_block, now)
        self.store.save_light_block(new_block)
        if self.store.size() > self.pruning_size:
            self.store.prune(self.pruning_size)

    # --- verification strategies ---------------------------------------------

    def _verify_sequential(
        self, trusted: LightBlock, new_block: LightBlock, now: Timestamp
    ) -> None:
        """client.go verifySequential:554: fetch every header in between."""
        current = trusted
        for h in range(trusted.height + 1, new_block.height + 1):
            interim = (
                new_block if h == new_block.height else self._fetch_from_primary(h)
            )
            verifier.verify_adjacent(
                current.signed_header,
                interim.signed_header,
                interim.validator_set,
                self.trusting_period,
                now,
                self.max_clock_drift,
            )
            if h != new_block.height:
                self.store.save_light_block(interim)
            current = interim

    def _verify_skipping(
        self, trusted: LightBlock, new_block: LightBlock, now: Timestamp
    ) -> None:
        """client.go verifySkipping:647: bisection. Trust the target if
        trustLevel of the current trusted valset signed it; otherwise
        bisect towards the trusted block. Planned by default: the walk
        is decided on tallies and its signatures ride one scheduler
        super-batch (light/batch.py) instead of one device call per
        pivot."""
        if self.bisect_batching:
            return self._verify_skipping_batched(trusted, new_block, now)
        return self._verify_skipping_sequential(trusted, new_block, now)

    def _verify_skipping_batched(
        self, trusted: LightBlock, new_block: LightBlock, now: Timestamp
    ) -> None:
        """Same accept/reject decisions as the sequential loop, proved
        by the parity suite, with the signatures of a whole walk sent
        once: a round follows upstream's loop on tallies alone — a
        candidate the trusted set does not cover sends nothing and the
        midpoint is tried, a covered one becomes the next base — until
        the target is covered (``light_batch.Walk``), sends the lanes of
        the hops it took in ONE super-batch, and folds the verdicts back
        hop by hop: each verified pivot is saved as upstream saves it,
        the first hop that fails raises upstream's error, and what the
        walk owes beyond its hops (a pivot that could not be fetched,
        "cannot split further") is raised once they have verified. A
        second round runs only past a candidate the planner cannot
        express, which ``verifier.verify`` judges on its own."""
        base, current = trusted, new_block
        rounds = 0
        stats = self._last_walk
        try:
            while True:
                rounds += 1
                with tracing.span(
                    "light_round",
                    round=rounds,
                    base=base.height,
                    target=new_block.height,
                ) as rsp:
                    with tracing.span("light_plan") as psp:
                        walk = light_batch.Walk(
                            self.chain_id,
                            self.trusting_period,
                            now,
                            self.max_clock_drift,
                            self.trust_level,
                            psp,
                            self._set_hashes,
                        )
                        walk.plan(base, current, new_block, self._fetch_pivot)
                        psp.set(
                            hops=len(walk.hops),
                            refused_by_tally=walk.refused,
                            lanes=len(walk.batch.lanes),
                            merged=walk.batch.merged,
                        )
                    rsp.set(candidates=len(walk.hops) + walk.refused)
                    stats["refused_by_tally"] += walk.refused
                    stats["lanes"] += len(walk.batch.lanes)
                    stats["merged"] += walk.batch.merged
                    outcomes = walk.verify()
                for plan, out in zip(walk.hops, outcomes):
                    if out.kind != light_batch.OK:
                        raise out.error
                    stats["hops"] += 1
                    if plan.cand.height == new_block.height:
                        return
                    self.store.save_light_block(plan.cand)
                    base = plan.cand
                if walk.stop is not None:
                    raise walk.stop
                # the walk met a candidate only the sequential verifier
                # can judge: upstream's step for that one, then plan on
                base, current = walk.unplanned
                out = light_batch._resolve_sequential(
                    self.chain_id, base, current, self.trusting_period, now,
                    self.max_clock_drift, self.trust_level,
                )
                if out.kind == light_batch.BISECT:
                    stats["refused_by_tally"] += 1
                    current = self._fetch_pivot(base, current)
                    continue
                if out.kind != light_batch.OK:
                    raise out.error
                stats["hops"] += 1
                if current.height == new_block.height:
                    return
                self.store.save_light_block(current)
                base, current = current, new_block
        finally:
            self.metrics.bisection_rounds.observe(rounds)

    def _fetch_pivot(self, base: LightBlock, current: LightBlock) -> LightBlock:
        """The validated block half-way between the two, as upstream's
        loop fetches it when ``current`` cannot be trusted from ``base``."""
        pivot_height = (base.height + current.height) // 2
        if pivot_height in (base.height, current.height):
            raise LightClientError("bisection failed: cannot split further")
        pivot = self._fetch_from_primary(pivot_height)
        self._note_validated(pivot)
        return pivot

    def _note_validated(self, lb: LightBlock) -> None:
        """``lb.validate_basic``, keeping the set's hash it computed."""
        vals_hash = lb.validate_basic(self.chain_id)
        self._set_hashes[id(lb.validator_set)] = (lb.validator_set, vals_hash)

    def _verify_skipping_sequential(
        self, trusted: LightBlock, new_block: LightBlock, now: Timestamp
    ) -> None:
        """The reference's one-call-per-pivot loop, kept verbatim as the
        parity baseline (``bisect_batching=False``)."""
        verification_trace = [trusted]
        current = new_block
        while True:
            base = verification_trace[-1]
            try:
                verifier.verify(
                    base.signed_header,
                    base.validator_set,
                    current.signed_header,
                    current.validator_set,
                    self.trusting_period,
                    now,
                    self.max_clock_drift,
                    self.trust_level,
                )
            except verifier.NewValSetCantBeTrustedError:
                # Not enough trusted power: bisect to the midpoint.
                current = self._fetch_pivot(base, current)
                continue
            # Verified against base.
            if current.height == new_block.height:
                return
            verification_trace.append(current)
            self.store.save_light_block(current)
            current = new_block

    def _backwards(self, trusted: LightBlock, height: int) -> LightBlock:
        """client.go backwards:722: follow LastBlockID hashes down."""
        current = trusted
        for h in range(trusted.height - 1, height - 1, -1):
            interim = self._fetch_from_primary(h)
            verifier.verify_backwards(interim.signed_header.header, current.signed_header.header)
            self.store.save_light_block(interim)
            current = interim
        return current

    # --- detector (light/detector.go) ----------------------------------------

    def _detect_divergence(self, new_block: LightBlock, now: Timestamp) -> None:
        """detector.go:28-120: ask every witness for the same height; a
        conflicting header is an attack only if the witness's block itself
        verifies against our trust root — an unverifiable witness is just a
        bad witness and gets dropped (detector.go examineConflictingHeader)."""
        if not self.witnesses:
            return
        with tracing.span("light_detect", witnesses=len(self.witnesses)):
            self._cross_check(new_block, now)

    def _cross_check(self, new_block: LightBlock, now: Timestamp) -> None:
        # Gather every conflicting witness header first, then verify all
        # of them against the trusted root in ONE scheduler super-batch
        # (batched mode) — a round of witness cross-checks costs one
        # device call, not one per witness.
        bad_witnesses = []
        conflicts = []  # (witness index, witness, block, basic_ok)
        for i, witness in enumerate(list(self.witnesses)):
            try:
                w_block = witness.light_block(new_block.height)
            except (LightBlockNotFoundError, HeightTooHighError, ProviderError):
                continue
            if w_block.hash() == new_block.hash():
                continue
            # Verify the witness trace against the trusted root before
            # treating the conflict as evidence; garbage from a faulty
            # witness must not DoS the client or spawn bogus evidence.
            try:
                w_block.validate_basic(self.chain_id)
            except (ValueError, verifier.InvalidHeaderError):
                conflicts.append((i, witness, w_block, False))
                continue
            conflicts.append((i, witness, w_block, True))
        outcomes = {}
        # the trusted root is read only where a witness disagrees
        trusted = (
            self.store.light_block_before(new_block.height)
            if any(c[3] for c in conflicts)
            else None
        )
        to_verify = [
            c for c in conflicts if c[3] and trusted is not None
        ]
        if to_verify:
            if self.bisect_batching:
                evaluated = light_batch.evaluate_candidates(
                    self.chain_id,
                    trusted,
                    [c[2] for c in to_verify],
                    self.trusting_period,
                    now,
                    self.max_clock_drift,
                    self.trust_level,
                )
            else:
                evaluated = [
                    light_batch._resolve_sequential(
                        self.chain_id, trusted, c[2], self.trusting_period,
                        now, self.max_clock_drift, self.trust_level,
                    )
                    for c in to_verify
                ]
            for c, out in zip(to_verify, evaluated):
                outcomes[c[0]] = out
        for i, witness, w_block, basic_ok in conflicts:
            out = outcomes.get(i)
            if not basic_ok:
                bad_witnesses.append(witness)
                continue
            if out is not None and out.kind != light_batch.OK:
                err = out.error
                if isinstance(err, (ValueError, verifier.InvalidHeaderError)):
                    # includes NewValSetCantBeTrusted: an unverifiable
                    # witness is just a bad witness, not an attack
                    bad_witnesses.append(witness)
                    continue
                raise err  # e.g. NotEnoughVotingPowerError, raw as before
            # Conflict verified on both sides: a real light-client attack
            # (detector.go:122-215 abridged: common height = latest trusted
            # below the conflict).
            common = self.store.light_block_before(new_block.height)
            ev = LightClientAttackEvidence(
                conflicting_block=w_block,
                common_height=common.height if common else new_block.height - 1,
                total_voting_power=(
                    common.validator_set.total_voting_power() if common else 0
                ),
                timestamp=common.signed_header.header.time
                if common
                else new_block.signed_header.header.time,
            )
            for p in [self.primary] + self.witnesses:
                if p is not witness:
                    try:
                        p.report_evidence(ev)
                    except ProviderError:
                        pass
            raise DivergedHeaderError(ev, i)
        for w in bad_witnesses:
            self.witnesses.remove(w)

    # --- provider plumbing ----------------------------------------------------

    def _fetch_from_primary(self, height: int) -> LightBlock:
        lb = self.primary.light_block(height)
        if lb.height != height:
            raise LightClientError(
                f"primary returned height {lb.height}, wanted {height}"
            )
        return lb
