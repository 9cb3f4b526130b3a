"""Skipping verification planned on the host, its signatures sent once.

The sequential skipping loop (light/client.py) costs one
``verify_commit_light_trusting`` + ``verify_commit_light`` round-trip
per pivot, each a separate device launch, and checks a signature both
rules ask for twice. Here a candidate is *planned*: every check of
``verifier.verify(base, cand)`` that needs no signature runs on the
host — header shape, expiry, the set's hash, and both tallies — and the
signatures it would have checked become raw ed25519 lanes.

What makes a whole walk plannable: the descent of upstream's
``verifySkipping`` is decided by tallies alone. A candidate the trusted
set does not cover (``NewValSetCantBeTrusted``) is known from its tally
and sends nothing; a wrong signature never bisects, it aborts. So
:class:`Walk` follows upstream's loop from the trusted block towards
the target on tallies — refused: fetch the midpoint and try that;
covered: take it as the next base and try the target again — until the
target is covered, and only then sends the lanes of the hops it took,
all of them in ONE ``submit_many`` (one accumulator flush -> one
``verify_batch``). The verdicts are folded back hop by hop, in the
order upstream verifies them, so a bad signature stops the walk at its
hop with upstream's error and the hops before it stay trusted.

A round therefore sends exactly the signatures upstream's walk checks:
no lane for a candidate refused by tally or for a pivot the walk never
visits, none past either early exit, and a signature that both the
trusting rule and the 2/3 rule of one commit ask for is one lane
(``merged``), encoded once.

Parity contract: a candidate's outcome is EXACTLY what
``verifier.verify`` would have produced — same exception types, same
messages, same precedence (trusting tally before trusting signatures
before the full 2/3 check, ``NotEnoughVotingPowerError`` from the full
check propagating raw, ``InvalidCommitError`` surfacing as
``InvalidHeaderError``). Anything the lane planner can't express
byte-for-byte (non-ed25519 keys, sub-threshold commits, malformed
entries) ends the round before it and goes to the sequential verifier,
so this path never changes a verdict, only where the signatures run.

``evaluate_candidates`` plans several candidates against one base and
sends them together: the detector's conflicting witness headers, each
of which upstream verifies.

Every planned set goes through ``crypto_batch.note_validator_set``
(with the hash the header check has just computed), so a set met again
costs a recognition, not a rebuild.
"""

from __future__ import annotations

import time
from operator import methodcaller
from typing import Callable, Dict, List, Optional, Tuple

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto.keys import ED25519_KEY_TYPE
from tendermint_tpu.libs import tracing
from tendermint_tpu.light import verifier
from tendermint_tpu.types import Fraction
from tendermint_tpu.types.block import BLOCK_ID_FLAG_COMMIT
from tendermint_tpu.types.validation import (
    BATCH_VERIFY_THRESHOLD,
    InvalidCommitError,
    NotEnoughVotingPowerError,
    _safe_mul,
    _verify_basic_vals_and_commit,
)
from tendermint_tpu.verifyd.protocol import CLASS_LIGHT

# outcome kinds
OK = "ok"
BISECT = "bisect"  # NewValSetCantBeTrusted: descend to a deeper pivot
ERROR = "error"  # hard failure: propagate to the caller

# Verdict wait for one super-batch. It is a guard against a scheduler or
# a device that never answers, not a latency bound: ``verify_commit``
# waits for its kernel with no limit at all. The first batch of a shape
# carries its kernel's compile, and a process's first round can meet two
# shapes in one batch: 29.8 s + 25.7 s on a cold cache on the v5e
# (PERF.md section 6, PR 30; 10-38 s a shape, PR 21), which the 30 s
# this constant used to be turned into a refused header. Ten minutes is
# ten such rounds, and the longest a caller of verifyd may ask to wait
# (``protocol.MAX_DEADLINE_MS``); past it the round raises
# ``TimeoutError`` (``SuperBatch.send``).
DEFAULT_WAIT = 600.0


class Outcome:
    """Per-candidate verdict."""

    __slots__ = ("kind", "error")

    def __init__(self, kind: str, error: Optional[BaseException] = None):
        self.kind = kind
        self.error = error


class _SigStep:
    """Deferred check over lanes of the super-batch: the first False
    verdict becomes the sequential path's exact wrong-signature error."""

    __slots__ = ("lanes", "idxs", "commit")

    def __init__(self, lanes: List[int], idxs: List[int], commit):
        self.lanes = lanes
        self.idxs = idxs
        self.commit = commit


class _RaiseStep:
    """Deferred exception: raised only if every earlier step passed
    (mirrors the sequential check order)."""

    __slots__ = ("error",)

    def __init__(self, error: BaseException):
        self.error = error


class _Plan:
    __slots__ = ("base", "cand", "steps", "outcome", "fallback")

    def __init__(self, base, cand):
        self.base = base
        self.cand = cand
        self.steps: list = []
        self.outcome: Optional[Outcome] = None  # decided before any lane runs
        self.fallback = False  # only verifier.verify can judge this one

    @property
    def final(self) -> bool:
        """No walk continues past this candidate: it already failed, or
        fails once its signatures have passed."""
        return self.outcome is not None or any(
            isinstance(step, _RaiseStep) for step in self.steps
        )


def _header_checks(sh_t, sh_u, adjacent, trusting_period, now, max_clock_drift,
                   trust_level) -> None:
    """verifier.go:33-60 / 106-130 up to the supplied set's hash."""
    verifier._check_required_header_fields(sh_t)
    if not adjacent:
        verifier.validate_trust_level(trust_level)
    if verifier.header_expired(sh_t, trusting_period, now):
        raise verifier.HeaderExpiredError("old header has expired")
    verifier._check_new_header(sh_u, sh_t, now, max_clock_drift)


def _tally_trusting(by_address: Dict[bytes, tuple], commit, needed: int):
    """``verify_commit_light_trusting``'s loop less its signatures:
    ``(commit indices, validators, tallied)`` up to the early exit."""
    tallied = 0
    seen: dict = {}
    idxs: List[int] = []
    vals: list = []
    for idx, cs in enumerate(commit.signatures):
        if cs.block_id_flag != BLOCK_ID_FLAG_COMMIT:
            continue
        found = by_address.get(cs.validator_address)
        if found is None:
            continue
        val_idx, val = found
        if val_idx in seen:
            raise InvalidCommitError(
                f"double vote from validator {val_idx} "
                f"({seen[val_idx]} and {idx})"
            )
        seen[val_idx] = idx
        idxs.append(idx)
        vals.append(val)
        tallied += val.voting_power
        if tallied > needed:
            break
    return idxs, vals, tallied


def _tally_full(vals_u, commit, needed: int):
    """``verify_commit_light``'s loop less its signatures."""
    tallied = 0
    idxs: List[int] = []
    vals: list = []
    validators = vals_u.validators
    for idx, cs in enumerate(commit.signatures):
        if cs.block_id_flag != BLOCK_ID_FLAG_COMMIT:
            continue
        val = validators[idx]
        idxs.append(idx)
        vals.append(val)
        tallied += val.voting_power
        if tallied > needed:
            break
    return idxs, vals, tallied


def _run_tally(loop, *args):
    """One callable for the ``tally`` phase of both loops
    (``Span.timed`` takes one a phase)."""
    return loop(*args)


def _lane_bytes(encoder, idx: int) -> bytes:
    return encoder.lane(idx)


def _plannable(vals) -> bool:
    """Every signer must be a well-formed ed25519 key for raw scheduler
    lanes; anything else goes through the sequential verifier (which has
    the multi-key-type sub-batching)."""
    for v in vals.validators:
        pk = v.pub_key
        if pk is None or pk.type != ED25519_KEY_TYPE or len(pk.bytes()) != 32:
            return False
    return True


class SuperBatch:
    """The distinct signatures of one round, and the host steps that
    plan them. Built on a ``light_plan`` span, the steps are its phase
    totals (``header_checks``, ``valset_hash``, ``tally``,
    ``sign_bytes``: ``*_us`` and ``*_n``); on the no-op span they are
    the plain functions."""

    def __init__(self, span=tracing.NOP_SPAN, hashes: Optional[Dict[int, tuple]] = None):
        self.lanes: List[Tuple[bytes, bytes, bytes]] = []
        self.merged = 0  # signatures two rules asked for, sent once
        self.verdicts: List[bool] = []
        # id(set object) -> (the object, its hash): what this round
        # computed and what the caller holds from
        # ``LightBlock.validate_basic``; the entry keeps the object
        # alive, so its id names nothing else
        self._hashes: Dict[int, tuple] = {} if hashes is None else hashes
        self._by_address: Dict[int, tuple] = {}
        self.header_checks = span.timed("header_checks", _header_checks)
        self._hash = span.timed("valset_hash", methodcaller("hash"))
        self.tally = span.timed("tally", _run_tally)
        self.sign_bytes = span.timed("sign_bytes", _lane_bytes)

    def hash_of(self, vals) -> bytes:
        """``vals.hash()``, once for one set object (a target refused
        from one base is planned again from the next; a block the
        client has validated comes with its set's hash)."""
        known = self._hashes.get(id(vals))
        if known is None or known[0] is not vals:
            known = self._hashes[id(vals)] = (vals, self._hash(vals))
        return known[1]

    def by_address(self, vals) -> dict:
        """address -> (index, validator): what ``get_by_address`` finds
        by scanning, once a round for one set object."""
        known = self._by_address.get(id(vals))
        if known is None or known[0] is not vals:
            found: dict = {}
            for i, v in enumerate(vals.validators):
                found.setdefault(v.address, (i, v))
            known = self._by_address[id(vals)] = (vals, found)
        return known[1]

    def send(self, scheduler=None, timeout: float = DEFAULT_WAIT, hops: int = 0) -> None:
        """One ``submit_many`` for everything planned so far."""
        if not self.lanes:
            return
        sched = scheduler
        if sched is None:
            sched = crypto_batch.get_shared_scheduler()
        with tracing.span(
            "light_super_batch", lanes=len(self.lanes), candidates=hops,
            merged=self.merged,
        ):
            # the whole round is already assembled, each signature
            # planned once: it leaves as one verify_fn call (whole), and
            # at once (flush_by=now) instead of waiting out max_delay
            entries = sched.submit_many(
                self.lanes,
                priority=CLASS_LIGHT,
                flush_by=time.monotonic(),
                tag="light-bisect",
                whole=True,
            )
            self.verdicts = sched.wait_many(entries, timeout=timeout)
            # wait_many fails closed; a verdict that never came must not
            # be reported as a signature that is wrong
            late = sum(1 for e in entries if not e.done.is_set())
            if late:
                raise TimeoutError(
                    f"no verdict for {late} of {len(entries)} signatures "
                    f"within {timeout:g} s"
                )


def _plan_candidate(
    chain_id: str,
    base,
    cand,
    trusting_period: float,
    now,
    max_clock_drift: float,
    trust_level: Fraction,
    batch: SuperBatch,
) -> _Plan:
    """Host-side dry run of ``verifier.verify(base, cand)``: do every
    non-signature check now, emit the signature work as lanes of
    ``batch``."""
    plan = _Plan(base, cand)
    sh_t, vals_t = base.signed_header, base.validator_set
    sh_u, vals_u = cand.signed_header, cand.validator_set
    adjacent = sh_u.header.height == sh_t.header.height + 1

    # --- header-shape prechecks (verifier.go:33-60 / 106-130 order) ---------
    vhash_u = None
    try:
        batch.header_checks(
            sh_t, sh_u, adjacent, trusting_period, now, max_clock_drift,
            trust_level,
        )
        vhash_u = batch.hash_of(vals_u)
        verifier._check_vals_hash(sh_u, vhash_u)
        if adjacent and (
            sh_u.header.validators_hash != sh_t.header.next_validators_hash
        ):
            raise verifier.InvalidHeaderError(
                "expected old header's next validators to match those from "
                "new header"
            )
    except Exception as e:
        plan.outcome = Outcome(ERROR, e)
        return plan

    commit = sh_u.commit
    if (
        commit is None
        or vals_t is None
        or vals_u is None
        or len(commit.signatures) < BATCH_VERIFY_THRESHOLD
        or not _plannable(vals_t)
        or not _plannable(vals_u)
        or any(
            cs.signature is not None and len(cs.signature) != 64
            for cs in commit.signatures
            if cs.block_id_flag == BLOCK_ID_FLAG_COMMIT
        )
    ):
        plan.fallback = True
        return plan

    # both checks below read this commit's votes: a vote they share is
    # encoded once and is one lane
    encoder = commit.sign_bytes_encoder(chain_id)
    lane_of: Dict[Tuple[int, bytes], int] = {}

    def lanes_for(idxs: List[int], vals: list) -> List[int]:
        out = []
        for idx, val in zip(idxs, vals):
            pk = val.pub_key.bytes()
            at = lane_of.get((idx, pk))
            if at is None:
                at = lane_of[(idx, pk)] = len(batch.lanes)
                batch.lanes.append(
                    (pk, batch.sign_bytes(encoder, idx),
                     commit.signatures[idx].signature)
                )
            else:
                batch.merged += 1
            out.append(at)
        return out

    # --- trusting check (verify_commit_light_trusting, batch path) ----------
    if not adjacent:
        try:
            if trust_level.denominator == 0:
                raise InvalidCommitError("trustLevel has zero Denominator")
            total_mul, overflow = _safe_mul(
                vals_t.total_voting_power(), trust_level.numerator
            )
            if overflow:
                raise InvalidCommitError(
                    "int64 overflow while calculating voting power needed"
                )
            needed = total_mul // trust_level.denominator
            crypto_batch.note_validator_set_traced(vals_t)
            idxs, vals, tallied = batch.tally(
                _tally_trusting, batch.by_address(vals_t), commit, needed
            )
            if tallied <= needed:
                e = NotEnoughVotingPowerError(got=tallied, needed=needed)
                plan.outcome = Outcome(
                    BISECT, verifier.NewValSetCantBeTrustedError(str(e))
                )
                return plan
            plan.steps.append(_SigStep(lanes_for(idxs, vals), idxs, commit))
        except InvalidCommitError as e:
            # verify_non_adjacent wraps the ValueError family
            plan.outcome = Outcome(ERROR, verifier.InvalidHeaderError(str(e)))
            return plan

    # --- full 2/3 check (verify_commit_light, batch path) --------------------
    try:
        _verify_basic_vals_and_commit(
            vals_u, commit, sh_u.header.height, commit.block_id
        )
        needed2 = vals_u.total_voting_power() * 2 // 3
        crypto_batch.note_validator_set_traced(vals_u, vhash_u)
        idxs2, vals2, tallied2 = batch.tally(_tally_full, vals_u, commit, needed2)
        if tallied2 <= needed2:
            # NotEnoughVotingPowerError is not a ValueError: it escapes
            # verify_non_adjacent RAW (only after earlier steps pass)
            plan.steps.append(
                _RaiseStep(NotEnoughVotingPowerError(got=tallied2, needed=needed2))
            )
        else:
            plan.steps.append(_SigStep(lanes_for(idxs2, vals2), idxs2, commit))
    except InvalidCommitError as e:
        plan.steps.append(_RaiseStep(verifier.InvalidHeaderError(str(e))))
    return plan


def _resolve(plan: _Plan, verdicts: List[bool]) -> Outcome:
    if plan.outcome is not None:
        return plan.outcome
    for step in plan.steps:
        if isinstance(step, _RaiseStep):
            return Outcome(ERROR, step.error)
        for lane, idx in zip(step.lanes, step.idxs):
            if not verdicts[lane]:
                sig = step.commit.signatures[idx]
                e = InvalidCommitError(
                    f"wrong signature (#{idx}): {sig.signature.hex().upper()}"
                )
                return Outcome(ERROR, verifier.InvalidHeaderError(str(e)))
    return Outcome(OK)


def _resolve_sequential(
    chain_id, base, cand, trusting_period, now, max_clock_drift, trust_level
) -> Outcome:
    try:
        verifier.verify(
            base.signed_header,
            base.validator_set,
            cand.signed_header,
            cand.validator_set,
            trusting_period,
            now,
            max_clock_drift,
            trust_level,
        )
        return Outcome(OK)
    except verifier.NewValSetCantBeTrustedError as e:
        return Outcome(BISECT, e)
    except Exception as e:
        return Outcome(ERROR, e)


class Walk:
    """One round of skipping verification: upstream's ``verifySkipping``
    loop run on tallies from ``base`` towards ``target``, then one
    super-batch for the hops it took (module docstring).

    After :meth:`plan`: ``hops`` are the candidates the walk took, each
    covered by the block before it, in order, the last possibly one
    that fails (its error is owed only once the hops before it have
    verified); ``refused`` counts candidates refused by tally; ``stop``
    is the exception owed if every hop verifies (a pivot that could not
    be fetched or validated, "cannot split further"); ``unplanned`` is
    the ``(base, candidate)`` only ``verifier.verify`` can judge, where
    the walk met one. ``hashes`` maps ``id(validator set)`` to ``(the
    set, its hash)`` where the caller already holds it."""

    def __init__(self, chain_id, trusting_period, now, max_clock_drift,
                 trust_level, span=tracing.NOP_SPAN,
                 hashes: Optional[Dict[int, tuple]] = None):
        self._args = (trusting_period, now, max_clock_drift, trust_level)
        self.chain_id = chain_id
        self.batch = SuperBatch(span, hashes)
        self.hops: List[_Plan] = []
        self.refused = 0
        self.stop: Optional[BaseException] = None
        self.unplanned: Optional[tuple] = None

    def plan(self, base, current, target, fetch_pivot: Callable) -> None:
        """``fetch_pivot(base, current)`` returns the validated block
        half-way between the two, or raises what upstream's loop raises
        there."""
        while True:
            plan = _plan_candidate(
                self.chain_id, base, current, *self._args, self.batch
            )
            if plan.fallback:
                self.unplanned = (base, current)
                return
            if plan.outcome is not None and plan.outcome.kind == BISECT:
                self.refused += 1
                try:
                    current = fetch_pivot(base, current)
                except Exception as exc:
                    self.stop = exc
                    return
                continue
            self.hops.append(plan)
            if plan.final or current.height == target.height:
                return
            base, current = current, target

    def verify(self, scheduler=None, timeout: float = DEFAULT_WAIT) -> List[Outcome]:
        """Send the round's lanes; one outcome per hop, in order."""
        self.batch.send(scheduler, timeout, hops=len(self.hops))
        return [_resolve(p, self.batch.verdicts) for p in self.hops]


def evaluate_candidates(
    chain_id: str,
    base,
    candidates: list,
    trusting_period: float,
    now,
    max_clock_drift: float,
    trust_level: Fraction,
    scheduler=None,
    timeout: float = DEFAULT_WAIT,
) -> List[Outcome]:
    """Verify every candidate against ``base`` with at most ONE
    scheduler super-batch, returning outcomes aligned with
    ``candidates``: for candidates each of which the caller has to
    verify (the detector's conflicting witness headers). Candidates the
    planner can't express fall back to the sequential verifier
    individually (still host-side, no extra device calls)."""
    with tracing.span("light_plan", candidates=len(candidates)) as psp:
        batch = SuperBatch(psp)
        plans = [
            _plan_candidate(
                chain_id, base, c, trusting_period, now, max_clock_drift,
                trust_level, batch,
            )
            for c in candidates
        ]
        psp.set(lanes=len(batch.lanes), merged=batch.merged)
    batch.send(scheduler, timeout, hops=len(candidates))
    out: List[Outcome] = []
    for p in plans:
        if p.fallback:
            out.append(
                _resolve_sequential(
                    chain_id, base, p.cand, trusting_period, now,
                    max_clock_drift, trust_level,
                )
            )
        else:
            out.append(_resolve(p, batch.verdicts))
    return out
