"""One-device-call bisection rounds for the light client.

The sequential skipping loop (light/client.py) costs one
``verify_commit_light_trusting`` + ``verify_commit_light`` round-trip
per pivot — each a separate device launch. This module turns a whole
bisection round into ONE scheduler super-batch: every candidate in the
pivot ladder (and every conflicting witness header in the detector) is
*planned* host-side into raw ed25519 lanes, the union of all lanes is
submitted through the process-wide ``VerifyScheduler`` in a single
atomic ``submit_many`` (one accumulator flush -> one device call), and
the verdicts are then folded back into per-candidate accept / bisect /
error outcomes host-side.

Parity contract: a candidate's outcome is EXACTLY what
``verifier.verify`` would have produced — same exception types, same
messages, same precedence (trusting tally before trusting signatures
before the full 2/3 check, ``NotEnoughVotingPowerError`` from the full
check propagating raw, ``InvalidCommitError`` surfacing as
``InvalidHeaderError``). Anything the lane planner can't express
byte-for-byte (non-ed25519 keys, sub-threshold commits, malformed
entries) falls back to the sequential verifier for that candidate, so
the batch path never changes a verdict, only where the signatures run.

Validator-set reuse rides the existing PR 2/8 paths: every planned set
goes through ``crypto_batch.note_validator_set`` so repeated sets cost
resident-table index-gathers, not rebuilds.
"""

from __future__ import annotations

import os
import time
from typing import List, Optional, Tuple

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

DEFAULT_WAIT = 30.0  # verdict wait for one super-batch


def batching_enabled() -> bool:
    """Batched rounds are the default; TENDERMINT_TPU_LIGHT_BATCH=off
    restores the one-call-per-pivot sequential loop (parity baseline)."""
    return os.environ.get("TENDERMINT_TPU_LIGHT_BATCH", "on").lower() not in (
        "off", "0", "false",
    )


class Outcome:
    """Per-candidate verdict of one evaluated ladder."""

    __slots__ = ("kind", "error")

    def __init__(self, kind: str, error: Optional[BaseException] = None):
        self.kind = kind
        self.error = error


class _SigStep:
    """Deferred check over a contiguous lane slice: the first False
    verdict becomes the sequential path's exact wrong-signature error."""

    __slots__ = ("start", "idxs", "commit")

    def __init__(self, start: int, idxs: List[int], commit):
        self.start = start
        self.idxs = idxs
        self.commit = commit


class _RaiseStep:
    """Deferred exception: raised only if every earlier step passed
    (mirrors the sequential check order)."""

    __slots__ = ("error",)

    def __init__(self, error: BaseException):
        self.error = error


class _Plan:
    __slots__ = ("cand", "steps", "outcome", "fallback", "lanes")

    def __init__(self, cand):
        self.cand = cand
        self.steps: list = []
        self.outcome: Optional[Outcome] = None  # decided before any lane runs
        self.fallback = False  # punt this candidate to verifier.verify
        self.lanes: List[Tuple[bytes, bytes, bytes]] = []


def _plannable(vals) -> bool:
    """Every signer must be a well-formed ed25519 key for raw scheduler
    lanes; anything else goes through the sequential verifier (which has
    the multi-key-type sub-batching)."""
    for v in vals.validators:
        pk = v.pub_key
        if pk is None or pk.type != ED25519_KEY_TYPE or len(pk.bytes()) != 32:
            return False
    return True


def _plan_candidate(
    chain_id: str,
    base,
    cand,
    trusting_period: float,
    now,
    max_clock_drift: float,
    trust_level: Fraction,
) -> _Plan:
    """Host-side dry run of ``verifier.verify(base, cand)``: do every
    non-signature check now, emit the signature work as lanes."""
    plan = _Plan(cand)
    sh_t, vals_t = base.signed_header, base.validator_set
    sh_u, vals_u = cand.signed_header, cand.validator_set
    adjacent = sh_u.header.height == sh_t.header.height + 1

    # --- header-shape prechecks (verifier.go:33-60 / 106-130 order) ---------
    try:
        verifier._check_required_header_fields(sh_t)
        if not adjacent:
            verifier.validate_trust_level(trust_level)
        if verifier.header_expired(sh_t, trusting_period, now):
            raise verifier.HeaderExpiredError("old header has expired")
        verifier._verify_new_header_and_vals(
            sh_u, vals_u, sh_t, now, max_clock_drift
        )
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

    # both checks below read this commit's votes: what they share is
    # encoded once for the two loops
    sign_bytes = commit.sign_bytes_encoder(chain_id).lane

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
            crypto_batch.note_validator_set(vals_t)
            tallied = 0
            seen: dict = {}
            lanes: List[Tuple[bytes, bytes, bytes]] = []
            idxs: List[int] = []
            for idx, cs in enumerate(commit.signatures):
                if cs.block_id_flag != BLOCK_ID_FLAG_COMMIT:
                    continue
                val_idx, val = vals_t.get_by_address(cs.validator_address)
                if val is None:
                    continue
                if val_idx in seen:
                    raise InvalidCommitError(
                        f"double vote from validator {val_idx} "
                        f"({seen[val_idx]} and {idx})"
                    )
                seen[val_idx] = idx
                lanes.append(
                    (
                        val.pub_key.bytes(),
                        sign_bytes(idx),
                        cs.signature,
                    )
                )
                idxs.append(idx)
                tallied += val.voting_power
                if tallied > needed:
                    break
            if tallied <= needed:
                e = NotEnoughVotingPowerError(got=tallied, needed=needed)
                plan.outcome = Outcome(
                    BISECT, verifier.NewValSetCantBeTrustedError(str(e))
                )
                return plan
            plan.steps.append(_SigStep(len(plan.lanes), idxs, commit))
            plan.lanes.extend(lanes)
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
        crypto_batch.note_validator_set(vals_u)
        tallied2 = 0
        lanes2: List[Tuple[bytes, bytes, bytes]] = []
        idxs2: List[int] = []
        for idx, cs in enumerate(commit.signatures):
            if cs.block_id_flag != BLOCK_ID_FLAG_COMMIT:
                continue
            val = vals_u.validators[idx]
            lanes2.append(
                (
                    val.pub_key.bytes(),
                    sign_bytes(idx),
                    cs.signature,
                )
            )
            idxs2.append(idx)
            tallied2 += val.voting_power
            if tallied2 > needed2:
                break
        if tallied2 <= needed2:
            # NotEnoughVotingPowerError is not a ValueError: it escapes
            # verify_non_adjacent RAW (only after earlier steps pass)
            plan.steps.append(
                _RaiseStep(NotEnoughVotingPowerError(got=tallied2, needed=needed2))
            )
        else:
            plan.steps.append(_SigStep(len(plan.lanes), idxs2, commit))
            plan.lanes.extend(lanes2)
    except InvalidCommitError as e:
        plan.steps.append(_RaiseStep(verifier.InvalidHeaderError(str(e))))
    return plan


def _resolve(plan: _Plan, verdicts: List[bool], base_off: int) -> Outcome:
    if plan.outcome is not None:
        return plan.outcome
    for step in plan.steps:
        if isinstance(step, _RaiseStep):
            return Outcome(ERROR, step.error)
        for rel, idx in enumerate(step.idxs):
            if not verdicts[base_off + step.start + rel]:
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
    ``candidates``. Candidates the planner can't express fall back to
    the sequential verifier individually (still host-side, no extra
    device calls)."""
    plans = [
        _plan_candidate(
            chain_id, base, c, trusting_period, now, max_clock_drift,
            trust_level,
        )
        for c in candidates
    ]
    lanes: List[Tuple[bytes, bytes, bytes]] = []
    offsets: List[int] = []
    for p in plans:
        offsets.append(len(lanes))
        lanes.extend(p.lanes)
    verdicts: List[bool] = []
    if lanes:
        sched = scheduler
        if sched is None:
            sched = crypto_batch.get_shared_scheduler()
        with tracing.span(
            "light_super_batch", lanes=len(lanes), candidates=len(candidates)
        ):
            # flush_by=now: the whole round is already assembled — pull
            # the accumulator's deadline to "immediately" so the batch
            # ships as one device call without waiting out max_delay
            entries = sched.submit_many(
                lanes,
                priority=CLASS_LIGHT,
                flush_by=time.monotonic(),
                tag="light-bisect",
            )
            verdicts = sched.wait_many(entries, timeout=timeout)
    out: List[Outcome] = []
    for p, off in zip(plans, offsets):
        if p.fallback:
            out.append(
                _resolve_sequential(
                    chain_id, base, p.cand, trusting_period, now,
                    max_clock_drift, trust_level,
                )
            )
        else:
            out.append(_resolve(p, verdicts, off))
    return out
