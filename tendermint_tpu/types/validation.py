"""Commit verification: the framework's crypto hot path.

Mirrors types/validation.go exactly: ignore/count predicates per entry
point, tally-then-verify, batch dispatch above a threshold with
single-verify fallback, and first-bad-signature fault attribution on
batch failure (validation.go:244-251).

The batch path feeds ``crypto.batch.create_batch_verifier`` which routes
to the TPU Straus kernel (ops/ed25519_batch.py) for ed25519 — one device
launch verifies every signature in the commit.
"""

from __future__ import annotations

from itertools import accumulate
from typing import Callable, NamedTuple, Optional

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.libs import tracing
from tendermint_tpu.types.block import BlockID, Commit, CommitSig, BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.verifyd.client import classify as _classify
from tendermint_tpu.verifyd.protocol import (
    CLASS_BLOCKSYNC as _CLASS_BLOCKSYNC,
    CLASS_CONSENSUS as _CLASS_CONSENSUS,
    CLASS_LIGHT as _CLASS_LIGHT,
)

BATCH_VERIFY_THRESHOLD = 2  # validation.go:12


class Fraction(NamedTuple):
    """libs/math Fraction: unsigned numerator/denominator."""

    numerator: int
    denominator: int


INT64_MAX = 2**63 - 1


def _safe_mul(a: int, b: int) -> tuple:
    """libs/math SafeMul: (result, overflowed) for int64."""
    r = a * b
    if r > INT64_MAX or r < -(2**63):
        return 0, True
    return r, False


class NotEnoughVotingPowerError(Exception):
    def __init__(self, got: int, needed: int):
        self.got = got
        self.needed = needed
        super().__init__(
            f"invalid commit -- insufficient voting power: got {got}, needed more than {needed}"
        )


class InvalidCommitError(ValueError):
    pass


def _should_batch_verify(commit: Commit) -> bool:
    """validation.go:14-16, less its look at the proposer's key: the
    batch path takes a set of any key types (MultiBatchVerifier puts
    the lanes of a type that cannot batch on the host), so a secp256k1
    proposer no longer sends a mixed commit to single verification."""
    return len(commit.signatures) >= BATCH_VERIFY_THRESHOLD


def verify_commit(
    chain_id: str, vals: ValidatorSet, block_id: BlockID, height: int, commit: Commit
) -> None:
    """validation.go:28-54: +2/3 signed; checks ALL signatures (ABCI apps
    depend on the full LastCommitInfo for incentivization)."""
    # Outermost-wins workload class: a configured verifyd remote treats
    # full commit verification as consensus-priority (never shed).
    with _classify(_CLASS_CONSENSUS), tracing.span(
        "verify_commit",
        height=height,
        round=commit.round,
        sigs=len(commit.signatures),
    ):
        _verify_basic_vals_and_commit(vals, commit, height, block_id)
        voting_power_needed = vals.total_voting_power() * 2 // 3
        ignore = lambda c: c.block_id_flag == BLOCK_ID_FLAG_ABSENT
        count = lambda c: c.block_id_flag == BLOCK_ID_FLAG_COMMIT
        if _should_batch_verify(commit):
            return _verify_commit_batch(
                chain_id, vals, commit, voting_power_needed, ignore, count,
                True, True,
            )
        return _verify_commit_single(
            chain_id, vals, commit, voting_power_needed, ignore, count,
            True, True,
        )


def verify_commit_light(
    chain_id: str, vals: ValidatorSet, block_id: BlockID, height: int, commit: Commit
) -> None:
    """validation.go:58-87: light-client/blocksync variant; stops at +2/3."""
    # Blocksync-priority by default; the light package classifies its
    # own calls "light" first (outermost wins).
    with _classify(_CLASS_BLOCKSYNC), tracing.span(
        "verify_commit",
        mode="light",
        height=height,
        round=commit.round,
        sigs=len(commit.signatures),
    ):
        _verify_basic_vals_and_commit(vals, commit, height, block_id)
        voting_power_needed = vals.total_voting_power() * 2 // 3
        ignore = lambda c: c.block_id_flag != BLOCK_ID_FLAG_COMMIT
        count = lambda c: True
        if _should_batch_verify(commit):
            return _verify_commit_batch(
                chain_id, vals, commit, voting_power_needed, ignore, count,
                False, True,
            )
        return _verify_commit_single(
            chain_id, vals, commit, voting_power_needed, ignore, count,
            False, True,
        )


def verify_commit_light_trusting(
    chain_id: str, vals: ValidatorSet, commit: Commit, trust_level: Fraction
) -> None:
    """validation.go:89-135: trustLevel of a DIFFERENT valset signed;
    lookup is by address, double-signs detected."""
    if vals is None:
        raise InvalidCommitError("nil validator set")
    if trust_level.denominator == 0:
        raise InvalidCommitError("trustLevel has zero Denominator")
    if commit is None:
        raise InvalidCommitError("nil commit")
    total_mul, overflow = _safe_mul(vals.total_voting_power(), trust_level.numerator)
    if overflow:
        raise InvalidCommitError(
            "int64 overflow while calculating voting power needed"
        )
    voting_power_needed = total_mul // trust_level.denominator
    ignore = lambda c: c.block_id_flag != BLOCK_ID_FLAG_COMMIT
    count = lambda c: True
    # Trusting verification only happens on the light-client path.
    with _classify(_CLASS_LIGHT):
        if _should_batch_verify(commit):
            return _verify_commit_batch(
                chain_id, vals, commit, voting_power_needed, ignore, count,
                False, False,
            )
        return _verify_commit_single(
            chain_id, vals, commit, voting_power_needed, ignore, count,
            False, False,
        )


def _by_flag(predicate: Callable[[CommitSig], bool], flags) -> dict:
    """What one of a rule's predicates says of a commit signature, by
    its BlockIDFlag: the predicate is asked once a flag the commit
    carries, not once a lane (the three rules read nothing else of an
    entry, validation.go:28-135)."""
    return {flag: bool(predicate(CommitSig(block_id_flag=flag))) for flag in set(flags)}


def _select_lanes(
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable[[CommitSig], bool],
    count_sig: Callable[[CommitSig], bool],
    count_all_signatures: bool,
    look_up_by_index: bool,
    val_lookup: Callable,
):
    """The commit's entries that become lanes, chosen and tallied
    without a call into the verifier: ``(idxs, validators, tallied,
    fault)``, the lanes in the commit's order up to the one at which a
    light rule's tally first passes ``voting_power_needed``. ``fault``
    is the double vote that ends them, for the caller to raise once the
    lanes before it are in and none of them was refused."""
    entries = commit.signatures
    flags = [commit_sig.block_id_flag for commit_sig in entries]
    ignored, counted = _by_flag(ignore_sig, flags), _by_flag(count_sig, flags)
    idxs = [idx for idx, flag in enumerate(flags) if not ignored[flag]]
    tallied = 0
    if not look_up_by_index:
        kept, seats, seen_vals = [], [], {}
        for idx in idxs:
            val_idx, val = val_lookup(entries[idx].validator_address)
            if val is None:
                continue
            if val_idx in seen_vals:
                return kept, seats, tallied, InvalidCommitError(
                    f"double vote from validator {val_idx} ({seen_vals[val_idx]} and {idx})"
                )
            seen_vals[val_idx] = idx
            kept.append(idx)
            seats.append(val)
            if counted[flags[idx]]:
                tallied += val.voting_power
            if not count_all_signatures and tallied > voting_power_needed:
                break
        return kept, seats, tallied, None
    validators = vals.validators
    seats = [validators[idx] for idx in idxs]
    powers = [val.voting_power if counted[flags[idx]] else 0 for idx, val in zip(idxs, seats)]
    if not count_all_signatures:
        for lanes, tallied in enumerate(accumulate(powers), 1):
            if tallied > voting_power_needed:
                return idxs[:lanes], seats[:lanes], tallied, None
    return idxs, seats, sum(powers), None


def _add_lane_by_lane(bv, encoder, entries, idxs, pub_keys) -> bool:
    """A block holding an entry no sign-bytes are made of (an unknown
    BlockIDFlag): lane by lane up to it, where ``lane`` raises what it
    always did, so that a lane before it that the verifier refuses is
    still the one that decides (False: it refused one)."""
    for idx, pub_key in zip(idxs, pub_keys):
        vote_sign_bytes = encoder.lane(idx)
        try:
            bv.add(pub_key, vote_sign_bytes, entries[idx].signature)
        except ValueError:
            return False
    return True


def _verify_commit_batch(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable[[CommitSig], bool],
    count_sig: Callable[[CommitSig], bool],
    count_all_signatures: bool,
    look_up_by_index: bool,
) -> None:
    """validation.go:151-258.

    Divergence (improvement): a mixed commit sub-batches per key type
    (crypto/batch.MultiBatchVerifier), ed25519 and sr25519 each on its
    own device kernel and the lanes of a type with no batch support
    (secp256k1) on the host under ``host_lanes``, verified while the
    device runs the two sub-batches, which are both dispatched before
    either is collected — the reference's single-key-type verifier
    would fail the whole commit. Every sub-batch is verified whatever
    another found, and the first bad lane across types is named. Only
    an entry its own verifier refuses to take (a malformed ed25519 key
    or signature) sends the commit to single verification.
    """
    # Make this set's keys eligible for the device precompute cache —
    # the second commit from the same validators skips its table builds.
    crypto_batch.note_validator_set_traced(vals)
    # Mixed validator sets sub-batch per key type (BASELINE config 5);
    # a malformed entry raises on add -> single fallback.
    bv = crypto_batch.MultiBatchVerifier()
    unbatchable = False
    early_lanes = blocks = at = 0
    encoder = commit.sign_bytes_encoder(chain_id)
    entries = commit.signatures
    batch_sig_idxs = None
    try:
        # The lanes are built a block at a time, a block being the lanes
        # up to the one that fills the verifier's next engine job (bv.room
        # says which: in a mixed set it depends on the seats) or the rest
        # of the commit: its sign-bytes in one call, one add_many, and
        # then the verifier is told to begin the job, so that the device
        # works while the next block is built. One build_lanes span a
        # block, the begin between two of them and never inside one: a
        # span's steps are phase totals in its arguments (wrapped once a
        # span: on the no-op span these are the callables themselves),
        # and none of them holds engine time. The first span also holds
        # the choice of the commit's lanes and their tally.
        while True:
            with tracing.span("build_lanes") as lsp:
                if batch_sig_idxs is None:
                    batch_sig_idxs, seats, tallied, fault = _select_lanes(
                        vals, commit, voting_power_needed, ignore_sig, count_sig,
                        count_all_signatures, look_up_by_index,
                        lsp.timed("val_lookup", vals.get_by_address),
                    )
                    pub_keys = [val.pub_key for val in seats]
                sign_bytes = lsp.timed("sign_bytes", encoder.lanes)
                batch_add = lsp.timed("batch_add", bv.add_many)
                end = min(at + bv.room(pub_keys[at:]), len(pub_keys))
                idxs, keys = batch_sig_idxs[at:end], pub_keys[at:end]
                blocks += 1
                block_lanes = 0
                try:
                    vote_sign_bytes = sign_bytes(idxs)
                except ValueError:
                    unbatchable = not _add_lane_by_lane(bv, encoder, entries, idxs, keys)
                else:
                    try:
                        batch_add(keys, vote_sign_bytes, [entries[idx].signature for idx in idxs])
                        block_lanes = len(idxs)
                    except ValueError:
                        unbatchable = True
                lsp.set(lanes=len(idxs), block_lanes=block_lanes, sign_bytes_prefixes=encoder.prefixes)
            at = end
            if unbatchable or at == len(batch_sig_idxs):
                break
            early_lanes += bv.begin_ready()
        tracing.tag(early_lanes=early_lanes, blocks=blocks)  # on the caller's verify_commit span
        if not unbatchable:
            if fault is not None:
                raise fault
            if tallied <= voting_power_needed:
                raise NotEnoughVotingPowerError(got=tallied, needed=voting_power_needed)
            ok, valid_sigs = bv.verify()
    finally:
        # whatever left before verify() had lanes on the device: collected
        bv.close()
    if unbatchable:
        return _verify_commit_single(
            chain_id,
            vals,
            commit,
            voting_power_needed,
            ignore_sig,
            count_sig,
            count_all_signatures,
            look_up_by_index,
        )
    if ok:
        return
    with tracing.span("merge_verdicts", lanes=len(valid_sigs), scan="first_bad"):
        for i, sig_ok in enumerate(valid_sigs):
            if not sig_ok:
                idx = batch_sig_idxs[i]
                sig = commit.signatures[idx]
                raise InvalidCommitError(
                    f"wrong signature (#{idx}): {sig.signature.hex().upper()}"
                )
    raise InvalidCommitError(
        "BUG: batch verification failed with no invalid signatures"
    )


def _verify_commit_single(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable[[CommitSig], bool],
    count_sig: Callable[[CommitSig], bool],
    count_all_signatures: bool,
    look_up_by_index: bool,
) -> None:
    """validation.go:262-330."""
    tallied = 0
    seen_vals = {}
    # The batch path's steps, less the batch: the same phase names on
    # one span, the signature checks themselves the span's own time.
    with tracing.span("single_verify", lanes=len(commit.signatures)) as lsp:
        sign_bytes = lsp.timed("sign_bytes", commit.sign_bytes_encoder(chain_id).lane)
        val_lookup = lsp.timed("val_lookup", vals.get_by_address)
        for idx, commit_sig in enumerate(commit.signatures):
            if ignore_sig(commit_sig):
                continue
            if look_up_by_index:
                val = vals.validators[idx]
            else:
                val_idx, val = val_lookup(commit_sig.validator_address)
                if val is None:
                    continue
                if val_idx in seen_vals:
                    raise InvalidCommitError(
                        f"double vote from validator {val_idx} "
                        f"({seen_vals[val_idx]} and {idx})"
                    )
                seen_vals[val_idx] = idx
            vote_sign_bytes = sign_bytes(idx)
            if not val.pub_key.verify_signature(vote_sign_bytes, commit_sig.signature):
                raise InvalidCommitError(
                    f"wrong signature (#{idx}): {commit_sig.signature.hex().upper()}"
                )
            if count_sig(commit_sig):
                tallied += val.voting_power
            if not count_all_signatures and tallied > voting_power_needed:
                return
    if tallied <= voting_power_needed:
        raise NotEnoughVotingPowerError(got=tallied, needed=voting_power_needed)


def _verify_basic_vals_and_commit(
    vals: Optional[ValidatorSet],
    commit: Optional[Commit],
    height: int,
    block_id: BlockID,
) -> None:
    """validation.go:334-356."""
    if vals is None:
        raise InvalidCommitError("nil validator set")
    if commit is None:
        raise InvalidCommitError("nil commit")
    if len(vals) != len(commit.signatures):
        raise InvalidCommitError(
            f"invalid commit -- wrong set size: {len(vals)} vs "
            f"{len(commit.signatures)}"
        )
    if height != commit.height:
        raise InvalidCommitError(
            f"invalid commit -- wrong height: {height} vs {commit.height}"
        )
    if block_id != commit.block_id:
        raise InvalidCommitError(
            f"invalid commit -- wrong block ID: want {block_id}, got {commit.block_id}"
        )
