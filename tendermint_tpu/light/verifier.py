"""Stateless light-client verification (light/verifier.go).

Both the adjacent and non-adjacent (skipping) paths end in batched commit
verification (types/validation.py), so bisection over long header ranges
rides the device batch verifier — the reference's hot path at
light/verifier.go:70,85,149.
"""

from __future__ import annotations

from typing import Optional

from tendermint_tpu.encoding.canonical import Timestamp
from tendermint_tpu.types import Fraction, NotEnoughVotingPowerError
from tendermint_tpu.types.block import Header
from tendermint_tpu.types.light import SignedHeader
from tendermint_tpu.types.validation import (
    verify_commit_light,
    verify_commit_light_trusting,
)
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.verifyd.client import classify as _classify
from tendermint_tpu.verifyd.protocol import CLASS_LIGHT as _CLASS_LIGHT

DEFAULT_TRUST_LEVEL = Fraction(1, 3)


class InvalidHeaderError(ValueError):
    pass


class HeaderExpiredError(ValueError):
    pass


class NewValSetCantBeTrustedError(ValueError):
    """< trustLevel of the trusted valset signed the new header."""


def validate_trust_level(lvl: Fraction) -> None:
    """light/verifier.go:176-186: trustLevel in [1/3, 1)."""
    if (
        lvl.numerator * 3 < lvl.denominator
        or lvl.numerator >= lvl.denominator
        or lvl.denominator == 0
    ):
        raise ValueError(f"trustLevel must be within [1/3, 1], given {lvl}")


def header_expired(
    h: SignedHeader, trusting_period: float, now: Timestamp
) -> bool:
    """light/verifier.go:189-192."""
    expiration_ns = h.header.time.to_unix_ns() + int(trusting_period * 1e9)
    return expiration_ns <= now.to_unix_ns()


def _check_required_header_fields(h: SignedHeader) -> None:
    if h.header is None:
        raise InvalidHeaderError("missing header")
    if not h.header.chain_id or h.header.height == 0 or not h.header.next_validators_hash:
        raise InvalidHeaderError("trusted header missing required fields")


def _check_new_header(
    untrusted: SignedHeader,
    trusted: SignedHeader,
    now: Timestamp,
    max_clock_drift: float,
) -> None:
    """light/verifier.go:236-285: everything ``verifyNewHeaderAndVals``
    checks before it hashes the supplied validator set."""
    untrusted.validate_basic(trusted.chain_id)
    if untrusted.header.height <= trusted.header.height:
        raise InvalidHeaderError(
            f"expected new header height {untrusted.header.height} to be greater "
            f"than one of old header {trusted.header.height}"
        )
    if untrusted.header.time.to_unix_ns() <= trusted.header.time.to_unix_ns():
        raise InvalidHeaderError(
            "expected new header time to be after old header time"
        )
    if untrusted.header.time.to_unix_ns() >= now.to_unix_ns() + int(
        max_clock_drift * 1e9
    ):
        raise InvalidHeaderError(
            "new header has a time from the future"
        )


def _check_vals_hash(untrusted: SignedHeader, vals_hash: bytes) -> None:
    """light/verifier.go:286-292, given the supplied set's hash."""
    if untrusted.header.validators_hash != vals_hash:
        raise InvalidHeaderError(
            "expected new header validators to match those that were supplied"
        )


def _verify_new_header_and_vals(
    untrusted: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusted: SignedHeader,
    now: Timestamp,
    max_clock_drift: float,
) -> None:
    """light/verifier.go:236-292."""
    _check_new_header(untrusted, trusted, now, max_clock_drift)
    _check_vals_hash(untrusted, untrusted_vals.hash())


def verify_non_adjacent(
    trusted_header: SignedHeader,
    trusted_vals: ValidatorSet,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period: float,
    now: Timestamp,
    max_clock_drift: float,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
) -> None:
    """light/verifier.go:33-91: trustLevel of old valset + 2/3 of new."""
    _check_required_header_fields(trusted_header)
    if untrusted_header.height == trusted_header.height + 1:
        raise InvalidHeaderError("headers must be non adjacent in height")
    validate_trust_level(trust_level)
    # the TRUSTED header's age gates verification (verifier.go:47): an
    # expired trust root must not anchor new updates, however fresh the
    # untrusted header looks — that is the long-range-attack window
    if header_expired(trusted_header, trusting_period, now):
        raise HeaderExpiredError("old header has expired")
    _verify_new_header_and_vals(
        untrusted_header, untrusted_vals, trusted_header, now, max_clock_drift
    )
    # Light-client workload class (outermost wins over validation's
    # blocksync default): a verifyd remote may shed this under load.
    with _classify(_CLASS_LIGHT):
        try:
            verify_commit_light_trusting(
                trusted_header.chain_id, trusted_vals, untrusted_header.commit, trust_level
            )
        except NotEnoughVotingPowerError as e:
            raise NewValSetCantBeTrustedError(str(e)) from e
        except ValueError as e:
            raise InvalidHeaderError(str(e)) from e
        try:
            verify_commit_light(
                trusted_header.chain_id,
                untrusted_vals,
                untrusted_header.commit.block_id,
                untrusted_header.height,
                untrusted_header.commit,
            )
        except ValueError as e:
            raise InvalidHeaderError(str(e)) from e


def verify_adjacent(
    trusted_header: SignedHeader,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period: float,
    now: Timestamp,
    max_clock_drift: float,
) -> None:
    """light/verifier.go:106-152: valhash chain link + 2/3 of new valset."""
    _check_required_header_fields(trusted_header)
    if untrusted_header.height != trusted_header.height + 1:
        raise InvalidHeaderError("headers must be adjacent in height")
    # trusted-header expiry, as above (verifier.go:116)
    if header_expired(trusted_header, trusting_period, now):
        raise HeaderExpiredError("old header has expired")
    _verify_new_header_and_vals(
        untrusted_header, untrusted_vals, trusted_header, now, max_clock_drift
    )
    if untrusted_header.header.validators_hash != trusted_header.header.next_validators_hash:
        raise InvalidHeaderError(
            "expected old header's next validators to match those from new header"
        )
    with _classify(_CLASS_LIGHT):
        try:
            verify_commit_light(
                trusted_header.chain_id,
                untrusted_vals,
                untrusted_header.commit.block_id,
                untrusted_header.height,
                untrusted_header.commit,
            )
        except ValueError as e:
            raise InvalidHeaderError(str(e)) from e


def verify(
    trusted_header: SignedHeader,
    trusted_vals: ValidatorSet,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period: float,
    now: Timestamp,
    max_clock_drift: float,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
) -> None:
    """light/verifier.go:158-174."""
    if untrusted_header.height != trusted_header.height + 1:
        verify_non_adjacent(
            trusted_header,
            trusted_vals,
            untrusted_header,
            untrusted_vals,
            trusting_period,
            now,
            max_clock_drift,
            trust_level,
        )
    else:
        verify_adjacent(
            trusted_header,
            untrusted_header,
            untrusted_vals,
            trusting_period,
            now,
            max_clock_drift,
        )


def verify_backwards(untrusted_header: Header, trusted_header: Header) -> None:
    """light/verifier.go:195-233: hash-chain link going backwards."""
    untrusted_header.validate_basic()
    if untrusted_header.chain_id != trusted_header.chain_id:
        raise InvalidHeaderError("new header belongs to a different chain")
    if untrusted_header.time.to_unix_ns() >= trusted_header.time.to_unix_ns():
        raise InvalidHeaderError(
            "expected older header time to be before new header time"
        )
    if untrusted_header.hash() != trusted_header.last_block_id.hash:
        raise InvalidHeaderError(
            "older header hash does not match trusted header's last block"
        )
