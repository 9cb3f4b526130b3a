"""The validator set of each height of a chain whose set changes: the
reference the benchmark holds a rotating blocksync catch-up to. Plain
Python over plain values; it imports nothing of the program.

The rule is upstream's (tendermint v0.35.9): ``internal/state/execution.go
updateState`` applies a block's validator updates to ``NextValidators``
with ``types/validator_set.go UpdateWithChangeSet``: an update of power 0
removes the validator with that key, any other power adds it (or changes
its power); the set is then sorted by voting power, larger first, and by
address among equals. An ed25519 validator's address is the first 20
bytes of the SHA-256 of its public key (``crypto/ed25519 Address``).
Proposer priorities are left out: neither a commit's verification nor a
set's hash looks at them.

A schedule is ``[(height, [(public key, power), ...]), ...]``: the
updates that take effect *at* ``height``, the first height whose commit
the changed set signs. ``validators_at`` answers ``[(public key,
power), ...]`` in the set's order, which is what
``reference_light.verify_block`` takes beside a commit's signatures.
"""

from __future__ import annotations

import hashlib
from bisect import bisect_right


def address(pub: bytes) -> bytes:
    return hashlib.sha256(pub).digest()[:20]


def _sorted(powers: dict) -> list:
    return sorted(powers.items(), key=lambda kv: (-kv[1], address(kv[0])))


def apply_updates(validators: list, updates: list) -> list:
    """``validators`` after ``updates``, each ``(public key, power)``.
    Removing a key the set does not hold, or the same key twice in one
    change set, is refused, as upstream refuses it."""
    powers = dict(validators)
    if len({pub for pub, _ in updates}) != len(updates):
        raise ValueError("duplicate key in one change set")
    for pub, power in updates:
        if power < 0:
            raise ValueError("negative voting power")
        if power == 0:
            if pub not in powers:
                raise ValueError("removal of a validator the set does not hold")
            del powers[pub]
    for pub, power in updates:
        if power > 0:
            powers[pub] = power
    if not powers:
        raise ValueError("the change set would leave no validator")
    return _sorted(powers)


class Chain:
    """The sets of a chain: ``genesis`` validators and a schedule of
    changes in order of height."""

    def __init__(self, genesis: list, schedule: list):
        self._from = [1]
        self._sets = [_sorted(dict(genesis))]
        for height, updates in schedule:
            if height <= self._from[-1]:
                raise ValueError("the schedule's heights must rise")
            self._from.append(height)
            self._sets.append(apply_updates(self._sets[-1], updates))

    def validators_at(self, height: int) -> list:
        if height < 1:
            raise ValueError("heights start at 1")
        return self._sets[bisect_right(self._from, height) - 1]

    def first_height_of_set_at(self, height: int) -> int:
        """The height at which the set that signs ``height`` took effect."""
        return self._from[bisect_right(self._from, height) - 1]
