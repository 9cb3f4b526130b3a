"""Seeded validator sets, commits and tampering: the benchmark's own
generators (a copy of what ``tests/helpers.py`` and ``chip_smoke.py``
do, seeded from ``--seed``). Uses the program's types, because a
``ValidatorSet`` and a ``Commit`` are what the caller hands to
``verify_commit``; signs with ``cryptography``, which is what
``Ed25519PrivKey.sign`` uses, and never with the program's pure-Python
key derivation (2 ms a key).
"""

from __future__ import annotations

import hashlib
from typing import List, Tuple

import numpy as np
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

CHAIN_ID = "chipbench-chain"
BASE_NS = 1_700_000_000_000_000_000
SECOND_NS = 1_000_000_000
# group order of the ed25519 base point
L = 2**252 + 27742317777372353535851937790883648493

TAMPER_KINDS = ("R", "s", "s>=L")


def _digest(*parts) -> bytes:
    return hashlib.sha256("/".join(str(p) for p in parts).encode()).digest()


def rng_for(seed: int, *stream) -> np.random.Generator:
    """A generator of its own for every use, so that what one draws
    never shifts what another gets. ``seed`` may exceed 32 bits."""
    words = [int(seed) & 0xFFFFFFFF, int(seed) >> 32]
    words += [int.from_bytes(_digest(*stream)[:4], "little")]
    return np.random.default_rng(words)


class Signer:
    """One ed25519 key: raw public bytes and a signing function."""

    __slots__ = ("pub", "_key")

    def __init__(self, seed32: bytes):
        self._key = Ed25519PrivateKey.from_private_bytes(seed32)
        self.pub = self._key.public_key().public_bytes_raw()

    def sign(self, msg: bytes) -> bytes:
        return self._key.sign(msg)


def make_signers(seed: int, tag: str, n: int) -> List[Signer]:
    return [Signer(_digest("chipbench-key", seed, tag, i)) for i in range(n)]


def make_validator_set(signers: List[Signer], power: int = 10):
    """(signers in the set's canonical order, ValidatorSet): equal
    power, so the order is by address."""
    from tendermint_tpu.crypto.keys import Ed25519PubKey
    from tendermint_tpu.types import Validator, ValidatorSet

    vals = [Validator(Ed25519PubKey(s.pub), power) for s in signers]
    vset = ValidatorSet(vals)
    by_pub = {s.pub: s for s in signers}
    ordered = [by_pub[v.pub_key.bytes()] for v in vset.validators]
    return ordered, vset


def block_id(seed: int, tag: str, height: int):
    from tendermint_tpu.types import BlockID, PartSetHeader

    return BlockID(
        _digest("chipbench-block", seed, tag, height),
        PartSetHeader(1, _digest("chipbench-parts", seed, tag, height)),
    )


def vote_times(seed: int, tag: str, height: int, n: int) -> np.ndarray:
    """Per-validator vote times for one height: the height's second
    plus nanoseconds drawn over the whole second, so the timestamp's
    varint takes 4 or 5 bytes (and now and then fewer) as real votes'
    do, and a commit's sign-bytes are not all one length."""
    nanos = rng_for(seed, "times", tag, height).integers(0, SECOND_NS, size=n)
    return BASE_NS + height * SECOND_NS + nanos


def make_commit(seed: int, tag: str, height: int, addresses, signers):
    """A commit for ``height`` in which every validator signs a
    precommit for the block. ``addresses[i]`` is validator i's."""
    from tendermint_tpu.encoding.canonical import Timestamp
    from tendermint_tpu.types import BLOCK_ID_FLAG_COMMIT, Commit, CommitSig

    times = vote_times(seed, tag, height, len(signers))
    commit = Commit(height=height, round=0, block_id=block_id(seed, tag, height))
    commit.signatures = [
        CommitSig(
            BLOCK_ID_FLAG_COMMIT, addr, Timestamp.from_unix_ns(int(t)), b""
        )
        for addr, t in zip(addresses, times)
    ]
    for i, (cs, signer) in enumerate(zip(commit.signatures, signers)):
        cs.signature = signer.sign(commit.vote_sign_bytes(CHAIN_ID, i))
    return commit


def commit_lanes(commit, pubs) -> Tuple[list, list, list]:
    msgs = [commit.vote_sign_bytes(CHAIN_ID, i) for i in range(len(pubs))]
    sigs = [cs.signature for cs in commit.signatures]
    return list(pubs), msgs, sigs


def tamper_signature(sig: bytes, kind: str) -> bytes:
    """A signature that ZIP-215 refuses: a flipped bit of R, a flipped
    bit of s, or s + L (the curve equation still holds; only s < L
    refuses it)."""
    out = bytearray(sig)
    if kind == "R":
        out[3] ^= 0x01
    elif kind == "s":
        out[32] ^= 0x01
    elif kind == "s>=L":
        out[32:] = (int.from_bytes(sig[32:], "little") + L).to_bytes(32, "little")
    else:
        raise ValueError("unknown tamper kind %r" % kind)
    return bytes(out)


def tamper_lanes(seed: int, tag: str, n: int) -> dict:
    """{lane: kind}: three distinct lanes drawn from the seed, one of
    each kind."""
    lanes = rng_for(seed, "tamper", tag).choice(n, size=3, replace=False)
    return {int(lane): kind for lane, kind in zip(lanes, TAMPER_KINDS)}
