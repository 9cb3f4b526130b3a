"""Plain skipping verification of a light client: the reference the
benchmark holds ``light/client.LightClient.verify_light_block_at_height``
to. It imports ``reference.verify`` (big-integer ZIP-215) and nothing of
the program, and takes plain values only.

A block is a dict::

    {"header": {...the 14 fields, bytes/ints/str; "last_block_id" and the
                commit's "block_id" are (hash, parts_total, parts_hash)},
     "validators": [(address, public key, power), ...] in the set's order,
     "commit": {"height", "round", "block_id",
                "signatures": [(flag, address, time_ns, signature), ...]}}

The rules are upstream's (tendermint v0.35.9):

- ``light/client.go verifySkipping``: verify the target from the latest
  trusted block; if the trusted set does not cover it
  (``ErrNewValSetCantBeTrusted``), fetch the block half-way and try
  that; a block that verifies becomes the next base and is stored, and
  the target is tried again; any other failure ends the call.
- ``light/verifier.go VerifyNonAdjacent`` / ``VerifyAdjacent``, in their
  order of checks: the trusted header not expired; the new header well
  formed, its hash the commit's block, later than the trusted one, not
  from the future, its ``validators_hash`` the supplied set's hash (for
  an adjacent header also the trusted header's ``next_validators_hash``);
  then the trusting rule and the 2/3 rule.
- the trusting rule (``VerifyCommitLightTrusting``): walk the commit's
  signatures; skip what is not a vote for the block; look the signer up
  *by address in the trusted set* and skip who is not in it; add the
  power; stop once the tally passes ``trust_level`` of the trusted total.
  A tally that never passes it is "cannot be trusted", and no signature
  is looked at; else every signature walked that far must verify.
- the 2/3 rule (``VerifyCommitLight``): the same over the new set by
  position, past two thirds. What comes after either stop is never
  looked at.
- the two hashes: RFC 6962 Merkle roots (``crypto/merkle``) over the
  validators' ``SimpleValidator`` encodings and the header's 14 field
  encodings; canonical precommit sign-bytes (``types/canonical.go``).
"""

from __future__ import annotations

import hashlib

from chipbench import reference

FLAG_COMMIT = 2  # types/block.go BlockIDFlagCommit; 1 is absent, 3 is nil
PRECOMMIT = 2  # SignedMsgType

OK = "ok"
CANT_TRUST = "cannot be trusted"


# --- encodings -------------------------------------------------------------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _uvarint_field(field: int, n: int) -> bytes:
    return b"" if n == 0 else _varint(field << 3) + _varint(n)


def _bytes_field(field: int, b: bytes, always: bool = False) -> bytes:
    if not b and not always:
        return b""
    return _varint(field << 3 | 2) + _varint(len(b)) + b


def _sfixed64_field(field: int, n: int) -> bytes:
    return b"" if n == 0 else _varint(field << 3 | 1) + n.to_bytes(8, "little", signed=True)


def _timestamp(time_ns: int) -> bytes:
    return _uvarint_field(1, time_ns // 10**9) + _uvarint_field(2, time_ns % 10**9)


def _block_id(block_id) -> bytes:
    hash_, total, parts_hash = block_id
    parts = _uvarint_field(1, total) + _bytes_field(2, parts_hash)
    return _bytes_field(1, hash_) + _bytes_field(2, parts, always=True)


def merkle_root(items) -> bytes:
    """RFC 6962: leaves ``sha256(0x00 || item)``, inner nodes
    ``sha256(0x01 || left || right)``, split at the largest power of two
    below the count."""
    n = len(items)
    if n == 0:
        return hashlib.sha256(b"").digest()
    if n == 1:
        return hashlib.sha256(b"\x00" + items[0]).digest()
    k = 1 << ((n - 1).bit_length() - 1)
    return hashlib.sha256(
        b"\x01" + merkle_root(items[:k]) + merkle_root(items[k:])
    ).digest()


def validators_hash(validators) -> bytes:
    """``ValidatorSet.Hash``: each leaf ``SimpleValidator{pub_key=1
    (PublicKey{ed25519=1}), voting_power=2}``."""
    return merkle_root([
        _bytes_field(1, _bytes_field(1, pub)) + _uvarint_field(2, power)
        for _, pub, power in validators
    ])


def header_hash(h: dict) -> bytes:
    """``Header.Hash``: the 14 fields, each wrapped as upstream's
    ``cdcEncode`` wraps it (an empty value is an empty leaf)."""
    if not h["validators_hash"]:
        return b""
    return merkle_root([
        _uvarint_field(1, h["version_block"]) + _uvarint_field(2, h["version_app"]),
        _bytes_field(1, h["chain_id"].encode()),
        _uvarint_field(1, h["height"]),
        _timestamp(h["time_ns"]),
        _block_id(h["last_block_id"]),
        _bytes_field(1, h["last_commit_hash"]),
        _bytes_field(1, h["data_hash"]),
        _bytes_field(1, h["validators_hash"]),
        _bytes_field(1, h["next_validators_hash"]),
        _bytes_field(1, h["consensus_hash"]),
        _bytes_field(1, h["app_hash"]),
        _bytes_field(1, h["last_results_hash"]),
        _bytes_field(1, h["evidence_hash"]),
        _bytes_field(1, h["proposer_address"]),
    ])


def vote_sign_bytes(chain_id: str, commit: dict, idx: int) -> bytes:
    """Length-prefixed ``CanonicalVote`` of the commit's idx-th precommit
    for the block."""
    body = (
        _uvarint_field(1, PRECOMMIT)
        + _sfixed64_field(2, commit["height"])
        + _sfixed64_field(3, commit["round"])
        + _bytes_field(4, _block_id(commit["block_id"]))
        + _bytes_field(5, _timestamp(commit["signatures"][idx][2]), always=True)
        + _bytes_field(6, chain_id.encode())
    )
    return _varint(len(body)) + body


# --- the rules ---------------------------------------------------------------


class Refused(Exception):
    """A check failed for good: ``why`` is a short stable word."""

    def __init__(self, why: str, detail=None):
        super().__init__(why)
        self.why, self.detail = why, detail


def _validate_signed_header(block: dict, chain_id: str) -> None:
    """``SignedHeader.ValidateBasic`` as far as seeded blocks can fail it."""
    h, commit = block["header"], block["commit"]
    if h["chain_id"] != chain_id:
        raise Refused("chain id")
    if commit["height"] != h["height"]:
        raise Refused("commit height")
    if not commit["signatures"]:
        raise Refused("no signatures")
    if header_hash(h) != commit["block_id"][0]:
        raise Refused("commit signs another block")


def _validate_light_block(block: dict, chain_id: str) -> None:
    """``LightBlock.ValidateBasic``: the signed header, then the set."""
    _validate_signed_header(block, chain_id)
    if not block["validators"]:
        raise Refused("empty set")
    if block["header"]["validators_hash"] != validators_hash(block["validators"]):
        raise Refused("validators_hash")


def _tally_trusting(trusted_vals, commit, level) -> tuple:
    """(commit indices with their signer's key, enough power?)."""
    by_address = {}
    for i, (address, pub, power) in enumerate(trusted_vals):
        by_address.setdefault(address, (i, pub, power))
    num, den = level
    needed = sum(p for _, _, p in trusted_vals) * num // den
    tallied, seen, picked = 0, {}, []
    for idx, (flag, address, _, _) in enumerate(commit["signatures"]):
        if flag != FLAG_COMMIT or address not in by_address:
            continue
        i, pub, power = by_address[address]
        if i in seen:
            raise Refused("double vote", (seen[i], idx))
        seen[i] = idx
        picked.append((idx, pub))
        tallied += power
        if tallied > needed:
            return picked, True
    return picked, False


def _tally_full(vals, commit) -> tuple:
    needed = sum(p for _, _, p in vals) * 2 // 3
    tallied, picked = 0, []
    for idx, (flag, _, _, _) in enumerate(commit["signatures"]):
        if flag != FLAG_COMMIT:
            continue
        picked.append((idx, vals[idx][1]))
        tallied += vals[idx][2]
        if tallied > needed:
            return picked, True
    return picked, False


class Params:
    def __init__(self, chain_id, trusting_period_ns, now_ns, max_clock_drift_ns,
                 trust_level=(1, 3), check_signatures=True):
        self.chain_id = chain_id
        self.trusting_period_ns = trusting_period_ns
        self.now_ns = now_ns
        self.max_clock_drift_ns = max_clock_drift_ns
        self.trust_level = trust_level
        # False: tallies and hashes only (what decides the walk); the
        # signatures a sound chain would have had checked are still listed
        self.check_signatures = check_signatures


def verify(trusted: dict, new: dict, p: Params, checked: list) -> str:
    """``light.Verify``: ``OK``, ``CANT_TRUST``, or raises ``Refused``.
    Appends ``(height, commit index, public key)`` to ``checked`` for
    every signature looked at, each once."""
    th, nh = trusted["header"], new["header"]
    adjacent = nh["height"] == th["height"] + 1
    if not th["chain_id"] or not th["height"] or not th["next_validators_hash"]:
        raise Refused("trusted header incomplete")
    num, den = p.trust_level
    if not adjacent and (num * 3 < den or num >= den or den == 0):
        raise Refused("trust level")
    if th["time_ns"] + p.trusting_period_ns <= p.now_ns:
        raise Refused("trusted header expired")
    _validate_signed_header(new, p.chain_id)
    if nh["height"] <= th["height"]:
        raise Refused("height not above")
    if nh["time_ns"] <= th["time_ns"]:
        raise Refused("time not after")
    if nh["time_ns"] >= p.now_ns + p.max_clock_drift_ns:
        raise Refused("from the future")
    if nh["validators_hash"] != validators_hash(new["validators"]):
        raise Refused("validators_hash")
    if adjacent and nh["validators_hash"] != th["next_validators_hash"]:
        raise Refused("next_validators_hash")
    commit = new["commit"]
    lanes = {}
    if not adjacent:
        picked, enough = _tally_trusting(trusted["validators"], commit, p.trust_level)
        if not enough:
            return CANT_TRUST
        _check(nh["height"], commit, picked, lanes, p, checked)
    if len(new["validators"]) != len(commit["signatures"]):
        raise Refused("set size")
    if commit["block_id"][0] != header_hash(nh):
        raise Refused("block id")
    picked, enough = _tally_full(new["validators"], commit)
    if not enough:
        raise Refused("insufficient power")
    _check(nh["height"], commit, picked, lanes, p, checked)
    return OK


def _check(height, commit, picked, lanes, p: Params, checked: list) -> None:
    for idx, pub in picked:
        if (idx, pub) in lanes:
            continue  # the other rule has looked at this one
        lanes[(idx, pub)] = True
        checked.append((height, idx, pub))
        if p.check_signatures and not reference.verify(
            pub, vote_sign_bytes(p.chain_id, commit, idx), commit["signatures"][idx][3]
        ):
            raise Refused("wrong signature", (height, idx))


def verify_skipping(trusted: dict, target_height: int, fetch, p: Params) -> dict:
    """``VerifyLightBlockAtHeight`` forward from ``trusted`` by skipping.
    ``fetch(height)`` answers a block or None. Returns ``verdict`` (``OK``
    or the ``Refused`` word), ``detail``, the heights ``fetched`` in
    order, ``refused`` for trust, ``accepted`` (stored: the pivots that
    verified and, when the verdict is OK, the target) and ``checked``,
    the (height, commit index, public key) of every signature looked at."""
    out = {"verdict": OK, "detail": None, "fetched": [], "refused": [],
           "accepted": [], "checked": []}
    try:
        target = _fetch(fetch, target_height, p, out)
        base, current = trusted, target
        while True:
            if verify(base, current, p, out["checked"]) == CANT_TRUST:
                out["refused"].append(current["header"]["height"])
                pivot = (base["header"]["height"] + current["header"]["height"]) // 2
                if pivot in (base["header"]["height"], current["header"]["height"]):
                    raise Refused("cannot split further")
                current = _fetch(fetch, pivot, p, out)
                continue
            out["accepted"].append(current["header"]["height"])
            if current["header"]["height"] == target_height:
                return out
            base, current = current, target
    except Refused as e:
        out["verdict"], out["detail"] = e.why, e.detail
        return out


def _fetch(fetch, height: int, p: Params, out: dict) -> dict:
    block = fetch(height)
    if block is None:
        raise Refused("no block", height)
    out["fetched"].append(height)
    _validate_light_block(block, p.chain_id)
    return block
