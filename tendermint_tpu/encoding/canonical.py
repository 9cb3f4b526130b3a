"""Canonical sign-bytes encoders.

Mirrors the reference's canonicalization + delimited marshalling
(types/canonical.go, types/vote.go:141-170, proto/tendermint/types/
canonical.proto, internal/libs/protoio/writer.go:110): sign-bytes are the
varint-length-prefixed protobuf encoding of the canonical struct.

Field-presence rules were verified against the generated gogo marshaller
(canonical.pb.go:590-640): proto3 zero values are omitted, EXCEPT the
non-nullable Timestamp in CanonicalVote/CanonicalProposal, which is always
serialized (possibly as an empty message), and the non-nullable
PartSetHeader inside CanonicalBlockID.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Iterable, List, NamedTuple, Optional, Tuple

from tendermint_tpu.encoding.proto import (
    WIRE_BYTES,
    WIRE_VARINT,
    encode_bytes_field,
    encode_message_field,
    encode_sfixed64_field,
    encode_string_field,
    encode_varint,
    encode_varint_field,
    length_delimited,
    tag,
)

# SignedMsgType values (proto/tendermint/types/types.proto)
SIGNED_MSG_TYPE_UNKNOWN = 0
SIGNED_MSG_TYPE_PREVOTE = 1
SIGNED_MSG_TYPE_PRECOMMIT = 2
SIGNED_MSG_TYPE_PROPOSAL = 32


class Timestamp(NamedTuple):
    """google.protobuf.Timestamp: seconds + nanos since the Unix epoch."""

    seconds: int = 0
    nanos: int = 0

    def encode(self) -> bytes:
        return encode_varint_field(1, self.seconds) + encode_varint_field(
            2, self.nanos
        )

    @classmethod
    def from_unix_ns(cls, ns: int) -> "Timestamp":
        return cls(ns // 1_000_000_000, ns % 1_000_000_000)

    def to_unix_ns(self) -> int:
        return self.seconds * 1_000_000_000 + self.nanos


ZERO_TIME = Timestamp(0, 0)


def encode_canonical_part_set_header(total: int, hash_: bytes) -> bytes:
    return encode_varint_field(1, total) + encode_bytes_field(2, hash_)


def encode_canonical_block_id(
    hash_: bytes, psh_total: int, psh_hash: bytes
) -> Optional[bytes]:
    """Returns None for a nil BlockID (omitted entirely from the canonical
    vote; reference: types/canonical.go CanonicalizeBlockID)."""
    if not hash_ and psh_total == 0 and not psh_hash:
        return None
    psh = encode_canonical_part_set_header(psh_total, psh_hash)
    return encode_bytes_field(1, hash_) + encode_message_field(2, psh, always=True)


_NANOS_TAG = tag(2, WIRE_VARINT)  # Timestamp.nanos

# The varint of a nanos field without a loop a lane: under 2**16 it is
# one entry of the second table, from there to 2**30 (a valid nanos is
# under 10**9) its low 14 bits from the first, both bytes marked as
# continued, and the rest from the second. Built by the first block that
# asks (~35 ms, ~4 MB): a process that encodes lane by lane never does.
_NANOS_BY_TABLES = 1 << 30


@functools.lru_cache(maxsize=None)
def _nanos_tables() -> Tuple[List[bytes], List[bytes]]:
    low14 = [bytes((k & 0x7F | 0x80, k >> 7 | 0x80)) for k in range(1 << 14)]
    return low14, [encode_varint(k) for k in range(1 << 16)]


class VoteSignBytesEncoder:
    """Sign-bytes of votes that share chain id, type, height and round
    (the votes of one commit, or one vote).

    What they share is encoded once here; a canonical block id once per
    :meth:`for_block_id`; per vote only the timestamp, the two length
    bytes that follow its width, and one join. The one encoding of
    ``CanonicalVote`` in the tree: field order and presence as in
    canonical.pb.go:590-640 (type 1, height 2 and round 3 as sfixed64,
    block id 4 left out when nil, timestamp 5 always, chain id 6).
    """

    __slots__ = ("_head", "_tail", "prefixes")

    def __init__(self, chain_id: str, msg_type: int, height: int, round_: int):
        self._head = (
            encode_varint_field(1, msg_type)
            + encode_sfixed64_field(2, height)
            + encode_sfixed64_field(3, round_)
        )
        self._tail = encode_string_field(6, chain_id)
        self.prefixes = 0  # block-id prefixes built so far

    def for_block_id(
        self, block_id_hash: bytes, psh_total: int, psh_hash: bytes
    ) -> Callable[[Timestamp], bytes]:
        """``timestamp -> sign-bytes`` for this encoder's votes for one
        block id (all-empty arguments: a nil vote). Its ``many`` is the
        same for a block of votes, ``timestamps -> [sign-bytes]``."""
        self.prefixes += 1
        bid = encode_canonical_block_id(block_id_hash, psh_total, psh_hash)
        # everything up to the timestamp's own length byte
        head = self._head
        if bid is not None:
            head += encode_message_field(4, bid, always=True)
        head += tag(5, WIRE_BYTES)
        tail = self._tail
        rest = len(head) + 1 + len(tail)  # the message less the timestamp's body
        # a commit's votes fall in a second or two: seconds -> its field
        seconds_fields: Dict[int, bytes] = {}
        # timestamp body length -> message length prefix + head + body
        # length byte; a body is at most 22 bytes, so its length is one
        fronts: Dict[int, bytes] = {}

        def front_of(n: int) -> bytes:
            front = fronts[n] = encode_varint(rest + n) + head + bytes((n,))
            return front

        def encode(timestamp: Timestamp) -> bytes:
            seconds, nanos = timestamp
            try:
                seconds_field = seconds_fields[seconds]
            except KeyError:
                seconds_field = encode_varint_field(1, seconds)
                seconds_fields[seconds] = seconds_field
            if nanos:
                body = seconds_field + _NANOS_TAG + encode_varint(nanos)
            else:
                body = seconds_field
            n = len(body)
            try:
                front = fronts[n]
            except KeyError:
                front = front_of(n)
            return front + body + tail

        # seconds -> width of the nanos varint (1 to 5) -> all that lies before it
        starts_by_second: Dict[int, List[bytes]] = {}

        def starts_of(seconds: int) -> List[bytes]:
            field = encode_varint_field(1, seconds) + _NANOS_TAG
            starts = starts_by_second[seconds] = [b""] + [
                front_of(len(field) + width) + field for width in range(1, 6)
            ]
            return starts

        def many(timestamps: Iterable[Timestamp]) -> List[bytes]:
            low14, varint16 = _nanos_tables()
            out = []
            for seconds, nanos in timestamps:
                if 0 < nanos < _NANOS_BY_TABLES:
                    try:
                        starts = starts_by_second[seconds]
                    except KeyError:
                        starts = starts_of(seconds)
                    if nanos < 65536:
                        digits = varint16[nanos]
                    else:
                        digits = low14[nanos & 16383] + varint16[nanos >> 14]
                    out.append(starts[len(digits)] + digits + tail)
                else:  # no nanos field, or one no valid time has
                    out.append(encode((seconds, nanos)))
            return out

        encode.many = many
        return encode


def vote_sign_bytes(
    chain_id: str,
    msg_type: int,
    height: int,
    round_: int,
    block_id_hash: bytes,
    psh_total: int,
    psh_hash: bytes,
    timestamp: Timestamp,
) -> bytes:
    """types.VoteSignBytes equivalent: delimited canonical vote."""
    encoder = VoteSignBytesEncoder(chain_id, msg_type, height, round_)
    return encoder.for_block_id(block_id_hash, psh_total, psh_hash)(timestamp)


def proposal_sign_bytes(
    chain_id: str,
    height: int,
    round_: int,
    pol_round: int,
    block_id_hash: bytes,
    psh_total: int,
    psh_hash: bytes,
    timestamp: Timestamp,
) -> bytes:
    """types.ProposalSignBytes equivalent (canonical.proto CanonicalProposal)."""
    bid = encode_canonical_block_id(block_id_hash, psh_total, psh_hash)
    out = encode_varint_field(1, SIGNED_MSG_TYPE_PROPOSAL)
    out += encode_sfixed64_field(2, height)
    out += encode_sfixed64_field(3, round_)
    out += encode_varint_field(4, pol_round)
    if bid is not None:
        out += encode_message_field(5, bid, always=True)
    out += encode_message_field(6, timestamp.encode(), always=True)
    out += encode_string_field(7, chain_id)
    return length_delimited(out)


def vote_extension_sign_bytes(
    chain_id: str, extension: bytes, height: int, round_: int
) -> bytes:
    """types.VoteExtensionSignBytes equivalent (CanonicalVoteExtension)."""
    out = encode_bytes_field(1, extension)
    out += encode_sfixed64_field(2, height)
    out += encode_sfixed64_field(3, round_)
    out += encode_string_field(4, chain_id)
    return length_delimited(out)
