"""Core block types: BlockID, CommitSig, Commit, Vote, Header, Block, Proposal.

Mirrors types/block.go, types/vote.go, types/proposal.go. Wire encoding is
hand-rolled gogoproto-compatible bytes (ascending field order, proto3
zero-omission, non-nullable embedded messages always serialized) so hashes
and sign-bytes are byte-exact with the reference without a protoc step.

Time is represented as :class:`Timestamp` (seconds, nanos); the Go zero
time (year 1) is ``GO_ZERO_TIME`` and is what gogo's StdTime marshals for
an unset time.Time.
"""

from __future__ import annotations

import hashlib
import operator
from dataclasses import dataclass, field as dc_field
from typing import Callable, Dict, List, Optional, Sequence

from tendermint_tpu.crypto import merkle
from tendermint_tpu.crypto.keys import ADDRESS_LEN, PubKey
from tendermint_tpu.libs import tracing
from tendermint_tpu.encoding.canonical import (
    SIGNED_MSG_TYPE_PRECOMMIT,
    SIGNED_MSG_TYPE_PREVOTE,
    SIGNED_MSG_TYPE_PROPOSAL,
    Timestamp,
    VoteSignBytesEncoder,
    proposal_sign_bytes,
    vote_extension_sign_bytes,
    vote_sign_bytes,
)
from tendermint_tpu.encoding.proto import (
    Reader,
    encode_bytes_field,
    encode_message_field,
    encode_varint_field,
)

HASH_SIZE = 32
MAX_CHAIN_ID_LEN = 50
BLOCK_PART_SIZE_BYTES = 65536  # types/params.go:21
MAX_VOTE_EXTENSION_SIZE = 1024 * 1024  # types/vote.go:20

# Go's time.Time{} (January 1, year 1 UTC) in Unix seconds.
GO_ZERO_SECONDS = -62135596800
GO_ZERO_TIME = Timestamp(GO_ZERO_SECONDS, 0)

# BlockIDFlag (types/block.go:583-592)
BLOCK_ID_FLAG_ABSENT = 1
BLOCK_ID_FLAG_COMMIT = 2
BLOCK_ID_FLAG_NIL = 3


def is_zero_time(ts: Timestamp) -> bool:
    return ts == GO_ZERO_TIME or ts == Timestamp(0, 0)


def validate_hash(h: bytes) -> None:
    """types/validation.go ValidateHash: empty or exactly 32 bytes."""
    if h and len(h) != HASH_SIZE:
        raise ValueError(f"expected hash size {HASH_SIZE}, got {len(h)}")


def _encode_time_field(field_no: int, ts: Timestamp) -> bytes:
    """Non-nullable stdtime field: always serialized (gogo marshaller)."""
    return encode_message_field(field_no, ts.encode(), always=True)


def _decode_time(data: bytes) -> Timestamp:
    r = Reader(data)
    seconds = nanos = 0
    for f, w in r.fields():
        if f == 1 and w == 0:
            seconds = r.read_svarint()
        elif f == 2 and w == 0:
            nanos = r.read_svarint()
        else:
            r.skip(w)
    return Timestamp(seconds, nanos)


def cdc_encode_bytes(b: bytes) -> bytes:
    """gogotypes.BytesValue wrapper (types/encoding_helper.go:11)."""
    if not b:
        return b""
    return encode_bytes_field(1, b)


def cdc_encode_string(s: str) -> bytes:
    if not s:
        return b""
    return encode_bytes_field(1, s.encode("utf-8"))


def cdc_encode_int64(n: int) -> bytes:
    if n == 0:
        return b""
    return encode_varint_field(1, n)


# --- Version ----------------------------------------------------------------

BLOCK_PROTOCOL = 11  # version/version.go BlockProtocol


@dataclass(frozen=True)
class Consensus:
    """tendermint.version.Consensus {block=1, app=2}."""

    block: int = BLOCK_PROTOCOL
    app: int = 0

    def to_proto_bytes(self) -> bytes:
        return encode_varint_field(1, self.block) + encode_varint_field(2, self.app)

    @classmethod
    def from_proto_bytes(cls, data: bytes) -> "Consensus":
        r = Reader(data)
        block = app = 0
        for f, w in r.fields():
            if f == 1 and w == 0:
                block = r.read_varint()
            elif f == 2 and w == 0:
                app = r.read_varint()
            else:
                r.skip(w)
        return cls(block, app)


# --- PartSetHeader / BlockID ------------------------------------------------


@dataclass(frozen=True)
class PartSetHeader:
    """types/part_set.go PartSetHeader {total=1 uint32, hash=2 bytes}."""

    total: int = 0
    hash: bytes = b""

    def is_zero(self) -> bool:
        return self.total == 0 and not self.hash

    def validate_basic(self) -> None:
        if self.total < 0:
            raise ValueError("negative Total")
        validate_hash(self.hash)

    def to_proto_bytes(self) -> bytes:
        return encode_varint_field(1, self.total) + encode_bytes_field(2, self.hash)

    @classmethod
    def from_proto_bytes(cls, data: bytes) -> "PartSetHeader":
        r = Reader(data)
        total, hash_ = 0, b""
        for f, w in r.fields():
            if f == 1 and w == 0:
                total = r.read_varint()
            elif f == 2 and w == 2:
                hash_ = r.read_bytes()
            else:
                r.skip(w)
        return cls(total, hash_)


@dataclass(frozen=True)
class BlockID:
    """types/block.go BlockID {hash=1, part_set_header=2 non-nullable}."""

    hash: bytes = b""
    part_set_header: PartSetHeader = dc_field(default_factory=PartSetHeader)

    def is_nil(self) -> bool:
        return not self.hash and self.part_set_header.is_zero()

    def is_complete(self) -> bool:
        return (
            len(self.hash) == HASH_SIZE
            and self.part_set_header.total > 0
            and len(self.part_set_header.hash) == HASH_SIZE
        )

    def validate_basic(self) -> None:
        validate_hash(self.hash)
        self.part_set_header.validate_basic()

    def key(self) -> bytes:
        """Map key: hash + psh proto (types/block.go BlockID.Key)."""
        return self.hash + self.part_set_header.to_proto_bytes()

    def to_proto_bytes(self) -> bytes:
        return encode_bytes_field(1, self.hash) + encode_message_field(
            2, self.part_set_header.to_proto_bytes(), always=True
        )

    @classmethod
    def from_proto_bytes(cls, data: bytes) -> "BlockID":
        r = Reader(data)
        hash_, psh = b"", PartSetHeader()
        for f, w in r.fields():
            if f == 1 and w == 2:
                hash_ = r.read_bytes()
            elif f == 2 and w == 2:
                psh = PartSetHeader.from_proto_bytes(r.read_bytes())
            else:
                r.skip(w)
        return cls(hash_, psh)


NIL_BLOCK_ID = BlockID()


# --- CommitSig / Commit -----------------------------------------------------


@dataclass
class CommitSig:
    """types/block.go:604-615."""

    block_id_flag: int = BLOCK_ID_FLAG_ABSENT
    validator_address: bytes = b""
    timestamp: Timestamp = GO_ZERO_TIME
    signature: bytes = b""

    @classmethod
    def absent(cls) -> "CommitSig":
        return cls()

    @classmethod
    def for_block(
        cls, address: bytes, timestamp: Timestamp, signature: bytes
    ) -> "CommitSig":
        return cls(BLOCK_ID_FLAG_COMMIT, address, timestamp, signature)

    def is_absent(self) -> bool:
        return self.block_id_flag == BLOCK_ID_FLAG_ABSENT

    def is_commit(self) -> bool:
        return self.block_id_flag == BLOCK_ID_FLAG_COMMIT

    def block_id(self, commit_block_id: BlockID) -> BlockID:
        """The BlockID this signature signed over (types/block.go:641-653)."""
        if self.block_id_flag == BLOCK_ID_FLAG_ABSENT:
            return NIL_BLOCK_ID
        if self.block_id_flag == BLOCK_ID_FLAG_COMMIT:
            return commit_block_id
        if self.block_id_flag == BLOCK_ID_FLAG_NIL:
            return NIL_BLOCK_ID
        raise ValueError(f"unknown BlockIDFlag: {self.block_id_flag}")

    def validate_basic(self) -> None:
        if self.block_id_flag not in (
            BLOCK_ID_FLAG_ABSENT,
            BLOCK_ID_FLAG_COMMIT,
            BLOCK_ID_FLAG_NIL,
        ):
            raise ValueError(f"unknown BlockIDFlag: {self.block_id_flag}")
        if self.block_id_flag == BLOCK_ID_FLAG_ABSENT:
            if self.validator_address:
                raise ValueError("validator address is present for absent CommitSig")
            if not is_zero_time(self.timestamp):
                raise ValueError("time is present for absent CommitSig")
            if self.signature:
                raise ValueError("signature is present for absent CommitSig")
        else:
            if len(self.validator_address) != ADDRESS_LEN:
                raise ValueError(
                    f"expected ValidatorAddress size {ADDRESS_LEN}, got "
                    f"{len(self.validator_address)}"
                )
            if not self.signature:
                raise ValueError("signature is missing")
            if len(self.signature) > MAX_SIGNATURE_SIZE:
                raise ValueError("signature is too big")

    def to_proto_bytes(self) -> bytes:
        return (
            encode_varint_field(1, self.block_id_flag)
            + encode_bytes_field(2, self.validator_address)
            + _encode_time_field(3, self.timestamp)
            + encode_bytes_field(4, self.signature)
        )

    @classmethod
    def from_proto_bytes(cls, data: bytes) -> "CommitSig":
        r = Reader(data)
        out = cls()
        for f, w in r.fields():
            if f == 1 and w == 0:
                out.block_id_flag = r.read_varint()
            elif f == 2 and w == 2:
                out.validator_address = r.read_bytes()
            elif f == 3 and w == 2:
                out.timestamp = _decode_time(r.read_bytes())
            elif f == 4 and w == 2:
                out.signature = r.read_bytes()
            else:
                r.skip(w)
        return out


MAX_SIGNATURE_SIZE = 64  # ed25519/sr25519; secp256k1 is also 64 here

_BLOCK_ID_FLAG = operator.attrgetter("block_id_flag")
_TIMESTAMP = operator.attrgetter("timestamp")


class CommitSignBytes:
    """Canonical sign-bytes of one commit's precommits, for one loop.

    What the votes share (chain id, type, height, round) is encoded when
    this is built, the canonical block id once per BlockIDFlag met (the
    commit's own for COMMIT, none for NIL), and per lane only the
    timestamp (encoding/canonical.VoteSignBytesEncoder). It reads the
    commit as it is now and lives as long as its loop: nothing of it is
    kept on the Commit, so the next pass does the work again.
    """

    __slots__ = ("_signatures", "_block_id", "_encoder", "_by_flag")

    def __init__(self, commit: "Commit", chain_id: str):
        self._signatures = commit.signatures
        self._block_id = commit.block_id
        self._encoder = VoteSignBytesEncoder(
            chain_id, SIGNED_MSG_TYPE_PRECOMMIT, commit.height, commit.round
        )
        self._by_flag: Dict[int, Callable[[Timestamp], bytes]] = {}

    @property
    def prefixes(self) -> int:
        """Shared prefixes built so far: one per BlockIDFlag met."""
        return self._encoder.prefixes

    def lane(self, val_idx: int) -> bytes:
        """types/block.go:851-868: canonical sign-bytes for signature i."""
        cs = self._signatures[val_idx]
        try:
            encode = self._by_flag[cs.block_id_flag]
        except KeyError:
            encode = self._prefix(cs)
        return encode(cs.timestamp)

    def _prefix(self, cs: CommitSig) -> Callable[[Timestamp], bytes]:
        """The encoder of the votes that carry ``cs``'s BlockIDFlag,
        built when the first of them is met."""
        bid = cs.block_id(self._block_id)  # ValueError on an unknown flag
        encode = self._by_flag[cs.block_id_flag] = self._encoder.for_block_id(
            bid.hash, bid.part_set_header.total, bid.part_set_header.hash
        )
        return encode

    def lanes(self, val_idxs: Sequence[int]) -> List[bytes]:
        """``[lane(i) for i in val_idxs]``, a block of votes in one call:
        the votes of one BlockIDFlag go to the encoder together, and a
        block that holds two flags is put back in its order."""
        signatures = self._signatures
        votes = [signatures[i] for i in val_idxs]
        flags = set(map(_BLOCK_ID_FLAG, votes))
        if len(flags) <= 1:
            return self._encode_many(flags.pop(), votes) if votes else []
        seats_by_flag: Dict[int, List[int]] = {}  # in the order the flags are met, as lane meets them
        for seat, cs in enumerate(votes):
            try:
                seats_by_flag[cs.block_id_flag].append(seat)
            except KeyError:
                seats_by_flag[cs.block_id_flag] = [seat]
        out = [b""] * len(votes)
        for flag, seats in seats_by_flag.items():
            for seat, sign_bytes in zip(seats, self._encode_many(flag, [votes[seat] for seat in seats])):
                out[seat] = sign_bytes
        return out

    def _encode_many(self, flag: int, votes: List[CommitSig]) -> List[bytes]:
        try:
            encode = self._by_flag[flag]
        except KeyError:
            encode = self._prefix(votes[0])
        return encode.many(map(_TIMESTAMP, votes))


@dataclass
class Commit:
    """types/block.go:815-828; signatures ordered by validator index."""

    height: int = 0
    round: int = 0
    block_id: BlockID = dc_field(default_factory=BlockID)
    signatures: List[CommitSig] = dc_field(default_factory=list)
    _hash: Optional[bytes] = dc_field(default=None, repr=False, compare=False)

    def size(self) -> int:
        return len(self.signatures)

    def get_vote(self, val_idx: int) -> "Vote":
        """types/block.go:836-849 (no vote extensions in commits)."""
        cs = self.signatures[val_idx]
        return Vote(
            type=SIGNED_MSG_TYPE_PRECOMMIT,
            height=self.height,
            round=self.round,
            block_id=cs.block_id(self.block_id),
            timestamp=cs.timestamp,
            validator_address=cs.validator_address,
            validator_index=val_idx,
            signature=cs.signature,
        )

    def sign_bytes_encoder(self, chain_id: str) -> "CommitSignBytes":
        """The sign-bytes of this commit's votes for one pass over them:
        take it once before the loop, call its ``lane(idx)`` per vote."""
        return CommitSignBytes(self, chain_id)

    def vote_sign_bytes(self, chain_id: str, val_idx: int) -> bytes:
        """types/block.go:851-868: canonical sign-bytes for signature i."""
        return self.sign_bytes_encoder(chain_id).lane(val_idx)

    def validate_basic(self) -> None:
        if self.height < 0:
            raise ValueError("negative Height")
        if self.round < 0:
            raise ValueError("negative Round")
        if self.height >= 1:
            if self.block_id.is_nil():
                raise ValueError("commit cannot be for nil block")
            if not self.signatures:
                raise ValueError("no signatures in commit")
            for i, cs in enumerate(self.signatures):
                try:
                    cs.validate_basic()
                except ValueError as e:
                    raise ValueError(f"wrong CommitSig #{i}: {e}") from e

    def hash(self) -> bytes:
        """Merkle root of the proto-encoded CommitSigs (types/block.go:901)."""
        if self._hash is None:
            self._hash = merkle.hash_from_byte_slices(
                [cs.to_proto_bytes() for cs in self.signatures]
            )
        return self._hash

    def to_proto_bytes(self) -> bytes:
        out = encode_varint_field(1, self.height)
        out += encode_varint_field(2, self.round)
        out += encode_message_field(3, self.block_id.to_proto_bytes(), always=True)
        for cs in self.signatures:
            out += encode_message_field(4, cs.to_proto_bytes(), always=True)
        return out

    @classmethod
    def from_proto_bytes(cls, data: bytes) -> "Commit":
        r = Reader(data)
        out = cls()
        for f, w in r.fields():
            if f == 1 and w == 0:
                out.height = r.read_svarint()
            elif f == 2 and w == 0:
                out.round = r.read_svarint()
            elif f == 3 and w == 2:
                out.block_id = BlockID.from_proto_bytes(r.read_bytes())
            elif f == 4 and w == 2:
                out.signatures.append(CommitSig.from_proto_bytes(r.read_bytes()))
            else:
                r.skip(w)
        return out


# --- ExtendedCommit (ABCI++ vote extensions) --------------------------------


@dataclass
class ExtendedCommitSig:
    """types/block.go:728-744: CommitSig + extension + extension sig."""

    commit_sig: CommitSig = dc_field(default_factory=CommitSig)
    extension: bytes = b""
    extension_signature: bytes = b""

    def validate_basic(self) -> None:
        self.commit_sig.validate_basic()
        if self.commit_sig.block_id_flag == BLOCK_ID_FLAG_COMMIT:
            if len(self.extension) > MAX_VOTE_EXTENSION_SIZE:
                raise ValueError("vote extension is too big")
            if not self.extension_signature:
                raise ValueError("vote extension signature is missing")
            if len(self.extension_signature) > MAX_SIGNATURE_SIZE:
                raise ValueError("vote extension signature is too big")
        elif self.extension_signature or self.extension:
            raise ValueError(
                "vote extension and signature must be empty for non-commit sig"
            )

    def ensure_extension(self) -> None:
        """types/block.go:766-779: commit sigs must carry an extension sig."""
        if (
            self.commit_sig.block_id_flag == BLOCK_ID_FLAG_COMMIT
            and not self.extension_signature
        ):
            raise ValueError("vote extension data is missing")

    def to_proto_bytes(self) -> bytes:
        cs = self.commit_sig
        return (
            encode_varint_field(1, cs.block_id_flag)
            + encode_bytes_field(2, cs.validator_address)
            + _encode_time_field(3, cs.timestamp)
            + encode_bytes_field(4, cs.signature)
            + encode_bytes_field(5, self.extension)
            + encode_bytes_field(6, self.extension_signature)
        )

    @classmethod
    def from_proto_bytes(cls, data: bytes) -> "ExtendedCommitSig":
        r = Reader(data)
        cs = CommitSig()
        ext = ext_sig = b""
        for f, w in r.fields():
            if f == 1 and w == 0:
                cs.block_id_flag = r.read_varint()
            elif f == 2 and w == 2:
                cs.validator_address = r.read_bytes()
            elif f == 3 and w == 2:
                cs.timestamp = _decode_time(r.read_bytes())
            elif f == 4 and w == 2:
                cs.signature = r.read_bytes()
            elif f == 5 and w == 2:
                ext = r.read_bytes()
            elif f == 6 and w == 2:
                ext_sig = r.read_bytes()
            else:
                r.skip(w)
        return cls(cs, ext, ext_sig)


@dataclass
class ExtendedCommit:
    """types/block.go ExtendedCommit: commit + vote extensions."""

    height: int = 0
    round: int = 0
    block_id: BlockID = dc_field(default_factory=BlockID)
    extended_signatures: List[ExtendedCommitSig] = dc_field(default_factory=list)

    def size(self) -> int:
        return len(self.extended_signatures)

    def to_commit(self) -> Commit:
        return Commit(
            height=self.height,
            round=self.round,
            block_id=self.block_id,
            signatures=[e.commit_sig for e in self.extended_signatures],
        )

    @classmethod
    def wrap_commit(cls, commit: Commit) -> "ExtendedCommit":
        return cls(
            height=commit.height,
            round=commit.round,
            block_id=commit.block_id,
            extended_signatures=[ExtendedCommitSig(s) for s in commit.signatures],
        )

    def get_extended_vote(self, val_idx: int) -> "Vote":
        """The precommit this entry came from, WITH its extension —
        catch-up gossip must serve these when vote extensions are
        enabled, or a lagging peer (which requires extensions on every
        non-nil precommit) rejects the reconstruction and deadlocks.
        Built directly from the entry (no O(n) Commit rebuild)."""
        e = self.extended_signatures[val_idx]
        cs = e.commit_sig
        return Vote(
            type=SIGNED_MSG_TYPE_PRECOMMIT,
            height=self.height,
            round=self.round,
            block_id=cs.block_id(self.block_id),
            timestamp=cs.timestamp,
            validator_address=cs.validator_address,
            validator_index=val_idx,
            signature=cs.signature,
            extension=e.extension,
            extension_signature=e.extension_signature,
        )

    def ensure_extensions(self) -> None:
        for e in self.extended_signatures:
            e.ensure_extension()

    def strip_extensions(self) -> bool:
        stripped = any(
            e.extension or e.extension_signature for e in self.extended_signatures
        )
        for e in self.extended_signatures:
            e.extension = b""
            e.extension_signature = b""
        return stripped

    def to_proto_bytes(self) -> bytes:
        out = encode_varint_field(1, self.height)
        out += encode_varint_field(2, self.round)
        out += encode_message_field(3, self.block_id.to_proto_bytes(), always=True)
        for e in self.extended_signatures:
            out += encode_message_field(4, e.to_proto_bytes(), always=True)
        return out

    @classmethod
    def from_proto_bytes(cls, data: bytes) -> "ExtendedCommit":
        r = Reader(data)
        out = cls()
        for f, w in r.fields():
            if f == 1 and w == 0:
                out.height = r.read_svarint()
            elif f == 2 and w == 0:
                out.round = r.read_svarint()
            elif f == 3 and w == 2:
                out.block_id = BlockID.from_proto_bytes(r.read_bytes())
            elif f == 4 and w == 2:
                out.extended_signatures.append(
                    ExtendedCommitSig.from_proto_bytes(r.read_bytes())
                )
            else:
                r.skip(w)
        return out


# --- Vote -------------------------------------------------------------------


@dataclass
class Vote:
    """types/vote.go:55-66."""

    type: int = 0
    height: int = 0
    round: int = 0
    block_id: BlockID = dc_field(default_factory=BlockID)
    timestamp: Timestamp = GO_ZERO_TIME
    validator_address: bytes = b""
    validator_index: int = 0
    signature: bytes = b""
    extension: bytes = b""
    extension_signature: bytes = b""
    # Pre-verification tags set by the reactor's scheduler-batched vote
    # path (consensus/reactor.py VotePreverifier): the (chain_id, pubkey
    # bytes) this vote's signature(s) were already verified against via
    # the device batch. verify() honors a matching tag and re-verifies
    # inline otherwise, so a stale or wrong tag only costs the
    # optimization, never correctness.
    _pre_verified: Optional[tuple] = dc_field(
        default=None, compare=False, repr=False
    )
    _pre_verified_ext: Optional[tuple] = dc_field(
        default=None, compare=False, repr=False
    )

    def mark_pre_verified(
        self,
        chain_id: str,
        pub_key_bytes: bytes,
        extension_too: bool = False,
        sign_bytes_digest: Optional[bytes] = None,
        extension_digest: Optional[bytes] = None,
    ) -> None:
        """Record that a batch path already verified this vote.

        The tag is self-validating: it carries a digest of the sign-bytes
        that were actually verified, and :meth:`verify` recomputes the
        digest before honoring the tag — so mutating any signed field
        after pre-verification silently demotes the vote to a full
        signature check instead of skipping it. Callers that verified
        specific bytes (the preverifier) pass their digest; otherwise it
        is computed here from the vote's current content.
        """
        if sign_bytes_digest is None:
            sign_bytes_digest = hashlib.sha256(self.sign_bytes(chain_id)).digest()
        self._pre_verified = (chain_id, pub_key_bytes, sign_bytes_digest)
        if extension_too:
            if extension_digest is None:
                extension_digest = hashlib.sha256(
                    self.extension_sign_bytes(chain_id)
                ).digest()
            self._pre_verified_ext = (chain_id, pub_key_bytes, extension_digest)

    def is_nil_vote(self) -> bool:
        return self.block_id.is_nil()

    def sign_bytes(self, chain_id: str) -> bytes:
        return vote_sign_bytes(
            chain_id,
            self.type,
            self.height,
            self.round,
            self.block_id.hash,
            self.block_id.part_set_header.total,
            self.block_id.part_set_header.hash,
            self.timestamp,
        )

    def extension_sign_bytes(self, chain_id: str) -> bytes:
        return vote_extension_sign_bytes(
            chain_id, self.extension, self.height, self.round
        )

    def commit_sig(self) -> CommitSig:
        """types/vote.go:95-115."""
        if self.block_id.is_complete():
            flag = BLOCK_ID_FLAG_COMMIT
        elif self.block_id.is_nil():
            flag = BLOCK_ID_FLAG_NIL
        else:
            raise ValueError(f"invalid vote BlockID {self.block_id}")
        return CommitSig(flag, self.validator_address, self.timestamp, self.signature)

    def extended_commit_sig(self) -> ExtendedCommitSig:
        return ExtendedCommitSig(
            self.commit_sig(), self.extension, self.extension_signature
        )

    def strip_extension(self) -> bool:
        stripped = bool(self.extension or self.extension_signature)
        self.extension = b""
        self.extension_signature = b""
        return stripped

    def verify(self, chain_id: str, pub_key: PubKey) -> None:
        """types/vote.go Verify: address match + signature over sign-bytes."""
        if pub_key.address() != self.validator_address:
            raise VoteError("invalid validator address")
        sb = self.sign_bytes(chain_id)
        if self._pre_verified == (
            chain_id,
            pub_key.bytes(),
            hashlib.sha256(sb).digest(),
        ):
            return  # batch-verified this key over these EXACT sign-bytes
        if not pub_key.verify_signature(sb, self.signature):
            raise VoteError("invalid signature")

    def verify_vote_and_extension(self, chain_id: str, pub_key: PubKey) -> None:
        """types/vote.go:258-274: also checks the extension signature for
        non-nil precommits."""
        self.verify(chain_id, pub_key)
        if (
            self.type == SIGNED_MSG_TYPE_PRECOMMIT
            and not self.block_id.is_nil()
        ):
            self.verify_extension(chain_id, pub_key)

    def verify_extension(self, chain_id: str, pub_key: PubKey) -> None:
        if self.type != SIGNED_MSG_TYPE_PRECOMMIT or self.block_id.is_nil():
            return
        esb = self.extension_sign_bytes(chain_id)
        if self._pre_verified_ext == (
            chain_id,
            pub_key.bytes(),
            hashlib.sha256(esb).digest(),
        ):
            return
        if not pub_key.verify_signature(esb, self.extension_signature):
            raise VoteError("invalid extension signature")

    def validate_basic(self) -> None:
        if self.type not in (SIGNED_MSG_TYPE_PREVOTE, SIGNED_MSG_TYPE_PRECOMMIT):
            raise ValueError("invalid Type")
        if self.height < 0:
            raise ValueError("negative Height")
        if self.round < 0:
            raise ValueError("negative Round")
        if not self.block_id.is_nil():
            self.block_id.validate_basic()
            if not self.block_id.is_complete():
                raise ValueError(f"blockID must be either empty or complete")
        if len(self.validator_address) != ADDRESS_LEN:
            raise ValueError(
                f"expected ValidatorAddress size {ADDRESS_LEN}, got "
                f"{len(self.validator_address)}"
            )
        if self.validator_index < 0:
            raise ValueError("negative ValidatorIndex")
        if not self.signature:
            raise ValueError("signature is missing")
        if len(self.signature) > MAX_SIGNATURE_SIZE:
            raise ValueError("signature is too big")
        if self.type != SIGNED_MSG_TYPE_PRECOMMIT and (
            self.extension or self.extension_signature
        ):
            raise ValueError("extension only allowed on precommits")
        if len(self.extension) > MAX_VOTE_EXTENSION_SIZE:
            raise ValueError("vote extension is too big")
        if self.extension and not self.extension_signature:
            raise ValueError("vote extension signature absent on vote with extension")
        if len(self.extension_signature) > MAX_SIGNATURE_SIZE:
            raise ValueError("vote extension signature is too big")

    def to_proto_bytes(self) -> bytes:
        out = encode_varint_field(1, self.type)
        out += encode_varint_field(2, self.height)
        out += encode_varint_field(3, self.round)
        out += encode_message_field(4, self.block_id.to_proto_bytes(), always=True)
        out += _encode_time_field(5, self.timestamp)
        out += encode_bytes_field(6, self.validator_address)
        out += encode_varint_field(7, self.validator_index)
        out += encode_bytes_field(8, self.signature)
        out += encode_bytes_field(9, self.extension)
        out += encode_bytes_field(10, self.extension_signature)
        return out

    @classmethod
    def from_proto_bytes(cls, data: bytes) -> "Vote":
        r = Reader(data)
        out = cls()
        for f, w in r.fields():
            if f == 1 and w == 0:
                out.type = r.read_varint()
            elif f == 2 and w == 0:
                out.height = r.read_svarint()
            elif f == 3 and w == 0:
                out.round = r.read_svarint()
            elif f == 4 and w == 2:
                out.block_id = BlockID.from_proto_bytes(r.read_bytes())
            elif f == 5 and w == 2:
                out.timestamp = _decode_time(r.read_bytes())
            elif f == 6 and w == 2:
                out.validator_address = r.read_bytes()
            elif f == 7 and w == 0:
                out.validator_index = r.read_svarint()
            elif f == 8 and w == 2:
                out.signature = r.read_bytes()
            elif f == 9 and w == 2:
                out.extension = r.read_bytes()
            elif f == 10 and w == 2:
                out.extension_signature = r.read_bytes()
            else:
                r.skip(w)
        return out


class VoteError(ValueError):
    pass


# --- Proposal ---------------------------------------------------------------


@dataclass
class Proposal:
    """types/proposal.go: a proposed block at (height, round) with POL round."""

    type: int = SIGNED_MSG_TYPE_PROPOSAL
    height: int = 0
    round: int = 0
    pol_round: int = -1
    block_id: BlockID = dc_field(default_factory=BlockID)
    timestamp: Timestamp = GO_ZERO_TIME
    signature: bytes = b""

    def sign_bytes(self, chain_id: str) -> bytes:
        return proposal_sign_bytes(
            chain_id,
            self.height,
            self.round,
            self.pol_round,
            self.block_id.hash,
            self.block_id.part_set_header.total,
            self.block_id.part_set_header.hash,
            self.timestamp,
        )

    def validate_basic(self) -> None:
        if self.type != SIGNED_MSG_TYPE_PROPOSAL:
            raise ValueError("invalid Type")
        if self.height < 0:
            raise ValueError("negative Height")
        if self.round < 0:
            raise ValueError("negative Round")
        if self.pol_round < -1 or (
            self.pol_round >= 0 and self.pol_round >= self.round
        ):
            raise ValueError("invalid POLRound")
        self.block_id.validate_basic()
        if not self.block_id.is_complete():
            raise ValueError("expected a complete, non-empty BlockID")
        if not self.signature:
            raise ValueError("signature is missing")
        if len(self.signature) > MAX_SIGNATURE_SIZE:
            raise ValueError("signature is too big")

    def to_proto_bytes(self) -> bytes:
        out = encode_varint_field(1, self.type)
        out += encode_varint_field(2, self.height)
        out += encode_varint_field(3, self.round)
        # pol_round is int32; -1 encodes as 10-byte two's-complement varint
        out += encode_varint_field(4, self.pol_round)
        out += encode_message_field(5, self.block_id.to_proto_bytes(), always=True)
        out += _encode_time_field(6, self.timestamp)
        out += encode_bytes_field(7, self.signature)
        return out

    @classmethod
    def from_proto_bytes(cls, data: bytes) -> "Proposal":
        r = Reader(data)
        out = cls()
        for f, w in r.fields():
            if f == 1 and w == 0:
                out.type = r.read_varint()
            elif f == 2 and w == 0:
                out.height = r.read_svarint()
            elif f == 3 and w == 0:
                out.round = r.read_svarint()
            elif f == 4 and w == 0:
                v = r.read_svarint()
                out.pol_round = v
            elif f == 5 and w == 2:
                out.block_id = BlockID.from_proto_bytes(r.read_bytes())
            elif f == 6 and w == 2:
                out.timestamp = _decode_time(r.read_bytes())
            elif f == 7 and w == 2:
                out.signature = r.read_bytes()
            else:
                r.skip(w)
        return out


# --- Data / Block -----------------------------------------------------------


def tx_hash(tx: bytes) -> bytes:
    """types/tx.go Tx.Hash: SHA256 of the raw bytes."""
    import hashlib

    return hashlib.sha256(tx).digest()


@dataclass
class Data:
    """types/block.go Data: the transactions."""

    txs: List[bytes] = dc_field(default_factory=list)
    _hash: Optional[bytes] = dc_field(default=None, repr=False, compare=False)

    def hash(self) -> bytes:
        """Merkle root over per-tx SHA-256 hashes (types/tx.go Txs.Hash:
        leaf_i = sha256(tx_i), then HashFromByteSlices)."""
        if self._hash is None:
            self._hash = merkle.hash_from_byte_slices(
                [tx_hash(tx) for tx in self.txs]
            )
        return self._hash

    def to_proto_bytes(self) -> bytes:
        out = b""
        for tx in self.txs:
            out += encode_bytes_field(1, tx) if tx else encode_message_field(1, b"", always=True)
        return out

    @classmethod
    def from_proto_bytes(cls, data: bytes) -> "Data":
        r = Reader(data)
        txs: List[bytes] = []
        for f, w in r.fields():
            if f == 1 and w == 2:
                txs.append(r.read_bytes())
            else:
                r.skip(w)
        return cls(txs)


@dataclass
class Header:
    """types/block.go:332-358."""

    version: Consensus = dc_field(default_factory=Consensus)
    chain_id: str = ""
    height: int = 0
    time: Timestamp = GO_ZERO_TIME
    last_block_id: BlockID = dc_field(default_factory=BlockID)
    last_commit_hash: bytes = b""
    data_hash: bytes = b""
    validators_hash: bytes = b""
    next_validators_hash: bytes = b""
    consensus_hash: bytes = b""
    app_hash: bytes = b""
    last_results_hash: bytes = b""
    evidence_hash: bytes = b""
    proposer_address: bytes = b""

    def hash(self) -> bytes:
        """Merkle tree over the 14 encoded fields (types/block.go:447-490)."""
        if not self.validators_hash:
            return b""
        with tracing.span("header_hash"):
            return self._merkle_root()

    def _merkle_root(self) -> bytes:
        return merkle.hash_from_byte_slices(
            [
                self.version.to_proto_bytes(),
                cdc_encode_string(self.chain_id),
                cdc_encode_int64(self.height),
                self.time.encode(),
                self.last_block_id.to_proto_bytes(),
                cdc_encode_bytes(self.last_commit_hash),
                cdc_encode_bytes(self.data_hash),
                cdc_encode_bytes(self.validators_hash),
                cdc_encode_bytes(self.next_validators_hash),
                cdc_encode_bytes(self.consensus_hash),
                cdc_encode_bytes(self.app_hash),
                cdc_encode_bytes(self.last_results_hash),
                cdc_encode_bytes(self.evidence_hash),
                cdc_encode_bytes(self.proposer_address),
            ]
        )

    def validate_basic(self) -> None:
        if self.version.block != BLOCK_PROTOCOL:
            raise ValueError(
                f"block protocol is incorrect: got {self.version.block}, "
                f"want {BLOCK_PROTOCOL}"
            )
        if len(self.chain_id) > MAX_CHAIN_ID_LEN:
            raise ValueError("chainID is too long")
        if self.height < 0:
            raise ValueError("negative Height")
        if self.height == 0:
            raise ValueError("zero Height")
        self.last_block_id.validate_basic()
        for name in (
            "last_commit_hash",
            "data_hash",
            "evidence_hash",
            "validators_hash",
            "next_validators_hash",
            "consensus_hash",
            "last_results_hash",
        ):
            try:
                validate_hash(getattr(self, name))
            except ValueError as e:
                raise ValueError(f"wrong {name}: {e}") from e
        if len(self.proposer_address) != ADDRESS_LEN:
            raise ValueError("invalid ProposerAddress length")

    def to_proto_bytes(self) -> bytes:
        out = encode_message_field(1, self.version.to_proto_bytes(), always=True)
        out += encode_bytes_field(2, self.chain_id.encode("utf-8"))
        out += encode_varint_field(3, self.height)
        out += _encode_time_field(4, self.time)
        out += encode_message_field(5, self.last_block_id.to_proto_bytes(), always=True)
        out += encode_bytes_field(6, self.last_commit_hash)
        out += encode_bytes_field(7, self.data_hash)
        out += encode_bytes_field(8, self.validators_hash)
        out += encode_bytes_field(9, self.next_validators_hash)
        out += encode_bytes_field(10, self.consensus_hash)
        out += encode_bytes_field(11, self.app_hash)
        out += encode_bytes_field(12, self.last_results_hash)
        out += encode_bytes_field(13, self.evidence_hash)
        out += encode_bytes_field(14, self.proposer_address)
        return out

    @classmethod
    def from_proto_bytes(cls, data: bytes) -> "Header":
        r = Reader(data)
        out = cls()
        for f, w in r.fields():
            if f == 1 and w == 2:
                out.version = Consensus.from_proto_bytes(r.read_bytes())
            elif f == 2 and w == 2:
                out.chain_id = r.read_bytes().decode("utf-8")
            elif f == 3 and w == 0:
                out.height = r.read_svarint()
            elif f == 4 and w == 2:
                out.time = _decode_time(r.read_bytes())
            elif f == 5 and w == 2:
                out.last_block_id = BlockID.from_proto_bytes(r.read_bytes())
            elif f == 6 and w == 2:
                out.last_commit_hash = r.read_bytes()
            elif f == 7 and w == 2:
                out.data_hash = r.read_bytes()
            elif f == 8 and w == 2:
                out.validators_hash = r.read_bytes()
            elif f == 9 and w == 2:
                out.next_validators_hash = r.read_bytes()
            elif f == 10 and w == 2:
                out.consensus_hash = r.read_bytes()
            elif f == 11 and w == 2:
                out.app_hash = r.read_bytes()
            elif f == 12 and w == 2:
                out.last_results_hash = r.read_bytes()
            elif f == 13 and w == 2:
                out.evidence_hash = r.read_bytes()
            elif f == 14 and w == 2:
                out.proposer_address = r.read_bytes()
            else:
                r.skip(w)
        return out


@dataclass
class Block:
    """types/block.go Block = Header + Data + EvidenceList + LastCommit."""

    header: Header = dc_field(default_factory=Header)
    data: Data = dc_field(default_factory=Data)
    evidence: List[object] = dc_field(default_factory=list)  # Evidence objects
    last_commit: Optional[Commit] = None
    _hash: Optional[bytes] = dc_field(default=None, repr=False, compare=False)

    def hash(self) -> bytes:
        if self._hash is None:
            self.fill_header()
            self._hash = self.header.hash()
        return self._hash

    def evidence_hash(self) -> bytes:
        hashes = [ev.hash() for ev in self.evidence]
        return merkle.hash_from_byte_slices(hashes)

    def fill_header(self) -> None:
        """types/block.go:133-148: derive the data-dependent header hashes."""
        if not self.header.last_commit_hash and self.last_commit is not None:
            self.header.last_commit_hash = self.last_commit.hash()
        if not self.header.data_hash:
            self.header.data_hash = self.data.hash()
        if not self.header.evidence_hash:
            self.header.evidence_hash = self.evidence_hash()

    def validate_basic(self) -> None:
        """types/block.go:55-93."""
        self.header.validate_basic()
        if self.last_commit is None:
            raise ValueError("nil LastCommit")
        try:
            self.last_commit.validate_basic()
        except ValueError as e:
            raise ValueError(f"wrong LastCommit: {e}") from e
        if self.header.last_commit_hash != self.last_commit.hash():
            raise ValueError("wrong Header.LastCommitHash")
        if self.header.data_hash != self.data.hash():
            raise ValueError("wrong Header.DataHash")
        for i, ev in enumerate(self.evidence):
            ev.validate_basic()
        if self.header.evidence_hash != self.evidence_hash():
            raise ValueError("wrong Header.EvidenceHash")

    def make_block_id(self, part_set_header: Optional[PartSetHeader] = None) -> BlockID:
        return BlockID(self.hash(), part_set_header or PartSetHeader())

    def to_proto_bytes(self) -> bytes:
        out = encode_message_field(1, self.header.to_proto_bytes(), always=True)
        out += encode_message_field(2, self.data.to_proto_bytes(), always=True)
        ev_payload = b""
        for ev in self.evidence:
            ev_payload += encode_message_field(1, ev.to_proto_bytes(), always=True)
        out += encode_message_field(3, ev_payload, always=True)
        if self.last_commit is not None:
            out += encode_message_field(4, self.last_commit.to_proto_bytes(), always=True)
        return out

    @classmethod
    def from_proto_bytes(cls, data: bytes) -> "Block":
        from tendermint_tpu.types import evidence as ev_mod

        r = Reader(data)
        out = cls()
        for f, w in r.fields():
            if f == 1 and w == 2:
                out.header = Header.from_proto_bytes(r.read_bytes())
            elif f == 2 and w == 2:
                out.data = Data.from_proto_bytes(r.read_bytes())
            elif f == 3 and w == 2:
                ev_list = r.read_bytes()
                er = Reader(ev_list)
                for ef, ew in er.fields():
                    if ef == 1 and ew == 2:
                        out.evidence.append(
                            ev_mod.evidence_from_proto_bytes(er.read_bytes())
                        )
                    else:
                        er.skip(ew)
            elif f == 4 and w == 2:
                out.last_commit = Commit.from_proto_bytes(r.read_bytes())
            else:
                r.skip(w)
        return out


def make_block(
    height: int,
    txs: List[bytes],
    last_commit: Optional[Commit],
    evidence: Optional[List[object]] = None,
) -> Block:
    """types/block.go MakeBlock."""
    block = Block(
        header=Header(height=height),
        data=Data(txs=list(txs)),
        evidence=list(evidence or []),
        last_commit=last_commit,
    )
    block.fill_header()
    return block
