"""SignedHeader and LightBlock (types/light.go).

The light client's unit of verification: a header plus the commit that
signed it, and the validator set that produced the commit.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from tendermint_tpu.encoding.proto import Reader, encode_message_field, encode_varint_field
from tendermint_tpu.libs import tracing
from tendermint_tpu.types.block import Commit, Header
from tendermint_tpu.types.validator_set import ValidatorSet


@dataclass
class SignedHeader:
    """types/light.go SignedHeader {header=1, commit=2}."""

    header: Optional[Header] = None
    commit: Optional[Commit] = None

    @property
    def height(self) -> int:
        return self.header.height if self.header else 0

    @property
    def chain_id(self) -> str:
        return self.header.chain_id if self.header else ""

    def hash(self) -> bytes:
        return self.header.hash() if self.header else b""

    def validate_basic(self, chain_id: str) -> None:
        """types/light.go SignedHeader.ValidateBasic."""
        if self.header is None:
            raise ValueError("missing header")
        if self.commit is None:
            raise ValueError("missing commit")
        self.header.validate_basic()
        self.commit.validate_basic()
        if self.header.chain_id != chain_id:
            raise ValueError(
                f"header belongs to another chain {self.header.chain_id!r}, "
                f"not {chain_id!r}"
            )
        if self.commit.height != self.header.height:
            raise ValueError(
                f"header and commit height mismatch: {self.header.height} vs "
                f"{self.commit.height}"
            )
        if self.header.hash() != self.commit.block_id.hash:
            raise ValueError("commit signs a different block than the header")

    def to_proto_bytes(self) -> bytes:
        out = b""
        if self.header is not None:
            out += encode_message_field(1, self.header.to_proto_bytes(), always=True)
        if self.commit is not None:
            out += encode_message_field(2, self.commit.to_proto_bytes(), always=True)
        return out

    @classmethod
    def from_proto_bytes(cls, data: bytes) -> "SignedHeader":
        r = Reader(data)
        out = cls()
        for f, w in r.fields():
            if f == 1 and w == 2:
                out.header = Header.from_proto_bytes(r.read_bytes())
            elif f == 2 and w == 2:
                out.commit = Commit.from_proto_bytes(r.read_bytes())
            else:
                r.skip(w)
        return out


@dataclass
class LightBlock:
    """types/light.go LightBlock {signed_header=1, validator_set=2}."""

    signed_header: Optional[SignedHeader] = None
    validator_set: Optional[ValidatorSet] = None

    @property
    def height(self) -> int:
        return self.signed_header.height if self.signed_header else 0

    @property
    def header(self) -> Optional[Header]:
        return self.signed_header.header if self.signed_header else None

    def hash(self) -> bytes:
        return self.signed_header.hash() if self.signed_header else b""

    def validate_basic(self, chain_id: str) -> bytes:
        """types/light.go LightBlock.ValidateBasic. Returns the validator
        set's hash, which it has just computed and found in the header:
        a caller that goes on to verify the block need not hash the set
        again."""
        if self.signed_header is None:
            raise ValueError("missing signed header")
        if self.validator_set is None:
            raise ValueError("missing validator set")
        with tracing.span("light_block_checks"):
            self.signed_header.validate_basic(chain_id)
            self.validator_set.validate_basic()
        vals_hash = self.validator_set.hash()
        if self.signed_header.header.validators_hash != vals_hash:
            raise ValueError(
                "expected validator hash of header to match validator set hash"
            )
        return vals_hash

    def to_proto_bytes(self) -> bytes:
        out = b""
        if self.signed_header is not None:
            out += encode_message_field(
                1, self.signed_header.to_proto_bytes(), always=True
            )
        if self.validator_set is not None:
            out += encode_message_field(
                2, self.validator_set.to_proto_bytes(), always=True
            )
        return out

    @classmethod
    def from_proto_bytes(cls, data: bytes) -> "LightBlock":
        r = Reader(data)
        out = cls()
        for f, w in r.fields():
            if f == 1 and w == 2:
                out.signed_header = SignedHeader.from_proto_bytes(r.read_bytes())
            elif f == 2 and w == 2:
                out.validator_set = ValidatorSet.from_proto_bytes(r.read_bytes())
            else:
                r.skip(w)
        return out
