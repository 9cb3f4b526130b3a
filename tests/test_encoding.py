"""Sign-bytes golden vectors from the reference (types/vote_test.go:81-150)
plus protobuf wire codec round-trips."""

import random

import pytest

from tendermint_tpu.encoding import canonical
from tendermint_tpu.encoding.canonical import (
    SIGNED_MSG_TYPE_PRECOMMIT,
    SIGNED_MSG_TYPE_PREVOTE,
    Timestamp,
    VoteSignBytesEncoder,
)
from tendermint_tpu.encoding.proto import (
    Reader,
    encode_bytes_field,
    encode_message_field,
    encode_sfixed64_field,
    encode_string_field,
    encode_varint,
    encode_varint_field,
    length_delimited,
)

# Go's zero time.Time as a protobuf Timestamp.
GO_ZERO_TIME = Timestamp(-62135596800, 0)

_GO_ZERO_FIELD = [0x2A, 0xB, 0x8, 0x80, 0x92, 0xB8, 0xC3, 0x98, 0xFE, 0xFF, 0xFF, 0xFF, 0x1]
_ONE = [1, 0, 0, 0, 0, 0, 0, 0]

# types/vote_test.go:88-150: (chain id, type, height, round) -> sign-bytes
# of a nil vote at Go's zero time
GOLDEN_VECTORS = {
    "empty": (("", 0, 0, 0), [0xD] + _GO_ZERO_FIELD),
    "precommit": (
        ("", SIGNED_MSG_TYPE_PRECOMMIT, 1, 1),
        [0x21, 0x8, 0x2, 0x11] + _ONE + [0x19] + _ONE + _GO_ZERO_FIELD,
    ),
    "prevote": (
        ("", SIGNED_MSG_TYPE_PREVOTE, 1, 1),
        [0x21, 0x8, 0x1, 0x11] + _ONE + [0x19] + _ONE + _GO_ZERO_FIELD,
    ),
    "no_type": (("", 0, 1, 1), [0x1F, 0x11] + _ONE + [0x19] + _ONE + _GO_ZERO_FIELD),
    "with_chain_id": (
        ("test_chain_id", 0, 1, 1),
        [0x2E, 0x11] + _ONE + [0x19] + _ONE + _GO_ZERO_FIELD
        + [0x32, 0xD] + list(b"test_chain_id"),
    ),
}


def one_shot(shared, block_id, timestamp):
    return canonical.vote_sign_bytes(*shared, *block_id, timestamp)


NIL = (b"", 0, b"")
FULL = (bytes(range(32)), 3, bytes(range(32, 64)))


@pytest.mark.parametrize("through", ["one_shot", "shared_encoder"])
@pytest.mark.parametrize("vector", GOLDEN_VECTORS)
def test_vote_sign_bytes_golden_vectors(vector, through):
    shared, want = GOLDEN_VECTORS[vector]
    if through == "one_shot":
        assert one_shot(shared, NIL, GO_ZERO_TIME) == bytes(want)
        return
    # the golden vote between other votes of the same encoder: neither
    # another block id's prefix nor the vote before it may show in it
    encoder = VoteSignBytesEncoder(*shared)
    for_nil, for_block = encoder.for_block_id(*NIL), encoder.for_block_id(*FULL)
    for_block(Timestamp(1_700_000_000, 999_999_999))
    for_nil(Timestamp(1_700_000_000, 5))
    assert for_nil(GO_ZERO_TIME) == bytes(want)
    for_block(GO_ZERO_TIME)
    assert for_nil(GO_ZERO_TIME) == bytes(want)
    assert encoder.prefixes == 2


def field_by_field(shared, block_id, timestamp):
    """CanonicalVote written out a field at a time with the wire helpers
    alone (canonical.proto; canonical.pb.go:590-640 for what is omitted):
    what the encoder must equal on every vote."""
    chain_id, msg_type, height, round_ = shared
    hash_, psh_total, psh_hash = block_id
    vote = encode_varint_field(1, msg_type)
    vote += encode_sfixed64_field(2, height)
    vote += encode_sfixed64_field(3, round_)
    if hash_ or psh_total or psh_hash:
        psh = encode_varint_field(1, psh_total) + encode_bytes_field(2, psh_hash)
        bid = encode_bytes_field(1, hash_) + encode_message_field(2, psh, always=True)
        vote += encode_message_field(4, bid, always=True)
    ts = encode_varint_field(1, timestamp.seconds) + encode_varint_field(2, timestamp.nanos)
    vote += encode_message_field(5, ts, always=True)
    vote += encode_string_field(6, chain_id)
    return length_delimited(vote)


EDGE_TIMES = [
    Timestamp(0, 0),
    GO_ZERO_TIME,
    Timestamp(1_700_000_000, 0),  # nanos omitted
    Timestamp(1_700_000_000, 1),
    Timestamp(1_700_000_000, 127),
    Timestamp(1_700_000_000, 128),
    Timestamp(1_700_000_000, 268_435_455),  # the widest 4-byte varint
    Timestamp(1_700_000_000, 268_435_456),  # the narrowest 5-byte one
    Timestamp(1_700_000_000, 999_999_999),
    Timestamp(0, 7),  # seconds omitted, nanos not
    Timestamp(-1, 5),
    Timestamp(2**62, 999_999_999),
]


@pytest.mark.parametrize("chain_id", ["c", "c" * 50], ids=["chain1", "chain50"])
@pytest.mark.parametrize("round_", [0, 3, 2**31 - 1])
@pytest.mark.parametrize("height", [0, 1, 2**62])
@pytest.mark.parametrize(
    "msg_type", [SIGNED_MSG_TYPE_PRECOMMIT, SIGNED_MSG_TYPE_PREVOTE], ids=["precommit", "prevote"]
)
def test_vote_sign_bytes_equal_a_field_by_field_encoding(msg_type, height, round_, chain_id):
    """One encoder per case, asked for 40 votes in a row as a commit's
    loop asks: nil and full block ids mixed, the second changing (and
    returning) mid-run, every edge timestamp; the one-shot function on
    the same votes. 36 cases x 40 votes."""
    shared = (chain_id, msg_type, height, round_)
    rng = random.Random(f"{msg_type}/{height}/{round_}/{chain_id}")
    other = (rng.randbytes(32), rng.randrange(1, 2**32), rng.randbytes(32))
    base = rng.randrange(1, 2**33)
    times = EDGE_TIMES + [
        Timestamp(base + i // 9 - (i == 20), rng.randrange(10**9)) for i in range(28)
    ]
    rng.shuffle(times)
    encoder = VoteSignBytesEncoder(*shared)
    lanes = {bid: encoder.for_block_id(*bid) for bid in (NIL, FULL, other)}
    lengths = set()
    for i, timestamp in enumerate(times):
        bid = (FULL, FULL, NIL, FULL, other)[i % 5]
        want = field_by_field(shared, bid, timestamp)
        assert lanes[bid](timestamp) == want, (bid is NIL, timestamp)
        assert one_shot(shared, bid, timestamp) == want
        lengths.add(len(want))
    assert encoder.prefixes == 3
    # a 50-character chain id takes the message past 127 bytes: a
    # two-byte length prefix, which one character never needs
    assert (max(lengths) > 129) == (len(chain_id) == 50)


def test_vote_extension_sign_bytes():
    # extension field does not affect vote sign bytes; it has its own
    # canonical struct (types/vote_test.go:152-170 case 5 matches case 4).
    got = canonical.vote_extension_sign_bytes("test_chain_id", b"extension", 1, 1)
    r = Reader(got)
    total = r.read_varint()
    assert total == len(got) - 1
    fields = {}
    for field, wire in r.fields():
        if wire == 2:
            fields[field] = r.read_bytes()
        elif wire == 1:
            fields[field] = r.read_sfixed64()
        else:
            r.skip(wire)
    assert fields == {1: b"extension", 2: 1, 3: 1, 4: b"test_chain_id"}


def test_varint_negative_is_ten_bytes():
    assert len(encode_varint(-1)) == 10
    r = Reader(encode_varint(-62135596800))
    assert r.read_svarint() == -62135596800


def test_timestamp_roundtrip():
    ts = Timestamp.from_unix_ns(1700000000_000000123)
    assert ts == Timestamp(1700000000, 123)
    enc = ts.encode()
    assert enc == bytes([0x08, 0x80, 0xE2, 0xCF, 0xAA, 0x06, 0x10, 0x7B])


def test_proposal_sign_bytes_parses():
    got = canonical.proposal_sign_bytes(
        "chain", 5, 2, -1, b"\xaa" * 32, 3, b"\xbb" * 32, Timestamp(100, 5)
    )
    r = Reader(got)
    r.read_varint()
    fields = {}
    for field, wire in r.fields():
        if wire == 2:
            fields[field] = r.read_bytes()
        elif wire == 1:
            fields[field] = r.read_sfixed64()
        else:
            fields[field] = r.read_svarint()
    assert fields[1] == 32  # SIGNED_MSG_TYPE_PROPOSAL
    assert fields[2] == 5 and fields[3] == 2
    assert fields[4] == -1  # pol_round, varint-encoded
    assert fields[7] == b"chain"
