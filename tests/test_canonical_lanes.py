"""``CommitSignBytes.lanes`` (ISSUE 45): the sign-bytes of a block of a
commit's votes in one call are, byte for byte, what ``lane`` gives for
each of them: whatever the timestamps' widths, seconds and flags, in
whatever order the votes are asked for, and an entry ``lane`` raises at
raises here too."""

import random

import pytest

from tendermint_tpu.encoding import canonical
from tendermint_tpu.encoding.canonical import SIGNED_MSG_TYPE_PRECOMMIT, Timestamp
from tendermint_tpu.encoding.proto import encode_varint
from tendermint_tpu.types import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    Commit,
    CommitSig,
)
from tendermint_tpu.types.block import GO_ZERO_TIME
from tests.helpers import CHAIN_ID, make_block_id

SECOND = 1_700_000_123
C, N, A = BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL, BLOCK_ID_FLAG_ABSENT

# the widths of a nanos varint, on both sides of every step
WIDTHS = [1, 127, 128, 16_383, 16_384, 65_535, 65_536, 2**21 - 1, 2**21, 2**28 - 1, 2**28, 2**28 + 1, 999_999_999]

# name -> the (flag, timestamp) of each entry
CASES = {
    "zero_time": [(C, Timestamp(0, 0))] * 3,  # an empty timestamp body
    "go_zero_time": [(C, GO_ZERO_TIME), (C, Timestamp(SECOND, 5)), (C, GO_ZERO_TIME)],
    "nanos_zero": [(C, Timestamp(SECOND, 0)), (C, Timestamp(SECOND, 7)), (C, Timestamp(SECOND, 0))],
    "every_width": [(C, Timestamp(SECOND, nanos)) for nanos in WIDTHS],
    "seconds_omitted": [(C, Timestamp(0, nanos)) for nanos in WIDTHS],
    "two_seconds": [(C, Timestamp(SECOND + (i % 3 == 0), 10**8 * (i % 10) + i)) for i in range(40)],
    "pre_epoch": [(C, Timestamp(-1, 5)), (C, Timestamp(-62135596800, 999)), (C, Timestamp(SECOND, 2**28))],
    "seconds_past_32_bits": [(C, Timestamp(2**32, 1)), (C, Timestamp(2**62, 999_999_999)), (C, Timestamp(2**32 - 1, 2**21))],
    "nanos_no_time_has": [(C, Timestamp(SECOND, -1)), (C, Timestamp(SECOND, 2**30)), (C, Timestamp(SECOND, 2**35)), (C, Timestamp(SECOND, 2**30 - 1))],
    "commit_and_nil": [((N if i % 7 == 3 else C), Timestamp(SECOND, 10**7 * i + 1)) for i in range(30)],
    "nil_first": [(N, Timestamp(SECOND, 3)), (C, Timestamp(SECOND, 4)), (N, Timestamp(SECOND + 1, 0)), (C, Timestamp(0, 0))],
    "absent_among_them": [(C, Timestamp(SECOND, 1)), (A, GO_ZERO_TIME), (N, Timestamp(SECOND, 2)), (A, Timestamp(SECOND, 9))],
    "one_vote": [(N, Timestamp(SECOND, 2**28 - 1))],
}


def commit_of(entries, seed=b"lanes"):
    commit = Commit(height=9, round=2, block_id=make_block_id(seed))
    commit.signatures = [
        CommitSig(flag, bytes([i % 251]) * 20, timestamp, b"") for i, (flag, timestamp) in enumerate(entries)
    ]
    return commit


def orders(n, name):
    """Every entry in the commit's order; the same backwards; a sample
    of them with repeats; one at a time."""
    rng = random.Random(name)
    yield list(range(n))
    yield list(range(n - 1, -1, -1))
    yield [rng.randrange(n) for _ in range(n + 3)]
    for i in range(n):
        yield [i]


@pytest.mark.parametrize("name", CASES)
def test_a_block_of_lanes_equals_the_lanes_one_by_one(name):
    commit = commit_of(CASES[name])
    for idxs in orders(len(commit.signatures), name):
        one_by_one = commit.sign_bytes_encoder(CHAIN_ID)
        want = [one_by_one.lane(i) for i in idxs]
        block = commit.sign_bytes_encoder(CHAIN_ID)
        assert block.lanes(idxs) == want, idxs
        assert block.prefixes == one_by_one.prefixes  # a prefix a flag met, no more
        assert block.lanes(idxs) == want and block.prefixes == one_by_one.prefixes  # and asked again
        # lane after lanes and the reverse share what was built
        assert [block.lane(i) for i in idxs] == want
        assert one_by_one.lanes(idxs) == want


@pytest.mark.parametrize("name", ["every_width", "commit_and_nil", "pre_epoch", "nanos_no_time_has", "zero_time"])
def test_a_block_of_lanes_equals_the_one_shot_encoding(name):
    """Against ``encoding/canonical.vote_sign_bytes``, which shares no
    state with any other vote."""
    commit = commit_of(CASES[name])
    got = commit.sign_bytes_encoder(CHAIN_ID).lanes(range(len(commit.signatures)))
    for cs, sign_bytes in zip(commit.signatures, got):
        bid = cs.block_id(commit.block_id)
        assert sign_bytes == canonical.vote_sign_bytes(
            CHAIN_ID, SIGNED_MSG_TYPE_PRECOMMIT, commit.height, commit.round,
            bid.hash, bid.part_set_header.total, bid.part_set_header.hash, cs.timestamp,
        )


def test_no_lanes_are_no_sign_bytes_and_build_no_prefix():
    encoder = commit_of(CASES["commit_and_nil"]).sign_bytes_encoder(CHAIN_ID)
    assert encoder.lanes([]) == [] and encoder.prefixes == 0


@pytest.mark.parametrize("at", [0, 4, 9])
@pytest.mark.parametrize("others", ["commit", "commit_and_nil"])
def test_an_unknown_flag_raises_for_the_block_as_for_its_lane(others, at):
    """A block that holds the entry raises what ``lane`` raises at it;
    the votes before it still encode, block-wise or one by one."""
    entries = [((N if others == "commit_and_nil" and i % 3 == 1 else C), Timestamp(SECOND, i + 1)) for i in range(10)]
    entries[at] = (7, Timestamp(SECOND, 5))
    commit = commit_of(entries)
    with pytest.raises(ValueError, match="unknown BlockIDFlag: 7") as by_lane:
        commit.sign_bytes_encoder(CHAIN_ID).lane(at)
    encoder = commit.sign_bytes_encoder(CHAIN_ID)
    with pytest.raises(ValueError, match="unknown BlockIDFlag: 7") as by_block:
        encoder.lanes(range(10))
    assert str(by_block.value) == str(by_lane.value)
    before = list(range(at))
    assert encoder.lanes(before) == [commit.sign_bytes_encoder(CHAIN_ID).lane(i) for i in before]


def test_the_tables_hold_every_varint_they_answer_for():
    """The nanos varint comes from two tables; each entry against the
    loop of ``encoding/proto.encode_varint``, and the join at the
    seams."""
    low14, varint16 = canonical._nanos_tables()
    assert len(low14) == 1 << 14 and len(varint16) == 1 << 16
    assert all(varint16[k] == encode_varint(k) for k in range(1 << 16))
    rng = random.Random(45)
    picks = WIDTHS + [rng.randrange(1 << 16, canonical._NANOS_BY_TABLES) for _ in range(2000)]
    picks += [canonical._NANOS_BY_TABLES - 1, 1 << 16, (1 << 16) + 1]
    for n in picks:
        if n >= 1 << 16:
            assert low14[n & 16383] + varint16[n >> 14] == encode_varint(n), n
