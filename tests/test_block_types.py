"""Core block type tests: proto round-trips, hashing, validation.

Mirrors the shape of types/block_test.go / types/vote_test.go.
"""

import hashlib

import pytest

from tendermint_tpu.crypto import merkle
from tendermint_tpu.crypto.keys import Ed25519PrivKey
from tendermint_tpu.encoding.canonical import (
    SIGNED_MSG_TYPE_PRECOMMIT,
    SIGNED_MSG_TYPE_PREVOTE,
    Timestamp,
)
from tendermint_tpu.types import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    Block,
    BlockID,
    Commit,
    CommitSig,
    Consensus,
    Data,
    ExtendedCommit,
    GO_ZERO_TIME,
    Header,
    PartSetHeader,
    Proposal,
    Vote,
    VoteError,
    make_block,
)
from tests.helpers import CHAIN_ID, make_block_id, make_commit, make_validators


def _ts(n=1_700_000_000_000_000_000):
    return Timestamp.from_unix_ns(n)


class TestBlockID:
    def test_nil_and_complete(self):
        assert BlockID().is_nil()
        assert not BlockID().is_complete()
        bid = make_block_id()
        assert bid.is_complete()
        assert not bid.is_nil()

    def test_roundtrip(self):
        bid = make_block_id()
        assert BlockID.from_proto_bytes(bid.to_proto_bytes()) == bid
        assert BlockID.from_proto_bytes(BlockID().to_proto_bytes()) == BlockID()

    def test_key_distinct(self):
        assert make_block_id(b"a").key() != make_block_id(b"b").key()


class TestCommitSig:
    def test_absent_validation(self):
        CommitSig.absent().validate_basic()
        with pytest.raises(ValueError):
            CommitSig(BLOCK_ID_FLAG_ABSENT, b"\x01" * 20).validate_basic()

    def test_commit_requires_signature(self):
        cs = CommitSig(BLOCK_ID_FLAG_COMMIT, b"\x01" * 20, _ts(), b"")
        with pytest.raises(ValueError, match="missing"):
            cs.validate_basic()

    def test_roundtrip(self):
        cs = CommitSig(BLOCK_ID_FLAG_COMMIT, b"\x01" * 20, _ts(), b"\x05" * 64)
        back = CommitSig.from_proto_bytes(cs.to_proto_bytes())
        assert back == cs

    def test_absent_roundtrip_preserves_zero_time(self):
        back = CommitSig.from_proto_bytes(CommitSig.absent().to_proto_bytes())
        assert back.timestamp == GO_ZERO_TIME


class TestCommit:
    def test_hash_covers_signatures(self):
        privs, vset = make_validators(4)
        bid = make_block_id()
        c1 = make_commit(bid, 5, 0, vset, privs)
        c2 = make_commit(bid, 5, 0, vset, privs, absent={0})
        assert c1.hash() != c2.hash()

    def test_roundtrip(self):
        privs, vset = make_validators(4)
        c = make_commit(make_block_id(), 5, 2, vset, privs, absent={1})
        back = Commit.from_proto_bytes(c.to_proto_bytes())
        assert back.height == 5 and back.round == 2
        assert back.block_id == c.block_id
        assert back.signatures == c.signatures
        assert back.hash() == c.hash()

    def test_vote_sign_bytes_verifiable(self):
        privs, vset = make_validators(3)
        c = make_commit(make_block_id(), 7, 1, vset, privs)
        for i, priv in enumerate(privs):
            sb = c.vote_sign_bytes(CHAIN_ID, i)
            assert priv.pub_key().verify_signature(sb, c.signatures[i].signature)

    def test_validate_basic(self):
        privs, vset = make_validators(3)
        c = make_commit(make_block_id(), 7, 1, vset, privs)
        c.validate_basic()
        with pytest.raises(ValueError, match="nil block"):
            Commit(height=2, block_id=BlockID(), signatures=[]).validate_basic()


class TestCommitSignBytes:
    """ISSUE 27: what a commit's votes share is encoded once a loop and
    the timestamp spliced per lane; nothing of it outlives the loop."""

    ABSENT = {3, 40}
    NIL = {5, 41, 63}

    @pytest.fixture(scope="class")
    def signed(self):
        privs, vset = make_validators(64)
        block_id = make_block_id(b"issue-27")
        commit = make_commit(
            block_id, 9, 1, vset, privs, absent=self.ABSENT, nil_votes=self.NIL
        )
        return vset, block_id, commit

    @pytest.fixture
    def block_ids_encoded(self, monkeypatch):
        """Calls of the canonical block id's encoder, as (hash, ...)."""
        from tendermint_tpu.encoding import canonical

        calls = []
        real = canonical.encode_canonical_block_id

        def counting(*args):
            calls.append(args)
            return real(*args)

        monkeypatch.setattr(canonical, "encode_canonical_block_id", counting)
        return calls

    @pytest.mark.parametrize(
        "flag", [BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL], ids=["for_block", "nil"]
    )
    def test_one_lane_equals_the_encoders_bytes(self, signed, flag):
        vset, _, commit = signed
        encoder = commit.sign_bytes_encoder(CHAIN_ID)
        lanes = [i for i, cs in enumerate(commit.signatures) if cs.block_id_flag == flag]
        assert len(lanes) == (len(self.NIL) if flag == BLOCK_ID_FLAG_NIL else 59)
        for i in lanes:
            sign_bytes = encoder.lane(i)
            assert sign_bytes == commit.vote_sign_bytes(CHAIN_ID, i)
            assert sign_bytes == commit.get_vote(i).sign_bytes(CHAIN_ID)
            assert vset.validators[i].pub_key.verify_signature(
                sign_bytes, commit.signatures[i].signature
            )
        assert encoder.prefixes == 1

    def test_a_pass_over_every_vote_builds_two_prefixes(self, signed, block_ids_encoded):
        _, block_id, commit = signed
        encoder = commit.sign_bytes_encoder(CHAIN_ID)
        for i, cs in enumerate(commit.signatures):
            if cs.block_id_flag != BLOCK_ID_FLAG_ABSENT:
                encoder.lane(i)
        assert encoder.prefixes == 2
        assert sorted(args[0] for args in block_ids_encoded) == [b"", block_id.hash]

    @pytest.mark.parametrize("through", ["vote_sign_bytes", "encoder"])
    def test_unknown_block_id_flag_raises(self, through):
        commit = Commit(
            height=3, block_id=make_block_id(), signatures=[CommitSig(7, b"a" * 20, _ts(), b"s")]
        )
        with pytest.raises(ValueError, match="unknown BlockIDFlag: 7"):
            if through == "encoder":
                commit.sign_bytes_encoder(CHAIN_ID).lane(0)
            else:
                commit.vote_sign_bytes(CHAIN_ID, 0)

    @pytest.mark.parametrize(
        "entry,prefixes",
        [
            ("verify_commit", 2),  # nil votes are verified too
            ("verify_commit_light", 1),
            ("verify_commit_light_trusting", 1),
            ("single", 2),
            ("pipelined", 1),
        ],
    )
    def test_each_call_encodes_the_block_id_once_and_keeps_nothing(
        self, signed, block_ids_encoded, entry, prefixes
    ):
        from tendermint_tpu.parallel.pipeline import CommitTask, verify_commits_pipelined
        from tendermint_tpu.types import validation

        vset, block_id, commit = signed

        def call():
            if entry == "pipelined":
                task = CommitTask(CHAIN_ID, vset, block_id, 9, commit)
                (verdict,) = verify_commits_pipelined([task])
                assert verdict.ok, verdict.error
            elif entry == "verify_commit_light_trusting":
                validation.verify_commit_light_trusting(
                    CHAIN_ID, vset, commit, validation.Fraction(1, 3)
                )
            elif entry == "single":
                validation._verify_commit_single(
                    CHAIN_ID, vset, commit, vset.total_voting_power() * 2 // 3,
                    lambda cs: cs.block_id_flag == BLOCK_ID_FLAG_ABSENT,
                    lambda cs: cs.block_id_flag == BLOCK_ID_FLAG_COMMIT,
                    True, True,
                )
            else:
                getattr(validation, entry)(CHAIN_ID, vset, block_id, 9, commit)

        def state():
            return [sorted(vars(commit))] + [sorted(vars(cs)) for cs in commit.signatures]

        before = state()
        call()
        assert len(block_ids_encoded) == prefixes
        assert state() == before
        # nothing was kept: the second call does the work again
        call()
        assert len(block_ids_encoded) == 2 * prefixes
        assert state() == before

    def test_the_encoder_reads_the_commit_as_it_is_when_built(self, signed):
        _, _, commit = signed
        before = commit.vote_sign_bytes(CHAIN_ID, 0)
        commit.round += 1
        try:
            after = commit.vote_sign_bytes(CHAIN_ID, 0)
        finally:
            commit.round -= 1
        assert after != before and commit.vote_sign_bytes(CHAIN_ID, 0) == before


class TestVote:
    def test_sign_and_verify(self):
        priv = Ed25519PrivKey.from_seed(b"\x07" * 32)
        vote = Vote(
            type=SIGNED_MSG_TYPE_PREVOTE,
            height=10,
            round=2,
            block_id=make_block_id(),
            timestamp=_ts(),
            validator_address=priv.pub_key().address(),
            validator_index=0,
        )
        vote.signature = priv.sign(vote.sign_bytes(CHAIN_ID))
        vote.verify(CHAIN_ID, priv.pub_key())
        with pytest.raises(VoteError, match="address"):
            other = Ed25519PrivKey.from_seed(b"\x08" * 32)
            vote.verify(CHAIN_ID, other.pub_key())
        vote.signature = b"\x00" * 64
        with pytest.raises(VoteError, match="signature"):
            vote.verify(CHAIN_ID, priv.pub_key())

    def test_extension_verify(self):
        priv = Ed25519PrivKey.from_seed(b"\x09" * 32)
        vote = Vote(
            type=SIGNED_MSG_TYPE_PRECOMMIT,
            height=3,
            round=0,
            block_id=make_block_id(),
            timestamp=_ts(),
            validator_address=priv.pub_key().address(),
            extension=b"oracle-price:42",
        )
        vote.signature = priv.sign(vote.sign_bytes(CHAIN_ID))
        vote.extension_signature = priv.sign(vote.extension_sign_bytes(CHAIN_ID))
        vote.verify_vote_and_extension(CHAIN_ID, priv.pub_key())
        vote.extension_signature = b"\x01" * 64
        with pytest.raises(VoteError, match="extension"):
            vote.verify_vote_and_extension(CHAIN_ID, priv.pub_key())

    def test_pre_verified_fast_path_is_self_validating(self):
        """The _pre_verified tag carries a digest of the verified
        sign-bytes; mutating any signed field after marking must demote
        the vote to a full (failing) signature check."""
        priv = Ed25519PrivKey.from_seed(b"\x0b" * 32)
        vote = Vote(
            type=SIGNED_MSG_TYPE_PREVOTE,
            height=10,
            round=2,
            block_id=make_block_id(),
            timestamp=_ts(),
            validator_address=priv.pub_key().address(),
            validator_index=0,
        )
        vote.signature = priv.sign(vote.sign_bytes(CHAIN_ID))
        vote.mark_pre_verified(CHAIN_ID, priv.pub_key().bytes())
        # tag honored while content is untouched (even with a clobbered
        # signature — that is the point of the fast path)
        vote.signature = b"\x00" * 64
        vote.verify(CHAIN_ID, priv.pub_key())
        # any signed-field mutation invalidates the tag
        vote.height = 11
        with pytest.raises(VoteError, match="signature"):
            vote.verify(CHAIN_ID, priv.pub_key())

    def test_pre_verified_extension_tag_checks_digest(self):
        priv = Ed25519PrivKey.from_seed(b"\x0c" * 32)
        vote = Vote(
            type=SIGNED_MSG_TYPE_PRECOMMIT,
            height=3,
            round=0,
            block_id=make_block_id(),
            timestamp=_ts(),
            validator_address=priv.pub_key().address(),
            extension=b"oracle-price:42",
        )
        vote.signature = priv.sign(vote.sign_bytes(CHAIN_ID))
        vote.extension_signature = priv.sign(
            vote.extension_sign_bytes(CHAIN_ID)
        )
        vote.mark_pre_verified(
            CHAIN_ID, priv.pub_key().bytes(), extension_too=True
        )
        vote.verify_vote_and_extension(CHAIN_ID, priv.pub_key())
        # tampering with the extension after pre-verification must not
        # ride the fast path
        vote.extension = b"oracle-price:9000"
        with pytest.raises(VoteError, match="extension"):
            vote.verify_extension(CHAIN_ID, priv.pub_key())

    def test_pre_verified_explicit_digest_must_match(self):
        priv = Ed25519PrivKey.from_seed(b"\x0d" * 32)
        vote = Vote(
            type=SIGNED_MSG_TYPE_PREVOTE,
            height=10,
            round=2,
            block_id=make_block_id(),
            timestamp=_ts(),
            validator_address=priv.pub_key().address(),
            validator_index=0,
        )
        vote.signature = b"\x00" * 64  # invalid; only the tag could pass
        # a stale digest (of DIFFERENT bytes than the vote's current
        # sign-bytes) must not be honored
        vote.mark_pre_verified(
            CHAIN_ID,
            priv.pub_key().bytes(),
            sign_bytes_digest=hashlib.sha256(b"not these bytes").digest(),
        )
        with pytest.raises(VoteError, match="signature"):
            vote.verify(CHAIN_ID, priv.pub_key())
        # the digest of the exact sign-bytes is honored
        vote.signature = priv.sign(vote.sign_bytes(CHAIN_ID))
        vote.mark_pre_verified(
            CHAIN_ID,
            priv.pub_key().bytes(),
            sign_bytes_digest=hashlib.sha256(
                vote.sign_bytes(CHAIN_ID)
            ).digest(),
        )
        vote.signature = b"\x00" * 64
        vote.verify(CHAIN_ID, priv.pub_key())

    def test_commit_sig_conversion(self):
        priv = Ed25519PrivKey.from_seed(b"\x0a" * 32)
        vote = Vote(
            type=SIGNED_MSG_TYPE_PRECOMMIT,
            height=3,
            round=0,
            block_id=make_block_id(),
            timestamp=_ts(),
            validator_address=priv.pub_key().address(),
            signature=b"\x02" * 64,
        )
        cs = vote.commit_sig()
        assert cs.block_id_flag == BLOCK_ID_FLAG_COMMIT
        assert cs.validator_address == vote.validator_address

    def test_roundtrip(self):
        vote = Vote(
            type=SIGNED_MSG_TYPE_PRECOMMIT,
            height=11,
            round=3,
            block_id=make_block_id(),
            timestamp=_ts(),
            validator_address=b"\x04" * 20,
            validator_index=7,
            signature=b"\x05" * 64,
            extension=b"ext",
            extension_signature=b"\x06" * 64,
        )
        assert Vote.from_proto_bytes(vote.to_proto_bytes()) == vote


class TestProposal:
    def test_sign_bytes_and_roundtrip(self):
        p = Proposal(
            height=4,
            round=1,
            pol_round=-1,
            block_id=make_block_id(),
            timestamp=_ts(),
            signature=b"\x01" * 64,
        )
        p.validate_basic()
        assert len(p.sign_bytes(CHAIN_ID)) > 0
        back = Proposal.from_proto_bytes(p.to_proto_bytes())
        assert back == p
        assert back.pol_round == -1

    def test_invalid_pol_round(self):
        p = Proposal(
            height=4,
            round=1,
            pol_round=1,
            block_id=make_block_id(),
            timestamp=_ts(),
            signature=b"\x01" * 64,
        )
        with pytest.raises(ValueError, match="POLRound"):
            p.validate_basic()


class TestHeaderAndBlock:
    def _header(self):
        return Header(
            version=Consensus(block=11, app=1),
            chain_id=CHAIN_ID,
            height=5,
            time=_ts(),
            last_block_id=make_block_id(b"prev"),
            last_commit_hash=hashlib.sha256(b"lc").digest(),
            data_hash=hashlib.sha256(b"d").digest(),
            validators_hash=hashlib.sha256(b"v").digest(),
            next_validators_hash=hashlib.sha256(b"nv").digest(),
            consensus_hash=hashlib.sha256(b"c").digest(),
            app_hash=hashlib.sha256(b"a").digest(),
            last_results_hash=hashlib.sha256(b"r").digest(),
            evidence_hash=hashlib.sha256(b"e").digest(),
            proposer_address=b"\x01" * 20,
        )

    def test_hash_changes_with_fields(self):
        h = self._header()
        h2 = self._header()
        h2.height = 6
        assert h.hash() != h2.hash()
        assert len(h.hash()) == 32

    def test_hash_nil_without_validators_hash(self):
        h = self._header()
        h.validators_hash = b""
        assert h.hash() == b""

    def test_roundtrip(self):
        h = self._header()
        assert Header.from_proto_bytes(h.to_proto_bytes()) == h

    def test_block_fill_and_validate(self):
        privs, vset = make_validators(3)
        last_commit = make_commit(make_block_id(b"prev"), 4, 0, vset, privs)
        block = make_block(5, [b"tx1", b"tx2"], last_commit)
        block.header.version = Consensus(block=11)
        block.header.chain_id = CHAIN_ID
        block.header.time = _ts()
        block.header.last_block_id = make_block_id(b"prev")
        block.header.validators_hash = vset.hash()
        block.header.next_validators_hash = vset.hash()
        block.header.proposer_address = vset.validators[0].address
        block.validate_basic()
        assert len(block.hash()) == 32

    def test_block_roundtrip(self):
        privs, vset = make_validators(3)
        last_commit = make_commit(make_block_id(b"prev"), 4, 0, vset, privs)
        block = make_block(5, [b"tx1"], last_commit)
        back = Block.from_proto_bytes(block.to_proto_bytes())
        assert back.data.txs == [b"tx1"]
        assert back.last_commit.hash() == last_commit.hash()
        assert back.header.data_hash == block.header.data_hash

    def test_data_hash_is_merkle_of_tx_hashes(self):
        # Leaves are sha256(tx), not raw tx bytes (types/tx.go Txs.Hash).
        d = Data(txs=[b"a", b"b"])
        assert d.hash() == merkle.hash_from_byte_slices(
            [hashlib.sha256(b"a").digest(), hashlib.sha256(b"b").digest()]
        )


class TestExtendedCommit:
    def test_wrap_and_strip(self):
        privs, vset = make_validators(3)
        c = make_commit(make_block_id(), 5, 0, vset, privs)
        ec = ExtendedCommit.wrap_commit(c)
        assert ec.to_commit().hash() == c.hash()
        with pytest.raises(ValueError):
            ec.ensure_extensions()  # no extension signatures present

    def test_roundtrip(self):
        privs, vset = make_validators(3)
        c = make_commit(make_block_id(), 5, 0, vset, privs)
        ec = ExtendedCommit.wrap_commit(c)
        for e in ec.extended_signatures:
            e.extension = b"x"
            e.extension_signature = b"\x01" * 64
        back = ExtendedCommit.from_proto_bytes(ec.to_proto_bytes())
        assert back.extended_signatures == ec.extended_signatures
