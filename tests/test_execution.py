"""BlockExecutor end-to-end against the kvstore app: the first chain
slice — propose, validate, apply, repeat (internal/state/execution_test.go
analog, without consensus gossip)."""

import pytest

from tendermint_tpu.abci.client import LocalClient
from tendermint_tpu.abci.kvstore import KVStoreApplication
from tendermint_tpu.abci import types as abci
from tendermint_tpu.encoding.canonical import Timestamp
from tendermint_tpu.state import StateStore, state_from_genesis
from tendermint_tpu.state.execution import BlockExecutor, InvalidBlockError
from tendermint_tpu.storage import MemDB
from tendermint_tpu.storage.blockstore import BlockStore
from tendermint_tpu.types import BlockID, ExtendedCommit
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.types.part_set import PartSet
from tests.helpers import CHAIN_ID, make_commit, make_validators


BASE_NS = 1_700_000_000_000_000_000


def make_chain_env(n_vals=4, validators=None):
    """``validators``: a ``ValidatorSet`` to start the chain from (the
    caller then holds its keys: ``privs`` is None), else ``n_vals``
    seeded ones."""
    privs, vset = (None, validators) if validators is not None else make_validators(n_vals)
    gen = GenesisDoc(
        chain_id=CHAIN_ID,
        genesis_time=Timestamp.from_unix_ns(BASE_NS),
        validators=[
            GenesisValidator(pub_key=v.pub_key, power=v.voting_power)
            for v in vset.validators
        ],
    )
    state = state_from_genesis(gen)
    app = KVStoreApplication()
    client = LocalClient(app)
    client.start()
    init = client.init_chain(
        abci.RequestInitChain(chain_id=CHAIN_ID, initial_height=1)
    )
    state.app_hash = init.app_hash
    state_store = StateStore(MemDB())
    state_store.save(state)
    block_store = BlockStore(MemDB())
    clock = {"ns": BASE_NS}

    def now():
        clock["ns"] += 1_000_000_000
        return Timestamp.from_unix_ns(clock["ns"])

    executor = BlockExecutor(state_store, client, block_store, now=now)
    return executor, state, privs, vset, app


def advance_one_height(executor, state, privs, vset, txs, last_ec):
    height = state.last_block_height + 1
    proposer = state.validators.get_proposer().address

    class _Pool:
        def lock(self): pass
        def unlock(self): pass
        def reap_max_bytes_max_gas(self, mb, mg): return txs
        def update(self, *a, **k): pass
        def remove_tx_by_key(self, key): pass

    executor.mempool = _Pool()
    block = executor.create_proposal_block(height, state, last_ec, proposer)
    assert executor.process_proposal(block, state)
    parts = PartSet.from_data(block.to_proto_bytes())
    block_id = BlockID(block.hash(), parts.header())
    new_state = executor.apply_block(state, block_id, block)
    executor.block_store.save_block(
        block, parts, make_commit(block_id, height, 0, vset, privs)
    )
    commit = make_commit(
        block_id, height, 0, vset, privs,
        time_ns=BASE_NS + height * 1_000_000_000,
    )
    return new_state, ExtendedCommit.wrap_commit(commit)


class TestChainSlice:
    def test_three_heights_with_txs(self):
        executor, state, privs, vset, app = make_chain_env()
        ec = ExtendedCommit()
        hashes = [state.app_hash]
        for h, txs in enumerate([[b"a=1"], [b"b=2", b"c=3"], []], start=1):
            state, ec = advance_one_height(executor, state, privs, vset, txs, ec)
            assert state.last_block_height == h
            hashes.append(state.app_hash)
        # app state reflects txs
        q = app.query(abci.RequestQuery(data=b"b"))
        assert q.value == b"2"
        # app hash changed when txs landed, and also at empty block (height in hash)
        assert hashes[1] != hashes[0] and hashes[2] != hashes[1]
        # state store has the chain of validators
        for h in (1, 2, 3, 4):
            executor.state_store.load_validators(h)

    def test_reloaded_state_matches(self):
        executor, state, privs, vset, app = make_chain_env()
        state, ec = advance_one_height(executor, state, privs, vset, [b"x=9"], ExtendedCommit())
        loaded = executor.state_store.load()
        assert loaded.last_block_height == state.last_block_height
        assert loaded.app_hash == state.app_hash
        assert loaded.last_results_hash == state.last_results_hash
        assert loaded.validators.hash() == state.validators.hash()

    def test_invalid_block_rejected(self):
        executor, state, privs, vset, app = make_chain_env()
        ec = ExtendedCommit()
        state, ec = advance_one_height(executor, state, privs, vset, [], ec)
        # Build a block with the wrong app hash.
        proposer = state.validators.get_proposer().address
        block = executor.create_proposal_block(2, state, ec, proposer)
        block.header.app_hash = b"\x01" * 32
        block._hash = None
        parts = PartSet.from_data(block.to_proto_bytes())
        with pytest.raises(InvalidBlockError, match="AppHash"):
            executor.apply_block(state, BlockID(block.hash(), parts.header()), block)

    def test_validator_update_tx_rotates_set(self):
        executor, state, privs, vset, app = make_chain_env()
        import base64

        from tendermint_tpu.crypto.keys import Ed25519PrivKey

        new_priv = Ed25519PrivKey.from_seed(b"\x77" * 32)
        pk_b64 = base64.b64encode(new_priv.pub_key().bytes()).decode()
        tx = f"val:{pk_b64}!25".encode()
        ec = ExtendedCommit()
        state, ec = advance_one_height(executor, state, privs, vset, [tx], ec)
        # valset change lands in NextValidators after the delay
        assert state.last_height_validators_changed == 3
        assert len(state.next_validators) == 5
        assert len(state.validators) == 4
