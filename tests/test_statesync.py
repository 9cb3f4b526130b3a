"""State sync: fresh node joins via app snapshot + light-block verification.

The e2e-level statesync scenario (internal/statesync): node A runs a
chain with an app producing snapshots; fresh node B discovers a
snapshot over the Snapshot channel, builds a verified state at the
snapshot height from light blocks anchored at a trusted (height, hash),
restores the app chunk-by-chunk, backfills verified headers, block-syncs
the remainder, and follows consensus — never fetching the full history.
"""

import time

import pytest

from tendermint_tpu.abci.client import LocalClient
from tendermint_tpu.abci.kvstore import KVStoreApplication
from tendermint_tpu.abci import types as abci
from tendermint_tpu.node import Node, NodeConfig
from tendermint_tpu.p2p.transport import MemoryNetwork
from tendermint_tpu.privval import FilePV
from tendermint_tpu.statesync import StateSyncConfig
from tendermint_tpu.statesync.syncer import StateSyncer

from tests.helpers import warm_verify
from tests.test_node import fast_genesis, wait_for

SNAPSHOT_INTERVAL = 4


@pytest.fixture()
def one_priv(tmp_path):
    return [
        FilePV.generate(str(tmp_path / "pk0.json"), str(tmp_path / "ps0.json"))
    ]


def _mk_node(name, privs, net, *, index=None, snapshot_interval=0, statesync=None,
             persistent_peers=()):
    genesis = fast_genesis(privs)
    app = KVStoreApplication(snapshot_interval=snapshot_interval)
    cfg = NodeConfig(
        chain_id=genesis.chain_id,
        listen_addr=name,
        blocksync=True,
        wal_enabled=False,
        persistent_peers=list(persistent_peers),
        moniker=name,
        statesync=statesync,
    )
    node = Node(
        cfg,
        genesis,
        LocalClient(app),
        priv_validator=privs[index] if index is not None else None,
        memory_network=net,
    )
    return node, app


class TestKVStoreSnapshots:
    def test_snapshot_take_list_load_restore(self):
        app = KVStoreApplication(snapshot_interval=2)
        app.finalize_block(
            abci.RequestFinalizeBlock(height=1, txs=[b"k1=v1"])
        )
        app.commit()
        app.finalize_block(
            abci.RequestFinalizeBlock(height=2, txs=[b"k2=" + b"v2" * 3000])
        )
        app.commit()  # forces multiple chunks
        snaps = app.list_snapshots(None).snapshots
        assert [s.height for s in snaps] == [2]
        snap = snaps[0]
        assert snap.chunks >= 2
        chunks = [
            app.load_snapshot_chunk(
                abci.RequestLoadSnapshotChunk(height=2, format=1, chunk=i)
            ).chunk
            for i in range(snap.chunks)
        ]
        assert all(chunks)

        fresh = KVStoreApplication()
        res = fresh.offer_snapshot(
            abci.RequestOfferSnapshot(snapshot=snap, app_hash=app._app_hash)
        )
        assert res.result == abci.OFFER_SNAPSHOT_ACCEPT
        for i, c in enumerate(chunks):
            r = fresh.apply_snapshot_chunk(
                abci.RequestApplySnapshotChunk(index=i, chunk=c)
            )
            assert r.result == abci.APPLY_CHUNK_ACCEPT
        assert fresh._height == 2
        assert fresh._app_hash == app._app_hash
        assert fresh._db.get(b"k2") == b"v2" * 3000

    def test_corrupt_chunk_restarts_snapshot(self):
        app = KVStoreApplication(snapshot_interval=1)
        app.finalize_block(
            abci.RequestFinalizeBlock(height=1, txs=[b"k=" + b"v" * 9000])
        )
        app.commit()
        snap = app.list_snapshots(None).snapshots[0]
        chunks = [
            app.load_snapshot_chunk(
                abci.RequestLoadSnapshotChunk(height=1, format=1, chunk=i)
            ).chunk
            for i in range(snap.chunks)
        ]
        fresh = KVStoreApplication()
        fresh.offer_snapshot(
            abci.RequestOfferSnapshot(snapshot=snap, app_hash=app._app_hash)
        )
        bad = b"\x00" * len(chunks[0])
        fresh.apply_snapshot_chunk(abci.RequestApplySnapshotChunk(index=0, chunk=bad))
        for i, c in enumerate(chunks[1:], start=1):
            r = fresh.apply_snapshot_chunk(
                abci.RequestApplySnapshotChunk(index=i, chunk=c)
            )
        assert r.result == abci.APPLY_CHUNK_RETRY_SNAPSHOT
        # Retry with good chunks succeeds.
        for i, c in enumerate(chunks):
            r = fresh.apply_snapshot_chunk(
                abci.RequestApplySnapshotChunk(index=i, chunk=c)
            )
        assert r.result == abci.APPLY_CHUNK_ACCEPT
        assert fresh._height == 1


class TestStateSyncJoin:
    def test_fresh_node_joins_via_snapshot(self, one_priv):
        # A makes eight blocks a second and keeps the last hundred. B's first
        # window of blocks must not be what compiles the verify kernels: those
        # seconds (a minute, on a cold compile cache) are enough for A to prune
        # the blocks B is asking for, and B then never moves again.
        warm_verify()
        deadline = time.monotonic() + 60  # for the whole of the test
        net = MemoryNetwork()
        node_a, app_a = _mk_node(
            "nodeA", one_priv, net, index=0, snapshot_interval=SNAPSHOT_INTERVAL
        )
        node_a.start()
        node_b = None
        try:
            # A needs a snapshot at h with headers to h+2 available.
            assert wait_for(
                lambda: node_a.height >= SNAPSHOT_INTERVAL * 2 + 3, deadline=deadline
            ), f"A stuck at {node_a.height}"
            trust_hash = node_a.block_store.load_block_meta(1).header.hash()

            sync_cfg = StateSyncConfig(
                enabled=True,
                trust_height=1,
                trust_hash=trust_hash,
                discovery_time=0.5,
                backfill_blocks=2,
            )
            node_b, app_b = _mk_node(
                "nodeB",
                one_priv,
                net,
                statesync=sync_cfg,
                persistent_peers=[f"{node_a.node_key.node_id}@nodeA"],
            )
            node_b.start()

            assert wait_for(
                lambda: node_b.statesyncer is not None
                and node_b.sm_state.last_block_height >= SNAPSHOT_INTERVAL,
                deadline=deadline,
            ), "state sync never completed"
            snap_height = node_b.sm_state.last_block_height
            assert snap_height % SNAPSHOT_INTERVAL == 0

            # The distinguishing property: no full blocks below the
            # snapshot height were ever fetched.
            assert node_b.block_store.load_block(1) is None
            assert node_b.block_store.load_block(snap_height) is None

            # Backfill produced verified headers below the snapshot.
            assert sorted(node_b.statesyncer.backfilled) == [
                snap_height - 2,
                snap_height - 1,
            ]

            # The restored app reports the snapshot state.
            info = app_b.info(None)
            assert info.last_block_height >= snap_height

            # B block-syncs the gap and follows consensus past A's tip
            # at join time.
            target = node_a.height + 3
            assert wait_for(lambda: node_b.height >= target, deadline=deadline), (
                f"B stuck at {node_b.height}, target {target}, A at {node_a.height} "
                f"with its oldest block {node_a.block_store.base()}"
            )
            assert node_b.block_store.load_block(snap_height + 1) is not None
        finally:
            node_a.stop()
            if node_b is not None:
                node_b.stop()
