"""Full-node integration: multi-node networks over real transports.

The e2e analog (test/e2e) in-process: nodes with complete stacks —
encrypted TCP or memory transport, router, reactors, consensus, mempool
gossip — forming a network, committing blocks, syncing a late joiner.
"""

import time

import pytest

from tendermint_tpu.abci.client import LocalClient
from tendermint_tpu.abci.kvstore import KVStoreApplication
from tendermint_tpu.abci import types as abci
from tendermint_tpu.encoding.canonical import Timestamp
from tendermint_tpu.node import Node, NodeConfig
from tendermint_tpu.p2p.key import NodeKey
from tendermint_tpu.p2p.transport import MemoryNetwork
from tendermint_tpu.privval import FilePV
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.types.params import ConsensusParams, TimeoutParams
from tests.helpers import warm_verify

CHAIN = "node-chain"
BASE_NS = 1_700_000_000_000_000_000


def fast_genesis(privs):
    params = ConsensusParams()
    params.timeout = TimeoutParams(
        propose=0.6, propose_delta=0.2, vote=0.3, vote_delta=0.1, commit=0.1
    )
    return GenesisDoc(
        chain_id=CHAIN,
        genesis_time=Timestamp.from_unix_ns(BASE_NS),
        consensus_params=params,
        validators=[
            GenesisValidator(pub_key=pv.get_pub_key(), power=10) for pv in privs
        ],
    )


def make_node(tmp_path, name, privs, index=None, net=None, blocksync=True,
              persistent_peers=()):
    genesis = fast_genesis(privs)
    app = KVStoreApplication()
    cfg = NodeConfig(
        chain_id=CHAIN,
        listen_addr=name if net is not None else "127.0.0.1:0",
        blocksync=blocksync,
        wal_enabled=False,
        persistent_peers=list(persistent_peers),
        moniker=name,
    )
    node = Node(
        cfg,
        genesis,
        LocalClient(app),
        priv_validator=privs[index] if index is not None else None,
        memory_network=net,
    )
    return node, app


def wait_for(fn, timeout=60.0, interval=0.05, deadline=None):
    """Poll ``fn`` until it holds: for ``timeout`` seconds, or up to a
    ``deadline`` (``time.monotonic()``) that several waits of one test
    share, so that the test as a whole waits no longer than one of them."""
    if deadline is None:
        deadline = time.monotonic() + timeout
    while True:
        if fn():
            return True
        if time.monotonic() >= deadline:
            return False
        time.sleep(interval)


@pytest.fixture()
def four_privs(tmp_path):
    return [
        FilePV.generate(
            str(tmp_path / f"pk{i}.json"), str(tmp_path / f"ps{i}.json")
        )
        for i in range(4)
    ]


class TestMemoryNetworkCluster:
    def test_four_validators_commit_and_gossip_tx(self, tmp_path, four_privs):
        net = MemoryNetwork()
        nodes = []
        apps = []
        for i in range(4):
            node, app = make_node(tmp_path, f"node{i}", four_privs, index=i, net=net)
            nodes.append(node)
            apps.append(app)
        seed_addr = "node0"
        for i, node in enumerate(nodes):
            if i > 0:
                node.config.persistent_peers = [
                    f"{nodes[0].node_key.node_id}@{seed_addr}"
                ]
        for node in nodes:
            node.start()
        try:
            assert wait_for(
                lambda: all(len(n.router.connected_peers()) >= 1 for n in nodes),
                timeout=10,
            ), "peers failed to connect"
            assert wait_for(
                lambda: all(n.height >= 2 for n in nodes), timeout=90
            ), f"heights: {[n.height for n in nodes]}"
            # Submit a tx at node 3; it must gossip to the proposer and commit.
            nodes[3].submit_tx(b"color=indigo")
            assert wait_for(
                lambda: all(
                    a.query(abci.RequestQuery(data=b"color")).value == b"indigo"
                    for a in apps
                ),
                timeout=90,
            ), "tx failed to commit on all nodes"
            # PEX propagated addresses: later nodes know more than the seed.
            assert wait_for(
                lambda: len(nodes[3].peer_manager.connected_peers()) >= 2,
                timeout=30,
            ), "pex failed to spread addresses"
        finally:
            for node in nodes:
                node.stop()

    def test_late_joiner_blocksyncs(self, tmp_path, four_privs):
        net = MemoryNetwork()
        nodes = []
        for i in range(3):
            node, _ = make_node(tmp_path, f"v{i}", four_privs, index=i, net=net)
            if i > 0:
                node.config.persistent_peers = []
            nodes.append(node)
        for i, node in enumerate(nodes):
            if i > 0:
                node.config.persistent_peers = [
                    f"{nodes[0].node_key.node_id}@v0"
                ]
            node.start()
        warm_verify()  # the observer's first window of blocks compiles nothing on the test's clock
        deadline = time.monotonic() + 90  # for the whole of the test
        try:
            assert wait_for(lambda: all(n.height >= 3 for n in nodes), deadline=deadline), (
                f"validators at {[n.height for n in nodes]} after 90 s"
            )
            # A non-validator observer joins late and blocksyncs.
            observer, obs_app = make_node(
                tmp_path, "observer", four_privs, index=None, net=net,
                persistent_peers=[f"{nodes[0].node_key.node_id}@v0"],
            )
            observer.start()
            target = max(n.height for n in nodes)
            assert wait_for(lambda: observer.height >= target, deadline=deadline), (
                f"observer at {observer.height}, target {target}, "
                f"validators at {[n.height for n in nodes]}"
            )
            observer.stop()
        finally:
            for node in nodes:
                node.stop()


class TestTCPCluster:
    def test_two_validators_over_tcp(self, tmp_path):
        privs = [
            FilePV.generate(
                str(tmp_path / f"k{i}.json"), str(tmp_path / f"s{i}.json")
            )
            for i in range(2)
        ]
        node0, app0 = make_node(tmp_path, "tcp0", privs, index=0)
        node0.start()
        addr = node0.node_info.listen_addr
        node1, app1 = make_node(
            tmp_path, "tcp1", privs, index=1,
            persistent_peers=[f"{node0.node_key.node_id}@{addr}"],
        )
        node1.start()
        try:
            assert wait_for(
                lambda: node0.height >= 2 and node1.height >= 2, timeout=90
            ), f"heights: {node0.height}, {node1.height}"
            node1.submit_tx(b"transport=tcp")
            assert wait_for(
                lambda: app0.query(abci.RequestQuery(data=b"transport")).value
                == b"tcp",
                timeout=90,
            )
        finally:
            node1.stop()
            node0.stop()
