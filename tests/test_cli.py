"""CLI + TOML config tests (cmd/tendermint + config/toml.go analogs).

The flagship case mirrors the reference testnet flow: generate 4 home
dirs with `testnet`, start 4 separate OS processes with `start`, and
watch every node commit blocks over real TCP with filedb persistence.
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

from tendermint_tpu.config import Config
from tendermint_tpu.cli import main as cli_main
from tests.helpers import free_port_block

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rpc_height(port: int) -> int:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/status", timeout=2
    ) as resp:
        doc = json.load(resp)
    return int(doc["result"]["sync_info"]["latest_block_height"])


def _run(args) -> int:
    return cli_main(args)


class TestConfigToml:
    def test_roundtrip(self, tmp_path):
        cfg = Config(home=str(tmp_path))
        cfg.base.moniker = "alpha"
        cfg.base.proxy_app = "persistent_kvstore"
        cfg.p2p.laddr = "127.0.0.1:11111"
        cfg.p2p.persistent_peers = ["aa@1.2.3.4:5", "bb@6.7.8.9:10"]
        cfg.rpc.laddr = "127.0.0.1:22222"
        cfg.mempool.size = 77
        cfg.statesync.enabled = True
        cfg.statesync.trust_height = 42
        cfg.statesync.trust_hash = b"\xab\xcd"
        cfg.privval.laddr = "tcp://127.0.0.1:33333"
        cfg.save()

        loaded = Config.load(str(tmp_path))
        assert loaded.base.moniker == "alpha"
        assert loaded.base.proxy_app == "persistent_kvstore"
        assert loaded.p2p.persistent_peers == cfg.p2p.persistent_peers
        assert loaded.mempool.size == 77
        assert loaded.statesync.enabled is True
        assert loaded.statesync.trust_height == 42
        assert loaded.statesync.trust_hash == b"\xab\xcd"
        assert loaded.privval.laddr == "tcp://127.0.0.1:33333"

    def test_to_node_config(self, tmp_path):
        cfg = Config(home=str(tmp_path))
        cfg.statesync.enabled = False
        nc = cfg.to_node_config(chain_id="x")
        assert nc.chain_id == "x"
        assert nc.statesync is None  # disabled -> not wired
        cfg.statesync.enabled = True
        assert cfg.to_node_config().statesync is cfg.statesync

    def test_unknown_keys_ignored(self, tmp_path):
        text = '[base]\nmoniker = "m"\nfuture_knob = 3\n[bogus]\nx = 1\n'
        cfg = Config.from_toml(text)
        assert cfg.base.moniker == "m"


class TestInitAndKeys:
    def test_init_creates_layout(self, tmp_path):
        home = str(tmp_path / "h")
        assert _run(["--home", home, "init", "--chain-id", "c1"]) == 0
        cfg = Config(home=home)
        for path in (
            cfg.config_file(),
            cfg.genesis_file(),
            cfg.node_key_file(),
            cfg.privval_key_file(),
        ):
            assert os.path.exists(path), path
        # refuses to clobber without --force
        assert _run(["--home", home, "init"]) == 1
        assert _run(["--home", home, "init", "--force"]) == 0

    def test_show_commands(self, tmp_path, capsys):
        home = str(tmp_path / "h")
        _run(["--home", home, "init"])
        capsys.readouterr()  # drain init output
        assert _run(["--home", home, "show-node-id"]) == 0
        node_id = capsys.readouterr().out.strip()
        assert len(node_id) == 40
        assert _run(["--home", home, "show-validator"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["type"] == "ed25519" and doc["value"]

    def test_unsafe_reset_keeps_keys(self, tmp_path):
        home = str(tmp_path / "h")
        _run(["--home", home, "init"])
        cfg = Config(home=home)
        key_before = open(cfg.privval_key_file()).read()
        marker = os.path.join(cfg.data_dir(), "junk.db")
        open(marker, "w").write("x")
        assert _run(["--home", home, "unsafe-reset-all"]) == 0
        assert not os.path.exists(marker)
        assert open(cfg.privval_key_file()).read() == key_before

    def test_start_without_init_errors(self, tmp_path):
        assert _run(["--home", str(tmp_path / "nope"), "start"]) == 1


def _fast_genesis_overwrite(home: str) -> None:
    """Shrink consensus timeouts for test speed (operators tune these via
    genesis consensus_params; tests are just an aggressive operator)."""
    from tendermint_tpu.types.genesis import GenesisDoc
    from tendermint_tpu.types.params import TimeoutParams

    cfg = Config(home=home)
    doc = GenesisDoc.from_file(cfg.genesis_file())
    doc.consensus_params.timeout = TimeoutParams(
        propose=0.6, propose_delta=0.2, vote=0.3, vote_delta=0.1, commit=0.1
    )
    doc.save_as(cfg.genesis_file())


class TestNodeLifecycle:
    def _spawn(self, home: str):
        """``start`` in a child that writes to ``<home>/start.log``: a
        pipe that nothing reads holds 64 KiB, and a node that has said
        more than that stops inside ``write()`` for good."""
        with open(os.path.join(home, "start.log"), "wb") as log:
            return subprocess.Popen(
                [sys.executable, "-m", "tendermint_tpu", "--home", home, "start"],
                cwd=REPO,
                stdout=log,
                stderr=subprocess.STDOUT,
            )

    @staticmethod
    def _said(homes) -> str:
        """The end of what each node wrote, for a failure's message."""
        out = []
        for home in homes:
            with open(os.path.join(home, "start.log"), "rb") as log:
                out.append("--- %s\n%s" % (home, log.read()[-1500:].decode(errors="replace")))
        return "\n".join(out)

    def _wait_heights(self, ports, target: int, timeout: float) -> list:
        """The height each RPC port reports, polled until all are at
        ``target`` or one deadline for all of them has passed (-1: never
        answered)."""
        deadline = time.monotonic() + timeout
        heights = [-1] * len(ports)
        while True:
            for at, port in enumerate(ports):
                if heights[at] < target:
                    try:
                        heights[at] = _rpc_height(port)
                    except Exception:
                        pass
            if min(heights) >= target or time.monotonic() >= deadline:
                return heights
            time.sleep(0.5)

    def _wait_height(self, port: int, target: int, timeout: float) -> int:
        return self._wait_heights([port], target, timeout)[0]

    def test_single_node_commits_and_persists(self, tmp_path):
        home = str(tmp_path / "n0")
        _run(["--home", home, "init", "--chain-id", "cli-one"])
        _fast_genesis_overwrite(home)
        port = free_port_block(2)
        cfg = Config.load(home)
        cfg.p2p.laddr = f"127.0.0.1:{port}"
        cfg.rpc.laddr = f"127.0.0.1:{port + 1}"
        cfg.save()

        proc = self._spawn(home)
        try:
            height = self._wait_height(port + 1, 3, timeout=60)
            assert height >= 3, f"node never reached height 3 (got {height})"
        finally:
            proc.send_signal(signal.SIGTERM)
            proc.wait(timeout=15)

        # stores survived shutdown: inspect sees the committed chain
        out = subprocess.run(
            [sys.executable, "-m", "tendermint_tpu", "--home", home, "inspect"],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=30,
        )
        doc = json.loads(out.stdout)
        assert doc["latest_block_height"] >= 3
        assert doc["chain_id"] == "cli-one"

    def test_four_process_testnet_commits(self, tmp_path):
        """VERDICT round-2 item 10 'Done =': a 4-process localhost testnet
        starts from generated configs and commits blocks."""
        out_dir = str(tmp_path / "tn")
        base = free_port_block(8)
        assert (
            _run(
                [
                    "testnet",
                    "-v",
                    "4",
                    "-o",
                    out_dir,
                    "--chain-id",
                    "cli-tn",
                    "--starting-port",
                    str(base),
                ]
            )
            == 0
        )
        homes = [os.path.join(out_dir, f"node{i}") for i in range(4)]
        for home in homes:
            _fast_genesis_overwrite(home)
        procs = [self._spawn(h) for h in homes]
        try:
            heights = self._wait_heights(
                [base + 2 * i + 1 for i in range(4)], 2, timeout=90
            )
            assert all(h >= 2 for h in heights), (
                f"heights after 90 s: {heights}\n{self._said(homes)}"
            )
        finally:
            for p in procs:
                p.send_signal(signal.SIGTERM)
            for p in procs:
                try:
                    p.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    p.kill()


class TestRollback:
    def test_rollback_then_restart(self, tmp_path):
        home = str(tmp_path / "n0")
        _run(["--home", home, "init", "--chain-id", "rb"])
        _fast_genesis_overwrite(home)
        port = free_port_block(2)
        cfg = Config.load(home)
        cfg.p2p.laddr = f"127.0.0.1:{port}"
        cfg.rpc.laddr = f"127.0.0.1:{port + 1}"
        cfg.save()
        proc = subprocess.Popen(
            [sys.executable, "-m", "tendermint_tpu", "--home", home, "start"],
            cwd=REPO,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        try:
            deadline = time.monotonic() + 60
            height = -1
            while time.monotonic() < deadline and height < 3:
                try:
                    height = _rpc_height(port + 1)
                except Exception:
                    pass
                time.sleep(0.5)
            assert height >= 3
        finally:
            proc.send_signal(signal.SIGTERM)
            proc.wait(timeout=15)

        assert _run(["--home", home, "rollback"]) == 0
        # replay pushes the stored blocks back into a fresh app
        assert _run(["--home", home, "replay"]) == 0


class TestDebugTools:
    def test_wal2json(self, tmp_path, capsys):
        from tendermint_tpu.consensus.wal import WAL, EndHeightMessage, TimeoutInfo

        path = str(tmp_path / "cs.wal")
        w = WAL(path)
        w.start()
        w.write(TimeoutInfo(0.5, 3, 1, 2))
        w.write_sync(EndHeightMessage(3))
        w.stop()
        assert _run(["wal2json", path]) == 0
        lines = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
        assert [d["type"] for d in lines] == ["TimeoutInfo", "EndHeightMessage"]
        assert lines[0]["height"] == 3 and lines[0]["round"] == 1
        assert lines[1]["height"] == 3

    def test_abci_cli_against_socket_app(self, capsys):
        import subprocess
        import socket as socketlib
        import time as timelib

        s = socketlib.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        proc = subprocess.Popen(
            [
                sys.executable,
                "-m",
                "tendermint_tpu.abci.socket_server",
                "--addr",
                f"127.0.0.1:{port}",
            ],
            cwd=REPO,
        )
        try:
            deadline = timelib.monotonic() + 15
            while timelib.monotonic() < deadline:
                try:
                    probe = socketlib.create_connection(("127.0.0.1", port), 1)
                    probe.close()
                    break
                except OSError:
                    timelib.sleep(0.2)
            else:
                pytest.fail("socket app never came up")
            addr = f"tcp://127.0.0.1:{port}"
            assert _run(["abci", "echo", "ping!", "--addr", addr]) == 0
            assert capsys.readouterr().out.strip() == "ping!"
            assert _run(["abci", "info", "--addr", addr]) == 0
            doc = json.loads(capsys.readouterr().out)
            assert doc["last_block_height"] == 0
            assert _run(["abci", "check-tx", "a=b", "--addr", addr]) == 0
            assert json.loads(capsys.readouterr().out)["code"] == 0
            assert _run(["abci", "query", "a", "--addr", addr]) == 0
            assert "log" in json.loads(capsys.readouterr().out)
        finally:
            proc.terminate()
            proc.wait(timeout=10)

    def test_compact_db(self, tmp_path):
        from tendermint_tpu.storage import open_db

        home = str(tmp_path / "h")
        data = os.path.join(home, "data")
        os.makedirs(data)
        os.makedirs(os.path.join(home, "config"))
        db = open_db("filedb", data, "bloat")
        for _ in range(300):
            db.set(b"k", b"v" * 100)  # 299 dead versions
        db.set(b"other", b"live")
        db.close()
        before = os.path.getsize(os.path.join(data, "bloat.fdb"))
        assert _run(["--home", home, "compact-db"]) == 0
        after = os.path.getsize(os.path.join(data, "bloat.fdb"))
        assert after < before / 10
        db = open_db("filedb", data, "bloat")
        assert db.get(b"k") == b"v" * 100
        assert db.get(b"other") == b"live"
        db.close()

    def test_inspect_serve(self, tmp_path):
        """inspect --serve: read-only RPC over a stopped node's stores
        (internal/inspect/inspect.go:31)."""
        home = str(tmp_path / "h")
        _run(["--home", home, "init", "--chain-id", "ins"])
        _fast_genesis_overwrite(home)
        port = free_port_block(2)
        cfg = Config.load(home)
        cfg.p2p.laddr = f"127.0.0.1:{port}"
        cfg.rpc.laddr = f"127.0.0.1:{port + 1}"
        cfg.save()
        proc = subprocess.Popen(
            [sys.executable, "-m", "tendermint_tpu", "--home", home, "start"],
            cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        try:
            deadline = time.monotonic() + 60
            h = -1
            while time.monotonic() < deadline and h < 3:
                try:
                    h = _rpc_height(port + 1)
                except Exception:
                    pass
                time.sleep(0.5)
            assert h >= 3
        finally:
            proc.send_signal(signal.SIGTERM)
            proc.wait(timeout=15)
        # node stopped: serve the stores read-only
        iport = free_port_block(2)
        srv = subprocess.Popen(
            [sys.executable, "-m", "tendermint_tpu", "--home", home,
             "inspect", "--serve", f"127.0.0.1:{iport}"],
            cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        try:
            deadline = time.monotonic() + 30
            doc = None
            while time.monotonic() < deadline:
                try:
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{iport}/block?height=2", timeout=2
                    ) as resp:
                        doc = json.load(resp)
                    break
                except Exception:
                    time.sleep(0.5)
            assert doc and int(doc["result"]["block"]["header"]["height"]) == 2
            with urllib.request.urlopen(
                f"http://127.0.0.1:{iport}/validators?height=2", timeout=5
            ) as resp:
                vdoc = json.load(resp)
            assert vdoc["result"]["count"] == "1"
        finally:
            srv.send_signal(signal.SIGTERM)
            srv.wait(timeout=10)

    def test_reindex_event_rebuilds_lost_index(self, tmp_path):
        """commands/reindex_event.go: wipe the tx index of a stopped
        node, rebuild it from stored blocks + persisted FinalizeBlock
        responses, and find a committed tx again."""
        import base64
        import hashlib

        home = str(tmp_path / "h")
        _run(["--home", home, "init", "--chain-id", "reidx"])
        _fast_genesis_overwrite(home)
        port = free_port_block(2)
        cfg = Config.load(home)
        cfg.p2p.laddr = f"127.0.0.1:{port}"
        cfg.rpc.laddr = f"127.0.0.1:{port + 1}"
        cfg.save()
        proc = subprocess.Popen(
            [sys.executable, "-m", "tendermint_tpu", "--home", home, "start"],
            cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        tx = b"reidx=1"
        try:
            deadline = time.monotonic() + 60
            up = False
            while time.monotonic() < deadline and not up:
                try:
                    _rpc_height(port + 1)
                    up = True
                except Exception:
                    time.sleep(0.5)
            assert up
            body = json.dumps(
                {
                    "jsonrpc": "2.0", "id": 1, "method": "broadcast_tx_sync",
                    "params": {"tx": base64.b64encode(tx).decode()},
                }
            ).encode()
            urllib.request.urlopen(
                urllib.request.Request(
                    f"http://127.0.0.1:{port + 1}", body,
                    {"Content-Type": "application/json"},
                ),
                timeout=10,
            )
            h = hashlib.sha256(tx).hexdigest()
            committed = False
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and not committed:
                q = json.dumps(
                    {"jsonrpc": "2.0", "id": 2, "method": "tx",
                     "params": {"hash": "0x" + h}}
                ).encode()
                try:
                    with urllib.request.urlopen(
                        urllib.request.Request(
                            f"http://127.0.0.1:{port + 1}", q,
                            {"Content-Type": "application/json"},
                        ),
                        timeout=3,
                    ) as resp:
                        committed = "result" in json.load(resp)
                except Exception:
                    pass
                if not committed:
                    time.sleep(0.5)
            assert committed, "tx never committed"
        finally:
            proc.send_signal(signal.SIGTERM)
            proc.wait(timeout=15)

        # lose the index, rebuild, and find the tx offline
        for f in os.listdir(os.path.join(home, "data")):
            if f.startswith("tx_index"):
                os.unlink(os.path.join(home, "data", f))
        assert _run(["--home", home, "reindex-event"]) == 0
        from tendermint_tpu.indexer import KVIndexer
        from tendermint_tpu.storage import open_db

        idx = KVIndexer(open_db("filedb", os.path.join(home, "data"), "tx_index"))
        tr = idx.get_tx(hashlib.sha256(tx).digest())
        assert tr is not None and tr.tx == tx

    def test_confix_migrates_schema(self, tmp_path, capsys):
        home = str(tmp_path / "h")
        _run(["--home", home, "init", "--chain-id", "cfx"])
        capsys.readouterr()
        path = Config(home=home).config_file()
        text = open(path).read()
        text = text.replace('log_level = "info"\n', "")  # missing new key
        text = text.replace(
            "[p2p]", "obsolete_flag = true\n\n[p2p]", 1
        )  # dead key
        open(path, "w").write(text)
        assert _run(["--home", home, "confix", "--dry-run"]) == 0
        out = capsys.readouterr().out
        assert "obsolete_flag" in out and "log_level" in out
        assert 'log_level = "info"' not in open(path).read()  # not rewritten
        assert _run(["--home", home, "confix"]) == 0
        capsys.readouterr()
        migrated = open(path).read()
        assert 'log_level = "info"' in migrated
        assert "obsolete_flag" not in migrated
        assert os.path.exists(path + ".bak")
        # idempotent
        assert _run(["--home", home, "confix"]) == 0
        assert "already matches" in capsys.readouterr().out
        # node still starts from the migrated config
        loaded = Config.load(home)
        assert loaded.base.log_level == "info"


def test_key_migrate_roundtrip(tmp_path, capsys):
    """key-migrate re-encodes every store into a fresh backend dir and
    the migrated stores contain identical data (scripts/keymigrate
    analog over this tree's backend seam)."""
    from tendermint_tpu.cli import main
    from tendermint_tpu.storage import open_db

    home = str(tmp_path / "mig")
    assert main(["--home", home, "init", "--chain-id", "mig-chain"]) == 0
    # put some data in a store the migrated dir must reproduce
    data_dir = os.path.join(home, "data")
    db = open_db("filedb", data_dir, "state")
    for i in range(100):
        db.set(b"k%03d" % i, b"v%d" % i)
    db.close()

    assert main(["--home", home, "key-migrate", "--to-backend", "filedb-py"]) == 0
    out_dir = data_dir + "-migrated"
    assert os.path.isdir(out_dir)
    src = open_db("filedb", data_dir, "state")
    dst = open_db("filedb-py", out_dir, "state")
    src_kv = list(src.iterator())
    dst_kv = list(dst.iterator())
    assert src_kv == dst_kv and len(dst_kv) >= 100
    src.close()
    dst.close()
