"""Selector event-loop regressions (PR 9 tentpole, libs/evloop.py):
write backpressure against slow readers, mid-frame disconnects, the
connection gauge, and the 1k-connection soak proving thread count does
not scale with connections."""

import json
import socket
import threading
import time

import pytest

from tendermint_tpu.libs.evloop import EvloopServer
from tendermint_tpu.libs.grpc import PREFACE, GrpcChannel, GrpcServer
from tendermint_tpu.libs.metrics import EvloopMetrics, Registry
from tendermint_tpu.rpc.server import RPCServer

BLAST = bytes(range(256)) * 16384  # 4 MiB echo payload


class BlastProto:
    """Writes a 4 MiB payload for every byte received — the worst case
    for a slow reader: the outbuf must absorb it, pause reads past the
    high-water mark, and drain as the client catches up."""

    def __init__(self, transport):
        self.transport = transport

    def data_received(self, data):
        for _ in data:
            self.transport.write(BLAST)

    def eof_received(self):
        self.transport.close()

    def connection_lost(self, exc):
        pass


def start_evloop(proto_factory, **kw):
    lsock = socket.socket()
    lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(128)
    srv = EvloopServer(proto_factory, listener_ref=lambda: lsock, **kw)
    srv.start()
    return srv, lsock


def stop_evloop(srv, lsock):
    srv.stop()
    lsock.close()


class TestBackpressure:
    def test_slow_reader_gets_every_byte(self):
        transports = []

        def factory(t):
            transports.append(t)
            return BlastProto(t)

        srv, lsock = start_evloop(
            factory, name="blast", high_water=64 * 1024,
            low_water=16 * 1024,
        )
        try:
            with socket.create_connection(lsock.getsockname()) as c:
                c.sendall(b"x")
                time.sleep(0.2)  # let the outbuf climb past high water
                assert transports and transports[0].buffered() > 0
                got = bytearray()
                while len(got) < len(BLAST):
                    chunk = c.recv(65536)
                    assert chunk, "server dropped a backpressured conn"
                    got += chunk
                assert bytes(got) == BLAST
                # Reads resumed after the drain: a second request works.
                c.sendall(b"y")
                got = bytearray()
                while len(got) < len(BLAST):
                    chunk = c.recv(65536)
                    assert chunk
                    got += chunk
                assert bytes(got) == BLAST
        finally:
            stop_evloop(srv, lsock)

    def test_connection_gauge_tracks_sockets(self):
        reg = Registry()
        srv, lsock = start_evloop(
            BlastProto, name="gauged", metrics=EvloopMetrics(reg)
        )
        try:
            conns = [
                socket.create_connection(lsock.getsockname())
                for _ in range(3)
            ]
            deadline = time.monotonic() + 5
            while srv.connection_count() < 3:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            assert 'connections{server="gauged"} 3' in reg.expose()
            for c in conns:
                c.close()
            while srv.connection_count() > 0:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            assert 'connections{server="gauged"} 0' in reg.expose()
        finally:
            stop_evloop(srv, lsock)

    def test_the_gauge_never_lags_the_count_a_reader_sees(self, monkeypatch):
        """Open a connection, wait for ``connection_count()`` to say so,
        read the gauge at once; close it, the same; 200 times. The gauge
        is made as slow as a loaded host makes it (2 ms a ``set``), which
        is the window a count changed before its gauge leaves open."""
        from tendermint_tpu.libs import metrics

        prompt_set = metrics._BoundGauge.set

        def slow_set(self, v):
            time.sleep(0.002)
            prompt_set(self, v)

        monkeypatch.setattr(metrics._BoundGauge, "set", slow_set)
        reg = Registry()
        srv, lsock = start_evloop(BlastProto, name="prompt", metrics=EvloopMetrics(reg))

        def gauge_once_count_is(n):
            deadline = time.monotonic() + 5
            while srv.connection_count() != n:
                assert time.monotonic() < deadline, "the count never reached %d" % n
            return reg.expose()

        try:
            for turn in range(200):
                conn = socket.create_connection(lsock.getsockname())
                assert 'connections{server="prompt"} 1' in gauge_once_count_is(1), turn
                conn.close()
                assert 'connections{server="prompt"} 0' in gauge_once_count_is(0), turn
        finally:
            stop_evloop(srv, lsock)


class TestMidFrameDisconnect:
    def test_grpc_survives_torn_frames(self):
        srv = GrpcServer({"/echo.Echo/Ping": lambda b: b}, evloop=True)
        srv.start()
        try:
            host, port = srv.address
            # A client that dies mid-frame (preface + torn frame header)
            # must not wedge the loop or poison later connections.
            for torn in (b"", PREFACE[:7], PREFACE + b"\x00\x00"):
                with socket.create_connection((host, port)) as c:
                    c.sendall(torn)
            time.sleep(0.05)
            ch = GrpcChannel(host, port)
            try:
                assert ch.unary("/echo.Echo/Ping", b"hi") == b"hi"
            finally:
                ch.close()
        finally:
            srv.stop()

    def test_rpc_survives_torn_requests(self):
        srv = RPCServer({"echo": lambda **kw: kw}, evloop=True)
        srv.start()
        try:
            host, port = srv.address
            for torn in (b"", b"POST / HT", b"POST / HTTP/1.1\r\nContent"):
                with socket.create_connection((host, port)) as c:
                    if torn:
                        c.sendall(torn)
            body = json.dumps(
                {"jsonrpc": "2.0", "id": 1, "method": "echo",
                 "params": {"a": 1}}
            ).encode()
            with socket.create_connection((host, port)) as c:
                c.sendall(
                    b"POST / HTTP/1.1\r\nContent-Type: application/json\r\n"
                    b"Content-Length: %d\r\n\r\n%s" % (len(body), body)
                )
                resp = c.recv(65536)
            assert b'"a": 1' in resp
        finally:
            srv.stop()


@pytest.mark.slow
class TestSoak:
    def test_1k_connections_flat_thread_count(self):
        """Acceptance pin: 1k+ concurrent connections multiplex onto the
        loop + bounded pool; OS threads must NOT grow with connections
        (the threaded fallback would add one thread per socket)."""
        srv = RPCServer({"echo": lambda **kw: kw}, evloop=True)
        srv.start()
        conns = []
        try:
            host, port = srv.address
            before = threading.active_count()
            for _ in range(1000):
                c = socket.create_connection((host, port))
                conns.append(c)
            deadline = time.monotonic() + 30
            while srv._ev.connection_count() < 1000:
                assert time.monotonic() < deadline, (
                    "accepted %d" % srv._ev.connection_count()
                )
                time.sleep(0.05)
            grown = threading.active_count() - before
            # Loop thread + bounded worker pool; nothing per-connection.
            assert grown <= 24, "thread count grew to +%d" % grown
            # The tier still serves real requests under the idle herd.
            body = json.dumps(
                {"jsonrpc": "2.0", "id": 1, "method": "echo",
                 "params": {"n": 7}}
            ).encode()
            req = (
                b"POST / HTTP/1.1\r\nContent-Type: application/json\r\n"
                b"Content-Length: %d\r\n\r\n%s" % (len(body), body)
            )
            for c in conns[::100]:
                c.sendall(req)
                assert b'"n": 7' in c.recv(65536)
        finally:
            for c in conns:
                try:
                    c.close()
                except OSError:
                    pass
            srv.stop()
