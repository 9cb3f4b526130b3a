"""Fleet-scope trace propagation (ISSUE 15): the compact TraceContext
must survive every hop — TCP proto3 field 7, the shm slab header, the
JSON-RPC ``trace`` member — and the resulting per-process exports must
fuse into one causally-linked timeline via scripts/trace_merge.py.

The two-process classes at the bottom are the acceptance tests: a real
verifyd in a separate interpreter (own tracer, own perf-counter epoch)
serves a client in this process over TCP and over the shm slab ring;
each side exports its own ring, trace_merge fuses them, and the client's
``verifyd_call`` span must be an ancestor of the server's
``scheduler_dispatch`` span while the response's stage vector explains
>=90% of the client-observed wall time.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from scripts import trace_merge
from tendermint_tpu.crypto.scheduler import VerifyScheduler
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.tracing import TraceContext
from tendermint_tpu.verifyd import protocol, shm
from tendermint_tpu.verifyd.client import VerifydClient
from tendermint_tpu.verifyd.server import VerifydServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CTX = TraceContext("11aa22bb33cc44dd", "0102030405060708", 1)


def noop_verify(pks, msgs, sigs):
    return [True] * len(pks)


def junk_lanes(n, seed=0):
    return (
        [bytes([seed % 251 + 1]) * 32] * n,
        [b"trace-%d-%d" % (seed, i) for i in range(n)],
        [b"\x09" * 64] * n,
    )


@pytest.fixture
def ring_tracer():
    prev = tracing.tracer.mode
    tracing.configure(tracing.RING)
    tracing.tracer.clear()
    yield tracing.tracer
    tracing.configure(prev)
    tracing.tracer.clear()


def start_server(**kw):
    kw.setdefault("verify_fn", noop_verify)
    kw.setdefault("max_batch", 64)
    kw.setdefault("max_delay", 0.001)
    srv = VerifydServer(**kw)
    srv.start()
    return srv


# --- context codec -----------------------------------------------------------


class TestTraceContext:
    def test_bytes_round_trip(self):
        assert len(CTX.to_bytes()) == tracing.CTX_WIRE_LEN
        assert TraceContext.from_bytes(CTX.to_bytes()) == CTX

    def test_zero_trace_id_is_absent(self):
        assert TraceContext.from_bytes(b"\x00" * tracing.CTX_WIRE_LEN) is None

    def test_wrong_length_is_absent(self):
        assert TraceContext.from_bytes(b"\x01" * 5) is None
        assert TraceContext.from_bytes(b"") is None

    def test_header_round_trip(self):
        assert TraceContext.from_header(CTX.to_header()) == CTX

    def test_bad_headers_rejected(self):
        for bad in (None, 7, "", "xx-yy-zz", "11aa22bb33cc44dd-short-01"):
            assert TraceContext.from_header(bad) is None


# --- TCP wire format ---------------------------------------------------------


class TestWireFormat:
    def test_request_trace_round_trips(self):
        pks, msgs, sigs = junk_lanes(2)
        req = protocol.VerifyRequest(
            pks=pks, msgs=msgs, sigs=sigs, trace=CTX.to_bytes()
        )
        out = protocol.decode_request(protocol.encode_request(req))
        assert out.trace == CTX.to_bytes()
        assert TraceContext.from_bytes(out.trace) == CTX

    def test_old_frame_without_trace_is_byte_identical(self):
        # proto3 zero-omission: a pre-trace frame (no field 7) must
        # decode and re-encode to the identical bytes — trace is a pure
        # extension, not a format break
        pks, msgs, sigs = junk_lanes(3)
        req = protocol.VerifyRequest(pks=pks, msgs=msgs, sigs=sigs)
        wire = protocol.encode_request(req)
        out = protocol.decode_request(wire)
        assert out.trace == b""
        assert protocol.encode_request(out) == wire

    def test_encoded_request_size_counts_trace(self):
        pks, msgs, sigs = junk_lanes(2)
        for trace in (b"", CTX.to_bytes()):
            req = protocol.VerifyRequest(
                pks=pks, msgs=msgs, sigs=sigs, trace=trace
            )
            assert protocol.encoded_request_size(req) == len(
                protocol.encode_request(req)
            )

    def test_response_stages_round_trip(self):
        stages = {
            "wire_wait": 0.001,
            "admission": 0.002,
            "batch_residency": 0.003,
            "device": 0.25,
            "collect": 0.004,
        }
        resp = protocol.VerifyResponse(
            verdicts=[True], stages=protocol.pack_stages(stages)
        )
        out = protocol.decode_response(protocol.encode_response(resp))
        unpacked = protocol.unpack_stages(out.stages)
        assert set(unpacked) == set(protocol.STAGE_NAMES)
        for k, v in stages.items():
            assert unpacked[k] == pytest.approx(v, rel=1e-5)

    def test_old_response_without_stages_is_byte_identical(self):
        resp = protocol.VerifyResponse(verdicts=[True, False], queue_depth=3)
        wire = protocol.encode_response(resp)
        out = protocol.decode_response(wire)
        assert out.stages == b""
        assert protocol.encode_response(out) == wire

    def test_unpack_garbage_stages_is_empty(self):
        assert protocol.unpack_stages(b"") == {}
        assert protocol.unpack_stages(b"\x01\x02") == {}


# --- shm slab header ---------------------------------------------------------


class TestSlabTraceWords:
    def _hdr(self, trace=b""):
        buf = bytearray(shm.SLAB_HEADER_BYTES + 4096)
        shm.pack_header(
            buf, 0, gen=2, kind=protocol.KIND_RAW,
            klass=protocol.CLASS_RPC, deadline_ms=0,
            algo=protocol.ALGO_ED25519, lanes=2, trace=trace,
        )
        return shm.unpack_header(buf, 0)

    def test_trace_round_trips_through_slab(self):
        hdr = self._hdr(CTX.to_bytes())
        assert hdr["trace"] == CTX.to_bytes()
        assert TraceContext.from_bytes(hdr["trace"]) == CTX

    def test_absent_trace_is_empty(self):
        assert self._hdr(b"")["trace"] == b""

    def test_slab_reuse_zeroes_stale_trace(self):
        # the trace field is written unconditionally because slabs are
        # reused: a traced request followed by an untraced one on the
        # same slab must not leak the old context
        buf = bytearray(shm.SLAB_HEADER_BYTES + 4096)
        for gen, trace in ((2, CTX.to_bytes()), (4, b"")):
            shm.pack_header(
                buf, 0, gen=gen, kind=protocol.KIND_RAW,
                klass=protocol.CLASS_RPC, deadline_ms=0,
                algo=protocol.ALGO_ED25519, lanes=1, trace=trace,
            )
        assert shm.unpack_header(buf, 0)["trace"] == b""


# --- scheduler linkage -------------------------------------------------------


class TestSchedulerLinkage:
    def _signed(self, i):
        pks, msgs, sigs = junk_lanes(1, seed=i)
        return pks[0], msgs[0], sigs[0]

    def test_submit_captures_current_context(self, ring_tracer):
        s = VerifyScheduler(noop_verify, max_batch=8, max_delay=0.01)
        s.start()
        try:
            with tracing.span("caller") as sp:
                assert s.verify(*self._signed(1))
                caller_sid = sp.span_id
                caller_tid = sp.trace_id
            doc = ring_tracer.export()
            dispatches = trace_merge.spans_named(doc, "scheduler_dispatch")
            assert dispatches, doc
            assert dispatches[-1]["trace_id"] == caller_tid
            assert dispatches[-1]["parent_span_id"] == caller_sid
        finally:
            s.stop()

    def test_submit_many_group_rides_one_context(self, ring_tracer):
        s = VerifyScheduler(noop_verify, max_batch=16, max_delay=0.01)
        s.start()
        try:
            with tracing.span("group_caller") as sp:
                handles = s.submit_many(
                    [self._signed(i) for i in range(5)]
                )
                group_tid = sp.trace_id
            assert all(s.wait(h) for h in handles)
            doc = ring_tracer.export()
            dispatches = trace_merge.spans_named(doc, "scheduler_dispatch")
            assert dispatches[-1]["trace_id"] == group_tid
        finally:
            s.stop()

    def test_coalesced_duplicate_still_links_its_trace(self, ring_tracer):
        """Two waiters submit the IDENTICAL lane under different traces:
        the lane coalesces to one verifier slot, the dispatch span links
        under the first context, and the second context must still reach
        the dispatch span through a sched_trace_link instant (the merged
        timeline reaches it as an extra parent edge)."""
        s = VerifyScheduler(noop_verify, max_batch=64, max_delay=60.0)
        s.start()
        try:
            lane = self._signed(1)
            ctxs = []
            handles = []
            for name in ("waiter_a", "waiter_b"):
                with tracing.span(name) as sp:
                    handles.append(s.submit(*lane))
                    ctxs.append(sp.context())
            # force the flush rather than waiting out the deadline
            with s._wake:
                s.max_delay = 0.0
                s._wake.notify_all()
            assert all(s.wait(h) for h in handles)
            assert s.entries_coalesced == 1
            doc = ring_tracer.export()
            dispatch = trace_merge.spans_named(doc, "scheduler_dispatch")[-1]
            # first waiter is the dispatch span's remote parent
            assert dispatch["trace_id"] == ctxs[0].trace_id
            assert dispatch["parent_span_id"] == ctxs[0].span_id
            # second waiter reaches the dispatch span via the link edge
            assert trace_merge.is_ancestor(
                doc, ctxs[1].span_id, dispatch["span_id"]
            )
            links = [
                ev
                for ev in doc["traceEvents"]
                if ev.get("name") == "sched_trace_link"
            ]
            assert links[-1]["args"]["link_trace_id"] == ctxs[1].trace_id
        finally:
            s.stop()


# --- in-process client/server propagation ------------------------------------


class TestInProcessPropagation:
    def test_tcp_call_links_server_dispatch(self, ring_tracer):
        srv = start_server()
        h, p = srv.address
        try:
            c = VerifydClient(f"{h}:{p}", fallback=False)
            with tracing.span("client_root") as root:
                oks = c.verify(*junk_lanes(4))
                root_tid = root.trace_id
            assert oks == [True] * 4
            c.close()
        finally:
            srv.stop()
        doc = ring_tracer.export()
        calls = trace_merge.spans_named(doc, "verifyd_call")
        dispatches = [
            ev
            for ev in trace_merge.spans_named(doc, "scheduler_dispatch")
            if ev.get("trace_id") == root_tid
        ]
        assert calls[-1]["trace_id"] == root_tid
        assert dispatches, "server dispatch did not join the client trace"
        assert trace_merge.is_ancestor(
            doc, calls[-1]["span_id"], dispatches[-1]["span_id"]
        )

    def test_stage_vector_attributes_client_latency(self, ring_tracer):
        lane_s = 0.002

        def modeled(pks, msgs, sigs):
            time.sleep(lane_s * len(pks))
            return [True] * len(pks)

        srv = start_server(verify_fn=modeled)
        h, p = srv.address
        try:
            c = VerifydClient(f"{h}:{p}", fallback=False)
            c.verify(*junk_lanes(8))  # connection + path warmup
            base = dict(c.stats()["stage_totals"])
            walls = []
            for i in range(5):
                t0 = time.monotonic()
                assert all(c.verify(*junk_lanes(8, seed=i + 1)))
                walls.append(time.monotonic() - t0)
            stats = c.stats()
            c.close()
        finally:
            srv.stop()
        totals = stats["stage_totals"]
        assert set(protocol.STAGE_NAMES) <= set(totals)
        assert stats["stage_calls"] == 6
        attributed = sum(
            totals[k] - base.get(k, 0.0) for k in protocol.STAGE_NAMES
        )
        # 5 measured calls x 8 lanes x 2ms modeled device time: the
        # stage vector must account for the bulk of the observed wall
        assert attributed >= 0.9 * 5 * 8 * lane_s
        assert attributed <= sum(walls) * 1.1
        # the device stage dominates a modeled sleep server
        deltas = {
            k: totals[k] - base.get(k, 0.0) for k in protocol.STAGE_NAMES
        }
        assert max(deltas, key=deltas.get) == "device"

    def test_restart_mid_stream_keeps_propagating(self, ring_tracer):
        srv = start_server()
        h, p = srv.address
        c = VerifydClient(f"{h}:{p}", fallback=False)
        try:
            with tracing.span("before_restart") as sp1:
                assert all(c.verify(*junk_lanes(2)))
                tid1 = sp1.trace_id
            srv.stop()
            srv = start_server(host=h, port=p)
            with tracing.span("after_restart") as sp2:
                assert all(c.verify(*junk_lanes(2, seed=9)))
                tid2 = sp2.trace_id
        finally:
            c.close()
            srv.stop()
        doc = ring_tracer.export()
        dispatch_tids = {
            ev["trace_id"]
            for ev in trace_merge.spans_named(doc, "scheduler_dispatch")
            if ev.get("trace_id")
        }
        assert tid1 in dispatch_tids
        assert tid2 in dispatch_tids, (
            "post-restart call lost its trace context"
        )

    def test_shm_then_tcp_fallback_keeps_propagating(self, ring_tracer):
        srv = start_server(shm="on")
        h, p = srv.address
        c = VerifydClient(f"{h}:{p}", shm="auto", fallback=False)
        try:
            with tracing.span("over_shm") as sp1:
                assert all(c.verify(*junk_lanes(2)))
                tid1 = sp1.trace_id
            assert c.transport == "shm"
            srv.stop()
            srv = start_server(host=h, port=p, shm="off")
            with tracing.span("over_tcp") as sp2:
                assert all(c.verify(*junk_lanes(2, seed=5)))
                tid2 = sp2.trace_id
            assert c.transport == "tcp"
        finally:
            c.close()
            srv.stop()
        doc = ring_tracer.export()
        dispatch_tids = {
            ev["trace_id"]
            for ev in trace_merge.spans_named(doc, "scheduler_dispatch")
            if ev.get("trace_id")
        }
        assert tid1 in dispatch_tids, "shm leg lost its trace context"
        assert tid2 in dispatch_tids, "tcp fallback lost its trace context"


# --- trace_merge -------------------------------------------------------------


def _doc(epoch_us, events):
    return {
        "traceEvents": events,
        "otherData": {"epoch_unix_us": epoch_us},
    }


class TestTraceMerge:
    def test_base_alignment_orders_cross_process_events(self):
        a = _doc(1_000_000.0, [{"name": "x", "ph": "X", "ts": 500.0,
                                "span_id": "a1", "trace_id": "t"}])
        b = _doc(1_000_400.0, [{"name": "y", "ph": "X", "ts": 500.0,
                                "span_id": "b1", "trace_id": "t",
                                "parent_span_id": "a1"}])
        merged = trace_merge.merge([a, b])
        ts = {e["span_id"]: e["ts"] for e in merged["traceEvents"]}
        assert ts["b1"] - ts["a1"] == pytest.approx(400.0)

    def test_skew_correction_makes_child_follow_parent(self):
        # the server's wall clock runs 10ms behind: after base alignment
        # its dispatch span starts BEFORE the client span that caused it
        client = _doc(2_000_000.0, [
            {"name": "verifyd_call", "ph": "X", "ts": 100.0, "dur": 50.0,
             "span_id": "c1", "trace_id": "t"},
        ])
        server = _doc(1_990_000.0, [
            {"name": "scheduler_dispatch", "ph": "X", "ts": 105.0,
             "dur": 20.0, "span_id": "s1", "trace_id": "t",
             "parent_span_id": "c1"},
        ])
        merged = trace_merge.merge([client, server])
        ts = {e["span_id"]: e["ts"] for e in merged["traceEvents"]}
        assert ts["s1"] >= ts["c1"]  # causality restored
        corr = merged["otherData"]["skew_corrections_us"]
        assert corr[1] == pytest.approx(9995.0)

    def test_intra_document_edges_never_shift(self):
        doc = _doc(0.0, [
            {"name": "p", "ph": "X", "ts": 100.0, "span_id": "p1",
             "trace_id": "t"},
            {"name": "c", "ph": "X", "ts": 90.0, "span_id": "c1",
             "trace_id": "t", "parent_span_id": "p1"},
        ])
        merged = trace_merge.merge([doc])
        assert merged["otherData"]["skew_corrections_us"] == [0.0]

    def test_unusable_exports_skipped_not_fatal(self, capsys):
        """Regression (ISSUE 18): a drained ring (zero complete spans)
        or a pre-epoch export (no epoch_unix_us anchor) must not kill
        the merge or scatter the fleet timeline — it is skipped with a
        warning and counted."""
        good = _doc(1_000_000.0, [
            {"name": "x", "ph": "X", "ts": 5.0, "span_id": "g1",
             "trace_id": "t"},
        ])
        drained = _doc(1_000_100.0, [
            {"name": "only_an_instant", "ph": "i", "ts": 1.0},
        ])
        no_epoch = {
            "traceEvents": [
                {"name": "y", "ph": "X", "ts": 9.0, "span_id": "n1",
                 "trace_id": "t"},
            ],
            "otherData": {},
        }
        merged = trace_merge.merge([good, drained, no_epoch])
        assert merged["otherData"]["merged_from"] == 1
        assert merged["otherData"]["skipped"] == 2
        assert [e["span_id"] for e in merged["traceEvents"]] == ["g1"]
        err = capsys.readouterr().err
        assert "no complete spans" in err
        assert "epoch_unix_us" in err
        # explicit 0.0 anchor is NOT missing (single-doc exports)
        assert trace_merge.merge([_doc(0.0, good["traceEvents"])])[
            "otherData"
        ]["skipped"] == 0

    def test_all_unusable_yields_empty_merge(self):
        merged = trace_merge.merge([{"traceEvents": [], "otherData": {}}])
        assert merged["traceEvents"] == []
        assert merged["otherData"]["merged_from"] == 0
        assert merged["otherData"]["skipped"] == 1

    def test_link_instant_adds_parent_edge(self):
        doc = _doc(0.0, [
            {"name": "waiter_b", "ph": "X", "ts": 0.0, "span_id": "w2",
             "trace_id": "t2"},
            {"name": "scheduler_dispatch", "ph": "X", "ts": 10.0,
             "span_id": "d1", "trace_id": "t1"},
            {"name": "sched_trace_link", "ph": "i", "ts": 11.0,
             "trace_id": "t1", "parent_span_id": "d1",
             "args": {"link_trace_id": "t2", "link_span_id": "w2"}},
        ])
        assert trace_merge.is_ancestor(doc, "w2", "d1")
        assert not trace_merge.is_ancestor(doc, "d1", "w2")

    def test_cli_round_trip(self, tmp_path):
        a = tmp_path / "a.json"
        b = tmp_path / "b.json"
        out = tmp_path / "merged.json"
        a.write_text(json.dumps(_doc(0.0, [
            {"name": "x", "ph": "X", "ts": 1.0, "span_id": "a1",
             "trace_id": "t"}])))
        b.write_text(json.dumps(_doc(0.0, [
            {"name": "y", "ph": "X", "ts": 2.0, "span_id": "b1",
             "trace_id": "t", "parent_span_id": "a1"}])))
        assert trace_merge.main([str(out), str(a), str(b)]) == 0
        merged = trace_merge.load(str(out))
        assert merged["otherData"]["schema"] == trace_merge.MERGED_SCHEMA
        assert len(merged["traceEvents"]) == 2

    def test_cli_usage_error(self, capsys):
        assert trace_merge.main([]) == 2
        assert "usage" in capsys.readouterr().err


# --- two-process acceptance --------------------------------------------------


SERVER_SCRIPT = textwrap.dedent(
    """
    import json, sys, time
    from tendermint_tpu.libs import tracing
    from tendermint_tpu.verifyd.server import VerifydServer

    # What the first request and the first shm attach import lazily
    # (device health, the byte ledger: `tendermint_tpu.ops`, and jax
    # with it) takes seconds, more under a loaded host: past the shm
    # attach's 2 s and, some runs, the warm-up call's deadline. Pay it
    # before the address is announced.
    import tendermint_tpu.ops.device_policy
    import tendermint_tpu.ops.introspect

    export_path, shm_mode, lane_us = (
        sys.argv[1], sys.argv[2], float(sys.argv[3])
    )
    tracing.configure(tracing.RING)

    def modeled(pks, msgs, sigs):
        time.sleep(lane_us * 1e-6 * len(pks))
        return [True] * len(pks)

    srv = VerifydServer(
        # static batching: the acceptance measures the stage vector
        # tiling a fixed config's wall; the dyn controller shortening
        # residency deflates the wall the transport gap is judged against
        verify_fn=modeled, max_batch=64, max_delay=0.001, shm=shm_mode,
        dyn_batch=False,
    )
    srv.start()
    print("ADDR %s:%d" % srv.address, flush=True)
    sys.stdin.read()  # serve until the parent closes our stdin
    srv.stop()
    with open(export_path, "w") as f:
        json.dump(tracing.tracer.export(), f)
    """
)


@pytest.mark.parametrize("transport", ["tcp", "shm"])
def test_two_process_fleet_timeline(transport, ring_tracer, tmp_path):
    """The ISSUE 15 acceptance: client and verifyd in separate
    interpreters, each exporting its own ring; the merged timeline must
    show the client's spans as ancestors of the server's dispatch spans,
    and the stage vector must explain >=90% of the client p50."""
    server_export = tmp_path / "server_trace.json"
    client_export = tmp_path / "client_trace.json"
    # 16 lanes of modeled device time a probe: ~20 ms. What no stage
    # can hold (the wake-ups of two processes' threads) is ~0.3 ms on an
    # idle host and 1.1-1.2 ms beside five other test workers: at 400 us
    # a lane that was 12-13% of a 9.5 ms probe, and the 90% below failed
    # for the host's load, not for a stage that went missing.
    lane_us = 1200.0
    shm_mode = "on" if transport == "shm" else "off"
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c", SERVER_SCRIPT, str(server_export),
         shm_mode, str(lane_us)],
        stdin=subprocess.PIPE,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        env=env,
        cwd=REPO,
    )
    try:
        banner = proc.stdout.readline().strip()
        assert banner.startswith("ADDR "), banner
        addr = banner.split(" ", 1)[1]
        c = VerifydClient(
            addr, shm="auto" if transport == "shm" else "off",
            fallback=False,
        )
        with tracing.span("fleet_warmup"):
            assert all(c.verify(*junk_lanes(8)))
        if transport == "shm":
            assert c.transport == "shm"
        base = dict(c.stats()["stage_totals"])
        walls = []
        attrs = []
        root_tids = []
        for i in range(7):
            with tracing.span("verify_commit_probe", round=i) as sp:
                t0 = time.monotonic()
                assert all(c.verify(*junk_lanes(16, seed=i + 1)))
                walls.append(time.monotonic() - t0)
                root_tids.append(sp.trace_id)
            now = c.stats()["stage_totals"]
            attrs.append(sum(
                now.get(k, 0.0) - base.get(k, 0.0)
                for k in protocol.STAGE_NAMES
            ))
            base = dict(now)
        stats = c.stats()
        c.close()
    finally:
        proc.stdin.close()  # the server exports its ring and exits
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:  # pragma: no cover - cleanup
            proc.kill()
            proc.wait()
    assert proc.returncode == 0, proc.stderr.read()
    client_export.write_text(json.dumps(tracing.tracer.export()))

    merged = trace_merge.merge(
        [trace_merge.load(str(client_export)),
         trace_merge.load(str(server_export))]
    )
    # every probe's client span must be an ancestor of a server-side
    # dispatch span in the MERGED timeline (cross-process linkage)
    dispatches = trace_merge.spans_named(merged, "scheduler_dispatch")
    calls = {
        ev["trace_id"]: ev
        for ev in trace_merge.spans_named(merged, "verifyd_call")
        if ev.get("trace_id")
    }
    for tid in root_tids:
        assert tid in calls, "client call span missing for trace %s" % tid
        linked = [
            d for d in dispatches
            if d.get("trace_id") == tid
            or trace_merge.is_ancestor(
                merged, calls[tid]["span_id"], d.get("span_id", "")
            )
        ]
        assert linked, "no server dispatch joined trace %s" % tid
        assert trace_merge.is_ancestor(
            merged, calls[tid]["span_id"], linked[-1]["span_id"]
        )

    # stage vector explains >=90% of the client-observed p50: sort the
    # (wall, attributed) pairs by wall and compare at the median round,
    # the same check the bench latency_attrib section enforces
    assert stats["stage_calls"] == 8  # warmup + 7 probes, no splits
    pairs = sorted(zip(walls, attrs))
    p50_wall, p50_attr = pairs[len(pairs) // 2]
    assert p50_attr >= 0.9 * p50_wall, (
        "stage vector explains %.1f%% of p50 (%.2fms of %.2fms)"
        % (100.0 * p50_attr / p50_wall, p50_attr * 1e3, p50_wall * 1e3)
    )
