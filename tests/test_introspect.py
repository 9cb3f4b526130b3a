"""Device-tier introspection (ops/introspect.py, ISSUE 18).

The acceptance contract: the byte ledger stays EXACT — the
``resident_tables`` owner always equals the nbytes of the tensor the
store actually has installed (and 0 when none is), across upload,
committee rotation, eviction, and clear; slab-ring attach/retire is
symmetric to the byte; and every surface (/debug/memstats, flight
recorder dumps, verifyd stats) reports the same ledger.
"""

import json

import pytest

from tendermint_tpu.crypto import ed25519_ref as ref
from tendermint_tpu.libs.metrics import OpsMetrics, Registry
from tendermint_tpu.ops import introspect, precompute, resident

jax = pytest.importorskip("jax")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """Resident store on (auto keeps CPU off); ledger + caches isolated
    per test."""
    monkeypatch.setenv("TENDERMINT_TPU_RESIDENT", "on")
    precompute.reset()
    resident.reset()
    introspect.accountant.clear()
    introspect.profiler.clear()
    yield
    precompute.reset()
    resident.reset()
    introspect.accountant.clear()
    introspect.profiler.clear()


def _batch(n, seed=60):
    pks, msgs, sigs = [], [], []
    for i in range(n):
        sk, pk = ref.keypair_from_seed(bytes([seed + i]) * 32)
        m = b"introspect lane %03d" % i
        pks.append(pk)
        msgs.append(m)
        sigs.append(ref.sign(sk, m))
    return pks, msgs, sigs


# --- bucket labeler -----------------------------------------------------------


class TestBucketLabel:
    def test_rounds_up_to_power_of_two(self):
        assert introspect.bucket_label(1) == "1"
        assert introspect.bucket_label(2) == "2"
        assert introspect.bucket_label(3) == "4"
        assert introspect.bucket_label(100) == "128"
        assert introspect.bucket_label(8192) == "8192"

    def test_overflow_and_junk_collapse_to_other(self):
        assert introspect.bucket_label(1 << 15) == "other"
        assert introspect.bucket_label(0) == "other"
        assert introspect.bucket_label(-5) == "other"
        assert introspect.bucket_label(None) == "other"
        assert introspect.bucket_label("lots") == "other"

    def test_cardinality_is_bounded(self):
        labels = {introspect.bucket_label(n) for n in range(0, 100_000, 7)}
        assert len(labels) <= 16


# --- resident-table byte accounting ------------------------------------------


def _resident_bytes():
    return introspect.accountant.bytes_for("resident_tables")


class TestResidentBytes:
    def test_exact_across_upload_rotation_evict_clear(self):
        """Acceptance: ledger bytes == the store's actual upload sizes
        across a full rotation/evict cycle."""
        from tendermint_tpu.ops import ed25519_batch

        assert _resident_bytes() == 0
        pks, msgs, sigs = _batch(8)
        precompute.pin_pubkeys(pks)
        assert all(ed25519_batch.verify_batch(pks, msgs, sigs))

        first_upload = int(resident.stats()["h2d_bytes"])
        assert first_upload > 0
        assert _resident_bytes() == first_upload

        # committee growth: the re-upload replaces the install; the
        # ledger must track the NEW tensor size, not accumulate
        # (60 more keys: past the first upload's 64 columns, so the
        # new tensor is really larger than the one it replaces)
        p2, m2, s2 = _batch(60, seed=120)
        precompute.pin_pubkeys(p2)
        assert all(
            ed25519_batch.verify_batch(pks + p2, msgs + m2, sigs + s2)
        )
        stats = resident.stats()
        assert stats["uploads"] == 2
        second_upload = int(stats["h2d_bytes"]) - first_upload
        assert _resident_bytes() == second_upload > first_upload

        # clear (rotation observed by consensus): device copy dies,
        # ledger drops to zero with it
        resident.note_validator_rotation()
        assert _resident_bytes() == 0
        assert introspect.accountant.snapshot()["device_bytes"] == {}

    def test_invalidate_zeroes_then_reupload_restores(self):
        from tendermint_tpu.ops import ed25519_batch

        pks, msgs, sigs = _batch(6)
        precompute.pin_pubkeys(pks)
        ed25519_batch.verify_batch(pks, msgs, sigs)
        installed = _resident_bytes()
        assert installed > 0

        # host-cache eviction of a resident key invalidates the device
        # copy in lockstep; the ledger must not hold stale bytes
        resident.store.invalidate([pks[0]])
        assert _resident_bytes() == 0

        ed25519_batch.verify_batch(pks, msgs, sigs)
        assert _resident_bytes() == installed

    def test_gauge_mirrors_ledger(self):
        ops = OpsMetrics(Registry())
        introspect.bind_metrics(ops)
        key = (("owner", "resident_tables"),)
        introspect.set_bytes("resident_tables", 12345)
        assert ops.device_bytes._values.get(key) == 12345
        introspect.set_bytes("resident_tables", 0)
        assert ops.device_bytes._values.get(key) == 0
        introspect.bind_metrics(None)


# --- slab-ring attach / retire ------------------------------------------------


class TestShmSlabBytes:
    def _endpoint(self):
        from tendermint_tpu.verifyd.shm import ShmEndpoint

        return ShmEndpoint(serve=lambda *a, **k: None)

    def _session(self, size):
        import types

        return types.SimpleNamespace(_seg=types.SimpleNamespace(size=size))

    def test_attach_retire_symmetry(self):
        ep = self._endpoint()
        a, b = self._session(64 * 1024), self._session(128 * 1024)
        ep.register(a)
        assert introspect.accountant.bytes_for("shm_slabs") == 64 * 1024
        ep.register(b)
        assert introspect.accountant.bytes_for("shm_slabs") == 192 * 1024
        ep.unregister(a)
        assert introspect.accountant.bytes_for("shm_slabs") == 128 * 1024
        ep.unregister(b)
        assert introspect.accountant.bytes_for("shm_slabs") == 0

    def test_double_unregister_does_not_go_negative(self):
        ep = self._endpoint()
        a = self._session(4096)
        ep.register(a)
        ep.unregister(a)
        ep.unregister(a)  # connection_lost racing stop(): second is a no-op
        assert introspect.accountant.bytes_for("shm_slabs") == 0


# --- continuous profiler ------------------------------------------------------


class TestProfiler:
    def test_digests_fed_from_dispatch_spans(self):
        from tendermint_tpu.libs import tracing

        introspect.profiler.configure("on")
        try:
            for _ in range(4):
                with tracing.tracer.span(
                    "dispatch_chunk", stage="dispatch", engine="ed25519",
                    kind="raw", lanes=100,
                ):
                    pass
            with tracing.tracer.span(
                "kernel_compile", engine="ed25519", kernel="verify", lanes=128
            ):
                pass
            snap = introspect.profiler.snapshot()
        finally:
            introspect.profiler.configure("off")
        # the enqueue, named as what it is: no digest calls it the kernel
        assert "kernel" not in snap
        k = snap["dispatch"]["ed25519/b128"]
        assert k["count"] == 4
        assert k["p50_ms"] >= 0.0 and k["p99_ms"] >= k["p50_ms"]
        assert snap["compile"]["ed25519/b128"]["count"] == 1
        assert snap["device_wait"] == {}

    def test_device_wait_digest_fed_from_collect_spans(self):
        import time

        from tendermint_tpu.libs import tracing

        introspect.profiler.configure("on")
        try:
            with tracing.tracer.span(
                "dispatch_chunk", stage="dispatch", engine="ed25519",
                kind="resident", lanes=4096,
            ):
                pass  # the enqueue returns at once
            for _ in range(3):
                with tracing.tracer.span(
                    "collect_chunk", stage="collect", engine="ed25519",
                    kind="resident", lanes=4096,
                ):
                    time.sleep(0.002)  # the host waits for the verdicts here
            snap = introspect.profiler.snapshot()
        finally:
            introspect.profiler.configure("off")
        wait = snap["device_wait"]["ed25519/b4096"]
        assert wait["count"] == 3
        assert wait["p50_ms"] >= 2.0
        assert snap["dispatch"]["ed25519/b4096"]["count"] == 1
        assert snap["dispatch"]["ed25519/b4096"]["p50_ms"] < wait["p50_ms"]

    def test_profile_sink_keeps_spans_live_when_ring_off(self):
        """The tracer's NOP gate must treat the profile sink as a
        reason to record — otherwise an off-mode process profiles
        nothing."""
        from tendermint_tpu.libs.tracing import Tracer

        t = Tracer()  # default mode is off: ring never records
        seen = []
        t.set_profile_sink(lambda name, args, dur: seen.append(name))
        with t.span("dispatch_chunk", engine="x", lanes=4):
            pass
        assert seen == ["dispatch_chunk"]

    def test_off_profiler_uninstalls_sink(self):
        from tendermint_tpu.libs import tracing

        introspect.profiler.configure("off")
        assert tracing.tracer._profile is None
        introspect.profiler.configure("on")
        assert tracing.tracer._profile is not None
        introspect.profiler.configure("off")

    def test_non_kernel_spans_ignored(self):
        introspect.profiler.sink("verify_batch", {"lanes": 8}, 0.001)
        snap = introspect.profiler.snapshot()
        assert snap["dispatch"] == {} and snap["device_wait"] == {}
        assert snap["compile"] == {}


# --- compile accounting -------------------------------------------------------


class TestCompileAccounting:
    def test_traced_first_call_counts_once(self):
        calls = []
        fn = introspect.traced_first_call(
            lambda x: calls.append(x) or x, "ed25519", "verify", 64
        )
        before = introspect.accountant.snapshot()["compile_events"].get(
            "ed25519", 0
        )
        assert fn(1) == 1 and fn(2) == 2 and fn(3) == 3
        after = introspect.accountant.snapshot()["compile_events"]
        assert after.get("ed25519", 0) == before + 1
        assert calls == [1, 2, 3]

    def test_pallas_exec_cache_counts_every_entry_point(self):
        from tendermint_tpu.ops import pallas_verify

        factories = (
            pallas_verify.compiled_verify,
            pallas_verify.compiled_verify_tables,
            pallas_verify.compiled_verify_resident,
        )
        # builds the jitted wrapper; nothing compiles until it is called
        pallas_verify.compiled_verify_resident(16, block=8, interpret=True)
        want = sum(f.cache_info().currsize for f in factories)
        assert pallas_verify.compiled_verify_resident.cache_info().currsize >= 1
        assert introspect._exec_cache_entries()["pallas"] == want

    def test_xla_exec_cache_reads_the_one_factory(self):
        from tendermint_tpu.ops import ed25519_batch

        # builds the jitted wrapper; nothing compiles until it is called
        ed25519_batch._compiled_kernel(ed25519_batch.KINDS["tables"], 8, None, "vpu")
        size = ed25519_batch._compiled_kernel.cache_info().currsize
        assert size >= 1
        assert introspect._exec_cache_entries()["xla"] == size

    def test_counter_mirrors(self):
        ops = OpsMetrics(Registry())
        introspect.bind_metrics(ops)
        introspect.note_compile("sr25519")
        assert ops.compile_events._values.get((("engine", "sr25519"),)) == 1
        introspect.bind_metrics(None)


# --- surfaces -----------------------------------------------------------------


class TestSurfaces:
    def test_debug_memstats_endpoint(self):
        from tendermint_tpu.rpc.server import RPCServer

        introspect.set_bytes("resident_tables", 777)
        status, ctype, body = RPCServer(routes={})._get_response(
            "/debug/memstats"
        )
        assert status == 200 and ctype == "application/json"
        doc = json.loads(body)
        assert doc["device_bytes"]["resident_tables"] == 777
        assert "profile" in doc and "resident" in doc

    def test_verifyd_stats_carry_ledger(self):
        from tendermint_tpu.verifyd.server import VerifydServer

        introspect.set_bytes("shm_slabs", 4096)
        introspect.note_compile("ed25519")
        srv = VerifydServer(verify_fn=lambda pks, msgs, sigs: [])
        stats = srv.stats()
        assert stats["device_bytes"]["shm_slabs"] == 4096
        assert stats["compile_events"]["ed25519"] >= 1

    def test_flightrec_dump_embeds_memstats(self, tmp_path, monkeypatch):
        from tendermint_tpu.libs import flightrec

        monkeypatch.setenv(flightrec.DIR_ENV, str(tmp_path))
        introspect.set_bytes("resident_tables", 2048)
        rec = flightrec.FlightRecorder()
        rec.mark("unit_test", n=1)
        path = rec.dump("test")
        assert path is not None
        doc = json.loads(open(path).read())
        assert doc["memstats"]["device_bytes"]["resident_tables"] == 2048

    def test_memstats_json_respects_size_bound(self):
        # fill the profiler so the full doc is large, then bound it
        for i in range(64):
            introspect.profiler.sink(
                "dispatch_chunk", {"engine": "e%d" % (i % 4), "lanes": i + 1},
                0.001,
            )
        full = introspect.memstats_json()
        assert len(full) > 200
        bounded = introspect.memstats_json(limit_bytes=200)
        assert len(bounded) <= 200
        doc = json.loads(bounded)
        assert "device_bytes_total" in doc
