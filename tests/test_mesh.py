"""Mesh-aware sharded verify engine (parallel/mesh + parallel/sharding).

Covers the promotion of ``parallel/`` from demo to default engine:
sizing/config precedence, the small-batch bypass cutover, sharded
dispatch through the ordinary engine entry points (result cache, spans,
metrics counters), sr25519 and table-kernel parity, the sick-chip
degrade-to-(n-1) policy (never host), and COOLDOWN probe re-admission.

Shape discipline: every device run here uses 512 lanes on the virtual
8-mesh (or the 7-mesh the degrade test rebuilds) so the module compiles
each kernel at most once and otherwise hits the persistent compilation
cache shared with tests/test_parallel.py.
"""

import numpy as np
import pytest

from tendermint_tpu.crypto.keys import Ed25519PrivKey
from tendermint_tpu.libs import tracing
from tendermint_tpu.ops import ed25519_batch, fault_injection, precompute
from tendermint_tpu.ops.device_policy import shared as shared_health
from tendermint_tpu.ops.fault_injection import DeviceFault
from tendermint_tpu.parallel import mesh, sharding

LANES = 512  # = _mesh_bucket(512, 8): one padded 8-way chunk


@pytest.fixture(autouse=True)
def _mesh_enabled(monkeypatch):
    """Opt back into sharding (conftest pins TENDERMINT_TPU_MESH=1 for
    the general suite) and isolate health state per test."""
    monkeypatch.setenv(mesh.MESH_ENV, "8")
    mesh.manager.reset()
    shared_health.reset()
    yield
    mesh.manager.reset()
    shared_health.reset()


@pytest.fixture
def ring():
    tracing.configure("ring")
    tracing.tracer.clear()
    yield tracing.tracer
    tracing.configure("off")
    tracing.tracer.clear()


@pytest.fixture(scope="module")
def triples():
    privs = [Ed25519PrivKey.from_seed(bytes([i + 1]) * 32) for i in range(8)]
    pks, msgs, sigs = [], [], []
    for i in range(LANES):
        p = privs[i % 8]
        m = b"mesh-lane-%d" % i
        pks.append(p.pub_key().bytes())
        msgs.append(m)
        sigs.append(p.sign(m))
    return pks, msgs, sigs


@pytest.fixture(scope="module")
def sr_triples():
    """300 sr25519 lanes: they pad to the same 512-lane 8-way slab as
    the ed25519 runs."""
    from tendermint_tpu.crypto.sr25519 import Sr25519PrivKey

    privs = [Sr25519PrivKey.from_secret(b"mesh-sr" + bytes([i])) for i in range(4)]
    pks, msgs, sigs = [], [], []
    for i in range(300):
        p = privs[i % 4]
        m = b"sr-mesh-%d" % i
        pks.append(p.pub_key().bytes())
        msgs.append(m)
        sigs.append(p.sign(m))
    return pks, msgs, sigs


# --- attribution -----------------------------------------------------------


def test_attribute_device():
    ids = (0, 1, 2, 3)
    assert mesh.attribute_device(DeviceFault("x", device=2), ids) == 2
    assert mesh.attribute_device(DeviceFault("device 3 stalled"), ids) == 3
    assert mesh.attribute_device(RuntimeError("TPU_1 halted"), ids) == 1
    # ids outside the plan, bools, and plain errors are unattributed
    assert mesh.attribute_device(DeviceFault("x", device=9), ids) is None
    err = RuntimeError("generic failure")
    err.device = True
    assert mesh.attribute_device(err, ids) is None
    assert mesh.attribute_device(RuntimeError("chip 42"), ids) is None


# --- sizing / config precedence --------------------------------------------


def test_env_mesh_size_honored(monkeypatch):
    monkeypatch.setenv(mesh.MESH_ENV, "4")
    mesh.manager.reset()
    assert mesh.manager.device_count() == 4
    plan = mesh.manager.plan()
    assert plan is not None and plan.n_dev == 4
    mesh.manager.abandon(plan)


def test_env_off_disables_sharding(monkeypatch):
    monkeypatch.setenv(mesh.MESH_ENV, "off")
    mesh.manager.reset()
    assert mesh.manager.device_count() == 1
    assert mesh.manager.plan() is None


def test_config_overrides_env(monkeypatch):
    monkeypatch.setenv(mesh.MESH_ENV, "8")
    mesh.manager.reset()
    mesh.manager.configure(2)
    plan = mesh.manager.plan()
    assert plan is not None and plan.n_dev == 2
    mesh.manager.abandon(plan)
    mesh.manager.configure(1)  # 1 device = sharding off
    assert mesh.manager.plan() is None


def test_default_max_batch_scales_with_mesh(monkeypatch):
    from tendermint_tpu.crypto.scheduler import (
        DEFAULT_MAX_BATCH,
        default_max_batch,
    )

    assert default_max_batch() == DEFAULT_MAX_BATCH * 8
    monkeypatch.setenv(mesh.MESH_ENV, "1")
    mesh.manager.reset()
    assert default_max_batch() == DEFAULT_MAX_BATCH


# --- small-batch bypass ----------------------------------------------------


def test_small_batch_bypass_cutover():
    """Regression-pin the cutover: implicit sharding starts at exactly
    MIN_MESH_LANES (= 4 x the smallest padding bucket)."""
    below = mesh.plan_for_lanes(mesh.MIN_MESH_LANES - 1)
    assert below is None
    at = mesh.plan_for_lanes(mesh.MIN_MESH_LANES)
    assert at is not None and at.n_dev == 8
    mesh.manager.abandon(at)


def test_small_batch_stays_single_device(monkeypatch, triples):
    """A sub-floor batch through the ordinary entry point never reaches
    the sharded dispatcher, even with the mesh enabled."""
    calls = []
    real = sharding.run_chunk_mesh

    def spy(*args, **kwargs):
        calls.append(args[0])
        return real(*args, **kwargs)

    monkeypatch.setattr(sharding, "run_chunk_mesh", spy)
    pks, msgs, sigs = triples
    n = mesh.MIN_MESH_LANES - 1
    oks = ed25519_batch.verify_batch(pks[:n], msgs[:n], sigs[:n])
    assert all(oks)
    assert calls == []


# --- sharded dispatch through the ordinary entry points --------------------


def test_sharded_engine_spans_devices(ring, triples):
    """≥ floor batches through ops.verify_batch shard across all 8
    devices, with per-device dispatch/collect evidence in the trace
    ring and the manager's dispatch counter."""
    pks, msgs, sigs = (list(x) for x in triples)
    sigs[7] = bytes(64)
    oks = ed25519_batch.verify_batch(pks, msgs, sigs)
    assert not oks[7] and sum(oks) == LANES - 1
    snap = mesh.manager.snapshot()
    assert snap["dispatches"] >= 1
    events = ring.export()["traceEvents"]
    dispatched = {
        e["args"]["device"]
        for e in events
        if e.get("name") == "mesh_device_dispatch"
    }
    assert dispatched == set(range(8))
    collected = {
        e["args"]["device"]
        for e in events
        if e.get("name") == "collect_device" and e.get("ph") == "X"
    }
    assert len(collected) == 8


def test_sharded_chunk_carries_what_the_call_path_readers_match(ring, triples):
    """The mesh half of ``tests/test_chipbench_readers.py``'s contract
    (ISSUE 34): a sharded chunk's ``dispatch_chunk`` carries ``chunk``,
    ``chunks`` and the sharded call's time as ``launch_us`` — and no
    ``h2d_us``, the call takes host arrays and transfers inside itself
    — and its ``collect_chunk`` the wait on the whole sharded output as
    ``wait_us`` and the per-device copies as ``d2h_us``."""
    pks, msgs, sigs = triples
    assert all(ed25519_batch.verify_batch(pks, msgs, sigs))
    events = [e for e in ring.export()["traceEvents"] if e.get("ph") == "X"]
    (dispatch,) = [e for e in events if e["name"] == "dispatch_chunk"]
    (inner,) = [e for e in events if e["name"] == "mesh_dispatch"]
    args = dispatch["args"]
    assert (args["chunk"], args["chunks"], args["launch_n"]) == (0, 1, 1)
    assert 0 < args["launch_us"] <= inner["dur"] <= dispatch["dur"]
    assert "h2d_us" not in args
    (collect,) = [e for e in events if e["name"] == "collect_chunk"]
    shards = [e for e in events if e["name"] == "collect_device"]
    assert len(shards) == 8
    args = collect["args"]
    assert (args["chunk"], args["wait_n"], args["d2h_n"]) == (0, 1, 1)
    assert sum(e["dur"] for e in shards) <= args["d2h_us"]
    assert args["wait_us"] + args["d2h_us"] <= collect["dur"]
    assert [e["name"] for e in events if "cpu_us" in e["args"]] == ["verify_batch"]
    assert [e["name"] for e in events if "proc_cpu_us" in e["args"]] == ["verify_batch"]


def test_sharded_matches_host_oracle(triples):
    """Sharded verdicts == the host ZIP-215 oracle lane-for-lane, with
    corruptions spread across device shards."""
    from tendermint_tpu.crypto.ed25519_ref import verify_zip215

    pks, msgs, sigs = (list(x) for x in triples)
    sigs[3] = bytes(64)
    msgs[301] = b"tampered"
    sharded = ed25519_batch.verify_batch(pks, msgs, sigs)
    host = [verify_zip215(pk, m, s) for pk, m, s in zip(pks, msgs, sigs)]
    assert sharded == host
    assert not sharded[3] and not sharded[301]


def test_result_cache_routes_sharded(monkeypatch, triples):
    """Satellite: sharded calls ride the same digest-keyed result cache
    as the single-device path — a repeat super-batch answers from cache
    with zero additional mesh dispatches."""
    monkeypatch.setenv(precompute._RESULT_ENV, "1")
    precompute.reset()
    pks, msgs, sigs = triples
    first = ed25519_batch.verify_batch(pks, msgs, sigs)
    assert all(first)
    d1 = mesh.manager.snapshot()["dispatches"]
    assert d1 >= 1
    again = ed25519_batch.verify_batch(pks, msgs, sigs)
    assert again == first
    assert mesh.manager.snapshot()["dispatches"] == d1
    assert precompute.results.stats()["hits"] >= LANES


def test_scheduler_super_batch_sharded(ring, triples):
    """VerifyScheduler flushes span the mesh: a cross-caller super-batch
    lands as ONE sharded dispatch with per-device spans in the ring."""
    from tendermint_tpu.crypto.scheduler import VerifyScheduler

    pks, msgs, sigs = triples
    sched = VerifyScheduler(ed25519_batch.verify_batch, max_delay=5.0)
    assert sched.max_batch == 256 * 8  # mesh-aware default
    # size-flush exactly when the whole super-batch is queued, so this
    # test produces ONE sharded flush instead of racing the deadline
    sched.max_batch = LANES
    sched.start()
    try:
        entries = [
            sched.submit(pks[i], msgs[i], sigs[i]) for i in range(LANES)
        ]
        assert all(sched.wait(e, timeout=300.0) for e in entries)
    finally:
        sched.stop()
    assert mesh.manager.snapshot()["dispatches"] >= 1
    names = {e.get("name") for e in ring.export()["traceEvents"]}
    assert "mesh_device_dispatch" in names
    assert "sched_flush" in names


# --- parity: sr25519 and the table kernel ----------------------------------


def test_sr25519_sharded_parity(sr_triples):
    """Sharded sr25519 verdicts == the host oracle's, lane for lane, bad
    lanes isolated. (Until PR 46 the other side was one device's kernel
    at these 300 lanes: a 1,024-lane compile of its own for verdicts
    that tests/test_sr25519_device.py holds against the same oracle.)"""
    from tendermint_tpu.crypto.sr25519 import verify as verify_host

    pks, msgs, sigs = (list(x) for x in sr_triples)
    sigs[5] = bytes(64)
    sigs[250] = sigs[249]
    sharded = sharding.verify_batch_sharded_sr(
        pks, msgs, sigs, mesh=sharding.make_mesh(8), min_lanes=0
    )
    assert mesh.manager.snapshot()["dispatches"] >= 1
    assert sharded == [verify_host(*lane) for lane in zip(pks, msgs, sigs)]
    assert not sharded[5] and not sharded[250]
    assert sum(sharded) == 298


def test_table_kernel_sharded_parity(ring, triples):
    """Pinned (table-eligible) keys take the sharded TABLE kernel — the
    (8, 4, 32, N) precompute tensor sharded on its lane axis — and the
    verdicts match, bad lane isolated."""
    pks, msgs, sigs = (list(x) for x in triples)
    precompute.pin_pubkeys(set(pks))
    try:
        sigs[9] = bytes(64)
        oks = ed25519_batch.verify_batch(pks, msgs, sigs)
        assert not oks[9] and sum(oks) == LANES - 1
        assert mesh.manager.snapshot()["dispatches"] >= 1
        # the dispatch really took the table path
        table_dispatches = [
            e
            for e in ring.export()["traceEvents"]
            if e.get("name") == "dispatch_chunk"
            and e.get("args", {}).get("kind") == "tables"
        ]
        assert table_dispatches
    finally:
        precompute.tables.clear()


def test_resident_kernel_sharded_parity(ring, triples, monkeypatch):
    """With the device-resident store on, pinned keys ride the sharded
    RESIDENT kernel — the store replicated across the mesh, only (N,)
    int32 gather indices shipped per batch — verdicts exact, and the
    second batch pays zero table H2D."""
    from tendermint_tpu.ops import resident

    monkeypatch.setenv("TENDERMINT_TPU_RESIDENT", "on")
    resident.reset()
    pks, msgs, sigs = (list(x) for x in triples)
    precompute.pin_pubkeys(set(pks))
    try:
        sigs[9] = bytes(64)
        oks = ed25519_batch.verify_batch(pks, msgs, sigs)
        assert not oks[9] and sum(oks) == LANES - 1
        assert mesh.manager.snapshot()["dispatches"] >= 1
        resident_dispatches = [
            e
            for e in ring.export()["traceEvents"]
            if e.get("name") == "dispatch_chunk"
            and e.get("args", {}).get("kind") == "resident"
        ]
        assert resident_dispatches
        s1 = resident.stats()
        assert s1["uploads"] == 1 and s1["gathered_h2d_bytes"] == 0
        oks = ed25519_batch.verify_batch(pks, msgs, sigs)
        assert not oks[9] and sum(oks) == LANES - 1
        s2 = resident.stats()
        assert s2["h2d_bytes"] == s1["h2d_bytes"]
        assert s2["gathered_h2d_bytes"] == 0
    finally:
        precompute.tables.clear()
        resident.reset()


# --- degradation: sick chip -> smaller mesh, never host --------------------


@pytest.mark.parametrize("engine", ["ed25519", "sr25519"])
def test_sick_device_degrades_to_seven_way(ring, triples, sr_triples, engine):
    """Acceptance: killing one device mid-run rebuilds a 7-device mesh
    and continues sharded — no host fallback, no shared-health damage,
    every lane correct. Both engines: they share the runner."""
    from tendermint_tpu.ops.sr25519_batch import verify_batch_sr

    verify = ed25519_batch.verify_batch if engine == "ed25519" else verify_batch_sr
    pks, msgs, sigs = (list(x) for x in (triples if engine == "ed25519" else sr_triples))
    sigs[100] = bytes(64)
    fb_before = shared_health.snapshot()["fallback_batches"]
    with fault_injection.inject(
        site=engine + ".chunk",
        fail_from=1,
        fail_count=1,
        error_factory=lambda: DeviceFault("sick chip", device=3),
    ):
        oks = verify(pks, msgs, sigs)
    assert not oks[100] and sum(oks) == len(pks) - 1
    snap = mesh.manager.snapshot()
    assert snap["excluded"] == [3]
    assert snap["exclusions"] == 1
    assert snap["dispatches"] >= 1
    # the chunk was retried on the rebuilt 7-mesh, not the host
    assert shared_health.state == "healthy"
    assert shared_health.snapshot()["fallback_batches"] == fb_before
    events = ring.export()["traceEvents"]
    assert any(
        e.get("name") == "mesh_device_excluded"
        and e["args"]["device"] == 3
        for e in events
    )
    retry_devices = {
        e["args"]["device"]
        for e in events
        if e.get("name") == "mesh_device_dispatch"
    }
    assert retry_devices == set(range(8)) - {3}


def test_unattributed_failure_keeps_engine_fallback(triples):
    """A failure with no device attribution must NOT shrink the mesh —
    it propagates to the engine's ordinary per-chunk degradation."""
    pks, msgs, sigs = triples
    with fault_injection.inject(
        site="ed25519.chunk", fail_from=1, fail_count=1
    ):
        # default DeviceFault carries no device id and no 'device N'
        # text that maps into the plan
        oks = ed25519_batch.verify_batch(pks, msgs, sigs)
    assert all(oks)
    snap = mesh.manager.snapshot()
    assert snap["excluded"] == []
    assert snap["exclusions"] == 0


# --- COOLDOWN re-admission -------------------------------------------------


def test_probe_readmission(monkeypatch):
    """An excluded device rejoins the next plan after its cooldown as a
    half-open probe; one successful dispatch re-admits it."""
    now = [0.0]
    mgr = mesh.MeshManager(clock=lambda: now[0], cooldown_base=5.0)
    monkeypatch.setenv(mesh.MESH_ENV, "8")

    plan = mgr.plan()
    assert plan is not None and plan.n_dev == 8
    culprit = mgr.on_failure(plan, DeviceFault("bad", device=3))
    assert culprit == 3
    mgr.abandon(plan)

    degraded = mgr.plan()
    assert degraded is not None
    assert degraded.n_dev == 7 and 3 not in degraded.device_ids
    mgr.abandon(degraded)

    now[0] += 6.0  # past cooldown_base: device 3 becomes probe-able
    probing = mgr.plan()
    assert probing is not None and 3 in probing.device_ids
    assert probing.attempts[3].probe
    mgr.note_dispatch(probing, 512)
    mgr.on_success(probing)
    snap = mgr.snapshot()
    assert snap["readmissions"] == 1
    assert snap["excluded"] == []
    assert snap["devices"][3] == "healthy"


def test_probe_failure_rearms_cooldown(monkeypatch):
    now = [0.0]
    mgr = mesh.MeshManager(clock=lambda: now[0], cooldown_base=5.0)
    monkeypatch.setenv(mesh.MESH_ENV, "8")
    plan = mgr.plan()
    assert mgr.on_failure(plan, DeviceFault("bad", device=3)) == 3
    mgr.abandon(plan)
    now[0] += 6.0
    probing = mgr.plan()
    assert probing.attempts[3].probe
    # the probe dispatch dies (attributed to ANOTHER device): device 3's
    # cooldown re-arms without counting a readmission
    assert mgr.on_failure(probing, DeviceFault("bad", device=5)) == 5
    mgr.abandon(probing)
    snap = mgr.snapshot()
    assert snap["readmissions"] == 0
    assert 3 in snap["excluded"] and 5 in snap["excluded"]


# --- forced meshes ---------------------------------------------------------


def test_forced_mesh_skips_lane_floor():
    m = sharding.make_mesh(8)
    with mesh.manager.forced(m):
        plan = mesh.plan_for_lanes(8)  # far below MIN_MESH_LANES
        assert plan is not None and plan.n_dev == 8
        mesh.manager.abandon(plan)


def test_forced_mesh_excludes_sick_devices():
    m = sharding.make_mesh(8)
    plan = mesh.manager.plan()
    assert mesh.manager.on_failure(plan, DeviceFault("x", device=6)) == 6
    mesh.manager.abandon(plan)
    with mesh.manager.forced(m):
        forced_plan = mesh.manager.plan()
    assert forced_plan is not None
    assert forced_plan.n_dev == 7 and 6 not in forced_plan.device_ids
    mesh.manager.abandon(forced_plan)
