"""The mesh's Pallas kernels (parallel/sharding._sharded_kernel, PR 36).

A mesh-sharded chunk under ``pallas`` runs the kind's Pallas entry
point per shard under ``shard_map``, from a lowered program kept by
ops/kernel_store.py. Here, on the forced host devices of conftest.py:

- the ``tables`` and ``resident`` kinds with the kernel body replaced by
  a cheap lane-local stand-in, for everything around the body: what is
  replicated and what is sharded, lane order, a degraded mesh, the
  store and its span;
- the real kernels, marked ``slow``: the ``legacy`` kernel (interpret
  mode, 8 lanes a shard on two devices) against the ZIP-215 oracle on
  the lanes test_pallas_verify.py holds, and the table kernels once.
  Interpreted, the two-device program is a compile of its own beside
  test_pallas_verify.py's one-device program of the same body (431 s
  and 440 s cold, PR 46): tier-1 keeps that one, the body against the
  oracle, and ``big10k-x4``'s ``correct`` holds the body per shard on
  the chip in every PR's check.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tendermint_tpu.libs import tracing
from tendermint_tpu.ops import (
    ed25519_batch,
    fault_injection,
    introspect,
    kernel_store,
    pallas_verify,
)
from tendermint_tpu.ops.fault_injection import DeviceFault
from tendermint_tpu.parallel import mesh as mesh_mod, sharding
from tests import test_pallas_verify as one_device
from tests.test_pallas_verify import batch8  # noqa: F401  (a fixture)

KINDS = ed25519_batch.KINDS


@pytest.fixture(autouse=True)
def pallas_forced(monkeypatch):
    """On the CPU ``auto`` is the XLA graph."""
    monkeypatch.setattr(ed25519_batch, "active_impl", lambda backend=None: "pallas")
    monkeypatch.setattr(
        ed25519_batch, "_mul_impl_for_chunk", lambda impl, backend, lanes: "vpu"
    )


@pytest.fixture
def fresh_store(monkeypatch, tmp_path):
    """A store of the test's own, and no sharded kernel of one test
    left in the factory for the next."""
    root = str(tmp_path / "kernel_store")
    monkeypatch.setattr(kernel_store, "directory", lambda: root)
    sharding._sharded_kernel.cache_clear()
    yield root
    sharding._sharded_kernel.cache_clear()


@pytest.fixture
def ring():
    tracing.configure("ring")
    tracing.tracer.clear()
    yield tracing.tracer
    tracing.configure("off")
    tracing.tracer.clear()


def plan_of(n_dev):
    mesh = sharding.make_mesh(n_dev)
    return mesh_mod.MeshPlan(mesh, tuple(range(n_dev)), {}, True)


def spans(ring, name):
    return [
        e for e in ring.export()["traceEvents"]
        if e.get("ph") == "X" and e["name"] == name
    ]


# --- the legacy kernel for real ---------------------------------------------


def lanes_valid_and_pad(batch8):
    return tuple(list(x[:5]) for x in batch8), [True] * 5


@pytest.mark.slow  # 431 s cold: the body's second interpret-mode compile, for two devices (see above)
@pytest.mark.parametrize(
    "lanes_of",
    [
        lanes_valid_and_pad,
        one_device.lanes_bad_entries,
        one_device.lanes_zip215_edge_cases,
        one_device.lanes_off_curve_and_mutations,
    ],
    ids=lambda f: f.__name__,
)
def test_legacy_kernel_sharded_agrees_with_the_oracle(batch8, lanes_of):
    """Sixteen lanes, eight a shard on two devices: the batch's own
    lanes first, pad lanes behind them. Lane for lane the verdict is the
    oracle's, the pad lanes verify, and the runner says what ran. The
    real kernel in the real store (beside the compile cache that holds
    its executable): only a checkout's first run walks the kernel body,
    and the four cases run the one program."""
    (pks, msgs, sigs), want = lanes_of(batch8)
    n = len(pks)
    inputs, host_ok = ed25519_batch.prepare_batch(pks, msgs, sigs, pad_to=16)
    out, used, impl = ed25519_batch._run_chunk(
        KINDS["legacy"], inputs, None, plan_of(2)
    )
    assert impl == "pallas" and used.n_dev == 2
    assert len(out.addressable_shards) == 2
    got = np.asarray(out)
    assert got.shape == (16,)
    assert list(np.logical_and(got[:n], host_ok[:n])) == want
    assert want == [ref_ok for ref_ok in map(_oracle, pks, msgs, sigs)]
    assert got[n:].all()  # pad lanes verify: they can mask nothing


def _oracle(pk, msg, sig):
    from tendermint_tpu.crypto import ed25519_ref as ref

    return ref.verify_zip215(pk, msg, sig)


# --- around the body: a lane-local stand-in -----------------------------------


@pytest.fixture
def stand_in(monkeypatch, fresh_store):
    """``verify_tables_fn`` replaced by a lane-local function of every
    input (so a lane's verdict says which table column, ``ok`` bit and
    rows reached it); records the shapes each shard's trace saw."""
    seen = []

    def body(tab, a_ok, r, s, k, *, block, interpret):
        seen.append(
            {"tab": tab.shape, "ok": a_ok.shape, "r": r.shape, "block": block,
             "interpret": interpret}
        )
        same = (tab[0, 0, 0, :] == r[:, 0]) & (s[:, 1] == k[:, 1])
        return same & (a_ok != 0)

    monkeypatch.setattr(pallas_verify, "verify_tables_fn", body)
    return seen


def table_chunk(n, k_cols=11, seed=1):
    """A resident chunk and the gathered chunk of the same lanes, with
    two refused lanes, and the verdicts the stand-in must give."""
    rng = np.random.default_rng(seed)
    store = rng.integers(0, 255, (8, 4, 32, k_cols), dtype=np.uint8)
    idx = rng.integers(0, k_cols, n).astype(np.int32)
    r = rng.integers(0, 255, (n, 32), dtype=np.uint8)
    r[:, 0] = store[0, 0, 0, idx]
    s = rng.integers(0, 255, (n, 32), dtype=np.uint8)
    k = s.copy()
    ok = np.ones(n, np.uint8)
    want = np.ones(n, bool)
    r[n // 3, 0] ^= 1  # another column's table would not match
    ok[2 * n // 3] = 0
    k[n - 1, 1] ^= 1
    want[[n // 3, 2 * n // 3, n - 1]] = False
    resident = dict(store=store, idx=idx, ok=ok, r=r, s=s, k=k)
    gathered = dict(
        tab=np.ascontiguousarray(store[:, :, :, idx]), ok=ok, r=r, s=s, k=k
    )
    return resident, gathered, want


def on_mesh(resident, plan):
    """The store uploaded as ops/resident.py uploads it for a mesh."""
    out = dict(resident)
    out["store"] = jax.device_put(resident["store"], NamedSharding(plan.mesh, P()))
    out["mesh_key"] = tuple(plan.device_ids)
    return out


def test_resident_store_is_replicated_and_lanes_are_sharded(stand_in, ring):
    plan = plan_of(4)
    resident, _, want = table_chunk(16)
    out, used, impl = ed25519_batch._run_chunk(
        KINDS["resident"], on_mesh(resident, plan), None, plan
    )
    assert impl == "pallas" and used is plan
    np.testing.assert_array_equal(np.asarray(out), want)  # lane order
    # every shard took its 4 lanes' columns from the whole store
    (shard,) = stand_in
    assert shard == {"tab": (8, 4, 32, 4), "ok": (4,), "r": (4, 32), "block": 4,
                     "interpret": True}
    assert [sh.data.shape for sh in out.addressable_shards] == [(4,)] * 4
    (md,) = spans(ring, "mesh_dispatch")
    assert (md["args"]["impl"], md["args"]["lanes"], md["args"]["devices"]) == ("pallas", 16, 4)


def test_tables_chunk_sharded_in_lane_order(stand_in):
    plan = plan_of(4)
    _, gathered, want = table_chunk(16, seed=2)
    out, used, impl = ed25519_batch._run_chunk(KINDS["tables"], gathered, None, plan)
    assert impl == "pallas" and used is plan
    np.testing.assert_array_equal(np.asarray(out), want)
    (shard,) = stand_in
    assert shard["tab"] == (8, 4, 32, 4) and shard["r"] == (4, 32)


def test_first_call_span_says_what_the_store_did(stand_in, ring, monkeypatch):
    """The sharded first call has a ``kernel_compile`` span: ``miss``
    where the body was walked, ``hit`` (and no walk at all) in a process
    that finds the store warm; later calls have none."""
    plan = plan_of(4)
    _, gathered, want = table_chunk(16, seed=3)

    def counted():
        snap = introspect.accountant.snapshot()
        return (
            snap["compile_events"].get("pallas", 0),
            snap["stored_programs"].get("miss", 0),
            snap["stored_programs"].get("hit", 0),
        )

    before = counted()
    for _ in range(2):
        ed25519_batch._run_chunk(KINDS["tables"], gathered, None, plan)
    (kc,) = spans(ring, "kernel_compile")
    a = kc["args"]
    assert (a["engine"], a["kernel"], a["lanes"], a["devices"], a["stored"]) == (
        "pallas", "verify_tables", 4, 4, "miss",
    )
    assert tuple(np.subtract(counted(), before)) == (1, 1, 0)
    # "another process": the factory forgets, the store does not
    ring.clear()
    sharding._sharded_kernel.cache_clear()
    del stand_in[:]

    def boom(*args, **kwargs):
        raise AssertionError("the kernel body was walked in a warm process")

    monkeypatch.setattr(pallas_verify, "verify_tables_fn", boom)
    out, _, _ = ed25519_batch._run_chunk(KINDS["tables"], gathered, None, plan)
    np.testing.assert_array_equal(np.asarray(out), want)
    (kc,) = spans(ring, "kernel_compile")
    assert kc["args"]["stored"] == "hit"
    assert tuple(np.subtract(counted(), before)) == (2, 1, 1)


def test_degraded_mesh_gets_its_own_kernel_and_still_answers(
    stand_in, fresh_store, monkeypatch
):
    """A chip that fails mid-dispatch is excluded and the chunk retried
    on the rebuilt 3-device mesh: another key of the factory, another
    stored program (6-lane slabs), the same verdicts."""
    from tendermint_tpu.ops.device_policy import shared as shared_health

    monkeypatch.setenv(mesh_mod.MESH_ENV, "4")
    mesh_mod.manager.reset()
    shared_health.reset()
    try:
        plan = mesh_mod.manager.plan()
        assert plan.n_dev == 4
        _, gathered, want = table_chunk(16, seed=4)
        with pytest.warns(UserWarning, match="retrying on a 3-device mesh"):
            with fault_injection.inject(
                site="ed25519.chunk",
                fail_from=1,
                fail_count=1,
                error_factory=lambda: DeviceFault("sick chip", device=3),
            ):
                out, used, impl = ed25519_batch._run_chunk(
                    KINDS["tables"], gathered, None, plan
                )
        assert impl == "pallas" and used.n_dev == 3 and 3 not in used.device_ids
        got = np.asarray(out)
        assert got.shape == (18,)  # 16 -> 3 slabs of 6
        np.testing.assert_array_equal(got[:16], want)
        assert sharding._sharded_kernel.cache_info().currsize == 2
        # the 4-device program was never fetched: its dispatch failed first
        assert [s["r"] for s in stand_in] == [(6, 32)]
        mesh_mod.manager.abandon(used)
    finally:
        mesh_mod.manager.reset()
        shared_health.reset()
    assert len(os.listdir(fresh_store)) == 1


def test_resident_chunk_whose_mesh_died_reenters_gathered(stand_in, monkeypatch):
    """Degradation is unchanged: the store is committed to the dead
    mesh, so the chunk comes back as a gathered ``tables`` chunk on one
    device, through the one-device entry point."""
    from tendermint_tpu.ops.device_policy import shared as shared_health

    calls = []

    def entry(n):
        def kernel(tab, ok, r, s, k):
            calls.append((n, np.asarray(tab).shape))
            return jnp.ones((n,), bool)

        return kernel

    monkeypatch.setattr(pallas_verify, "compiled_verify_tables", entry)
    monkeypatch.setenv(mesh_mod.MESH_ENV, "4")
    mesh_mod.manager.reset()
    shared_health.reset()
    try:
        plan = mesh_mod.manager.plan()
        resident, _, _ = table_chunk(16, seed=5)
        with fault_injection.inject(
            site="ed25519.chunk",
            fail_from=1,
            fail_count=1,
            error_factory=lambda: DeviceFault("sick chip", device=2),
        ):
            out, used, impl = ed25519_batch._run_chunk(
                KINDS["resident"], on_mesh(resident, plan), None, plan
            )
        assert (used, impl) == (None, "pallas")
        assert calls == [(16, (8, 4, 32, 16))] and np.asarray(out).all()
        assert stand_in == []  # no sharded program was built
        mesh_mod.manager.abandon(plan)
    finally:
        mesh_mod.manager.reset()
        shared_health.reset()


@pytest.mark.parametrize("impl", ["xla", "mxu"])
def test_other_implementations_keep_the_xla_graph(monkeypatch, impl, stand_in):
    """``xla`` and ``mxu`` run the kind's XLA graph under GSPMD as they
    did; no program is fetched from the store."""
    plan = plan_of(2)
    ran = []

    def graph(*args):
        ran.append(tuple(a.shape for a in args))
        return jnp.ones((args[-1].shape[0],), bool)

    kind = KINDS["tables"]
    fake = type(kind)(
        kind.name, kind.engine, kind.kernel_name, graph, kind.pallas, kind.inputs
    )
    _, gathered, _ = table_chunk(16, seed=6)
    out, used = sharding.run_chunk_mesh(fake, gathered, impl, "vpu", plan)
    assert np.asarray(out).all() and used is plan
    assert ran == [((8, 4, 32, 16), (16,), (16, 32), (16, 32), (16, 32))]
    assert stand_in == []


def test_a_kind_without_a_pallas_entry_keeps_the_xla_graph(stand_in):
    """A kind whose ``pallas`` is None (sr25519's was until PR 40; a
    stand-in here): whatever the implementation says, its mesh kernel
    is the XLA graph."""
    from tendermint_tpu.ops.sr25519_batch import SR25519

    assert SR25519.pallas == "compiled_verify_sr" and SR25519.pallas in pallas_verify._ENTRIES
    ran = []

    def graph(*args):
        ran.append(len(args))
        return jnp.ones((args[-1].shape[0],), bool)

    fake = type(SR25519)(
        SR25519.name, SR25519.engine, SR25519.kernel_name, graph, None, SR25519.inputs
    )
    inputs = {
        i.name: np.zeros((16, 32), np.uint8) for i in SR25519.inputs
    }
    out, _ = sharding.run_chunk_mesh(fake, inputs, "pallas", "vpu", plan_of(2))
    assert np.asarray(out).all() and ran == [len(SR25519.inputs)]


@pytest.mark.parametrize(
    "lanes,want", [(1, 1), (64, 64), (256, 256), (257, 512), (4096, 4096), (5462, 5632)]
)
def test_a_slab_above_one_block_is_whole_blocks(lanes, want):
    """16,384 lanes retried on three devices are 5,462 a device: the
    kernels' grid wants whole 256-lane blocks."""
    assert pallas_verify.shard_lanes(lanes) == want


def test_a_slab_that_is_not_whole_blocks_is_refused():
    avals = (jax.ShapeDtypeStruct((300, 32), jnp.uint8),) * 4
    with pytest.raises(ValueError, match="not whole blocks"):
        pallas_verify.stored_program("compiled_verify", avals, jax.devices()[0])


def test_the_program_digest_covers_the_kernel_sources(monkeypatch):
    """The store's key holds this file's and field32's bytes and the
    constant tables that enter the program by value."""
    from tendermint_tpu.ops import field32

    first = pallas_verify._program_digest()
    assert first == pallas_verify._program_digest()
    seen = []
    real = kernel_store.source_digest
    monkeypatch.setattr(
        kernel_store, "source_digest", lambda *mods: seen.append(mods) or real(*mods)
    )
    pallas_verify._program_digest.cache_clear()
    try:
        assert pallas_verify._program_digest() == first
        assert set(seen[0]) == {field32, pallas_verify}
        monkeypatch.setattr(pallas_verify, "_CONSTS", pallas_verify._CONSTS + 1)
        pallas_verify._program_digest.cache_clear()
        assert pallas_verify._program_digest() != first
    finally:
        pallas_verify._program_digest.cache_clear()


# --- the real table kernels ---------------------------------------------------


@pytest.mark.slow  # the table kernel's interpret-mode compile runs for minutes
@pytest.mark.parametrize("kind", ["tables", "resident"])
def test_table_kernels_sharded_agree_with_the_oracle(batch8, kind, fresh_store):
    """Seven of the table path's edge lanes and nine pad lanes, eight a
    shard on two devices, through the real table kernel."""
    (pks, msgs, sigs), want = one_device.lanes_table_edges(batch8)
    plan = plan_of(2)
    resident, gathered, host_ok = one_device._resident_chunk((pks, msgs, sigs), 7, 16)
    inputs = on_mesh(resident, plan) if kind == "resident" else gathered
    out, used, impl = ed25519_batch._run_chunk(KINDS[kind], inputs, None, plan)
    assert impl == "pallas" and used is plan
    got = np.asarray(out)
    assert list(np.logical_and(got[:7], host_ok[:7])) == want[:7]
    assert got[7:].all()


# --- what a sharded program is called, and where the store keeps it (PR 48) ----------


def _sr_stand_in(pk, r, s, k, *, block, interpret):
    return (pk[:, 0] == r[:, 0]) & (s[:, 1] == k[:, 1])


def _sharded_names(monkeypatch, kind, impl, inputs):
    """The names of the functions ``jax.jit`` was handed while ``kind``'s
    chunk went out over two devices under ``impl``."""
    names, jit = [], jax.jit

    def recording(fun, *args, **kwargs):
        names.append(getattr(fun, "__name__", "?"))
        return jit(fun, *args, **kwargs)

    monkeypatch.setattr(jax, "jit", recording)
    sharding._sharded_kernel.cache_clear()
    out, _ = sharding.run_chunk_mesh(kind, inputs, impl, "vpu", plan_of(2))
    jax.block_until_ready(out)
    return names


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize(
    "kind_name,want", [("tables", "run_shard"), ("sr25519", "run_shard_sr25519")]
)
def test_a_sharded_program_is_named_by_its_kind(monkeypatch, stand_in, impl, kind_name, want):
    """``jit_run_shard`` for the ed25519 kinds, ``jit_run_shard_sr25519``
    for sr25519's, under shard_map and under GSPMD alike: each a module
    of its own on a device trace, told from the one-chip programs
    (``jit_run``, ``jit_run_sr25519``), all of them ``jit_run*``."""
    import fnmatch

    from tendermint_tpu.ops.sr25519_batch import SR25519

    monkeypatch.setattr(pallas_verify, "verify_sr_fn", _sr_stand_in)
    if kind_name == "sr25519":
        kind = SR25519
        inputs = {i.name: np.zeros((16, 32), np.uint8) for i in kind.inputs}
    else:
        kind = KINDS["tables"]
        _, inputs, _ = table_chunk(16, seed=5)
    if impl == "xla":
        # the name is what is asked about, not the graph: a cheap kernel of the kind's arguments
        kind = type(kind)(
            kind.name, kind.engine, kind.kernel_name,
            lambda *a: jnp.ones((a[-1].shape[0],), bool), kind.pallas, kind.inputs,
            program=kind.program,
        )
    assert sharding.shard_program(kind) == want
    names = _sharded_names(monkeypatch, kind, impl, inputs)
    # under ``pallas`` a cold store first lowers the entry's program, named as one
    # chip names it (the device op on a trace); the sharded program is the module
    inner = [pallas_verify._ENTRIES[kind.pallas][2]] if impl == "pallas" else []
    assert names == inner + [want], names
    module = "jit_" + want
    assert fnmatch.fnmatch(module, "jit_run*")
    assert not fnmatch.fnmatch(module, "jit_run_sr25519*")  # the one-chip sr25519 program's pattern
    assert module != "jit_" + kind.program


def test_every_kinds_sharded_program_has_a_name_of_its_own_pattern():
    from tendermint_tpu.ops.sr25519_batch import SR25519

    got = {k.name: sharding.shard_program(k) for k in [*KINDS.values(), SR25519]}
    assert got == {"legacy": "run_shard", "tables": "run_shard", "resident": "run_shard",
                   "sr25519": "run_shard_sr25519"}


def test_a_stored_programs_file_name_does_not_hold_the_jitted_name(stand_in, fresh_store):
    """The store's key is what it was (PR 36): jax and jaxlib versions,
    platform, the kernel's name, one shard's argument shapes, and the
    caller's key (device kind, block, interpret, the sources' digest).
    What the sharded call is named is not in it, so a program stored
    before the name changed is still a hit."""
    import hashlib

    import jaxlib

    plan = plan_of(4)
    _, gathered, _ = table_chunk(16, seed=6)
    ed25519_batch._run_chunk(KINDS["tables"], gathered, None, plan)
    shard = (((8, 4, 32, 4), "uint8"), ((4,), "uint8"), ((4, 32), "uint8"), ((4, 32), "uint8"),
             ((4, 32), "uint8"))
    key = (plan.mesh.devices.flat[0].device_kind, 4, True, pallas_verify._program_digest())
    ident = repr((jax.__version__, jaxlib.__version__, "cpu", "verify_tables", shard,
                  tuple(str(k) for k in key)))
    want = "verify_tables-%s.jaxexport" % hashlib.sha256(ident.encode()).hexdigest()[:32]
    assert os.listdir(fresh_store) == [want]


@pytest.mark.slow  # the sr25519 body's interpret-mode compile for two devices: 483 s beside two busy processes (PR 48)
@pytest.mark.limit(1800)
def test_sr25519_kernel_sharded_agrees_with_the_oracle(fresh_store, ring):
    """The real sr25519 shard body (``_ENTRIES["compiled_verify_sr"]``,
    interpret mode), 64 lanes over two devices — 32 a device, padded to
    the narrowest bucket, a 64-lane slab each — through the engine's own
    entry: valid, tampered and non-canonical lanes against
    the schnorrkel oracle lane for lane, from a stored program that a
    second process finds. ``chip_smoke.py`` holds the real widths, on
    the chip, compiled for real."""
    from tendermint_tpu.ops import sr25519_batch
    from tests.test_pallas_sr25519 import distinct_lanes

    pubs, msgs, sigs, _, oracle = distinct_lanes()
    with mesh_mod.manager.forced(sharding.make_mesh(2)):
        got = sr25519_batch.verify_batch_sr(pubs, msgs, sigs)
    assert got == oracle and True in got and False in got
    (md,) = spans(ring, "mesh_dispatch")
    assert (md["args"]["kind"], md["args"]["impl"], md["args"]["devices"], md["args"]["lanes"]) == (
        "sr25519", "pallas", 2, 128,
    )
    (kc,) = spans(ring, "kernel_compile")
    assert (kc["args"]["kernel"], kc["args"]["lanes"], kc["args"]["devices"], kc["args"]["stored"]) == (
        "verify_sr", 64, 2, "miss",
    )
    assert [f.split("-")[0] for f in os.listdir(fresh_store)] == ["verify_sr"]
    assert not spans(ring, "host_fallback")
