"""The one-device Pallas entry points run from the kernel store (PR 50).

What ``pallas_verify.compiled_verify`` / ``_tables`` / ``_resident`` /
``_sr`` jit is a thin function around the entry's lowered program from
ops/kernel_store.py, fetched at the first call with a set of argument
shapes (``pallas_verify.stored_program``, which a mesh runs per shard
under ``shard_map``: tests/test_sharded_pallas.py).
Here, on the CPU at 8 lanes with each kernel body replaced by a cheap
lane-local stand-in and the store pinned to ``tmp_path``, everything
around the body: a process that finds the store warm never walks it,
what names another file, what the programs are called on a device
trace, and what the first call's span says. The real bodies from a warm
store against the oracles: tests/test_pallas_verify.py and
tests/test_pallas_sr25519.py, beside the compiles they share.
"""

import fnmatch
import os
import re

import jax.numpy as jnp
import numpy as np
import pytest

from tendermint_tpu.libs import tracing
from tendermint_tpu.ops import ed25519_batch, introspect, kernel_store, pallas_verify
from tendermint_tpu.ops.sr25519_batch import SR25519

N = 8
# entry point -> (its body, the span's kernel, the module a device trace shows)
ENTRIES = {
    "compiled_verify": ("verify_fn", "verify", "jit__lambda*"),
    "compiled_verify_tables": ("verify_tables_fn", "verify_tables", "jit__lambda*"),
    "compiled_verify_resident": ("verify_resident_fn", "verify_resident", "jit__lambda*"),
    "compiled_verify_sr": ("verify_sr_fn", "verify_sr", "jit_run_sr25519*"),
}
KIND_OF = {k.pallas: k for k in [*ed25519_batch.KINDS.values(), SR25519]}
entries = pytest.mark.parametrize("entry", list(ENTRIES))


def _rows_body(pk, r, s, k, *, block, interpret):
    WALKS.append(("rows", pk.shape, block, interpret))
    return (pk[:, 0] == r[:, 0]) & (s[:, 1] == k[:, 1])


def _tables_body(tab, a_ok, r, s, k, *, block, interpret):
    WALKS.append(("tables", tab.shape, block, interpret))
    return (tab[0, 0, 0, :] == r[:, 0]) & (s[:, 1] == k[:, 1]) & (a_ok != 0)


def _boom(*args, **kwargs):
    raise AssertionError("the kernel body was walked in a process that found the store warm")


WALKS = []


def _forget():
    for entry in ENTRIES:
        getattr(pallas_verify, entry).cache_clear()


@pytest.fixture
def stand_ins(monkeypatch, tmp_path):
    """Cheap lane-local bodies (a lane's verdict says that its own rows,
    table column and ``ok`` bit reached it), a store of the test's own
    and no jitted entry of one test left in a factory for the next."""
    root = str(tmp_path / "kernel_store")
    monkeypatch.setattr(kernel_store, "directory", lambda: root)
    monkeypatch.setattr(pallas_verify, "verify_fn", _rows_body)
    monkeypatch.setattr(pallas_verify, "verify_sr_fn", _rows_body)
    monkeypatch.setattr(pallas_verify, "verify_tables_fn", _tables_body)
    del WALKS[:]
    _forget()
    yield root
    _forget()


def next_process(monkeypatch):
    """What a restart leaves: the store, and nothing traced or jitted.
    Every body raises from here on."""
    _forget()
    for body, _, _ in ENTRIES.values():
        monkeypatch.setattr(pallas_verify, body, _boom)


@pytest.fixture
def ring():
    tracing.configure("ring")
    tracing.tracer.clear()
    yield tracing.tracer
    tracing.configure("off")
    tracing.tracer.clear()


def first_calls(ring):
    return [
        e["args"] for e in ring.export(clear=True)["traceEvents"]
        if e.get("ph") == "X" and e["name"] == "kernel_compile"
    ]


def counted():
    snap = introspect.accountant.snapshot()
    return np.array([
        snap["compile_events"].get("pallas", 0),
        snap["stored_programs"].get("miss", 0),
        snap["stored_programs"].get("hit", 0),
    ])


def lanes(entry, k_cols=16, seed=7):
    """``(args, verdicts)`` of eight lanes for ``entry``'s stand-in: lane 2
    is forged (its k is not its s's), lane 5's R is another lane's, and a
    table entry refuses lane 6 by its ``ok`` bit."""
    rng = np.random.default_rng(seed)
    r = rng.integers(0, 255, (N, 32), dtype=np.uint8)
    s = rng.integers(0, 255, (N, 32), dtype=np.uint8)
    k = s.copy()
    want = np.ones(N, bool)
    k[2, 1] ^= 1
    want[2] = False
    if ENTRIES[entry][0] in ("verify_fn", "verify_sr_fn"):
        pk = rng.integers(0, 255, (N, 32), dtype=np.uint8)
        pk[:, 0] = r[:, 0]
        pk[5, 0] ^= 1
        want[5] = False
        return tuple(jnp.asarray(a) for a in (pk, r, s, k)), want
    store = rng.integers(0, 255, (8, 4, 32, k_cols), dtype=np.uint8)
    idx = rng.integers(0, k_cols, N).astype(np.int32)
    r[:, 0] = store[0, 0, 0, idx]
    r[5, 0] ^= 1
    ok = np.ones(N, np.uint8)
    ok[6] = 0
    want[[5, 6]] = False
    if entry == "compiled_verify_tables":
        head = (np.ascontiguousarray(store[:, :, :, idx]), ok)
    else:
        head = (store, idx, ok)
    return tuple(jnp.asarray(a) for a in (*head, r, s, k)), want


def run(entry, args):
    return np.asarray(getattr(pallas_verify, entry)(N, block=N, interpret=True)(*args))


# --- a restart loads, it does not walk ----------------------------------------


@entries
def test_a_process_that_finds_the_store_warm_never_walks_the_body(
    stand_ins, monkeypatch, entry
):
    args, want = lanes(entry)
    before = counted()
    first = run(entry, args)
    np.testing.assert_array_equal(first, want)
    assert not want.all() and want.any()
    assert len(WALKS) == 1 and WALKS[0][2:] == (N, True)
    (name,) = os.listdir(stand_ins)
    assert name.startswith(ENTRIES[entry][1] + "-") and name.endswith(".jaxexport")
    assert tuple(counted() - before) == (1, 1, 0)
    next_process(monkeypatch)
    np.testing.assert_array_equal(run(entry, args), first)
    np.testing.assert_array_equal(run(entry, args), first)  # and from the jitted program after
    assert tuple(counted() - before) == (2, 1, 1)
    assert os.listdir(stand_ins) == [name] and len(WALKS) == 1


@entries
def test_the_first_call_is_a_kernel_compile_span_that_says_hit_or_miss(
    stand_ins, monkeypatch, ring, entry
):
    """``first_call_s`` reads these spans: a hit records one too, it is
    just short. Later calls record none."""
    args, _ = lanes(entry)
    want = {"engine": "pallas", "kernel": ENTRIES[entry][1], "lanes": N, "impl": "pallas"}
    for stored in ("miss", "hit"):
        run(entry, args)
        run(entry, args)
        (span,) = first_calls(ring)
        assert {k: span[k] for k in want} == want
        assert span["stored"] == stored and "devices" not in span
        next_process(monkeypatch)


@entries
def test_a_program_of_other_sources_is_a_miss_and_a_second_file(stand_ins, monkeypatch, entry):
    """The sources' digest is in the file's name: after an upgrade the
    old program is not even looked at."""
    args, want = lanes(entry)
    run(entry, args)
    monkeypatch.setattr(pallas_verify, "_program_digest", lambda: "other sources")
    _forget()
    before = counted()
    np.testing.assert_array_equal(run(entry, args), want)
    assert tuple(counted() - before) == (1, 1, 0) and len(WALKS) == 2
    assert len(os.listdir(stand_ins)) == 2


@entries
def test_the_jitted_program_is_called_what_the_benchmark_reads(stand_ins, monkeypatch, entry):
    """The module's name on a device trace is the thin function's:
    ``jit__lambda*`` for the ed25519 entries, ``jit_run_sr25519*`` for
    sr25519's (chipbench/layer_metrics/kernel_ms.*, verify_roofline.*,
    sr25519_roofline), from a cold store and from a warm one; and the
    stored program, whose name the device op carries, is called the same."""
    args, _ = lanes(entry)
    pattern = ENTRIES[entry][2]
    lowered_names = []
    real = kernel_store.jax.export.export

    def export(fn, **kwargs):
        lowered_names.append(fn.__name__)
        return real(fn, **kwargs)

    monkeypatch.setattr(kernel_store.jax.export, "export", export)
    jitted = []
    real_jit = pallas_verify.jax.jit
    monkeypatch.setattr(
        pallas_verify.jax, "jit", lambda fn, **kw: jitted.append(real_jit(fn, **kw)) or jitted[-1]
    )
    for _ in ("miss", "hit"):
        run(entry, args)
        module = re.search(r"module @(\S+)", jitted[-1].lower(*args).as_text()).group(1)
        assert fnmatch.fnmatch(module, pattern), module
        assert fnmatch.fnmatch(module, "jit_run*") or pattern == "jit__lambda*"
        assert fnmatch.fnmatch(module, "jit_run_sr25519*") == (entry == "compiled_verify_sr")
        next_process(monkeypatch)
    assert ["jit_" + n.replace("<", "_").replace(">", "") for n in lowered_names] == [
        pattern.rstrip("*")
    ]


# --- the resident store's width ------------------------------------------------


def test_a_second_store_width_fetches_its_own_program(stand_ins, monkeypatch, ring):
    """A store width K the entry has not met is a program of its own: a
    miss and a second file where the store has not met it either — and
    in a process that finds both there, neither walks the body."""
    entry = "compiled_verify_resident"
    narrow, want_narrow = lanes(entry, k_cols=16)
    wide, want_wide = lanes(entry, k_cols=32, seed=8)
    before = counted()
    np.testing.assert_array_equal(run(entry, narrow), want_narrow)
    np.testing.assert_array_equal(run(entry, wide), want_wide)
    np.testing.assert_array_equal(run(entry, narrow), want_narrow)
    assert [w[1] for w in WALKS] == [(8, 4, 32, N)] * 2  # the gather ran ahead of both
    assert len(os.listdir(stand_ins)) == 2
    # one entry, one first call: the second width is no kernel_compile span
    assert tuple(counted() - before) == (1, 2, 0)
    assert [s["stored"] for s in first_calls(ring)] == ["miss"]
    next_process(monkeypatch)
    np.testing.assert_array_equal(run(entry, wide), want_wide)
    np.testing.assert_array_equal(run(entry, narrow), want_narrow)
    assert tuple(counted() - before) == (2, 2, 2)
    assert len(os.listdir(stand_ins)) == 2 and len(WALKS) == 2


# --- one builder, two users ----------------------------------------------------


@pytest.mark.parametrize("entry", ["compiled_verify_tables", "compiled_verify_sr"])
def test_one_device_and_a_shard_of_the_same_shapes_are_one_file(stand_ins, monkeypatch, entry):
    """The key is the mesh's (device kind, block, interpret, the sources'
    digest) and so is the name: what two devices stored for slabs of
    eight lanes, one device with eight lanes loads."""
    from tendermint_tpu.parallel import mesh as mesh_mod, sharding

    kind = KIND_OF[entry]
    args, want = lanes(entry)
    inputs = {i.name: np.concatenate([np.asarray(a)] * 2, axis=i.lane_axis)
              for i, a in zip(kind.inputs, args)}
    sharding._sharded_kernel.cache_clear()
    try:
        plan = mesh_mod.MeshPlan(sharding.make_mesh(2), (0, 1), {}, True)
        out, _ = sharding.run_chunk_mesh(kind, inputs, "pallas", "vpu", plan)
        np.testing.assert_array_equal(np.asarray(out), np.concatenate([want] * 2))
    finally:
        sharding._sharded_kernel.cache_clear()
    files = os.listdir(stand_ins)
    assert len(files) == 1
    next_process(monkeypatch)
    before = counted()
    np.testing.assert_array_equal(run(entry, args), want)
    assert tuple(counted() - before) == (1, 0, 1) and os.listdir(stand_ins) == files


@entries
def test_the_runner_reaches_the_store_on_one_device(stand_ins, monkeypatch, ring, entry):
    """``_run_chunk`` with ``pallas`` resolved and no mesh: the kind's
    entry point at the chunk's lanes, interpreted off the TPU without
    being told to, from the store."""
    monkeypatch.setattr(ed25519_batch, "active_impl", lambda backend=None: "pallas")
    monkeypatch.setattr(ed25519_batch, "_mul_impl_for_chunk", lambda impl, backend, lanes: "vpu")
    kind = KIND_OF[entry]
    args, want = lanes(entry)
    inputs = {i.name: a if i.lane_axis is None else np.asarray(a) for i, a in zip(kind.inputs, args)}
    if kind.store_bound:
        inputs["mesh_key"] = None
    for stored in ("miss", "hit"):
        out, used, impl = ed25519_batch._run_chunk(kind, inputs, None)
        assert (used, impl) == (None, "pallas")
        np.testing.assert_array_equal(np.asarray(out), want)
        (span,) = first_calls(ring)
        assert (span["kernel"], span["lanes"], span["stored"]) == (kind.kernel_name, N, stored)
        next_process(monkeypatch)
    assert [w[2:] for w in WALKS] == [(N, True)]
