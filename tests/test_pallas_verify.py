"""Interpret-mode parity: the Pallas kernel vs the ZIP-215 oracle.

The Pallas verifier (ops/pallas_verify.py) restates the field32/curve32
math with kernel-local ops; these tests pin it to the pure-Python
ZIP-215 oracle (crypto/ed25519_ref.py) on the same edge vectors
test_ops_ed25519.py uses for the XLA graph, running the kernel in
interpret mode so no TPU is needed (reference test model: substitute a
fake backend, SURVEY.md section 4; semantics from
crypto/ed25519/ed25519.go:24-31).

Interpret mode traces the kernel body as ordinary JAX ops, so one
compile of the 8-lane block is shared by every test in this module:
440 s of XLA:CPU on a cold ``.jax_cache`` (PR 46), which a fresh
checkout's always is, and the one interpreted compile of the ed25519
body that tier-1 keeps. ``tests/conftest.py`` therefore starts the run
with this file, and the four oracle tests, whichever of them runs
first, carry a limit of their own.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from tendermint_tpu.crypto import ed25519_ref as ref
from tendermint_tpu.ops import ed25519_batch, pallas_verify


# the first of the four oracle tests to run compiles the kernel: 440 s alone (PR 46), and what
# the limit cuts short is compiled again by the next test, so the limit leaves it room beside
# five busy workers
compiles_the_kernel = pytest.mark.limit(1200)


def keypair(i):
    return ref.keypair_from_seed(bytes([i + 1]) * 32)


def pallas_verify_batch(pks, msgs, sigs):
    """verify_batch semantics routed through the interpret-mode kernel."""
    n = len(pks)
    pad = ((n + 7) // 8) * 8
    inputs, host_ok = ed25519_batch.prepare_batch(pks, msgs, sigs, pad_to=pad)
    fn = pallas_verify.compiled_verify(pad, block=8, interpret=True)
    out = fn(
        jnp.asarray(inputs["pk"]),
        jnp.asarray(inputs["r"]),
        jnp.asarray(inputs["s"]),
        jnp.asarray(inputs["k"]),
    )
    return list(np.logical_and(np.asarray(out)[:n], host_ok))


@pytest.fixture(scope="module")
def batch8():
    pks, msgs, sigs = [], [], []
    for i in range(8):
        priv, pub = keypair(i)
        msg = b"vote %d" % i
        pks.append(pub)
        msgs.append(msg)
        sigs.append(ref.sign(priv, msg))
    return pks, msgs, sigs


@compiles_the_kernel
def test_pallas_valid_batch(batch8):
    pks, msgs, sigs = batch8
    assert pallas_verify_batch(pks, msgs, sigs) == [True] * 8


def lanes_bad_entries(batch8):
    """(lanes, verdicts): wrong s, wrong message, R replaced, wrong key."""
    pks, msgs, sigs = (list(x) for x in batch8)
    sigs[1] = sigs[1][:32] + bytes(32)  # wrong s
    msgs[3] = b"tampered"  # wrong msg
    sigs[5] = bytes(32) + sigs[5][32:]  # R replaced (y=0 IS on curve)
    pks[6] = keypair(7)[1]  # wrong key
    return (pks, msgs, sigs), [True, False, True, False, True, False, False, True]


def lanes_zip215_edge_cases(batch8):
    """(lanes, verdicts): the ZIP-215 edge cases of crypto/ed25519/ed25519.go:24-31."""
    pks, msgs, sigs = (list(x) for x in batch8)
    # identity pubkey: R = [s]B verifies for any msg (small-order accepted)
    ident = (1).to_bytes(32, "little")
    s = 12345
    rb = ref.pt_compress(ref.pt_mul(s, ref.B_POINT))
    sig215 = rb + s.to_bytes(32, "little")
    assert ref.verify_zip215_slow(ident, b"x", sig215)
    pks[0], msgs[0], sigs[0] = ident, b"x", sig215
    # non-canonical encoding of the same point
    pks[1], msgs[1], sigs[1] = (ref.P + 1).to_bytes(32, "little"), b"x", sig215
    # s >= L must be rejected even though the curve equation would hold
    pks[2], msgs[2], sigs[2] = ident, b"x", rb + (s + ref.L).to_bytes(32, "little")
    return (pks, msgs, sigs), [True, True, False, True, True, True, True, True]


def lanes_off_curve_and_mutations(batch8):
    """(lanes, verdicts): an off-curve key and single-bit mutations of
    R, s and the key; the verdicts are the oracle's."""
    pks, msgs, sigs = (list(x) for x in batch8)
    rng = np.random.RandomState(7)
    pks[0] = bytes([2] + [0] * 31)  # y=2: off-curve, must reject
    for i in range(1, 8):
        mode = i % 4
        if mode == 0:
            continue  # leave valid
        b = bytearray(sigs[i])
        if mode == 1:
            b[rng.randint(32)] ^= 1 << rng.randint(8)  # corrupt R
        elif mode == 2:
            b[32 + rng.randint(31)] ^= 1 << rng.randint(8)  # corrupt s
        else:
            pk = bytearray(pks[i])
            pk[rng.randint(32)] ^= 1 << rng.randint(8)
            pks[i] = bytes(pk)
        sigs[i] = bytes(b)
    want = [ref.verify_zip215(pk, m, s) for pk, m, s in zip(pks, msgs, sigs)]
    return (pks, msgs, sigs), want


@compiles_the_kernel
def test_pallas_flags_bad_entries(batch8):
    lanes, want = lanes_bad_entries(batch8)
    assert pallas_verify_batch(*lanes) == want


@compiles_the_kernel
def test_pallas_zip215_edge_cases(batch8):
    lanes, want = lanes_zip215_edge_cases(batch8)
    assert pallas_verify_batch(*lanes) == want


@compiles_the_kernel
def test_pallas_off_curve_and_mutations(batch8):
    lanes, want = lanes_off_curve_and_mutations(batch8)
    assert pallas_verify_batch(*lanes) == want


def pallas_verify_batch_tables(pks, msgs, sigs):
    """Table-input kernel: host-built precompute columns, no in-kernel
    table construction. What _run_chunk does with a ``tables`` chunk under
    ``pallas``."""
    from tendermint_tpu.ops import precompute

    n = len(pks)
    pad = ((n + 7) // 8) * 8
    tabs, oks = zip(*(precompute.build_table(pk) for pk in pks))
    inputs, host_ok = ed25519_batch._prep_table_chunk(
        pks, msgs, sigs, list(tabs), list(oks), pad_to=pad
    )
    fn = pallas_verify.compiled_verify_tables(pad, block=8, interpret=True)
    out = fn(
        jnp.asarray(inputs["tab"]),
        jnp.asarray(inputs["ok"]),
        jnp.asarray(inputs["r"]),
        jnp.asarray(inputs["s"]),
        jnp.asarray(inputs["k"]),
    )
    return list(np.logical_and(np.asarray(out)[:n], host_ok))


def lanes_table_edges(batch8):
    """(lanes, verdicts) for the table kernels: what a table and its
    ``ok`` bit must carry (off-curve and non-canonical keys) beside a
    flipped s and a wrong message; the verdicts are the oracle's."""
    pks, msgs, sigs = (list(x) for x in batch8)
    pks[0] = bytes([2] + [0] * 31)  # off-curve: identity table, ok=False
    sigs[1] = sigs[1][:33] + bytes([sigs[1][33] ^ 1]) + sigs[1][34:]
    msgs[2] = b"tampered"
    pks[3] = (ref.P + 1).to_bytes(32, "little")  # non-canonical encoding
    want = [ref.verify_zip215(pk, m, s) for pk, m, s in zip(pks, msgs, sigs)]
    return (pks, msgs, sigs), want


@pytest.mark.slow  # interpret-mode XLA compile of this kernel runs ~8 min
def test_pallas_table_path_parity(batch8):
    (pks, msgs, sigs), want = lanes_table_edges(batch8)
    assert pallas_verify_batch_tables(pks, msgs, sigs) == want


def _resident_store(pks):
    """The (8, 4, 32, K) uint8 tensor ops/resident.py uploads: column 0
    the pad key's table, then one column a key."""
    from tendermint_tpu.ops import precompute

    tabs, oks = zip(*(precompute.build_table(pk) for pk in pks))
    cols = [ed25519_batch._pad_table()] + list(tabs)
    store = np.ascontiguousarray(np.stack(cols).transpose(1, 2, 3, 0))
    return store, list(tabs), np.asarray(oks, dtype=np.uint8)


def _resident_chunk(batch, n, pad):
    """The first n lanes of a batch as _prep_resident_chunk hands them
    over, padded to ``pad`` lanes, beside the gathered-table chunk of
    the same lanes."""
    pks, msgs, sigs = (list(x[:n]) for x in batch)
    store, tabs, oks = _resident_store(pks)
    res, host_ok = ed25519_batch._prep_resident_chunk(
        pks, msgs, sigs, np.arange(1, n + 1), oks, store, None, pad_to=pad
    )
    gathered, _ = ed25519_batch._prep_table_chunk(
        pks, msgs, sigs, tabs, list(oks), pad_to=pad
    )
    return res, gathered, host_ok


def _resident_args(inputs):
    return (
        jnp.asarray(inputs["store"]),
        jnp.asarray(inputs["idx"]),
        jnp.asarray(inputs["ok"]),
        jnp.asarray(inputs["r"]),
        jnp.asarray(inputs["s"]),
        jnp.asarray(inputs["k"]),
    )


@pytest.mark.slow  # the table kernel's interpret-mode compile, as above
def test_pallas_resident_path_parity(batch8):
    """The resident entry: same edge lanes as the table path, seven
    lanes so that the eighth is a pad lane on column 0."""
    (pks, msgs, sigs), want = lanes_table_edges(batch8)
    inputs, _, host_ok = _resident_chunk((pks, msgs, sigs), 7, 8)
    fn = pallas_verify.compiled_verify_resident(8, block=8, interpret=True)
    out = np.asarray(fn(*_resident_args(inputs)))
    assert list(np.logical_and(out[:7], host_ok)) == want[:7]
    assert out[7]  # the pad lane verifies: it can mask nothing


@pytest.fixture
def resident_entry(batch8, monkeypatch, tmp_path):
    """One first call of the resident entry point with the kernel behind
    it stubbed (no Pallas compile): what reached ``verify_tables_fn``,
    the events of the call, and the program jit was given. The kernel
    store is the fixture's own: a file's name does not know a stubbed
    body, and the real store must never hold one."""
    import jax

    from tendermint_tpu.libs import tracing
    from tendermint_tpu.ops import kernel_store

    monkeypatch.setattr(kernel_store, "directory", lambda: str(tmp_path / "kernel_store"))

    def kernel_inputs(tab, a_ok, r, s, k, *, block, interpret):
        n = r.shape[0]
        # the layout verify_tables_fn hands _verify_tables_kernel
        return tab.astype(jnp.float32).reshape(8, 4 * pallas_verify.NLIMBS, n), a_ok

    jitted = []
    real_jit = jax.jit

    def spy_jit(fun, *args, **kwargs):
        jitted.append(real_jit(fun, *args, **kwargs))
        return jitted[-1]

    monkeypatch.setattr(pallas_verify, "verify_tables_fn", kernel_inputs)
    monkeypatch.setattr(jax, "jit", spy_jit)
    inputs, gathered, _ = _resident_chunk(batch8, 5, 8)
    args = _resident_args(inputs)
    # past the lru_cache: a stubbed program must not stay in it
    fn = pallas_verify.compiled_verify_resident.__wrapped__(8)
    from tendermint_tpu.ops import introspect

    def counted():
        return introspect.accountant.snapshot()["compile_events"].get("pallas", 0)

    before = counted()
    tracing.tracer.set_metrics_observer(None)
    tracing.configure("ring")
    tracing.tracer.clear()
    try:
        first = fn(*args)
        second = fn(*args)
        events = [
            e for e in tracing.tracer.export(clear=True)["traceEvents"]
            if e.get("ph") == "X"
        ]
    finally:
        tracing.configure("off")
        tracing.tracer.clear()
    monkeypatch.setattr(jax, "jit", real_jit)
    # the store's lowering of the (stubbed) body at the first call, then the program the calls run
    _, program = jitted
    return {
        "first": first, "second": second, "events": events,
        "gathered": gathered, "program": program, "args": args,
        "compile_events": counted() - before,
    }


def test_resident_entry_gathers_what_the_host_gather_ships(resident_entry):
    """take + reshape on the device == _prep_table_chunk's tensor in the
    kernel's (8, 128, n) layout, pad lanes (column 0) included."""
    gathered = resident_entry["gathered"]
    want = gathered["tab"].astype(np.float32).reshape(8, 128, 8)
    for tab, a_ok in (resident_entry["first"], resident_entry["second"]):
        np.testing.assert_array_equal(np.asarray(tab), want)
        np.testing.assert_array_equal(np.asarray(a_ok), gathered["ok"])
    # lanes 5..7 are pad lanes: the pad key's table, a_ok true
    pad = ed25519_batch._pad_table().astype(np.float32).reshape(8, 128)
    np.testing.assert_array_equal(want[:, :, 7], pad)


def test_resident_entry_keeps_the_names_the_benchmark_reads(resident_entry):
    """chipbench finds the padded width by ``kernel="verify_resident"``
    and the program's device time by the name ``jit__lambda*``."""
    import fnmatch
    import re

    compiles = [e for e in resident_entry["events"] if e["name"] == "kernel_compile"]
    assert len(compiles) == 1  # the first call only
    assert resident_entry["compile_events"] == 1
    a = compiles[0]["args"]
    assert (a["kernel"], a["engine"], a["lanes"]) == ("verify_resident", "pallas", 8)
    text = resident_entry["program"].lower(*resident_entry["args"]).as_text()
    name = re.search(r"module @(\S+)", text).group(1)
    assert fnmatch.fnmatch(name, "jit__lambda*"), name


def pallas_verify_batch_resident(pks, msgs, sigs):
    """The resident entry on up to eight lanes whose keys are the store's."""
    n = len(pks)
    inputs, _, host_ok = _resident_chunk((pks, msgs, sigs), n, 8)
    fn = pallas_verify.compiled_verify_resident(8, block=8, interpret=True)
    return list(np.logical_and(np.asarray(fn(*_resident_args(inputs)))[:n], host_ok))


@pytest.mark.parametrize(
    "entry,body,verify",
    [
        pytest.param("compiled_verify", "verify_fn", pallas_verify_batch, marks=compiles_the_kernel),
        # the table kernel's interpret-mode compile, as above
        pytest.param("compiled_verify_tables", "verify_tables_fn", pallas_verify_batch_tables,
                     marks=pytest.mark.slow),
        pytest.param("compiled_verify_resident", "verify_tables_fn", pallas_verify_batch_resident,
                     marks=pytest.mark.slow),
    ],
)
def test_a_restarted_process_runs_the_real_kernel_from_the_store(monkeypatch, entry, body, verify):
    """chip_smoke.py's edge vectors — the ZIP-215 cases, ordinary lanes
    and two forged ones — eight lanes a call through the real kernel,
    then again as a process that finds the kernel store warm does: the
    factory has forgotten its program, the body raises if it is walked,
    and lane for lane the verdicts are the first's and the oracle's.
    The real store, beside the compile cache that holds the executable
    (a file's name holds the sources' digest: no stand-in is ever here)."""
    import chip_smoke
    from tendermint_tpu.ops import kernel_store

    assert kernel_store.directory()
    pks, msgs, sigs = (col[:16] for col in chip_smoke.edge_vectors())
    want = [ref.verify_zip215(pk, m, s) for pk, m, s in zip(pks, msgs, sigs)]
    assert want.count(False) >= 4 and not want[11] and not want[15]  # the forged lanes

    def verdicts():
        return [v for at in (0, 8) for v in verify(pks[at:at + 8], msgs[at:at + 8], sigs[at:at + 8])]

    factory = getattr(pallas_verify, entry)
    try:
        assert verdicts() == want
        factory.cache_clear()

        def boom(*args, **kwargs):
            raise AssertionError("the kernel body was walked in a process that found the store warm")

        monkeypatch.setattr(pallas_verify, body, boom)
        assert verdicts() == want
    finally:
        factory.cache_clear()


def _stub_kernel(calls, name):
    def factory(n, *args, **kwargs):
        def kernel(*kargs):
            calls.append((name, n, kargs))
            return "verdicts of " + name

        return kernel

    return factory


_RUNNER_CASES = [
    (kind, case)
    for kind in ("legacy", "tables", "resident")
    for case in ("pallas", "xla", "mxu", "mesh")
] + [("resident", "context_mismatch")]


@pytest.mark.parametrize("kind,case", _RUNNER_CASES)
def test_run_chunk_picks_the_kernel(batch8, monkeypatch, kind, case):
    """The one runner, for every chunk kind: on one device and on a
    mesh it follows active_impl (the mesh is handed the implementation
    and runs its own kernel for it: the one-device entry points are not
    reached); a resident store committed elsewhere re-enters as a
    gathered-table chunk; and the ``impl`` it returns is what the chunk
    was handed to."""
    from types import SimpleNamespace

    from tendermint_tpu.parallel import sharding

    calls = []
    impl = case if case in ("xla", "mxu") else "pallas"
    monkeypatch.setattr(ed25519_batch, "active_impl", lambda backend=None: impl)
    monkeypatch.setattr(
        ed25519_batch, "_mul_impl_for_chunk", lambda impl, backend, lanes: "vpu"
    )
    for k in ed25519_batch.KINDS.values():
        monkeypatch.setattr(
            pallas_verify, k.pallas, _stub_kernel(calls, "pallas:" + k.name)
        )
    monkeypatch.setattr(
        ed25519_batch,
        "_compiled_kernel",
        lambda k, n, backend, mul_impl: _stub_kernel(calls, "xla:" + k.name)(n),
    )
    monkeypatch.setattr(
        sharding,
        "run_chunk_mesh",
        lambda k, inputs, impl, mul_impl, plan, sp: (
            calls.append(("mesh", k.lanes(inputs), (k.name, impl))),
            plan,
        ),
    )
    resident, gathered, _ = _resident_chunk(batch8, 5, 8)
    if kind == "legacy":
        pks, msgs, sigs = (list(x[:5]) for x in batch8)
        inputs, _ = ed25519_batch.prepare_batch(pks, msgs, sigs, pad_to=8)
    else:
        inputs = resident if kind == "resident" else gathered
    plan = None
    if case == "mesh":
        plan = SimpleNamespace(device_ids=(0, 1))
        if kind == "resident":
            inputs["mesh_key"] = (0, 1)
    elif case == "context_mismatch":
        inputs["mesh_key"] = (0, 1)  # uploaded for a mesh that is gone
    chunk_kind = ed25519_batch.KINDS[kind]
    out, used, got_impl = ed25519_batch._run_chunk(chunk_kind, inputs, None, plan)
    ((name, n, got),) = calls
    assert n == 8 and used is plan
    if case == "mesh":
        assert (name, got, got_impl) == ("mesh", (kind, "pallas"), "pallas")
    elif case == "context_mismatch":
        # re-gathered on the host and re-entered as a gathered-table chunk
        assert (name, got_impl) == ("pallas:tables", "pallas")
        np.testing.assert_array_equal(np.asarray(got[0]), gathered["tab"])
    else:
        assert name == ("pallas:" if case == "pallas" else "xla:") + kind
        assert out == "verdicts of " + name and got_impl == impl
        assert len(got) == len(chunk_kind.inputs)
        for arg, spec in zip(got, chunk_kind.inputs):
            if spec.lane_axis is None:
                assert arg is inputs[spec.name]  # the store, as uploaded
                continue
            assert arg.shape[spec.lane_axis] == 8
            np.testing.assert_array_equal(np.asarray(arg), inputs[spec.name])


def test_auto_resolves_to_one_impl_per_platform(monkeypatch):
    """``auto`` means exactly one implementation per platform (pallas on
    tpu, the XLA graph on cpu); the env switch overrides it."""
    from tendermint_tpu.ops import backend as backend_mod

    monkeypatch.delenv(ed25519_batch._IMPL_ENV, raising=False)
    monkeypatch.setattr(backend_mod, "platform", lambda b=None: "tpu")
    assert ed25519_batch.active_impl() == "pallas"
    monkeypatch.setattr(backend_mod, "platform", lambda b=None: "cpu")
    assert ed25519_batch.active_impl() == "xla"
    for mode in ("pallas", "xla", "mxu"):
        monkeypatch.setenv(ed25519_batch._IMPL_ENV, mode)
        assert ed25519_batch.active_impl() == mode
