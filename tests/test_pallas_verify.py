"""Interpret-mode parity: the Pallas kernel vs the ZIP-215 oracle.

The Pallas verifier (ops/pallas_verify.py) restates the field32/curve32
math with kernel-local ops; these tests pin it to the pure-Python
ZIP-215 oracle (crypto/ed25519_ref.py) on the same edge vectors
test_ops_ed25519.py uses for the XLA graph, running the kernel in
interpret mode so no TPU is needed (reference test model: substitute a
fake backend, SURVEY.md section 4; semantics from
crypto/ed25519/ed25519.go:24-31).

Interpret mode traces the kernel body as ordinary JAX ops, so one
compile of the 8-lane block is shared by every test in this module.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from tendermint_tpu.crypto import ed25519_ref as ref
from tendermint_tpu.ops import ed25519_batch, pallas_verify


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


def test_pallas_valid_batch(batch8):
    pks, msgs, sigs = batch8
    assert pallas_verify_batch(pks, msgs, sigs) == [True] * 8


def test_pallas_flags_bad_entries(batch8):
    pks, msgs, sigs = (list(x) for x in batch8)
    sigs[1] = sigs[1][:32] + bytes(32)  # wrong s
    msgs[3] = b"tampered"  # wrong msg
    sigs[5] = bytes(32) + sigs[5][32:]  # R replaced (y=0 IS on curve)
    pks[6] = keypair(7)[1]  # wrong key
    got = pallas_verify_batch(pks, msgs, sigs)
    assert got == [True, False, True, False, True, False, False, True]


def test_pallas_zip215_edge_cases(batch8):
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
    got = pallas_verify_batch(pks, msgs, sigs)
    assert got == [True, True, False, True, True, True, True, True]


def test_pallas_off_curve_and_mutations(batch8):
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
    got = pallas_verify_batch(pks, msgs, sigs)
    assert got == want


def pallas_verify_batch_tables(pks, msgs, sigs):
    """Table-input kernel: host-built precompute columns, no in-kernel
    table construction. Mirrors _run_chunk_tables' pallas branch."""
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


@pytest.mark.slow  # interpret-mode XLA compile of this kernel runs ~8 min
def test_pallas_table_path_parity(batch8):
    pks, msgs, sigs = (list(x) for x in batch8)
    pks[0] = bytes([2] + [0] * 31)  # off-curve: identity table, ok=False
    sigs[1] = sigs[1][:33] + bytes([sigs[1][33] ^ 1]) + sigs[1][34:]
    msgs[2] = b"tampered"
    pks[3] = (ref.P + 1).to_bytes(32, "little")  # non-canonical encoding
    want = [ref.verify_zip215(pk, m, s) for pk, m, s in zip(pks, msgs, sigs)]
    assert pallas_verify_batch_tables(pks, msgs, sigs) == want


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
