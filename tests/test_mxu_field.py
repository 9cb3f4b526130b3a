"""The int8-MXU field-multiply formulation vs the f32 engine and the
host oracle (ops/field_mxu.py).

Pins, on the CPU backend:

- value parity of fe_mul_mxu with field32.fe_mul and with Python-int
  arithmetic across random loose inputs and boundary values;
- the output invariant (limbs bounded like fe_carry's contract) so the
  mxu product composes with every downstream field op;
- the lowering contract the TPU path depends on: the hot contraction is
  a single dot_general with int8 operands and an int32 accumulator
  (the quantized-matmul pattern XLA maps to the MXU int8 systolic
  path);
- end-to-end signature verification parity through verify_kernel with
  the trace-time switch engaged, including the compiled-cache keying.

Reference semantics unchanged: crypto/ed25519/ed25519.go:198-233.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from tendermint_tpu.crypto import ed25519_ref as ref
from tendermint_tpu.ops import ed25519_batch as eb, field32 as field
from tendermint_tpu.ops.field_mxu import fe_mul_mxu


def _rand_loose(rng, n, hi=451):
    return jnp.asarray(rng.integers(0, hi, (field.NLIMBS, n)).astype(np.float32))


def test_mxu_mul_matches_vpu_and_oracle():
    rng = np.random.default_rng(7)
    a = _rand_loose(rng, 128)
    b = _rand_loose(rng, 128)
    vpu = np.asarray(field.fe_mul(a, b))
    mxu = np.asarray(fe_mul_mxu(a, b))
    an, bn = np.asarray(a), np.asarray(b)
    for i in range(128):
        want = (
            field.limbs_to_int(an[:, i]) * field.limbs_to_int(bn[:, i])
        ) % field.P
        assert field.limbs_to_int(mxu[:, i]) == want
        assert field.limbs_to_int(vpu[:, i]) == want


def test_mxu_mul_boundary_values():
    # All-zero, all-max-loose (450), p-1, and 2^256-ish wrap values.
    vals = [
        [0] * 32,
        [450] * 32,
        field.int_to_limbs(field.P - 1),
        field.int_to_limbs(2**255 - 20),
        [255] * 32,
    ]
    a = jnp.asarray(np.array(vals, dtype=np.float32).T)
    b = jnp.asarray(np.array(vals[::-1], dtype=np.float32).T)
    mxu = np.asarray(fe_mul_mxu(a, b))
    an, bn = np.asarray(a), np.asarray(b)
    for i in range(len(vals)):
        want = (
            field.limbs_to_int(an[:, i]) * field.limbs_to_int(bn[:, i])
        ) % field.P
        assert field.limbs_to_int(mxu[:, i]) == want


def test_mxu_mul_output_invariant():
    """Output limbs must satisfy the loose bound so every field op
    (including a following fe_sub, whose BIAS construction needs
    b <= 654 on limb 0) accepts the result."""
    rng = np.random.default_rng(11)
    out = np.asarray(fe_mul_mxu(_rand_loose(rng, 256), _rand_loose(rng, 256)))
    assert out.min() >= 0
    assert out.max() <= 293  # fe_carry's documented bound


def test_mxu_lowering_is_int8_dot_general():
    rng = np.random.default_rng(3)
    a = _rand_loose(rng, 16)
    b = _rand_loose(rng, 16)
    jaxpr = jax.make_jaxpr(fe_mul_mxu)(a, b)
    dots = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 1, "exactly one hot contraction expected"
    (eqn,) = dots
    assert all(v.aval.dtype == jnp.int8 for v in eqn.invars)
    assert eqn.params["preferred_element_type"] == jnp.int32
    assert eqn.outvars[0].aval.dtype == jnp.int32
    # batched over lanes, contracting the full 64-digit axis
    (contract, batch) = eqn.params["dimension_numbers"]
    assert contract == (((1,), (0,)))
    assert batch == (((2,), (1,)))


def test_mxu_switch_roundtrip():
    assert field.get_mul_impl() == "vpu"
    field.set_mul_impl("mxu")
    assert field.get_mul_impl() == "mxu"
    field.set_mul_impl("vpu")
    with pytest.raises(ValueError):
        field.set_mul_impl("gpu")


@pytest.fixture()
def batch12():
    pks, msgs, sigs = [], [], []
    for i in range(12):
        priv, pub = ref.keypair_from_seed(bytes([i + 101]) * 32)
        msg = b"mxu vote %d" % i
        pks.append(pub)
        msgs.append(msg)
        sigs.append(ref.sign(priv, msg))
    return pks, msgs, sigs


def test_mxu_verify_kernel_end_to_end(batch12):
    pks, msgs, sigs = batch12
    # Tamper lanes 2 (signature bit) and 9 (message).
    sigs = list(sigs)
    msgs = list(msgs)
    sigs[2] = sigs[2][:33] + bytes([sigs[2][33] ^ 1]) + sigs[2][34:]
    msgs[9] = b"a different message"
    inputs, host_ok = eb.prepare_batch(pks, msgs, sigs, pad_to=64)
    args = tuple(jnp.asarray(inputs[k]) for k in ("pk", "r", "s", "k"))
    legacy = eb.KINDS["legacy"]
    got_vpu = np.asarray(eb._compiled_kernel(legacy, 64, None, "vpu")(*args))[:12]
    got_mxu = np.asarray(eb._compiled_kernel(legacy, 64, None, "mxu")(*args))[:12]
    want = [ref.verify_zip215(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    assert list(np.logical_and(got_mxu, host_ok[:12])) == want
    assert list(got_mxu) == list(got_vpu)


def test_mxu_active_impl_env(monkeypatch):
    monkeypatch.setenv(eb._IMPL_ENV, "mxu")
    assert eb.active_impl() == "mxu"
    monkeypatch.setenv(eb._IMPL_ENV, "auto")
    assert eb.active_impl() in ("xla", "pallas")
