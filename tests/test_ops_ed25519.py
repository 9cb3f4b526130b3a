"""Device batch verifier vs the ZIP-215 oracle.

All batches here stay within one padded bucket (64) so the suite
compiles the kernel once (persisted across runs via the repo-local XLA
cache).
"""

import hashlib

import numpy as np
import jax.numpy as jnp
import pytest

from tendermint_tpu.crypto import ed25519_ref as ref
from tendermint_tpu.ops import verify_batch
from tendermint_tpu.ops import curve32 as curve, field32 as field
from tendermint_tpu.libs import tracing
from tendermint_tpu.ops import ed25519_batch
from tendermint_tpu.ops.ed25519_batch import (
    _bytes_to_fe,
    _s_canonical,
    _strip_sign,
    _to_windows,
)


def keypair(i):
    return ref.keypair_from_seed(bytes([i + 1]) * 32)


def _unpack(pks):
    raw = jnp.asarray(np.stack([np.frombuffer(p, dtype=np.uint8) for p in pks]))
    return _strip_sign(_bytes_to_fe(raw))


def test_decompress_matches_oracle():
    pks = [keypair(i)[1] for i in range(6)]
    pks.append((1).to_bytes(32, "little"))  # identity
    pks.append((ref.P + 1).to_bytes(32, "little"))  # non-canonical identity
    yl, sg = _unpack(pks)
    pt, ok = curve.pt_decompress(yl, sg)
    assert np.asarray(ok).all()
    for i, pk in enumerate(pks):
        o = ref.pt_decompress_liberal(pk)
        gx = field.limbs_to_int(np.asarray(field.fe_reduce_full(pt[0]))[:, i])
        gy = field.limbs_to_int(np.asarray(field.fe_reduce_full(pt[1]))[:, i])
        zo = pow(o[2], ref.P - 2, ref.P)
        assert gx == o[0] * zo % ref.P and gy == o[1] * zo % ref.P


def test_decompress_rejects_off_curve():
    # y=2 is not on the curve: x^2 = (y^2-1)/(d y^2+1) is non-square
    assert ref.pt_decompress_liberal((2).to_bytes(32, "little")) is None
    raw = [bytes([2] + [0] * 31)] * 8
    yl, sg = _unpack(raw)
    _, ok = curve.pt_decompress(yl, sg)
    assert not np.asarray(ok).any()


def test_windows_unpack():
    s = 0xDEADBEEF1234
    raw = jnp.asarray(np.frombuffer(s.to_bytes(32, "little"), dtype=np.uint8)[None, :])
    win = np.asarray(_to_windows(raw))  # (64, 1) MSB-first
    recon = 0
    for i in range(64):
        recon = recon * 16 + int(win[i, 0])
    assert recon == s


def test_windows_signed_unpack():
    from tendermint_tpu.ops.ed25519_batch import _to_windows_signed

    rng = np.random.default_rng(3)
    vals = [
        0,
        1,
        ref.L - 1,
        2**253 - 1,
        int.from_bytes(rng.integers(0, 256, 31, dtype=np.uint8).tobytes(), "little"),
    ]
    raw = jnp.asarray(
        np.stack(
            [np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8) for v in vals]
        )
    )
    win = np.asarray(_to_windows_signed(raw))  # (64, n) MSB-first signed digits
    for j, v in enumerate(vals):
        recon = 0
        for i in range(64):
            d = int(win[i, j])
            assert -8 <= d <= 7
            recon = recon * 16 + d
        assert recon == v


def test_s_canonical_boundary():
    L = ref.L
    vals = [0, 1, L - 1, L, L + 1, 2**256 - 1]
    arr = np.stack(
        [np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8) for v in vals]
    )
    assert list(_s_canonical(arr)) == [True, True, True, False, False, False]


# --- host prep: the challenge scalar ------------------------------------------


def _int_challenge(pk, msg, sig):
    """k = SHA-512(R || A || M) mod L in Python integers: the definition."""
    h = hashlib.sha512(sig[:32] + pk + msg).digest()
    return (int.from_bytes(h, "little") % ref.L).to_bytes(32, "little")


@pytest.fixture(scope="module")
def commit_lanes():
    """The lanes of a 40-validator commit whose vote times straddle the
    2^28 ns mark, so its sign-bytes are of two lengths as real votes'
    are (a 4- or a 5-byte nanos varint)."""
    from tests.helpers import CHAIN_ID, make_block_id, make_commit, make_validators

    privs, vset = make_validators(40)
    commit = make_commit(
        make_block_id(), 9, 0, vset, privs,
        time_ns=1_700_000_000 * 10**9 + 2**28 - 20,
    )
    pks = [v.pub_key.bytes() for v in vset.validators]
    msgs = [commit.vote_sign_bytes(CHAIN_ID, i) for i in range(40)]
    sigs = [cs.signature for cs in commit.signatures]
    assert len({len(m) for m in msgs}) == 2
    return pks, msgs, sigs


@pytest.mark.parametrize("branch", ["well_formed", "ill_formed"])
def test_prep_k_is_the_python_integer_challenge(commit_lanes, branch):
    """``_prep_rows`` (every well-formed batch) and ``prepare_batch``'s
    lane-by-lane branch (a batch with an ill-formed lane) both hand the
    kernels k = SHA-512(R || A || M) mod L, byte for byte."""
    pks, msgs, sigs = (list(x) for x in commit_lanes)
    want = [_int_challenge(*lane) for lane in zip(pks, msgs, sigs)]
    if branch == "well_formed":
        pk, r, s, k, host_ok = ed25519_batch._prep_rows(pks, msgs, sigs, None)
        assert host_ok.all()
    else:
        pks[3] = pks[3][:31]  # a short key
        sigs[17] = sigs[17] + b"\x00"  # a long signature
        inputs, host_ok = ed25519_batch.prepare_batch(pks, msgs, sigs)
        assert list(np.nonzero(~host_ok)[0]) == [3, 17]
        want[3] = want[17] = bytes(32)  # never hashed, never answered by the device
        pk, r, s, k = (inputs[name][:40] for name in ("pk", "r", "s", "k"))
        # the padded tail carries the pad triple's own challenge
        assert inputs["k"].shape == (64, 32)
        assert all(row.tobytes() == ed25519_batch._pad_k() for row in inputs["k"][40:])
    assert k.dtype == np.uint8 and k.shape == (40, 32)
    assert [row.tobytes() for row in k] == want
    for i in set(range(40)) - {3, 17}:
        assert pk[i].tobytes() == pks[i]
        assert r[i].tobytes() + s[i].tobytes() == sigs[i]


def test_pad_k_is_unchanged():
    """The pad lanes' challenge, as every earlier build computed it."""
    assert ed25519_batch._pad_k().hex() == (
        "5aecdc673e79d981a7f0fb1ce4c819c2c241f90f7bc1e50c3f15bfaf5190e00f"
    )
    assert ed25519_batch._pad_k() == _int_challenge(
        ed25519_batch._PAD_PK, ed25519_batch._PAD_MSG, ed25519_batch._PAD_SIG
    )
    assert ref.verify_zip215(
        ed25519_batch._PAD_PK, ed25519_batch._PAD_MSG, ed25519_batch._PAD_SIG
    )


@pytest.mark.parametrize("branch", ["well_formed", "ill_formed"])
def test_prep_tags_the_open_span_with_the_hash_path(commit_lanes, branch):
    pks, msgs, sigs = (list(x) for x in commit_lanes)
    if branch == "ill_formed":
        pks[0] = b""
    tracing.configure("ring")
    try:
        tracing.tracer.clear()
        with tracing.span("prep_chunk", stage="prep"):
            ed25519_batch.prepare_batch(pks, msgs, sigs)
        (ev,) = [e for e in tracing.tracer.export()["traceEvents"] if e["name"] == "prep_chunk"]
        assert ev["args"]["hash"] == "native"
    finally:
        tracing.configure("off")
        tracing.tracer.clear()


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


def test_verify_valid_batch(batch8):
    pks, msgs, sigs = batch8
    assert verify_batch(pks, msgs, sigs) == [True] * 8


def test_verify_flags_bad_entries(batch8):
    pks, msgs, sigs = (list(x) for x in batch8)
    sigs[1] = sigs[1][:32] + bytes(32)  # wrong s
    msgs[3] = b"tampered"  # wrong msg
    sigs[5] = bytes(32) + sigs[5][32:]  # R replaced (y=0 IS on curve)
    pks[6] = keypair(7)[1]  # wrong key
    got = verify_batch(pks, msgs, sigs)
    assert got == [True, False, True, False, True, False, False, True]


def test_verify_zip215_edge_cases(batch8):
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
    got = verify_batch(pks, msgs, sigs)
    assert got == [True, True, False, True, True, True, True, True]


def test_verify_agrees_with_oracle_on_random_mutations(batch8):
    pks, msgs, sigs = (list(x) for x in batch8)
    rng = np.random.RandomState(7)
    for i in range(8):
        mode = i % 4
        if mode == 0:
            continue  # leave valid
        b = bytearray(sigs[i])
        if mode == 1:
            b[rng.randint(32)] ^= 1 << rng.randint(8)  # corrupt R
        elif mode == 2:
            b[32 + rng.randint(31)] ^= 1 << rng.randint(8)  # corrupt s (low bytes)
        else:
            pk = bytearray(pks[i])
            pk[rng.randint(32)] ^= 1 << rng.randint(8)
            pks[i] = bytes(pk)
        sigs[i] = bytes(b)
    want = [ref.verify_zip215(pk, m, s) for pk, m, s in zip(pks, msgs, sigs)]
    got = verify_batch(pks, msgs, sigs)
    assert got == want
