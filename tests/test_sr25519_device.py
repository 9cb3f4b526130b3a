"""Device sr25519 batch (ops/sr25519_batch.py) vs the host schnorrkel
oracle, plus mixed-curve commit verification through per-key-type
sub-batching (crypto/batch.MultiBatchVerifier).

Reference surface: crypto/sr25519/batch.go:15-47 (batch), BASELINE
config 5 (mixed ed25519 + sr25519 validator set).
"""

import hashlib

import numpy as np
import jax.numpy as jnp
import pytest

from tendermint_tpu.crypto import ristretto
from tendermint_tpu.crypto.sr25519 import (
    Sr25519BatchVerifier,
    Sr25519PrivKey,
    verify as verify_host,
)
from tendermint_tpu.ops import field32 as field
from tendermint_tpu.ops.sr25519_batch import (
    ristretto_decompress,
    verify_batch_sr,
)


def _keys(n, salt=b"srdev"):
    out = []
    for i in range(n):
        out.append(Sr25519PrivKey.from_secret(salt + bytes([i])))
    return out


# --- ristretto decompress parity -------------------------------------------


def test_ristretto_decompress_matches_host():
    """Device DECODE == host decompress on generator multiples (the
    encodings every commit actually contains: valid pubkeys/R points)."""
    encs = []
    for i in range(1, 9):
        encs.append(ristretto.compress(ristretto.pt_mul(i, ristretto.B_POINT)))
    raw = jnp.asarray(
        np.stack([np.frombuffer(e, dtype=np.uint8) for e in encs])
    )
    fe = raw.astype(jnp.float32).T
    pt, ok = ristretto_decompress(fe)
    assert np.asarray(ok).all()
    for i, enc in enumerate(encs):
        hx, hy, hz, _ = ristretto.decompress(enc)
        zo = pow(hz, field.P - 2, field.P)
        gx = field.limbs_to_int(np.asarray(field.fe_reduce_full(pt[0]))[:, i])
        gy = field.limbs_to_int(np.asarray(field.fe_reduce_full(pt[1]))[:, i])
        gz = field.limbs_to_int(np.asarray(field.fe_reduce_full(pt[2]))[:, i])
        zo_g = pow(gz, field.P - 2, field.P)
        assert gx * zo_g % field.P == hx * zo % field.P
        assert gy * zo_g % field.P == hy * zo % field.P


def test_ristretto_decompress_rejects_invalid():
    """Non-square decode candidates must be rejected on device exactly
    as the host rejects them."""
    bad = []
    for i in range(40):
        cand = hashlib.sha256(b"bad%d" % i).digest()
        cand = bytes([cand[0] & 0xFE]) + cand[1:31] + bytes([cand[31] & 0x7F])
        if int.from_bytes(cand, "little") < field.P and ristretto.decompress(cand) is None:
            bad.append(cand)
        if len(bad) >= 4:
            break
    assert bad, "need at least one invalid encoding"
    raw = jnp.asarray(np.stack([np.frombuffer(e, dtype=np.uint8) for e in bad]))
    _, ok = ristretto_decompress(raw.astype(jnp.float32).T)
    assert not np.asarray(ok).any()


# --- batch verify parity ----------------------------------------------------


def test_device_batch_matches_host_with_tampering():
    privs = _keys(12)
    pks, msgs, sigs = [], [], []
    for i, priv in enumerate(privs):
        m = b"device sr vote %d" % i
        pks.append(priv.pub_key().bytes())
        msgs.append(m)
        sigs.append(priv.sign(m))
    # adversarial lanes
    sigs[1] = sigs[1][:33] + bytes([sigs[1][33] ^ 4]) + sigs[1][34:]  # R bit
    msgs[4] = b"swapped message"
    sigs[7] = sigs[7][:63] + bytes([sigs[7][63] & 0x7F])  # marker cleared
    s_nc = bytearray(sigs[9])  # non-canonical s (>= L)
    s_nc[32:64] = (ristretto.L + 7).to_bytes(32, "little")
    s_nc[63] |= 0x80
    sigs[9] = bytes(s_nc)
    got = verify_batch_sr(pks, msgs, sigs)
    want = [verify_host(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    assert list(map(bool, got)) == want
    assert want[1] is False and want[4] is False and want[7] is False
    assert want[9] is False


def _batch(engine, n=12):
    if engine == "sr25519":
        privs = _keys(n, salt=b"two-steps")
    else:
        from tendermint_tpu.crypto.keys import Ed25519PrivKey

        privs = [Ed25519PrivKey.from_seed(bytes([i]) * 32) for i in range(n)]
    pks = [priv.pub_key().bytes() for priv in privs]
    msgs = [b"two steps %d" % i for i in range(n)]
    sigs = [priv.sign(m) for priv, m in zip(privs, msgs)]
    msgs[5] = b"swapped message"
    return pks, msgs, sigs


@pytest.mark.parametrize("engine", ["ed25519", "sr25519"])
def test_begin_then_finish_is_the_one_call_in_two_steps(engine, ring_tracer):
    """``begin_verify_batch`` / ``begin_verify_batch_sr`` (ISSUE 41):
    every lane in flight when it returns and no collect yet, ``finish()``
    the verdicts ``verify_batch`` / ``verify_batch_sr`` give, each step
    under a ``verify_batch`` span of its own."""
    from tendermint_tpu.ops import ed25519_batch, sr25519_batch

    begin, whole = {
        "ed25519": (ed25519_batch.begin_verify_batch, ed25519_batch.verify_batch),
        "sr25519": (sr25519_batch.begin_verify_batch_sr, verify_batch_sr),
    }[engine]
    pks, msgs, sigs = _batch(engine)
    want = whole(pks, msgs, sigs)
    assert want == [i != 5 for i in range(12)]
    ring_tracer.clear()
    pending = begin(pks, msgs, sigs)
    assert (pending.engine, pending.lanes, pending.lanes_inflight) == (engine, 12, 12)
    so_far = [e["name"] for e in ring_tracer.export()["traceEvents"] if e.get("ph") == "X"]
    assert "dispatch_chunk" in so_far and "collect_chunk" not in so_far
    assert pending.finish() == want
    assert pending.lanes_inflight == 0
    phases = [
        (e["args"]["phase"], e["args"]["engine"], e["args"]["lanes"])
        for e in ring_tracer.export()["traceEvents"]
        if e.get("ph") == "X" and e["name"] == "verify_batch"
    ]
    assert phases == [("dispatch", engine, 12), ("collect", engine, 12)]
    assert begin([], [], []).finish() == []


def test_batch_verifier_routes_to_device():
    privs = _keys(20, salt=b"route")
    bv = Sr25519BatchVerifier(device_threshold=8)
    for i, priv in enumerate(privs):
        m = b"routed %d" % i
        bv.add(priv.pub_key(), m, priv.sign(m))
    ok, oks = bv.verify()
    assert ok and all(oks) and len(oks) == 20


def test_batch_verifier_host_path_below_threshold():
    privs = _keys(3, salt=b"small")
    bv = Sr25519BatchVerifier()  # default threshold 16 > 3 -> host RLC
    for i, priv in enumerate(privs):
        m = b"small %d" % i
        bv.add(priv.pub_key(), m, priv.sign(m))
    ok, oks = bv.verify()
    assert ok and all(oks)


# --- mixed-curve commit (BASELINE config 5) ---------------------------------


def _mixed_validators(n_ed, n_sr, power=10):
    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tests.helpers import make_validators

    def factory(i):
        if i < n_ed:
            return Ed25519PrivKey.from_seed(i.to_bytes(32, "big"))
        return Sr25519PrivKey.from_secret(b"mx" + bytes([i - n_ed]))

    return make_validators(n_ed + n_sr, power=power, key_factory=factory)


def test_mixed_curve_commit_verifies():
    """A commit signed by an ed25519+sr25519 validator set verifies
    through the batch path, each key type on its own sub-verifier."""
    from tests.helpers import CHAIN_ID, make_block_id, make_commit
    from tendermint_tpu.types import validation

    privs, vset = _mixed_validators(24, 24)
    block_id = make_block_id(b"mixed")
    commit = make_commit(block_id, 3, 0, vset, privs)
    validation.verify_commit(CHAIN_ID, vset, block_id, 3, commit)


def test_mixed_curve_commit_attributes_bad_signature():
    from tests.helpers import CHAIN_ID, make_block_id, make_commit
    from tendermint_tpu.types import validation

    privs, vset = _mixed_validators(20, 20)
    block_id = make_block_id(b"mixed-bad")
    commit = make_commit(block_id, 3, 0, vset, privs)
    # corrupt one sr25519 signature (find an sr validator index)
    from tendermint_tpu.crypto.keys import SR25519_KEY_TYPE

    sr_idx = next(
        i for i, v in enumerate(vset.validators)
        if v.pub_key.type == SR25519_KEY_TYPE
    )
    sig = bytearray(commit.signatures[sr_idx].signature)
    sig[33] ^= 1
    commit.signatures[sr_idx].signature = bytes(sig)
    with pytest.raises(validation.InvalidCommitError):
        validation.verify_commit(CHAIN_ID, vset, block_id, 3, commit)


def test_multibatch_merges_in_submission_order():
    from tendermint_tpu.crypto.batch import MultiBatchVerifier
    from tendermint_tpu.crypto.keys import Ed25519PrivKey

    ed = Ed25519PrivKey.from_seed(b"\x01" * 32)
    sr = Sr25519PrivKey.from_secret(b"\x02" * 32)
    mb = MultiBatchVerifier()
    entries = []
    for i in range(6):
        priv = ed if i % 2 == 0 else sr
        m = b"interleave %d" % i
        sig = priv.sign(m)
        if i == 3:  # corrupt one sr entry
            sig = sig[:34] + bytes([sig[34] ^ 1]) + sig[35:]
        mb.add(priv.pub_key(), m, sig)
        entries.append(i)
    ok, oks = mb.verify()
    assert not ok
    assert oks == [True, True, True, False, True, True]


def test_multibatch_takes_an_unsupported_key_as_host_lanes():
    """A key type without a batch verifier (secp256k1) no longer makes
    ``add`` raise: its lanes are a host sub-batch, verified lane by
    lane, merged in submission order like the others."""
    from tendermint_tpu.crypto.batch import HostLanesVerifier, MultiBatchVerifier
    from tendermint_tpu.crypto.keys import Ed25519PrivKey, Secp256k1PrivKey

    mb = MultiBatchVerifier()
    secp = Secp256k1PrivKey.generate()
    ed = Ed25519PrivKey.from_seed(b"\x05" * 32)
    mb.add(secp.pub_key(), b"m0", secp.sign(b"m0"))
    mb.add(ed.pub_key(), b"m1", ed.sign(b"m1"))
    mb.add(secp.pub_key(), b"m2", secp.sign(b"another message"))
    assert isinstance(mb._subs["secp256k1"], HostLanesVerifier)
    assert len(mb) == 3
    assert mb.verify() == (False, [True, True, False])
    # the host sub-verifier keeps the contract of every BatchVerifier
    lanes = HostLanesVerifier("secp256k1")
    assert lanes.verify() == (False, [])
    with pytest.raises(ValueError):
        lanes.add(ed.pub_key(), b"m", ed.sign(b"m"))
