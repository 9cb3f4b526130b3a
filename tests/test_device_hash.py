"""Fused on-device SHA-512 challenge hashing (ops/hash512.py).

The kernel must be bit-exact with hashlib: parity is asserted at every
Merkle-Damgard padding boundary (0/55/56/64/111/112/128 bytes — the
lengths where the 0x80 terminator and the 128-bit length field spill
into a new block), on sr25519-style prefixed challenge inputs, and — in
the slow battery — across 10k random messages grouped by length. The
fallback ladder (mixed lengths, oversize lanes, broken kernel, disabled
env) must always land on the host path, never wrong answers.
"""

import hashlib

import numpy as np
import pytest

from tendermint_tpu.crypto.hashing import reduce_mod_l_int
from tendermint_tpu.ops import ed25519_batch, hash512

# Padding boundaries for SHA-512's 128-byte blocks: empty input; 55/56
# straddle nothing for SHA-512 but mirror the SHA-256 battery; 111 is
# the last single-block length, 112 forces the length field into a
# second block, 128 is an exact block.
BOUNDARY_LENGTHS = (0, 55, 56, 64, 111, 112, 128)


@pytest.fixture(autouse=True)
def _device_hash_on(monkeypatch):
    """Force the fused path on (auto keeps CPU off) and reset the lane
    counter between tests."""
    monkeypatch.setenv("TENDERMINT_TPU_DEVICE_HASH", "1")
    hash512.reset_stats()
    yield
    hash512.reset_stats()


def _host_digests(msgs):
    return np.stack(
        [
            np.frombuffer(hashlib.sha512(m).digest(), dtype=np.uint8)
            for m in msgs
        ]
    )


# --- raw SHA-512 parity -----------------------------------------------------


@pytest.mark.parametrize("length", BOUNDARY_LENGTHS)
def test_sha512_device_boundary_length_parity(length):
    rng = np.random.default_rng(1000 + length)
    msgs = [rng.integers(0, 256, size=length, dtype=np.uint8).tobytes() for _ in range(5)]
    got = hash512.sha512_device(msgs)
    assert got.shape == (5, 64) and got.dtype == np.uint8
    np.testing.assert_array_equal(got, _host_digests(msgs))


def test_sha512_device_matrix_input():
    rng = np.random.default_rng(7)
    mat = rng.integers(0, 256, size=(9, 73), dtype=np.uint8)
    got = hash512.sha512_device(mat)
    np.testing.assert_array_equal(
        got, _host_digests([r.tobytes() for r in mat])
    )


def test_sha512_device_empty_batch():
    assert hash512.sha512_device([]).shape == (0, 64)


# --- fused challenge (prefix || msg, mod L) parity --------------------------


def _challenge_case(n, msg_len, seed):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, 256, size=(n, 64), dtype=np.uint8)
    msgs = [
        rng.integers(0, 256, size=msg_len, dtype=np.uint8).tobytes()
        for _ in range(n)
    ]
    return prefix, msgs


def _challenge_oracle(prefix, msgs):
    """SHA-512(prefix_i || msg_i) mod L by hashlib and Python integers."""
    return np.stack(
        [
            np.frombuffer(
                reduce_mod_l_int(hashlib.sha512(prefix[i].tobytes() + m).digest()),
                dtype=np.uint8,
            )
            for i, m in enumerate(msgs)
        ]
    )


@pytest.mark.parametrize("length", BOUNDARY_LENGTHS)
def test_challenge_device_boundary_parity(length):
    """sr25519/ed25519-style prefixed challenge: SHA-512(R||A||M) mod L
    on device must equal hashlib + ``int % L``."""
    prefix, msgs = _challenge_case(6, length, 2000 + length)
    out = hash512.try_challenge_device(prefix, msgs)
    assert out is not None, "uniform bounded batch must take the device path"
    want = _challenge_oracle(prefix, msgs)
    np.testing.assert_array_equal(np.asarray(out), want)


def test_challenge_counts_device_lanes():
    prefix, msgs = _challenge_case(11, 32, 3)
    assert hash512.try_challenge_device(prefix, msgs) is not None
    assert hash512.stats()["device_lanes"] == 11


def test_challenge_k_helper_parity_and_stage_times():
    """The engine-side _challenge_k wrapper returns host bytes equal to
    the host path, and the split of prep into hashing and packing is
    the ``prep_chunk`` span's: ``hash`` names the path that ran and
    ``hash_us`` is the one hashing call's time (the tracer's phase
    total; there is no second timing system to ask)."""
    from tendermint_tpu.libs import tracing

    prefix, msgs = _challenge_case(8, 40, 4)
    got = ed25519_batch._challenge_k(prefix, msgs, None)
    want = _challenge_oracle(prefix, msgs)
    np.testing.assert_array_equal(got, want)

    pk, r = prefix[:, 32:], prefix[:, :32]
    pks = [row.tobytes() for row in pk]
    sigs = [row.tobytes() + bytes(32) for row in r]
    tracing.tracer.set_metrics_observer(None)
    tracing.configure("ring")
    tracing.tracer.clear()
    try:
        with tracing.span("prep_chunk", lanes=8):
            _, _, _, k, _ = ed25519_batch._prep_rows(pks, msgs, sigs, None)
        (ev,) = tracing.tracer.export(clear=True)["traceEvents"][-1:]
    finally:
        tracing.configure("off")
        tracing.tracer.clear()
    np.testing.assert_array_equal(k, want)
    assert ev["name"] == "prep_chunk" and ev["args"]["hash"] == "device"
    assert ev["args"]["hash_n"] == 1
    assert 0.0 < ev["args"]["hash_us"] <= ev["dur"]
    # no span open: nothing is timed, and the call is the plain one
    assert tracing.timed("hash", ed25519_batch._challenge_k) is ed25519_batch._challenge_k


# --- fallback ladder --------------------------------------------------------


def test_mixed_lengths_fall_back_to_host():
    prefix, msgs = _challenge_case(4, 32, 5)
    msgs[2] = msgs[2] + b"x"  # one ragged lane
    assert hash512.try_challenge_device(prefix, msgs) is None


def test_oversize_lanes_fall_back(monkeypatch):
    monkeypatch.setenv("TENDERMINT_TPU_DEVICE_HASH_MAXLEN", "16")
    prefix, msgs = _challenge_case(4, 17, 6)
    assert hash512.try_challenge_device(prefix, msgs) is None


def test_env_off_disables(monkeypatch):
    monkeypatch.setenv("TENDERMINT_TPU_DEVICE_HASH", "off")
    prefix, msgs = _challenge_case(4, 32, 8)
    assert hash512.try_challenge_device(prefix, msgs) is None


def test_kernel_failure_raises_and_is_not_sticky():
    """A failing hash kernel is a device failure: it raises to the
    engine's chunk prep (-> health machine) instead of quietly becoming
    host hashing, and nothing is remembered once the kernel works."""

    def boom(backend):
        raise RuntimeError("injected compile failure")

    prefix, msgs = _challenge_case(4, 32, 9)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(hash512, "_compiled_challenge", boom)
        with pytest.raises(RuntimeError, match="injected compile failure"):
            hash512.try_challenge_device(prefix, msgs)
        assert hash512.stats()["device_lanes"] == 0
    assert hash512.try_challenge_device(prefix, msgs) is not None


def test_verify_batch_parity_with_device_hash():
    """End-to-end: verify_batch verdicts are identical with the fused
    hasher on, bad lane included."""
    from tendermint_tpu.crypto import ed25519_ref as ref

    pks, msgs, sigs = [], [], []
    for i in range(8):
        sk, pk = ref.keypair_from_seed(bytes([i + 40]) * 32)
        m = b"device-hash lane %03d" % i  # uniform length -> device path
        pks.append(pk)
        msgs.append(m)
        sigs.append(ref.sign(sk, m))
    sigs[5] = bytes(64)
    oks = ed25519_batch.verify_batch(pks, msgs, sigs)
    assert not oks[5] and sum(oks) == 7
    assert hash512.stats()["device_lanes"] >= 8


# --- slow battery -----------------------------------------------------------


@pytest.mark.slow
def test_sha512_device_random_length_battery():
    """10k random messages across random lengths, grouped by length so
    each group is one uniform device batch."""
    rng = np.random.default_rng(0xDEAD)
    lengths = rng.integers(0, 256, size=10_000)
    groups = {}
    for ln in lengths:
        groups.setdefault(int(ln), 0)
        groups[int(ln)] += 1
    for ln, count in sorted(groups.items()):
        msgs = [
            rng.integers(0, 256, size=ln, dtype=np.uint8).tobytes()
            for _ in range(count)
        ]
        got = hash512.sha512_device(msgs)
        np.testing.assert_array_equal(got, _host_digests(msgs))
