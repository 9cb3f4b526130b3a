"""Batched SHA-512 (C extension or fallback) + mod-L reduction vs hashlib."""

import hashlib

import numpy as np
import pytest

from tendermint_tpu.crypto import hashing


def test_sha512_batch_matches_hashlib():
    msgs = [
        b"",
        b"a",
        b"x" * 111,  # one-block padding boundary
        b"y" * 112,  # forces two-block padding
        b"z" * 127,
        b"w" * 128,
        b"v" * 129,
        bytes(range(256)) * 3,
    ]
    got = hashing.sha512_batch(msgs)
    for i, m in enumerate(msgs):
        assert got[i].tobytes() == hashlib.sha512(m).digest(), f"msg {i}"


def test_sha512_batch_large_n():
    msgs = [b"msg-%d" % i for i in range(1000)]
    got = hashing.sha512_batch(msgs)
    for i in (0, 1, 499, 999):
        assert got[i].tobytes() == hashlib.sha512(msgs[i]).digest()


@pytest.mark.parametrize("n", [1, 150, 1023, 1024, 1025, 4096])
def test_both_batch_entries_match_hashlib_on_each_side_of_the_parallel_threshold(n):
    """Batches under ``PARALLEL_MIN_BATCH`` (1,024) are hashed by the
    caller's thread, larger ones by the OpenMP team: same digests."""
    rng = np.random.default_rng(n)
    msgs = [rng.bytes(100 + (i % 40)) for i in range(n)]
    prefix = rng.integers(0, 256, (n, 64), dtype=np.uint8)
    plain = hashing.sha512_batch(msgs)
    prefixed = hashing.sha512_batch_prefixed(prefix, msgs)
    for i in sorted({0, n // 3, n // 2, n - 2 if n > 1 else 0, n - 1}):
        assert plain[i].tobytes() == hashlib.sha512(msgs[i]).digest(), i
        assert prefixed[i].tobytes() == hashlib.sha512(prefix[i].tobytes() + msgs[i]).digest(), i


def test_reduce_mod_l_random_and_edges():
    rng = np.random.default_rng(42)
    vals = [0, 1, hashing.L - 1, hashing.L, hashing.L + 1, 2**512 - 1, 2**252]
    vals += [int.from_bytes(rng.bytes(64), "little") for _ in range(64)]
    arr = np.stack(
        [np.frombuffer(v.to_bytes(64, "little"), dtype=np.uint8) for v in vals]
    )
    got = hashing.reduce_mod_l(arr)
    for i, v in enumerate(vals):
        assert int.from_bytes(got[i].tobytes(), "little") == v % hashing.L, f"val {i}"


def test_sha512_batch_mod_l_end_to_end():
    msgs = [b"challenge-%d" % i for i in range(10)]
    got = hashing.sha512_batch_mod_l(msgs)
    for m, g in zip(msgs, got):
        want = int.from_bytes(hashlib.sha512(m).digest(), "little") % hashing.L
        assert int.from_bytes(g, "little") == want


def test_native_extension_builds():
    # Informational: the C path should build in this image (gcc present).
    assert hashing._lib() is not None
