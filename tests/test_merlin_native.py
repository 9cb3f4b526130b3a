"""The batch Merlin challenge in C (native/merlin_batch.c through
crypto/hashing.sr25519_challenges_mod_l) against its definition, the
pure-Python transcript of crypto/merlin.py that
crypto/sr25519._challenge drives — which tests/test_sr25519.py pins to
the Merlin crate's published transcript vector. The C entry speaks the
signing transcript only, so it is held to that vector through the
oracle, byte for byte, on every message length that crosses a STROBE
block boundary and more."""

import numpy as np
import pytest

from tendermint_tpu.crypto import hashing
from tendermint_tpu.crypto.merlin import MerlinTranscript
from tendermint_tpu.crypto.sr25519 import _challenge, _signing_transcript


def oracle(pub: bytes, r: bytes, msg: bytes) -> bytes:
    return _challenge(_signing_transcript(msg), pub, r).to_bytes(32, "little")


def random_lanes(seed: int, lengths):
    rng = np.random.default_rng(seed)
    n = len(lengths)
    pubs = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    rs = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    return pubs, rs, [rng.bytes(k) for k in lengths]


def test_the_library_is_native_here():
    assert hashing.host_hash_impl() == "native"


@pytest.mark.parametrize("lo,hi", [(0, 100), (101, 200), (201, 300)])
def test_c_merlin_agrees_with_the_python_transcript_at_every_length(lo, hi):
    pubs, rs, msgs = random_lanes(lo, range(lo, hi + 1))
    out = hashing.sr25519_challenges_mod_l(pubs, rs, msgs)
    assert out.shape == (hi - lo + 1, 32) and out.dtype == np.uint8
    for i, msg in enumerate(msgs):
        assert out[i].tobytes() == oracle(pubs[i].tobytes(), rs[i].tobytes(), msg), len(msg)


def test_the_oracle_is_the_published_merlin_transcript():
    # the Merlin crate's transcript vector, as tests/test_sr25519.py
    # carries it: what the C code is held to through crypto/merlin.py
    t = MerlinTranscript(b"test protocol")
    t.append_message(b"some label", b"some data")
    assert t.challenge_bytes(b"challenge", 32).hex() == (
        "d5a21972d0d5fe320c0d263fac7fffb8145aa640af6e9bca177c03c7efcf0615"
    )


def test_a_batch_above_the_openmp_threshold_equals_its_lanes_alone():
    """From 1,024 lanes up the C loop runs on an OpenMP team; each lane
    must still read what it reads alone."""
    pubs, rs, msgs = random_lanes(7, [110 + i % 9 for i in range(1500)])
    out = hashing.sr25519_challenges_mod_l(pubs, rs, msgs)
    for i in (0, 1, 511, 1023, 1024, 1499):
        alone = hashing.sr25519_challenges_mod_l(pubs[i : i + 1], rs[i : i + 1], msgs[i : i + 1])
        assert out[i].tobytes() == alone[0].tobytes() == oracle(pubs[i].tobytes(), rs[i].tobytes(), msgs[i])
    assert len({row.tobytes() for row in out}) == 1500


def test_without_a_compiler_the_python_transcript_answers(monkeypatch):
    pubs, rs, msgs = random_lanes(3, [0, 1, 119, 166, 167])
    native = hashing.sr25519_challenges_mod_l(pubs, rs, msgs)
    monkeypatch.setattr(hashing, "_lib", lambda: None)
    np.testing.assert_array_equal(hashing.sr25519_challenges_mod_l(pubs, rs, msgs), native)


def test_an_empty_batch_and_wrong_shapes():
    empty = np.zeros((0, 32), np.uint8)
    assert hashing.sr25519_challenges_mod_l(empty, empty, []).shape == (0, 32)
    pubs, rs, msgs = random_lanes(1, [5, 6])
    with pytest.raises(ValueError):
        hashing.sr25519_challenges_mod_l(pubs[:1], rs, msgs)
    with pytest.raises(ValueError):
        hashing.sr25519_challenges_mod_l(pubs, rs.astype(np.int32), msgs)


def test_the_library_is_named_by_every_source():
    """D17: the file name carries a hash of every translation unit, so
    two checkouts that differ in either never load each other's build."""
    import hashlib
    import os

    native = os.path.join(os.path.dirname(os.path.dirname(hashing.__file__)), "native")
    h = hashlib.sha256()
    for name in hashing._SOURCES:
        with open(os.path.join(native, name), "rb") as f:
            h.update(f.read())
    assert hashing._SOURCES == ("sha512_batch.c", "merlin_batch.c", "secp256k1_batch.c")
    assert os.path.basename(hashing._lib()._name) == "libsha512batch-%s.so" % h.hexdigest()[:12]
