"""The native secp256k1 ECDSA routine (native/secp256k1_batch.c through
crypto/keys.Secp256k1PubKey.verify_many) against the two verifiers that
owe it nothing: OpenSSL's, which it replaced
(``Secp256k1PubKey._verify_openssl``, the code every lane ran until
PR 49 and still runs where there is no compiler), and the benchmark's
plain reference in Python integers
(``chipbench/reference_mixed.verify_secp256k1``). Every case holds all
three to one verdict list, and says what that list is.

Some inputs cannot be reached through a message, because SHA-256 does
not invert: a digest of n or above, scalars u1 = u2, an R whose x lies
in [n, p). Those are built from the signature backwards (the key
Q = (s R - e G) / r verifies (r, s) over e by construction) and go to
the three verifiers as digests: the native entry takes digests, OpenSSL
takes one as ``Prehashed``, and the reference's curve functions are
called as ``verify_secp256k1`` calls them.
"""

import hashlib
import random

import pytest
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import Prehashed, encode_dss_signature

from chipbench import reference_mixed
from chipbench.reference_mixed import _KG, _KN as N, _KP as P, _k_add, _k_mult
from tendermint_tpu.crypto import hashing
from tendermint_tpu.crypto.keys import Secp256k1PrivKey, Secp256k1PubKey

@pytest.fixture(autouse=True)
def native_or_skip():
    # asked by the first test that runs, not while the file is collected:
    # the answer builds the library where it is not built yet
    if hashing.host_secp256k1_impl() != "native":
        pytest.skip("no C compiler (or none with 128-bit integers): the OpenSSL path is all there is")

G = _KG + (1,)


def be(v: int) -> bytes:
    return v.to_bytes(32, "big")


def rs(sig: bytes):
    return int.from_bytes(sig[:32], "big"), int.from_bytes(sig[32:], "big")


def three_ways(lanes):
    """The verdicts of ``(key bytes, msg, sig)`` lanes, which the native
    batch, OpenSSL lane by lane and the plain reference must all give."""
    keys = [Secp256k1PubKey(k) for k, _, _ in lanes]
    msgs, sigs = [m for _, m, _ in lanes], [s for _, _, s in lanes]
    native = Secp256k1PubKey.verify_many(keys, msgs, sigs)
    assert native == [pk._verify_openssl(m, s) for pk, m, s in zip(keys, msgs, sigs)]
    assert native == [reference_mixed.verify_secp256k1(k, m, s) for k, m, s in lanes]
    # and a lane alone reads what it reads in its batch
    assert native == [pk.verify_signature(m, s) for pk, m, s in zip(keys, msgs, sigs)]
    return native


def signed(rng, scalar=None, msg=None):
    """A good lane: ``(key bytes, msg, sig)`` under a random key, or the
    key of ``scalar``."""
    sk = Secp256k1PrivKey(be(scalar if scalar is not None else rng.randrange(1, N)))
    msg = rng.randbytes(rng.randrange(0, 160)) if msg is None else msg
    return sk.pub_key().bytes(), msg, sk.sign(msg)


def flip(data: bytes, bit: int) -> bytes:
    out = bytearray(data)
    out[bit // 8] ^= 1 << (bit % 8)
    return bytes(out)


def an_x(rng, on_curve: bool) -> int:
    """A field element that is (or is not) some point's x."""
    while True:
        x = rng.randrange(1, P)
        if (reference_mixed.secp256k1_decode(b"\x02" + be(x)) is not None) == on_curve:
            return x


# --- one fault a case ---------------------------------------------------------


def _with_sig(lane, r=None, s=None):
    key, msg, sig = lane
    r0, s0 = rs(sig)
    return key, msg, be(r0 if r is None else r) + be(s0 if s is None else s)


FAULTS = {
    "message_bit": lambda rng, lane: (lane[0], flip(lane[1] + b"m", 3), lane[2]),
    "r_bit": lambda rng, lane: (lane[0], lane[1], flip(lane[2], rng.randrange(0, 256))),
    "s_bit": lambda rng, lane: (lane[0], lane[1], flip(lane[2], 256 + rng.randrange(0, 250))),
    "key_x_bit": lambda rng, lane: (flip(lane[0], 8 + rng.randrange(0, 256)), lane[1], lane[2]),
    "key_other_parity": lambda rng, lane: (bytes([lane[0][0] ^ 1]) + lane[0][1:], lane[1], lane[2]),
    "high_s": lambda rng, lane: _with_sig(lane, s=N - rs(lane[2])[1]),
    "r_zero": lambda rng, lane: _with_sig(lane, r=0),
    "s_zero": lambda rng, lane: _with_sig(lane, s=0),
    "r_is_n": lambda rng, lane: _with_sig(lane, r=N),
    "r_all_ones": lambda rng, lane: _with_sig(lane, r=2**256 - 1),
    "s_is_n": lambda rng, lane: _with_sig(lane, s=N),
    "s_all_ones": lambda rng, lane: _with_sig(lane, s=2**256 - 1),
    "s_just_over_half": lambda rng, lane: _with_sig(lane, s=N // 2 + 1),
    "prefix_0": lambda rng, lane: (b"\x00" + lane[0][1:], lane[1], lane[2]),
    "prefix_4": lambda rng, lane: (b"\x04" + lane[0][1:], lane[1], lane[2]),
    "prefix_5": lambda rng, lane: (b"\x05" + lane[0][1:], lane[1], lane[2]),
    "prefix_6": lambda rng, lane: (b"\x06" + lane[0][1:], lane[1], lane[2]),
    "x_is_p": lambda rng, lane: (b"\x02" + be(P), lane[1], lane[2]),
    # p + x is x to a decoder that reduces: an x of the curve, so that only the range rule refuses it
    "x_over_p": lambda rng, lane: (b"\x03" + be(P + next(x for x in range(1, 99) if reference_mixed.secp256k1_decode(b"\x03" + be(x)))), lane[1], lane[2]),
    "x_all_ones": lambda rng, lane: (b"\x02" + b"\xff" * 32, lane[1], lane[2]),
    "x_with_no_point": lambda rng, lane: (b"\x02" + be(an_x(rng, on_curve=False)), lane[1], lane[2]),
    "another_key": lambda rng, lane: (b"\x02" + be(an_x(rng, on_curve=True)), lane[1], lane[2]),
    "sig_63_bytes": lambda rng, lane: (lane[0], lane[1], lane[2][:63]),
    "sig_65_bytes": lambda rng, lane: (lane[0], lane[1], lane[2] + b"\x00"),
    "sig_empty": lambda rng, lane: (lane[0], lane[1], b""),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_one_fault_refuses_its_lane_and_no_other(fault):
    rng = random.Random("fault " + fault)
    good = [signed(rng) for _ in range(5)]
    lanes = list(good)
    lanes[2] = FAULTS[fault](rng, good[2])
    assert three_ways(lanes) == [True, True, False, True, True]
    assert three_ways([lanes[2]]) == [False]


def test_valid_lanes_from_random_keys():
    rng = random.Random(49)
    assert three_ways([signed(rng) for _ in range(40)]) == [True] * 40


def test_the_largest_low_s_is_accepted():
    """s = n / 2 rounded down is the last s the low-s rule lets through:
    the key that makes (r, s) good over the message, recovered."""
    rng = random.Random(50)
    msg = b"half"
    e = int.from_bytes(hashlib.sha256(msg).digest(), "big")
    key, r = recovered_key(e, rng.randrange(1, N), N // 2)
    assert three_ways([(key, msg, be(r) + be(N // 2)), (key, msg, be(r) + be(N // 2 + 1))]) == [True, False]


# --- the keys whose multiples meet G's ------------------------------------------


@pytest.mark.parametrize(
    "scalar",
    [1, 2, 3, 5, 63, 64, 65, N - 1, N - 2, N - 3, (N - 1) // 2],
    ids=["1", "2", "3", "5", "63", "64", "65", "n-1", "n-2", "n-3", "n_half"],
)
def test_keys_that_are_small_multiples_of_the_generator(scalar):
    """Q = k G for a small k, or its opposite: Q's window table and G's
    hold the same points, so an addition may meet its own operand (and
    must double) or its opposite (and must give infinity)."""
    rng = random.Random(scalar)
    good = [signed(rng, scalar=scalar) for _ in range(6)]
    bad = FAULTS["message_bit"](rng, good[0]), FAULTS["r_bit"](rng, good[1]), FAULTS["s_bit"](rng, good[2])
    assert three_ways(good + list(bad)) == [True] * 6 + [False] * 3


# --- lanes built backwards, as digests -------------------------------------------


def compress(point) -> bytes:
    x, y, z = point
    zi = pow(z, P - 2, P)
    x, y = x * zi * zi % P, y * zi * zi * zi % P
    return bytes([2 + (y & 1)]) + be(x)


def recovered_key(e: int, k_or_point, s: int):
    """``(key bytes, r)`` such that (r, s) is a good signature over the
    digest value e: R = k G (or the point given), r = R.x mod n,
    Q = (s R - e G) / r."""
    big_r = _k_mult(k_or_point, G) if isinstance(k_or_point, int) else k_or_point
    zi = pow(big_r[2], P - 2, P)
    r = big_r[0] * zi * zi % P % N
    q = _k_mult(pow(r, N - 2, N), _k_add(_k_mult(s, big_r), _k_mult((N - e) % N, G)))
    return compress(q), r


def digest_three_ways(key: bytes, digest: bytes, sig: bytes) -> bool:
    (native,) = hashing.secp256k1_verify_native(key, digest, sig, 1)
    r, s = rs(sig)
    try:
        ec.EllipticCurvePublicKey.from_encoded_point(ec.SECP256K1(), key).verify(
            encode_dss_signature(r, s), digest, ec.ECDSA(Prehashed(hashes.SHA256()))
        )
        openssl = True
    except (InvalidSignature, ValueError):
        openssl = False
    # chipbench/reference_mixed.verify_secp256k1 from its digest on
    q = reference_mixed.secp256k1_decode(key)
    w = pow(s, N - 2, N)
    x, _, z = _k_add(
        _k_mult(int.from_bytes(digest, "big") * w % N, G), _k_mult(r * w % N, q)
    )
    plain = z != 0 and x * pow(z * z, P - 2, P) % P % N == r
    assert native == openssl == plain
    return bool(native)


@pytest.mark.parametrize(
    "digest", [0, 1, N - 1, N, N + 5, 2**256 - 1], ids=["0", "1", "n-1", "n", "n+5", "all_ones"]
)
def test_digests_at_the_edges_of_the_reduction_mod_n(digest):
    """e is the digest mod n: at 0 and n the generator's scalar is 0
    and the sum is r/s Q alone."""
    rng = random.Random(digest)
    s = rng.randrange(1, N // 2)
    key, r = recovered_key(digest % N, rng.randrange(1, N), s)
    assert digest_three_ways(key, be(digest), be(r) + be(s)) is True
    assert digest_three_ways(key, be(digest ^ 2), be(r) + be(s)) is False


@pytest.mark.parametrize("s", [1, 2, N // 2], ids=["1", "2", "n_half"])
def test_scalars_at_the_edges_of_their_range(s):
    """s = 1 makes u1 = e and u2 = r themselves; e = n - 1 is the
    longest carry a signed-digit form of a scalar can have."""
    rng = random.Random(s)
    key, r = recovered_key(N - 1, rng.randrange(1, N), s)
    assert digest_three_ways(key, be(N - 1), be(r) + be(s)) is True
    assert digest_three_ways(key, be(N - 2), be(r) + be(s)) is False


def test_an_r_whose_point_lies_above_n_is_compared_as_r_plus_n():
    """R.x in [n, p), 2^-128 of all signatures: r = R.x - n, and the
    comparison R.x mod n = r has to find it. The same (r, s) over
    another R (x = r itself, where that is a point) is refused."""
    rng = random.Random(51)
    digest = hashlib.sha256(b"above n").digest()
    e, s = int.from_bytes(digest, "big"), rng.randrange(1, N // 2)
    while True:
        x = N + rng.randrange(0, P - N)
        point = reference_mixed.secp256k1_decode(b"\x02" + be(x))
        if point is not None:
            break
    key, r = recovered_key(e, point, s)
    assert r == x - N and r < P - N
    assert digest_three_ways(key, digest, be(r) + be(s)) is True
    assert digest_three_ways(key, digest, be(r + 1) + be(s)) is False
    assert digest_three_ways(key, digest, be(r) + be(s + 1)) is False


def low_s_multiple():
    """``(u, r, s)``: u1 = u2 = u over the digest value e = r, with
    r = (2u G).x and s = r / u low."""
    for u in range(1, 64, 2):
        x, y, z = _k_mult(2 * u, G)
        r = x * pow(z * z, P - 2, P) % P % N
        s = r * pow(u, N - 2, N) % N
        if s <= N // 2:
            return u, r, s
    raise AssertionError("no odd u under 64 gives a low s")


def test_an_addition_that_meets_its_own_operand_doubles():
    """Q = G and u1 = u2 = u, one odd digit: the pass adds u G from G's
    table and then u Q from Q's, the same point, and the sum is 2u G."""
    u, r, s = low_s_multiple()
    key = compress(G)
    assert digest_three_ways(key, be(r), be(r) + be(s)) is True
    assert digest_three_ways(key, be(r + 1), be(r) + be(s)) is False


def test_a_sum_that_is_infinity_is_refused():
    """Q = -G and u1 = u2: u G + u Q is the point at infinity, whatever
    r and s are."""
    rng = random.Random(52)
    key = Secp256k1PrivKey(be(N - 1)).pub_key().bytes()
    for r in (1, 3, rng.randrange(1, N)):
        assert digest_three_ways(key, be(r), be(r) + be(rng.randrange(1, N // 2))) is False


# --- batches ----------------------------------------------------------------------


def test_no_lanes_and_one():
    assert Secp256k1PubKey.verify_many([], [], []) == []
    assert hashing.secp256k1_verify_native(b"", b"", b"", 0) == b""
    rng = random.Random(53)
    assert three_ways([signed(rng)]) == [True]
    with pytest.raises(ValueError, match="as many"):
        Secp256k1PubKey.verify_many([], [b"m"], [])
    with pytest.raises(ValueError, match="lanes want"):
        hashing.secp256k1_verify_native(bytes(33), bytes(32), bytes(63), 1)


def test_a_hundred_lanes_with_bad_ones_at_known_places():
    """A commit's worth: two blocks of the routine's shared inversion,
    with a refused lane first and last in each, lanes whose s is out of
    range (they take no part in the inversion) and lanes refused after
    it."""
    rng = random.Random(54)
    lanes = [signed(rng) for _ in range(100)]
    bad = {0: "s_zero", 1: "message_bit", 17: "high_s", 62: "r_bit", 63: "s_is_n", 64: "key_x_bit",
           65: "r_zero", 80: "sig_63_bytes", 98: "x_with_no_point", 99: "s_bit"}
    for at, fault in bad.items():
        lanes[at] = FAULTS[fault](rng, lanes[at])
    assert three_ways(lanes) == [i not in bad for i in range(100)]


def test_a_thousand_random_lanes():
    rng = random.Random(55)
    faults = sorted(FAULTS)
    lanes, want = [], []
    for i in range(1000):
        lane = signed(rng, scalar=rng.choice([None, None, None, 1, 2, 3, N - 1]))
        if rng.random() < 0.3:
            lane = FAULTS[rng.choice(faults)](rng, lane)
            want.append(False)
        else:
            want.append(True)
        lanes.append(lane)
    assert three_ways(lanes) == want


# --- a compiler with no 128-bit integers -------------------------------------------


def test_built_without_128_bit_integers_the_entry_says_so_and_openssl_answers(tmp_path, monkeypatch):
    """The library still builds and links there (the hashing entries
    are the same), ``secp256k1_ecdsa_verify_batch`` returns 0 and writes
    nothing, and the key class takes the OpenSSL path."""
    import os
    import subprocess

    native = os.path.join(os.path.dirname(os.path.dirname(hashing.__file__)), "native")
    lib_path = str(tmp_path / "libno128.so")
    subprocess.run(
        ["cc", "-O1", "-shared", "-fPIC", "-fopenmp", "-U__SIZEOF_INT128__",
         *(os.path.join(native, name) for name in hashing._SOURCES), "-o", lib_path],
        check=True, capture_output=True, timeout=120,
    )
    lib = hashing._load(lib_path)
    assert lib is not None
    monkeypatch.setattr(hashing, "_LIB", lib)
    monkeypatch.setattr(hashing, "_LIB_TRIED", True)
    assert (hashing.host_hash_impl(), hashing.host_secp256k1_impl()) == ("native", "openssl")
    assert hashing.secp256k1_verify_native(bytes(33), bytes(32), bytes(64), 1) is None
    rng = random.Random(56)
    key, msg, sig = signed(rng)
    assert Secp256k1PubKey(key).verify_signature(msg, sig) is True
    assert Secp256k1PubKey(key).verify_signature(msg + b"x", sig) is False
    assert Secp256k1PubKey.verify_impl() == "openssl"
