"""Plain big-integer verification of the three key types a mixed
committee holds: the reference the ``mixed10k`` cell compares the
program's verdicts with. It imports nothing of the program and takes
nothing the program has made.

- ed25519: ``reference.verify`` (ZIP-215).
- sr25519: schnorrkel over ristretto255. The signature is R || s with
  the marker bit (bit 255 of s) set; s < L; A and R are ristretto
  encodings decoded by RFC 9496 4.3.1 (canonical, non-negative); the
  challenge k is 64 bytes of the Merlin signing transcript (STROBE-128
  over Keccak-f[1600], written out below) reduced mod L; accepted when
  [s]B - [k]A and R are the same ristretto element.
- secp256k1: ECDSA over SHA-256 as upstream's crypto/secp256k1: a
  33-byte compressed key, a 64-byte r || s signature, 1 <= r, s < n,
  and the low-s rule (s <= n / 2).
"""

from __future__ import annotations

import hashlib

from chipbench import reference
from chipbench.reference import D, L, P, SQRT_M1, point_add, scalar_mult

# --- Keccak-f[1600], FIPS 202 3.2 step by step --------------------------------

_M64 = (1 << 64) - 1
_RC = []
_R = 1
for _round in range(24):  # 3.2.5: the round constants from the LFSR rc(t)
    _rc = 0
    for _j in range(7):
        if _R & 1:
            _rc |= 1 << ((1 << _j) - 1)
        _R = ((_R << 1) ^ (0x71 if _R & 0x80 else 0)) & 0xFF
    _RC.append(_rc)
_RHO = [[0] * 5 for _ in range(5)]  # 3.2.2: offsets (t + 1)(t + 2) / 2 along the walk
_x, _y = 1, 0
for _t in range(24):
    _RHO[_x][_y] = ((_t + 1) * (_t + 2) // 2) % 64
    _x, _y = _y, (2 * _x + 3 * _y) % 5


def _rot(v: int, n: int) -> int:
    return ((v << n) | (v >> (64 - n))) & _M64 if n else v


def keccak_f(state: bytearray) -> None:
    a = [[int.from_bytes(state[8 * (x + 5 * y):8 * (x + 5 * y) + 8], "little") for y in range(5)] for x in range(5)]
    for rc in _RC:
        c = [a[x][0] ^ a[x][1] ^ a[x][2] ^ a[x][3] ^ a[x][4] for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rot(c[(x + 1) % 5], 1) for x in range(5)]
        a = [[a[x][y] ^ d[x] for y in range(5)] for x in range(5)]
        b = [[0] * 5 for _ in range(5)]
        for x in range(5):
            for y in range(5):
                b[y][(2 * x + 3 * y) % 5] = _rot(a[x][y], _RHO[x][y])
        a = [[b[x][y] ^ (~b[(x + 1) % 5][y] & b[(x + 2) % 5][y] & _M64) for y in range(5)] for x in range(5)]
        a[0][0] ^= rc
    for x in range(5):
        for y in range(5):
            state[8 * (x + 5 * y):8 * (x + 5 * y) + 8] = a[x][y].to_bytes(8, "little")


# --- STROBE-128 (strobe.sourceforge.io/specs, v1.0.2) and Merlin v1.0 ---------

_RATE = 166  # 200 - 128 / 4 - 2
_I, _A, _C, _M = 1, 2, 4, 16


class Transcript:
    """A Merlin transcript: every message is framed as
    meta-AD(label || LE32(len)) then AD(message); a challenge as
    meta-AD(label || LE32(n)) then PRF(n)."""

    def __init__(self, label: bytes):
        self.st = bytearray(200)
        self.st[:18] = bytes([1, _RATE + 2, 1, 0, 1, 96]) + b"STROBEv1.0.2"
        keccak_f(self.st)
        self.pos = self.begin = 0
        self._op(_M | _A, b"Merlin v1.0")
        self.append(b"dom-sep", label)

    def _permute(self) -> None:
        self.st[self.pos] ^= self.begin
        self.st[self.pos + 1] ^= 0x04
        self.st[_RATE + 1] ^= 0x80
        keccak_f(self.st)
        self.pos = self.begin = 0

    def _absorb(self, data: bytes) -> None:
        for byte in data:
            self.st[self.pos] ^= byte
            self.pos += 1
            if self.pos == _RATE:
                self._permute()

    def _op(self, flags: int, data: bytes = b"", more: bool = False) -> None:
        if not more:
            old, self.begin = self.begin, self.pos + 1
            self._absorb(bytes([old, flags]))
            if flags & _C and self.pos:
                self._permute()
        self._absorb(data)

    def append(self, label: bytes, message: bytes) -> None:
        self._op(_M | _A, label)
        self._op(_M | _A, len(message).to_bytes(4, "little"), more=True)
        self._op(_A, message)

    def challenge(self, label: bytes, n: int) -> bytes:
        self._op(_M | _A, label)
        self._op(_M | _A, n.to_bytes(4, "little"), more=True)
        self._op(_I | _A | _C)
        out = bytearray()
        for _ in range(n):
            out.append(self.st[self.pos])
            self.st[self.pos] = 0
            self.pos += 1
            if self.pos == _RATE:
                self._permute()
        return bytes(out)


# --- ristretto255, RFC 9496 4.3 -------------------------------------------------


def _is_negative(x: int) -> bool:
    return bool(x % P & 1)


def _sqrt_ratio_m1(u: int, v: int):
    """RFC 9496 4.2: (was_square, the non-negative root of u / v or of
    sqrt(-1) u / v)."""
    r = u * pow(v, 3, P) * pow(u * pow(v, 7, P), (P - 5) // 8, P) % P
    check = v * r * r % P
    correct = check == u % P
    flipped = check == -u % P
    flipped_i = check == -u * SQRT_M1 % P
    if flipped or flipped_i:
        r = r * SQRT_M1 % P
    if _is_negative(r):
        r = P - r
    return correct or flipped, r


def ristretto_decode(enc: bytes):
    """RFC 9496 4.3.1; None for what is not the canonical encoding of
    an element."""
    if len(enc) != 32:
        return None
    s = int.from_bytes(enc, "little")
    if s >= P or s & 1:
        return None
    ss = s * s % P
    u1, u2 = (1 - ss) % P, (1 + ss) % P
    v = (-D * u1 * u1 - u2 * u2) % P
    was_square, invsqrt = _sqrt_ratio_m1(1, v * u2 * u2 % P)
    den_x = invsqrt * u2 % P
    den_y = invsqrt * den_x * v % P
    x = 2 * s * den_x % P
    if _is_negative(x):
        x = P - x
    y = u1 * den_y % P
    t = x * y % P
    if not was_square or _is_negative(t) or y == 0:
        return None
    return (x, y, 1, t)


def ristretto_equal(p, q) -> bool:
    """RFC 9496 4.3.3."""
    x1, y1, _, _ = p
    x2, y2, _, _ = q
    return (x1 * y2 - y1 * x2) % P == 0 or (y1 * y2 - x1 * x2) % P == 0


def sr25519_challenge(pub: bytes, msg: bytes, r_enc: bytes, context: bytes = b"") -> int:
    """schnorrkel's signing transcript and challenge scalar."""
    t = Transcript(b"SigningContext")
    t.append(b"", context)
    t.append(b"sign-bytes", msg)
    t.append(b"proto-name", b"Schnorr-sig")
    t.append(b"sign:pk", pub)
    t.append(b"sign:R", r_enc)
    return int.from_bytes(t.challenge(b"sign:c", 64), "little") % L


def verify_sr25519(pub: bytes, msg: bytes, sig: bytes, context: bytes = b"") -> bool:
    if len(pub) != 32 or len(sig) != 64 or not sig[63] & 0x80:
        return False
    s = int.from_bytes(sig[32:], "little") & ((1 << 255) - 1)
    a = ristretto_decode(pub)
    r = ristretto_decode(sig[:32])
    if a is None or r is None or s >= L:
        return False
    k = sr25519_challenge(pub, msg, sig[:32], context)
    neg_a = ((-a[0]) % P, a[1], a[2], (-a[3]) % P)
    return ristretto_equal(point_add(scalar_mult(s, reference.BASE), scalar_mult(k, neg_a)), r)


# --- secp256k1 ECDSA (SEC 1 4.1.4, SEC 2 2.4.1) -----------------------------------

_KP = 2**256 - 2**32 - 977
_KN = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
_KG = (
    0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798,
    0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8,
)


def _k_add(p, q):
    """Addition on y^2 = x^3 + 7 in Jacobian coordinates (x = X / Z^2,
    y = Y / Z^3); Z = 0 is the point at infinity."""
    x1, y1, z1 = p
    x2, y2, z2 = q
    if not z1:
        return q
    if not z2:
        return p
    z1z1, z2z2 = z1 * z1 % _KP, z2 * z2 % _KP
    u1, u2 = x1 * z2z2 % _KP, x2 * z1z1 % _KP
    s1, s2 = y1 * z2 * z2z2 % _KP, y2 * z1 * z1z1 % _KP
    if u1 == u2:
        if s1 != s2:
            return (1, 1, 0)
        # doubling (a = 0)
        m = 3 * x1 * x1 % _KP
        s = 4 * x1 * y1 * y1 % _KP
        x3 = (m * m - 2 * s) % _KP
        return x3, (m * (s - x3) - 8 * pow(y1, 4, _KP)) % _KP, 2 * y1 * z1 % _KP
    h, r = (u2 - u1) % _KP, (s2 - s1) % _KP
    hh = h * h % _KP
    hhh, v = h * hh % _KP, u1 * hh % _KP
    x3 = (r * r - hhh - 2 * v) % _KP
    return x3, (r * (v - x3) - s1 * hhh) % _KP, h * z1 * z2 % _KP


def _k_mult(k: int, p):
    out = (1, 1, 0)
    while k:
        if k & 1:
            out = _k_add(out, p)
        p = _k_add(p, p)
        k >>= 1
    return out


def secp256k1_decode(enc: bytes):
    """SEC 1 2.3.4: a 33-byte compressed point; None if it is none."""
    if len(enc) != 33 or enc[0] not in (2, 3):
        return None
    x = int.from_bytes(enc[1:], "big")
    if x >= _KP:
        return None
    y = pow(x * x * x + 7, (_KP + 1) // 4, _KP)
    if y * y % _KP != (x * x * x + 7) % _KP:
        return None
    return x, y if y & 1 == enc[0] & 1 else _KP - y, 1


def verify_secp256k1(pub: bytes, msg: bytes, sig: bytes) -> bool:
    q = secp256k1_decode(pub)
    if q is None or len(sig) != 64:
        return False
    r, s = int.from_bytes(sig[:32], "big"), int.from_bytes(sig[32:], "big")
    if not 1 <= r < _KN or not 1 <= s <= _KN // 2:
        return False
    e = int.from_bytes(hashlib.sha256(msg).digest(), "big")
    w = pow(s, _KN - 2, _KN)
    x, _, z = _k_add(_k_mult(e * w % _KN, _KG + (1,)), _k_mult(r * w % _KN, q))
    return z != 0 and x * pow(z * z, _KP - 2, _KP) % _KP % _KN == r


VERIFY = {
    "ed25519": reference.verify,
    "sr25519": verify_sr25519,
    "secp256k1": verify_secp256k1,
}


def verify(key_type: str, pub: bytes, msg: bytes, sig: bytes) -> bool:
    return VERIFY[key_type](pub, msg, sig)
