"""Plain big-integer Ed25519 verification under the ZIP-215 rules: the
reference the benchmark compares the program's verdicts with. It
imports nothing of the program and takes nothing the program has made.

ZIP-215: A and R are decoded as in RFC 8032 5.1.3 except that a y
coordinate of p or more is accepted (and reduced); s must be below the
group order L; and the equation is the cofactored one,
[8][s]B = [8]R + [8][k]A with k = SHA-512(R || A || M) mod L.

Not settled by the sources at hand: an encoding with x = 0 and the sign
bit set. RFC 8032 refuses it and so does this file; the program's two
host oracles disagree with each other on it (PERF.md, open questions).
Seeded keys and signatures never produce it.
"""

from __future__ import annotations

import hashlib

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = (-121665 * pow(121666, P - 2, P)) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)

# extended coordinates (X, Y, Z, T), x = X/Z, y = Y/Z, T = XY/Z
IDENTITY = (0, 1, 1, 0)


def point_add(p, q):
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = 2 * D * t1 * t2 % P
    d = 2 * z1 * z2 % P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def scalar_mult(k: int, p):
    out = IDENTITY
    while k:
        if k & 1:
            out = point_add(out, p)
        p = point_add(p, p)
        k >>= 1
    return out


def decode_point(enc: bytes):
    """RFC 8032 5.1.3 without the y < p check; None if not a point."""
    if len(enc) != 32:
        return None
    y = int.from_bytes(enc, "little")
    sign = y >> 255
    y = (y & ((1 << 255) - 1)) % P
    u = (y * y - 1) % P
    v = (D * y * y + 1) % P
    x = u * pow(v, 3, P) * pow(u * pow(v, 7, P), (P - 5) // 8, P) % P
    vxx = v * x * x % P
    if vxx == u:
        pass
    elif vxx == (-u) % P:
        x = x * SQRT_M1 % P
    else:
        return None
    if x == 0 and sign:
        return None
    if x & 1 != sign:
        x = P - x
    return (x, y, 1, x * y % P)


_BY = 4 * pow(5, P - 2, P) % P
BASE = decode_point(_BY.to_bytes(32, "little"))


def is_identity(p) -> bool:
    x, y, z, _ = p
    return x % P == 0 and (y - z) % P == 0


def verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    if len(pub) != 32 or len(sig) != 64:
        return False
    a = decode_point(pub)
    r = decode_point(sig[:32])
    s = int.from_bytes(sig[32:], "little")
    if a is None or r is None or s >= L:
        return False
    k = int.from_bytes(hashlib.sha512(sig[:32] + pub + msg).digest(), "little") % L
    # [8]([s]B - R - [k]A) == identity
    neg = lambda p: ((-p[0]) % P, p[1], p[2], (-p[3]) % P)
    acc = point_add(scalar_mult(s, BASE), neg(r))
    acc = point_add(acc, neg(scalar_mult(k, a)))
    return is_identity(scalar_mult(8, acc))
