"""Batched SHA-512 (+ mod-L reduction) on device: fused challenge prep.

The verifier's challenge scalar ``k = SHA-512(R || A || M) mod L`` was
the last hot-path stage still computed on the host CPU (crypto/hashing
.py: C extension or hashlib). For the batches that dominate consensus —
N fixed-width vote/commit sign-bytes — this module computes it on
device instead: the host packs raw bytes into padded SHA-512 blocks
(one ``(N, B*128)`` uint8 matrix, no hashing work), and a jitted kernel
runs the 80-round compression plus the byte-limb Barrett reduction, so
the challenge never round-trips through host memory and the host "prep"
stage shrinks to byte packing.

Representation: one 64-bit SHA word is an (hi, lo) pair of uint32 lane
vectors — f64/i64 are banned on this accelerator path (tpulint TPJ003),
and uint32 pairs map directly onto the VPU. The mod-L reduction is
Barrett's in radix 2^8 on int32 columns (``q = floor(floor(x/2^240) *
mu / 2^272)``, three conditional subtracts) and gives the one residue
in [0, L), as the host's does (crypto/hashing.reduce_mod_l_int is the
definition: ``int.from_bytes(digest, "little") % L``), so device and
host scalars are bit-identical — pinned by the parity battery in
tests/test_device_hash.py.

Constants are derived, not transcribed: round constants are the
fractional cube roots of the first 80 primes and the init state the
fractional square roots of the first 8, computed exactly with integer
Newton roots at import.

Env knobs::

    TENDERMINT_TPU_DEVICE_HASH         auto (default: on for tpu) | on | off
    TENDERMINT_TPU_DEVICE_HASH_MAXLEN  widest per-lane message the fused
                                       path accepts (default 512 bytes)
"""

from __future__ import annotations

import math
import os
import threading
from functools import lru_cache
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.crypto.hashing import L

_ENV = "TENDERMINT_TPU_DEVICE_HASH"
_MAXLEN_ENV = "TENDERMINT_TPU_DEVICE_HASH_MAXLEN"

_MASK64 = (1 << 64) - 1


def _primes(count: int):
    out = []
    cand = 2
    while len(out) < count:
        if all(cand % p for p in out if p * p <= cand):
            out.append(cand)
        cand += 1
    return out


def _icbrt(n: int) -> int:
    """floor(n ** (1/3)) by integer Newton iteration."""
    x = 1 << -(-n.bit_length() // 3)
    while True:
        y = (2 * x + n // (x * x)) // 3
        if y >= x:
            return x
        x = y


_P80 = _primes(80)
# K[t] = frac(cbrt(p_t)) * 2^64; H0[i] = frac(sqrt(p_i)) * 2^64.
_K64 = [_icbrt(p << 192) & _MASK64 for p in _P80]
_H64 = [math.isqrt(p << 128) & _MASK64 for p in _P80[:8]]
_K_HI = [k >> 32 for k in _K64]
_K_LO = [k & 0xFFFFFFFF for k in _K64]
_H_HI = [h >> 32 for h in _H64]
_H_LO = [h & 0xFFFFFFFF for h in _H64]

# Round constants as a (80, 2) uint32 (hi, lo) table the round loop
# indexes dynamically, and the init state as plain python ints.
_K_ARR = np.array(list(zip(_K_HI, _K_LO)), dtype=np.uint32)

# Byte limbs (little-endian) of the Barrett constants; python ints so
# the traced kernel folds them in as scalars.
_MU = (1 << 512) // L
_MU_BYTES = [(_MU >> (8 * i)) & 0xFF for i in range((_MU.bit_length() + 7) // 8)]
_L_BYTES = [(L >> (8 * i)) & 0xFF for i in range(32)]


# --- 64-bit word ops on (hi, lo) uint32 pairs --------------------------------


def _add64(ah, al, bh, bl):
    lo = al + bl
    carry = (lo < al).astype(jnp.uint32)
    return ah + bh + carry, lo


def _rotr64(h, l, r: int):
    """Rotate right by static r in [1, 63], r % 32 != 0 (true for every
    rotation SHA-512 uses)."""
    hh, ll = (h, l) if r < 32 else (l, h)
    rr = r % 32
    s = 32 - rr
    return (hh >> rr) | (ll << s), (ll >> rr) | (hh << s)


def _shr64(h, l, n: int):
    """Logical shift right by static n in [1, 31]."""
    return h >> n, (l >> n) | (h << (32 - n))


def _xor3(a, b, c):
    return (a[0] ^ b[0] ^ c[0], a[1] ^ b[1] ^ c[1])


def _small_sigma0(h, l):
    return _xor3(_rotr64(h, l, 1), _rotr64(h, l, 8), _shr64(h, l, 7))


def _small_sigma1(h, l):
    return _xor3(_rotr64(h, l, 19), _rotr64(h, l, 61), _shr64(h, l, 6))


def _big_sigma0(h, l):
    return _xor3(_rotr64(h, l, 28), _rotr64(h, l, 34), _rotr64(h, l, 39))


def _big_sigma1(h, l):
    return _xor3(_rotr64(h, l, 14), _rotr64(h, l, 18), _rotr64(h, l, 41))


# --- compression -------------------------------------------------------------


def _sched_step(t, wbuf):
    """Message-schedule fill: w[t] = s1(w[t-2]) + w[t-7] + s0(w[t-15])
    + w[t-16]; wbuf is (80, 2, N) uint32."""
    w2 = jax.lax.dynamic_index_in_dim(wbuf, t - 2, keepdims=False)
    w7 = jax.lax.dynamic_index_in_dim(wbuf, t - 7, keepdims=False)
    w15 = jax.lax.dynamic_index_in_dim(wbuf, t - 15, keepdims=False)
    w16 = jax.lax.dynamic_index_in_dim(wbuf, t - 16, keepdims=False)
    s1 = _small_sigma1(w2[0], w2[1])
    s0 = _small_sigma0(w15[0], w15[1])
    acc = _add64(s1[0], s1[1], w7[0], w7[1])
    acc = _add64(acc[0], acc[1], s0[0], s0[1])
    acc = _add64(acc[0], acc[1], w16[0], w16[1])
    return jax.lax.dynamic_update_index_in_dim(
        wbuf, jnp.stack(acc), t, axis=0
    )


def _make_round(wbuf, k_arr):
    def round_step(t, vars8):
        """One compression round; vars8 is (8, 2, N) uint32 = a..h."""
        a, b, c, d = vars8[0], vars8[1], vars8[2], vars8[3]
        e, f, g, hh = vars8[4], vars8[5], vars8[6], vars8[7]
        wt = jax.lax.dynamic_index_in_dim(wbuf, t, keepdims=False)
        kt = jax.lax.dynamic_index_in_dim(k_arr, t, keepdims=False)
        ch = ((e[0] & f[0]) ^ (~e[0] & g[0]), (e[1] & f[1]) ^ (~e[1] & g[1]))
        bs1 = _big_sigma1(e[0], e[1])
        t1 = _add64(hh[0], hh[1], bs1[0], bs1[1])
        t1 = _add64(t1[0], t1[1], ch[0], ch[1])
        t1 = _add64(t1[0], t1[1], kt[0], kt[1])
        t1 = _add64(t1[0], t1[1], wt[0], wt[1])
        maj = (
            (a[0] & b[0]) ^ (a[0] & c[0]) ^ (b[0] & c[0]),
            (a[1] & b[1]) ^ (a[1] & c[1]) ^ (b[1] & c[1]),
        )
        bs0 = _big_sigma0(a[0], a[1])
        t2 = _add64(bs0[0], bs0[1], maj[0], maj[1])
        new_e = jnp.stack(_add64(d[0], d[1], t1[0], t1[1]))
        new_a = jnp.stack(_add64(t1[0], t1[1], t2[0], t2[1]))
        return jnp.stack([new_a, a, b, c, new_e, e, f, g])

    return round_step


def _sha512_blocks(data: jnp.ndarray) -> jnp.ndarray:
    """(N, B*128) uint8 pre-padded blocks -> (N, 64) uint8 digests.

    The block count is static (part of the traced shape); the schedule
    and round loops run as fori_loops so the traced graph stays small
    (the fully unrolled form took minutes to compile). Every lane runs
    the same compression — pure SIMD over the batch like the verify
    kernel.
    """
    n = data.shape[0]
    nblocks = data.shape[1] // 128
    k_arr = jnp.asarray(_K_ARR)  # (80, 2)
    state = jnp.stack(
        [
            jnp.stack(
                [
                    jnp.full((n,), _H_HI[i], dtype=jnp.uint32),
                    jnp.full((n,), _H_LO[i], dtype=jnp.uint32),
                ]
            )
            for i in range(8)
        ]
    )  # (8, 2, N)
    for blk in range(nblocks):
        bb = data[:, blk * 128 : (blk + 1) * 128]
        bb = bb.reshape(n, 16, 8).astype(jnp.uint32)
        hi = (
            (bb[:, :, 0] << 24) | (bb[:, :, 1] << 16)
            | (bb[:, :, 2] << 8) | bb[:, :, 3]
        )  # (N, 16)
        lo = (
            (bb[:, :, 4] << 24) | (bb[:, :, 5] << 16)
            | (bb[:, :, 6] << 8) | bb[:, :, 7]
        )
        w0 = jnp.stack([hi.T, lo.T], axis=1)  # (16, 2, N)
        wbuf = jnp.concatenate(
            [w0, jnp.zeros((64, 2, n), dtype=jnp.uint32)], axis=0
        )
        wbuf = jax.lax.fori_loop(16, 80, _sched_step, wbuf)
        vars8 = jax.lax.fori_loop(0, 80, _make_round(wbuf, k_arr), state)
        lo_s = state[:, 1] + vars8[:, 1]
        carry = (lo_s < state[:, 1]).astype(jnp.uint32)
        hi_s = state[:, 0] + vars8[:, 0] + carry
        state = jnp.stack([hi_s, lo_s], axis=1)
    # (8, 2, 4, N) big-endian bytes per 64-bit word, C-order flatten
    # gives word0 hi b3..b0, word0 lo b3..b0, word1 ... = the digest.
    by = jnp.stack([(state >> s) & 0xFF for s in (24, 16, 8, 0)], axis=2)
    return by.reshape(64, n).T.astype(jnp.uint8)


# --- byte-limb Barrett reduction mod L ---------------------------------------
#
# The residue crypto/hashing.reduce_mod_l_int defines, in radix 2^8 /
# int32: column magnitudes stay below 36 * 255^2 < 2^22, far inside int32.


def _mul_const_bytes(x: jnp.ndarray, const_bytes, out_len: int) -> jnp.ndarray:
    """(N, a) int32 byte limbs times a constant's byte limbs -> (N,
    out_len) un-carried columns (out_len >= a + len(const_bytes))."""
    a = x.shape[1]
    cols = jnp.zeros((x.shape[0], out_len), dtype=jnp.int32)
    for j, cb in enumerate(const_bytes):
        cols = cols.at[:, j : j + a].add(x * cb)
    return cols


def _carry_bytes(cols: jnp.ndarray, nlimbs: int) -> jnp.ndarray:
    """Carry-propagate int32 columns into nlimbs byte limbs (overflow
    beyond nlimbs dropped — callers rely on the mod-2^(8*nlimbs))."""
    outs = []
    c = jnp.zeros(cols.shape[0], dtype=jnp.int32)
    for i in range(nlimbs):
        v = c + cols[:, i]
        outs.append(v & 0xFF)
        c = v >> 8
    return jnp.stack(outs, axis=1)


def _sub_l_bytes(x: jnp.ndarray):
    """(N, 32) byte limbs minus L -> (limbs, borrow_out)."""
    outs = []
    borrow = jnp.zeros(x.shape[0], dtype=jnp.int32)
    for i in range(32):
        v = x[:, i] - _L_BYTES[i] - borrow
        borrow = (v < 0).astype(jnp.int32)
        outs.append(v + (borrow << 8))
    return jnp.stack(outs, axis=1), borrow


def _reduce_mod_l_bytes(digest: jnp.ndarray) -> jnp.ndarray:
    """(N, 64) uint8 little-endian 512-bit values -> (N, 32) uint8 mod L.

    Barrett with q from x >> 240, then >> 272, so r = x - q*L < 4L and
    three conditional subtracts leave the residue in [0, L): the host
    path's bytes, bit for bit.
    """
    x = digest.astype(jnp.int32)
    q1 = x[:, 30:]  # (N, 34): x >> 240
    q2_len = 34 + len(_MU_BYTES) + 1
    q2 = _carry_bytes(_mul_const_bytes(q1, _MU_BYTES, q2_len), q2_len)
    q = q2[:, 34:]  # >> 272; q < 2^261 fits the remaining limbs
    ql_cols = _mul_const_bytes(q, _L_BYTES, q.shape[1] + 32)
    ql = _carry_bytes(ql_cols, 32)  # mod 2^256: r < 4L < 2^255 fits
    outs = []
    borrow = jnp.zeros(x.shape[0], dtype=jnp.int32)
    for i in range(32):
        v = x[:, i] - ql[:, i] - borrow
        borrow = (v < 0).astype(jnp.int32)
        outs.append(v + (borrow << 8))
    r = jnp.stack(outs, axis=1)
    for _ in range(3):
        sub, borrow = _sub_l_bytes(r)
        r = jnp.where((borrow == 0)[:, None], sub, r)
    return r.astype(jnp.uint8)


def _challenge_kernel(data: jnp.ndarray) -> jnp.ndarray:
    return _reduce_mod_l_bytes(_sha512_blocks(data))


@lru_cache(maxsize=8)
def _compiled_sha512(backend: Optional[str]):
    return jax.jit(_sha512_blocks, backend=backend)


@lru_cache(maxsize=8)
def _compiled_challenge(backend: Optional[str]):
    return jax.jit(_challenge_kernel, backend=backend)


# --- host-side packing and entry points --------------------------------------


def _pack(rows: np.ndarray) -> np.ndarray:
    """(N, T) uint8 messages (all the same length) -> (N, B*128) padded
    SHA-512 blocks: 0x80, zeros, 128-bit big-endian bit length."""
    n, total = rows.shape
    padded = ((total + 17 + 127) // 128) * 128
    buf = np.zeros((n, padded), dtype=np.uint8)
    buf[:, :total] = rows
    buf[:, total] = 0x80
    buf[:, -16:] = np.frombuffer((total * 8).to_bytes(16, "big"), dtype=np.uint8)
    return buf


def device_hash_mode() -> str:
    return os.environ.get(_ENV, "auto").lower()


def device_hash_enabled(backend: Optional[str] = None) -> bool:
    """Whether the fused device-hash path serves eligible batches."""
    from tendermint_tpu.ops import backend as backend_mod

    return backend_mod.auto_on(device_hash_mode(), backend)


def max_msg_len() -> int:
    try:
        return max(0, int(os.environ.get(_MAXLEN_ENV, "512")))
    except ValueError:
        return 512


_metrics = None
_metrics_lock = threading.Lock()
_device_lanes = 0  # guarded-by: _metrics_lock


def bind_metrics(metrics) -> None:
    global _metrics
    with _metrics_lock:
        _metrics = metrics


def _count_lanes(n: int) -> None:
    global _device_lanes
    with _metrics_lock:
        _device_lanes += n
        metrics = _metrics
    if metrics is not None:
        metrics.hash_device_lanes.inc(n)


def stats() -> dict:
    with _metrics_lock:
        return {"device_lanes": _device_lanes}


def reset_stats() -> None:
    global _device_lanes
    with _metrics_lock:
        _device_lanes = 0


def sha512_device(msgs, backend: Optional[str] = None) -> np.ndarray:
    """Uniform-length messages -> (N, 64) uint8 digests, hashed on
    device (parity/test entry point; the hot path uses
    :func:`try_challenge_device`). Accepts a (N, T) uint8 matrix or a
    sequence of equal-length byte strings."""
    if isinstance(msgs, np.ndarray):
        mat = msgs.astype(np.uint8, copy=False)
    else:
        n = len(msgs)
        if n == 0:
            return np.zeros((0, 64), dtype=np.uint8)
        w = len(msgs[0])
        mat = np.frombuffer(b"".join(msgs), dtype=np.uint8).reshape(n, w)
    out = _compiled_sha512(backend)(jnp.asarray(_pack(mat)))
    return np.asarray(out)


def try_challenge_device(
    prefix: np.ndarray, msgs: Sequence[bytes], backend: Optional[str] = None
):
    """Fused challenge scalars for one chunk, or None for the host path.

    Returns a DEVICE-resident (N, 32) uint8 array of ``SHA-512(prefix_i
    || msg_i) mod L`` when the fused path applies: device hashing
    enabled for this backend and every message the same (bounded)
    length — true for the vote/commit batches that dominate consensus.
    A kernel failure is a device failure: it raises to the caller (the
    engines' chunk prep, which hands it to the health machine) and is
    never converted into host hashing here.
    """
    if not device_hash_enabled(backend):
        return None
    n = len(msgs)
    if n == 0:
        return None
    w = len(msgs[0])
    if w > max_msg_len():
        return None
    for m in msgs:
        if len(m) != w:
            return None
    mat = np.frombuffer(b"".join(msgs), dtype=np.uint8).reshape(n, w)
    data = _pack(np.concatenate([prefix, mat], axis=1))
    out = _compiled_challenge(backend)(jnp.asarray(data))
    _count_lanes(n)
    return out
