"""Batched host-side hashing for the device verifier.

``sha512_batch`` hashes N variable-length messages through a small C
extension (``native/sha512_batch.c``, OpenMP-parallel from 1,024
messages up, built lazily
with the system compiler and loaded via ctypes) with a pure-hashlib
fallback. ``sha512_batch_mod_l`` additionally reduces each 512-bit
digest mod the ed25519 group order L with a vectorized numpy Barrett
reduction — no per-signature Python arithmetic anywhere on the hot
path.

Reference analog: the challenge hashing inside curve25519-voi's batch
verifier (crypto/ed25519/ed25519.go:198-233).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import List, Optional, Sequence

import numpy as np

L = 2**252 + 27742317777372353535851937790883648493

_LIB: Optional[ctypes.CDLL] = None
_LIB_TRIED = False


def _build_and_load() -> Optional[ctypes.CDLL]:
    """Compile the C extension once per machine and load it."""
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native", "sha512_batch.c")
    if not os.path.exists(src):
        return None
    build_dir = os.environ.get(
        "TENDERMINT_TPU_BUILD_DIR",
        os.path.join(tempfile.gettempdir(), "tendermint_tpu_native"),
    )
    os.makedirs(build_dir, exist_ok=True)
    lib_path = os.path.join(build_dir, "libsha512batch.so")
    if not os.path.exists(lib_path) or os.path.getmtime(lib_path) < os.path.getmtime(src):
        for cc in ("cc", "gcc", "g++"):
            try:
                subprocess.run(
                    [cc, "-O3", "-shared", "-fPIC", "-fopenmp", src, "-o", lib_path + ".tmp"],
                    check=True,
                    capture_output=True,
                    timeout=120,
                )
                os.replace(lib_path + ".tmp", lib_path)
                break
            except Exception:
                continue
        else:
            return None
    try:
        lib = ctypes.CDLL(lib_path)
        lib.sha512_batch.argtypes = [
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint8),
        ]
        lib.sha512_batch.restype = None
        lib.sha512_batch_prefixed.argtypes = [
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint8),
        ]
        lib.sha512_batch_prefixed.restype = None
        return lib
    except Exception:
        return None


def _lib() -> Optional[ctypes.CDLL]:
    global _LIB, _LIB_TRIED
    if not _LIB_TRIED:
        _LIB_TRIED = True
        _LIB = _build_and_load()
    return _LIB


def host_hash_impl() -> str:
    """Which host hashing path is live: ``"native"`` (the C extension
    built and loaded) or ``"hashlib"`` (no source, no compiler, or a
    failed build). The choice is otherwise silent; chip_smoke.py prints
    it and treats ``hashlib`` as a failed build."""
    return "native" if _lib() is not None else "hashlib"


def sha512_batch(msgs: Sequence[bytes]) -> np.ndarray:
    """N messages -> (N, 64) uint8 digests."""
    n = len(msgs)
    if n == 0:
        return np.zeros((0, 64), dtype=np.uint8)
    lib = _lib()
    if lib is None:
        out = np.empty((n, 64), dtype=np.uint8)
        for i, m in enumerate(msgs):
            out[i] = np.frombuffer(hashlib.sha512(m).digest(), dtype=np.uint8)
        return out
    offsets = np.zeros(n + 1, dtype=np.uint64)
    np.cumsum([len(m) for m in msgs], out=offsets[1:])
    buf = np.frombuffer(b"".join(msgs), dtype=np.uint8)
    if buf.size == 0:
        buf = np.zeros(1, dtype=np.uint8)
    out = np.empty((n, 64), dtype=np.uint8)
    lib.sha512_batch(
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    return out


def sha512_batch_prefixed(prefix: np.ndarray, msgs: Sequence[bytes]) -> np.ndarray:
    """Hash prefix_i || msg_i for a (N, 64) uint8 prefix block -> (N, 64).

    The verifier's challenge is SHA-512(R || A || M); R and A already
    live in (N, 32) arrays, so the 64-byte prefix block costs one
    concatenate instead of N Python byte-string builds.
    """
    n = len(msgs)
    assert prefix.shape == (n, 64) and prefix.dtype == np.uint8
    if n == 0:
        return np.zeros((0, 64), dtype=np.uint8)
    lib = _lib()
    if lib is None:
        out = np.empty((n, 64), dtype=np.uint8)
        pb = np.ascontiguousarray(prefix)
        for i, m in enumerate(msgs):
            h = hashlib.sha512(pb[i].tobytes())
            h.update(m)
            out[i] = np.frombuffer(h.digest(), dtype=np.uint8)
        return out
    offsets = np.zeros(n + 1, dtype=np.uint64)
    np.cumsum([len(m) for m in msgs], out=offsets[1:])
    buf = np.frombuffer(b"".join(msgs), dtype=np.uint8)
    if buf.size == 0:
        buf = np.zeros(1, dtype=np.uint8)
    out = np.empty((n, 64), dtype=np.uint8)
    pb = np.ascontiguousarray(prefix)
    lib.sha512_batch_prefixed(
        pb.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    return out


# --- vectorized Barrett reduction mod L -------------------------------------
#
# Values are little-endian 16-bit limb vectors; all products accumulate
# in int64 (max column ~ 40 * 2^32 < 2^38, exact). Barrett with
# mu = floor(2^512 / L): q = floor(floor(x / 2^248) * mu / 2^264),
# r = x - q*L, then at most two conditional subtracts of L.

_NL16 = 16  # limbs of a 256-bit value
_L_LIMBS = np.array([(L >> (16 * i)) & 0xFFFF for i in range(16)], dtype=np.int64)
_MU = (1 << 512) // L
_MU_LIMBS = np.array([(_MU >> (16 * i)) & 0xFFFF for i in range((_MU.bit_length() + 15) // 16)], dtype=np.int64)


def _carry16(cols: np.ndarray, nlimbs: int) -> np.ndarray:
    """Carry-propagate int64 columns into nlimbs 16-bit limbs (drop overflow)."""
    out = np.zeros((cols.shape[0], nlimbs), dtype=np.int64)
    c = np.zeros(cols.shape[0], dtype=np.int64)
    for i in range(nlimbs):
        v = c + (cols[:, i] if i < cols.shape[1] else 0)
        out[:, i] = v & 0xFFFF
        c = v >> 16
    return out


def _mul_const(x: np.ndarray, const_limbs: np.ndarray) -> np.ndarray:
    """(N, a) 16-bit limbs times constant (b,) limbs -> (N, a+b) columns."""
    n, a = x.shape
    b = const_limbs.shape[0]
    cols = np.zeros((n, a + b), dtype=np.int64)
    for j in range(b):
        cols[:, j : j + a] += x * const_limbs[j]
    return cols


def _ge(x: np.ndarray, y_limbs: np.ndarray) -> np.ndarray:
    """(N, 16) >= const (16,) comparison, little-endian limbs."""
    diff = x - y_limbs[None, :]
    nz = diff != 0
    rev = nz[:, ::-1]
    first = np.argmax(rev, axis=1)
    rows = np.arange(x.shape[0])
    val = diff[:, ::-1][rows, first]
    any_nz = nz.any(axis=1)
    return np.where(any_nz, val > 0, True)


def reduce_mod_l(digests: np.ndarray) -> np.ndarray:
    """(N, 64) uint8 little-endian 512-bit values -> (N, 32) uint8 mod L."""
    n = digests.shape[0]
    x16 = (
        digests.reshape(n, 32, 2).astype(np.int64)[:, :, 0]
        + (digests.reshape(n, 32, 2).astype(np.int64)[:, :, 1] << 8)
    )  # (N, 32) 16-bit limbs, little-endian
    # q1 = floor(x / 2^248) -> drop 15.5 limbs; use 2^240 (15 limbs) for a
    # slightly larger q1*mu, then shift 2^272 total. Keep it simple and
    # exact: q = floor( floor(x/2^240) * mu / 2^272 ).
    q1 = x16[:, 15:]  # (N, 17) limbs: x >> 240
    q2 = _mul_const(q1, _MU_LIMBS)  # x/2^240 * mu, columns
    q2 = _carry16(q2, q2.shape[1])
    q = q2[:, 17:]  # >> 272
    # r = x - q*L (mod 2^256 is safe: r < 2L < 2^253)
    ql = _carry16(_mul_const(q, _L_LIMBS), 16)
    r = np.zeros((n, 16), dtype=np.int64)
    borrow = np.zeros(n, dtype=np.int64)
    for i in range(16):
        v = x16[:, i] - ql[:, i] - borrow
        borrow = (v < 0).astype(np.int64)
        r[:, i] = v + (borrow << 16)
    # Barrett error bound for this shift choice: r < 4L -> up to 3 subtracts.
    for _ in range(3):
        ge = _ge(r, _L_LIMBS)
        borrow = np.zeros(n, dtype=np.int64)
        sub = np.zeros_like(r)
        for i in range(16):
            v = r[:, i] - _L_LIMBS[i] - borrow
            borrow = (v < 0).astype(np.int64)
            sub[:, i] = v + (borrow << 16)
        r = np.where(ge[:, None], sub, r)
    out = np.zeros((n, 32), dtype=np.uint8)
    out[:, 0::2] = (r & 0xFF).astype(np.uint8)
    out[:, 1::2] = ((r >> 8) & 0xFF).astype(np.uint8)
    return out


def sha512_batch_mod_l(msgs: Sequence[bytes]) -> List[bytes]:
    """N messages -> N 32-byte little-endian scalars SHA-512(m) mod L."""
    if not msgs:
        return []
    digests = sha512_batch(msgs)
    reduced = reduce_mod_l(digests)
    return [reduced[i].tobytes() for i in range(reduced.shape[0])]
