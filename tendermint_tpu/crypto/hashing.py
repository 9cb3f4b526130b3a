"""Batched host-side hashing for the device verifier.

``sha512_batch`` hashes N variable-length messages through a small C
extension (``native/sha512_batch.c``, OpenMP-parallel from 1,024
messages up, built lazily with the system's C compiler and loaded via
ctypes) with a pure-hashlib fallback where there is no compiler.
``sha512_batch_prefixed_mod_l`` is the verifier's challenge
k = SHA-512(R || A || M) mod L: the same extension reduces each digest
mod the ed25519 group order L in the loop that made it, so no
per-signature Python or NumPy arithmetic sits on the hot path. The
definition every path is held to is ``int.from_bytes(digest, "little")
% L`` (:func:`reduce_mod_l_int`). ``sr25519_challenges_mod_l`` is the
sr25519 verifier's challenge, a Merlin transcript a lane
(``native/merlin_batch.c``, in the same library; ``crypto/merlin.py`` is
the definition it is held to). The same library carries the one native
routine that is not a hash, because the repo has one way to build C and
this module is it: ``secp256k1_verify_native``, the ECDSA verification
of a batch of secp256k1 lanes (``native/secp256k1_batch.c``; the rules
are ``crypto/keys.Secp256k1PubKey``'s, which calls it).

Reference analog: the challenge hashing inside curve25519-voi's batch
verifier (crypto/ed25519/ed25519.go:198-233).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import tempfile
from typing import Optional, Sequence

import numpy as np

L = 2**252 + 27742317777372353535851937790883648493

_LIB: Optional[ctypes.CDLL] = None
_LIB_TRIED = False

_U8P = ctypes.POINTER(ctypes.c_uint8)
_U64P = ctypes.POINTER(ctypes.c_uint64)

# Every entry point this module calls, with its C signature. A library
# is loaded only with all of them resolved, so one built from another
# version of the source, or by a compiler that mangles names, never
# answers for this one.
_SYMBOLS = {
    "sha512_batch": [_U8P, _U64P, ctypes.c_int64, _U8P],
    "sha512_batch_prefixed_mod_l": [_U8P, _U8P, _U64P, ctypes.c_int64, _U8P],
    "reduce512_mod_l": [_U8P, ctypes.c_int64, _U8P],
    "sr25519_challenges_mod_l": [_U8P, _U8P, _U8P, _U64P, ctypes.c_int64, _U8P],
    "secp256k1_ecdsa_verify_batch": [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p
    ],
}
# What an entry returns, where it returns anything.
_RETURNS = {"secp256k1_ecdsa_verify_batch": ctypes.c_int}

# The library's translation units, in the order they are hashed and
# handed to the compiler.
_SOURCES = ("sha512_batch.c", "merlin_batch.c", "secp256k1_batch.c")


def _load(path: str) -> Optional[ctypes.CDLL]:
    """The library at ``path`` with every symbol of ``_SYMBOLS``
    resolved and typed, or None where it cannot be loaded or lacks one."""
    try:
        lib = ctypes.CDLL(path)
        for name, argtypes in _SYMBOLS.items():
            fn = getattr(lib, name)
            fn.argtypes = argtypes
            fn.restype = _RETURNS.get(name)
    except (OSError, AttributeError):
        return None
    return lib


def _build_and_load() -> Optional[ctypes.CDLL]:
    """Compile the C extension once per machine and source, and load it.

    The library is named by a hash of its sources, so checkouts that
    differ in one of ``_SOURCES`` (a parent commit and its change run in
    turn on one machine) never load each other's build. It is compiled
    under a name of this process's own, loaded and checked from there,
    and only then renamed into place: processes that start together
    each end with a whole library. None means there is nothing to build
    with (no source, or neither ``cc`` nor ``gcc``: the hashlib path).
    A build that should have worked and did not raises.
    """
    native = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")
    srcs = [os.path.join(native, name) for name in _SOURCES]
    cc = shutil.which("cc") or shutil.which("gcc")  # never g++: it mangles the names
    if not all(os.path.exists(src) for src in srcs) or cc is None:
        return None
    h = hashlib.sha256()
    for src in srcs:
        with open(src, "rb") as f:
            h.update(f.read())
    digest = h.hexdigest()[:12]
    build_dir = os.environ.get(
        "TENDERMINT_TPU_BUILD_DIR",
        os.path.join(tempfile.gettempdir(), "tendermint_tpu_native"),
    )
    os.makedirs(build_dir, exist_ok=True)
    lib_path = os.path.join(build_dir, f"libsha512batch-{digest}.so")
    lib = _load(lib_path)
    if lib is not None:
        return lib
    # Absent, or a file of that name that is not this source's library
    # (cut short, built by other hands): build it, once.
    fd, tmp = tempfile.mkstemp(prefix=f"libsha512batch-{digest}.", suffix=".so", dir=build_dir)
    os.close(fd)
    try:
        proc = subprocess.run(
            [cc, "-O3", "-shared", "-fPIC", "-fopenmp", *srcs, "-o", tmp],
            capture_output=True,
            timeout=120,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"{cc} could not build {srcs}: {proc.stderr.decode(errors='replace')[-2000:]}"
            )
        lib = _load(tmp)
        if lib is None:
            raise RuntimeError(f"{cc} built {srcs} into a library that lacks one of {sorted(_SYMBOLS)}")
        os.chmod(tmp, 0o755)  # mkstemp made it the owner's alone
        os.replace(tmp, lib_path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    # the same file under the name every later process finds it by
    return _load(lib_path) or lib


def _lib() -> Optional[ctypes.CDLL]:
    global _LIB, _LIB_TRIED
    if not _LIB_TRIED:
        _LIB = _build_and_load()  # raises again on the next call if it raised
        _LIB_TRIED = True
    return _LIB


def host_hash_impl() -> str:
    """Which host hashing path is live: ``"native"`` (the C extension
    built and loaded) or ``"hashlib"`` (no source or no C compiler).
    The choice is otherwise silent; chip_smoke.py prints it and treats
    ``hashlib`` as a failed build, and the engine's ``prep_chunk`` span
    carries it as ``hash``."""
    return "native" if _lib() is not None else "hashlib"


def secp256k1_verify_native(keys: bytes, digests: bytes, sigs: bytes, n: int) -> Optional[bytes]:
    """The verdicts of ``n`` secp256k1 ECDSA lanes, one byte a lane (1 =
    good), from one call into ``native/secp256k1_batch.c``: ``keys`` 33
    compressed bytes a lane, ``digests`` the 32 of SHA-256 over the
    signed bytes, ``sigs`` 64 of r || s. None where there is no library,
    or one built without 128-bit integers: the caller then has its own
    way (OpenSSL's). ``n`` = 0 asks only which of the two it is."""
    if not (len(keys) == 33 * n and len(digests) == 32 * n and len(sigs) == 64 * n):
        raise ValueError(f"{n} lanes want {33 * n}, {32 * n} and {64 * n} bytes")
    lib = _lib()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(n)
    if not lib.secp256k1_ecdsa_verify_batch(keys, digests, sigs, n, out):
        return None
    return out.raw


def host_secp256k1_impl() -> str:
    """Which secp256k1 verification is live: ``"native"`` (the routine
    of ``native/secp256k1_batch.c``) or ``"openssl"`` (``cryptography``'s,
    lane by lane: no compiler, or none with 128-bit integers). As
    :func:`host_hash_impl`: chip_smoke.py prints it and treats
    ``openssl`` as a failed build, and the ``host_lanes`` span carries
    it as ``impl``."""
    return "native" if secp256k1_verify_native(b"", b"", b"", 0) is not None else "openssl"


def reduce_mod_l_int(digest: bytes) -> bytes:
    """A little-endian value mod L as 32 little-endian bytes, in Python
    integers: the definition the C reduction (``reduce512_mod_l``) and
    the device's (``ops/hash512``) are tested against, and what the
    hashlib path computes."""
    return (int.from_bytes(digest, "little") % L).to_bytes(32, "little")


def _pack(msgs: Sequence[bytes]):
    """``(buf, offsets)`` as the C entries take N messages: one
    concatenated uint8 buffer and N + 1 uint64 offsets into it."""
    offsets = np.zeros(len(msgs) + 1, dtype=np.uint64)
    np.cumsum([len(m) for m in msgs], out=offsets[1:])
    buf = np.frombuffer(b"".join(msgs), dtype=np.uint8)
    if buf.size == 0:
        buf = np.zeros(1, dtype=np.uint8)
    return buf, offsets


def _ptr(arr: np.ndarray, typ=_U8P):
    return arr.ctypes.data_as(typ)


def sha512_batch(msgs: Sequence[bytes]) -> np.ndarray:
    """N messages -> (N, 64) uint8 digests."""
    n = len(msgs)
    out = np.empty((n, 64), dtype=np.uint8)
    if n == 0:
        return out
    lib = _lib()
    if lib is None:
        for i, m in enumerate(msgs):
            out[i] = np.frombuffer(hashlib.sha512(m).digest(), dtype=np.uint8)
        return out
    buf, offsets = _pack(msgs)
    lib.sha512_batch(_ptr(buf), _ptr(offsets, _U64P), n, _ptr(out))
    return out


def sha512_batch_prefixed_mod_l(prefix: np.ndarray, msgs: Sequence[bytes]) -> np.ndarray:
    """The challenge scalars SHA-512(prefix_i || msg_i) mod L for a
    (N, 64) uint8 prefix block -> (N, 32) uint8, little-endian.

    The verifier's challenge is SHA-512(R || A || M) mod L; R and A
    already live in (N, 32) arrays, so the 64-byte prefix block costs
    one concatenate instead of N Python byte-string builds, and the C
    loop that hashes a lane reduces its digest too.
    """
    n = len(msgs)
    if prefix.shape != (n, 64) or prefix.dtype != np.uint8:
        raise ValueError(f"prefix must be ({n}, 64) uint8, got {prefix.shape} {prefix.dtype}")
    out = np.empty((n, 32), dtype=np.uint8)
    if n == 0:
        return out
    pb = np.ascontiguousarray(prefix)
    lib = _lib()
    if lib is None:
        for i, m in enumerate(msgs):
            h = hashlib.sha512(pb[i].tobytes())
            h.update(m)
            out[i] = np.frombuffer(reduce_mod_l_int(h.digest()), dtype=np.uint8)
        return out
    buf, offsets = _pack(msgs)
    lib.sha512_batch_prefixed_mod_l(_ptr(pb), _ptr(buf), _ptr(offsets, _U64P), n, _ptr(out))
    return out


def reduce512_mod_l(digests: np.ndarray) -> np.ndarray:
    """(N, 64) uint8 little-endian 512-bit values -> (N, 32) uint8 mod L."""
    n = digests.shape[0]
    if digests.shape != (n, 64) or digests.dtype != np.uint8:
        raise ValueError(f"digests must be (N, 64) uint8, got {digests.shape} {digests.dtype}")
    out = np.empty((n, 32), dtype=np.uint8)
    lib = _lib()
    if lib is None:
        for i in range(n):
            out[i] = np.frombuffer(reduce_mod_l_int(digests[i].tobytes()), dtype=np.uint8)
    else:
        lib.reduce512_mod_l(_ptr(np.ascontiguousarray(digests)), n, _ptr(out))
    return out


def sr25519_challenges_mod_l(
    pubs: np.ndarray, rs: np.ndarray, msgs: Sequence[bytes]
) -> np.ndarray:
    """The schnorrkel challenge scalars of N lanes, one call: (N, 32)
    uint8 public keys and R encodings and the N messages -> (N, 32)
    uint8 little-endian, the 64-byte ``sign:c`` challenge of the Merlin
    signing transcript (empty context) mod L — what
    ``crypto/sr25519._challenge(_signing_transcript(msg), pub, r)``
    gives lane by lane, and what computes it where there is no compiler."""
    n = len(msgs)
    for arr in (pubs, rs):
        if arr.shape != (n, 32) or arr.dtype != np.uint8:
            raise ValueError(f"keys and R must be ({n}, 32) uint8, got {arr.shape} {arr.dtype}")
    out = np.empty((n, 32), dtype=np.uint8)
    if n == 0:
        return out
    pubs, rs = np.ascontiguousarray(pubs), np.ascontiguousarray(rs)
    lib = _lib()
    if lib is None:
        from tendermint_tpu.crypto.sr25519 import _challenge, _signing_transcript

        for i, m in enumerate(msgs):
            k = _challenge(_signing_transcript(m), pubs[i].tobytes(), rs[i].tobytes())
            out[i] = np.frombuffer(k.to_bytes(32, "little"), dtype=np.uint8)
        return out
    buf, offsets = _pack(msgs)
    lib.sr25519_challenges_mod_l(
        _ptr(pubs), _ptr(rs), _ptr(buf), _ptr(offsets, _U64P), n, _ptr(out)
    )
    return out


def sha512_batch_mod_l(msgs: Sequence[bytes]) -> np.ndarray:
    """N messages -> (N, 32) uint8 little-endian scalars SHA-512(m) mod L."""
    return reduce512_mod_l(sha512_batch(msgs))
