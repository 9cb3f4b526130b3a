"""Batched sr25519 (schnorrkel/ristretto255) verification on device.

The VPU/MXU analog of the reference's sr25519 batch verifier
(crypto/sr25519/batch.go:15-47 over curve25519-voi): per-lane
verification of the schnorr equation

    [s_i]B - [k_i]A_i - R_i  ==  ristretto identity

on the SAME twisted-Edwards f32 limb engine as ed25519 — ristretto255
is a quotient of this curve, so the Straus double-scalar core
(ops/ed25519_batch.straus_sb_minus_ka) is shared verbatim. What differs:

- point decoding is the RFC 9496 ristretto DECODE map (square-root
  ratio with the sqrt(-1) fixups), batched here over field32;
- the accept test is membership in the identity coset — X == 0 or
  Y == 0 — instead of ed25519's cofactored multiply-by-8;
- Merlin transcript challenges stay host-side (sequential Keccak duplex
  — SURVEY §7 "Hard parts"); the device sees only (A, R, s, k) as raw
  32-byte strings, the transfer-minimal layout of the ed25519 kernel.

Per-entry verdicts (not a random-linear-combination single verdict):
fault attribution is free, so validation.go:244-251-style fallback
re-verification is never needed on this path.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from tendermint_tpu.libs import tracing
from tendermint_tpu.ops import curve32 as curve, device_policy, field32 as field
from tendermint_tpu.ops.chunk_kinds import ChunkInput, ChunkKind
from tendermint_tpu.ops.ed25519_batch import (
    PendingBatch,
    _bytes_to_fe,
    _chunk_rows,
    _dispatch_jobs,
    _early_tag,
    _Job,
    _mesh_span,
    _PendingJobs,
    _to_windows_signed,
    _undispatched,
    canonical_lt,
    straus_sb_minus_ka,
)

# Canonicity bounds: ristretto encodings must be < p; scalars < L
# (L imported lazily below to avoid a crypto<->ops import cycle at
# module load; cached here on first use).
_P_BYTES_BE = np.frombuffer(field.P.to_bytes(32, "big"), dtype=np.uint8)
_L_BYTES_BE: Optional[np.ndarray] = None

_NEG_ONE_FE = field.const_fe(field.P - 1)
_NEG_SQRT_M1_FE = field.const_fe(field.P - field.SQRT_M1)


def _l_bytes_be() -> np.ndarray:
    global _L_BYTES_BE
    if _L_BYTES_BE is None:
        from tendermint_tpu.crypto.ristretto import L

        _L_BYTES_BE = np.frombuffer(L.to_bytes(32, "big"), dtype=np.uint8)
    return _L_BYTES_BE


def ristretto_decompress(
    s_fe: jnp.ndarray,
) -> Tuple[curve.Point, jnp.ndarray]:
    """RFC 9496 4.3.1 DECODE, batched: (32, N) f32 limbs (canonical,
    non-negative — both pre-checked on host bytes) -> (point, valid).

    Invalid lanes hold the identity so downstream arithmetic stays
    well-defined (same convention as curve32.pt_decompress).
    """
    n = s_fe.shape[1]
    one = field.fe_one(n)
    ss = field.fe_sq(s_fe)
    u1 = field.fe_sub(one, ss)
    u2 = field.fe_add(one, ss)
    u2s = field.fe_sq(u2)
    # v = -(D * u1^2) - u2^2
    v = field.fe_sub(field.fe_neg(field.fe_mul_const(field.fe_sq(u1), field.D_FE)), u2s)
    # SQRT_RATIO_M1(1, v * u2s): candidate r = w^((p-5)/8) * w^3-ish via
    # the shared exponent chain; with u = 1 the candidate is
    # w^3 * (w^7)^((p-5)/8) for w = v*u2s.
    w = field.fe_mul(v, u2s)
    w3 = field.fe_mul(field.fe_sq(w), w)
    w7 = field.fe_mul(field.fe_sq(w3), w)
    r = field.fe_mul(w3, field.fe_pow22523(w7))
    check = field.fe_mul(w, field.fe_sq(r))
    correct = field.fe_eq(check, one)
    flipped = field.fe_eq(check, jnp.broadcast_to(jnp.asarray(_NEG_ONE_FE), one.shape))
    flipped_i = field.fe_eq(
        check, jnp.broadcast_to(jnp.asarray(_NEG_SQRT_M1_FE), one.shape)
    )
    r = field.fe_select(
        flipped | flipped_i, field.fe_mul_const(r, field.SQRT_M1_FE), r
    )
    was_square = correct | flipped
    # |r|: the non-negative square root
    r = field.fe_select(field.fe_parity(r) == 1.0, field.fe_neg(r), r)

    den_x = field.fe_mul(r, u2)
    den_y = field.fe_mul(field.fe_mul(r, den_x), v)
    x = field.fe_mul(field.fe_add(s_fe, s_fe), den_x)
    x = field.fe_select(field.fe_parity(x) == 1.0, field.fe_neg(x), x)
    y = field.fe_mul(u1, den_y)
    t = field.fe_mul(x, y)

    valid = (
        was_square
        & (field.fe_parity(t) != 1.0)
        & ~field.fe_is_zero(y)
    )
    pt: curve.Point = (x, y, one, t)
    return curve.pt_select(valid, pt, curve.pt_identity(n)), valid


def verify_kernel_sr(
    pk_bytes: jnp.ndarray,
    r_bytes: jnp.ndarray,
    s_bytes: jnp.ndarray,
    k_bytes: jnp.ndarray,
) -> jnp.ndarray:
    """(N,32)x4 uint8 -> (N,) bool: schnorrkel verify per lane."""
    a_fe = _bytes_to_fe(pk_bytes)
    r_fe = _bytes_to_fe(r_bytes)
    nn = a_fe.shape[1]
    # One 2N ristretto decode for A and R (same trick as ed25519).
    both_pt, both_ok = ristretto_decompress(
        jnp.concatenate([a_fe, r_fe], axis=1)
    )
    a_pt = tuple(c[:, :nn] for c in both_pt)
    r_pt = tuple(c[:, nn:] for c in both_pt)
    a_ok, r_ok = both_ok[:nn], both_ok[nn:]

    # Signed 4-bit windows, shared with ed25519: both s (masked to 255
    # bits and checked < L on host) and the Merlin challenge k (< L)
    # are < 2^253, so the signed recode is exact.
    s_win = _to_windows_signed(s_bytes)
    k_win = _to_windows_signed(k_bytes)
    acc = straus_sb_minus_ka(a_pt, s_win, k_win)
    acc = curve.pt_add(acc, curve.pt_neg(r_pt))
    # ristretto identity coset: X == 0 or Y == 0 (RFC 9496 equality
    # specialised to the identity; matches crypto/ristretto.equals).
    x, y, _, _ = acc
    is_ident = field.fe_is_zero(x) | field.fe_is_zero(y)
    return is_ident & a_ok & r_ok


# --- host-side preparation --------------------------------------------------


def _lane_arrays(
    pubkeys: Sequence[bytes], sigs: Sequence[bytes]
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """``(pk, r, s, host_ok)``: the raw (n, 32) uint8 rows the kernel
    takes (s with its marker bit cleared) and the (n,) bool structural
    verdicts — lengths, the schnorrkel marker bit, s < L, and A and R
    canonical (< p) and non-negative (even). Two joins where every lane
    is well-formed, as every batch from commit verification is; an
    ill-formed lane is a zero row that ``host_ok`` refuses."""
    n = len(pubkeys)
    if all(len(pk) == 32 and len(sg) == 64 for pk, sg in zip(pubkeys, sigs)):
        pk_arr = np.frombuffer(b"".join(pubkeys), dtype=np.uint8).reshape(n, 32)
        sig_arr = np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(n, 64)
        host_ok = np.ones(n, dtype=bool)
    else:
        pk_arr = np.zeros((n, 32), dtype=np.uint8)
        sig_arr = np.zeros((n, 64), dtype=np.uint8)
        host_ok = np.zeros(n, dtype=bool)
        for i, (pub, sig) in enumerate(zip(pubkeys, sigs)):
            if len(pub) == 32 and len(sig) == 64:
                pk_arr[i] = np.frombuffer(pub, dtype=np.uint8)
                sig_arr[i] = np.frombuffer(sig, dtype=np.uint8)
                host_ok[i] = True
    r_arr = sig_arr[:, :32]
    s_arr = sig_arr[:, 32:].copy()
    host_ok &= (s_arr[:, 31] & 0x80) != 0  # the schnorrkel marker
    s_arr[:, 31] &= 0x7F
    host_ok &= canonical_lt(s_arr, _l_bytes_be())
    for enc in (pk_arr, r_arr):
        host_ok &= canonical_lt(enc, _P_BYTES_BE)
        host_ok &= (enc[:, 0] & 1) == 0
    return pk_arr, r_arr, s_arr, host_ok


def verify_batch_sr(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    backend: Optional[str] = None,
) -> List[bool]:
    """Per-entry schnorrkel batch verification on the device, host
    Merlin challenges. Chunks go through the dispatch loop shared with
    ed25519 (ops/ed25519_batch._dispatch_jobs, then ``collect()`` of
    what it returns), double-buffered: the Merlin transcript challenges
    of chunk j+1 — one call of the C extension a chunk
    (crypto/hashing.sr25519_challenges_mod_l), under the
    ``merlin_challenge`` span — are computed while the device crunches
    chunk j (JAX async dispatch), instead of hashing the whole batch
    up front. Device failure degrades per CHUNK to the host oracle
    under the process-wide health state machine shared with ed25519
    (ops/device_policy.py), which cools down, probes, and re-promotes
    the device path by itself. The call runs under the engines' common
    ``verify_batch`` span; its lanes do not enter the verdict cache
    (ops/precompute.results holds ed25519 verdicts only)."""
    n = len(pubkeys)
    if n == 0:
        return []
    with tracing.span("verify_batch", engine="sr25519", lanes=n) as vsp:
        vsp.process_cpu()
        return _merge(_begin_lanes(pubkeys, msgs, sigs, backend).collect())


def begin_verify_batch_sr(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    backend: Optional[str] = None,
    early: bool = False,
) -> PendingBatch:
    """:func:`verify_batch_sr` in two steps, as ed25519's
    ``begin_verify_batch``: lane arrays, Merlin challenges and every
    ``dispatch_chunk`` here, the collects and the merge in ``finish()``
    of what this returns, each step under a ``verify_batch`` span of
    its own (``phase`` ``dispatch`` / ``collect``; ``early=1`` on the
    first where the caller says the lanes are an early block)."""
    n = len(pubkeys)
    if n == 0:
        return PendingBatch("sr25519", 0, None, lambda out: [])
    with tracing.span(
        "verify_batch", engine="sr25519", lanes=n, phase="dispatch", **_early_tag(early)
    ) as vsp:
        vsp.process_cpu()
        pending = _begin_lanes(pubkeys, msgs, sigs, backend)
    return PendingBatch("sr25519", n, pending, _merge)


def _merge(verdicts: np.ndarray) -> List[bool]:
    with tracing.span("merge_results", lanes=len(verdicts)):
        return verdicts.tolist()


def _begin_lanes(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    backend: Optional[str],
) -> _PendingJobs:
    """Every lane prepared and dispatched; ``collect()`` of the result
    gives the (n,) bool verdicts."""
    from tendermint_tpu.crypto.hashing import sr25519_challenges_mod_l
    from tendermint_tpu.crypto.sr25519 import verify as verify_host

    def host_verify(rows) -> np.ndarray:
        return np.array(
            [verify_host(pubkeys[i], msgs[i], sigs[i]) for i in rows], dtype=bool
        )

    health = device_policy.shared
    n = len(pubkeys)
    attempt = health.begin_attempt("sr25519")
    if attempt is None:
        return _undispatched("sr25519", n, host_verify)

    pk_arr, r_arr, s_arr, host_ok = _lane_arrays(pubkeys, sigs)

    def prep_job(job: _Job, pad_to: int) -> Tuple[dict, np.ndarray]:
        """Merlin challenges + padding for one chunk's rows — the host
        half of the double buffer."""
        rows = job.rows
        pk_c, r_c = pk_arr[rows], r_arr[rows]
        with tracing.span("merlin_challenge", lanes=len(rows)):
            k_c = sr25519_challenges_mod_l(pk_c, r_c, [msgs[i] for i in rows])
        inputs = dict(pk=pk_c, r=r_c, s=s_arr[rows], k=k_c)
        return SR25519.pad_lanes(inputs, pad_to - len(rows)), host_ok[rows]

    plan, span = _mesh_span(n)
    jobs = [_Job(SR25519, rows) for rows in _chunk_rows(np.arange(n), span)]
    return _dispatch_jobs(
        "sr25519", n, jobs, prep_job, host_verify, backend, plan, attempt
    )


_PAD: Optional[Tuple[np.ndarray, ...]] = None


def _pad_entry() -> Tuple[np.ndarray, ...]:
    """A known-good (pk, R, s, k) quadruple for padding lanes."""
    global _PAD
    if _PAD is None:
        from tendermint_tpu.crypto.sr25519 import (
            Sr25519PrivKey,
            _challenge,
            _signing_transcript,
        )

        priv = Sr25519PrivKey.from_secret(b"tendermint-tpu-sr-pad")
        msg = b"sr25519-pad"
        sig = priv.sign(msg)
        pub = priv.pub_key().bytes()
        s_raw = bytearray(sig[32:64])
        s_raw[31] &= 0x7F
        k = _challenge(_signing_transcript(msg), pub, sig[:32])
        _PAD = (
            np.frombuffer(pub, dtype=np.uint8),
            np.frombuffer(sig[:32], dtype=np.uint8),
            np.frombuffer(bytes(s_raw), dtype=np.uint8),
            np.frombuffer(k.to_bytes(32, "little"), dtype=np.uint8),
        )
    return _PAD


# What the kernel takes, and its pad lanes (ops/chunk_kinds.py). Its
# programs, the XLA graph and the Pallas entry point alike, are called
# ``run_sr25519``: the device trace tells them from ed25519's ``run``.
SR25519 = ChunkKind(
    "sr25519", "sr25519", "verify_sr", verify_kernel_sr, "compiled_verify_sr",
    tuple(
        ChunkInput(name, 0, lambda i=i: _pad_entry()[i])
        for i, name in enumerate(("pk", "r", "s", "k"))
    ),
    program="run_sr25519",
)
