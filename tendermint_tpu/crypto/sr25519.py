"""sr25519 (Schnorrkel over ristretto255) — sign, verify, batch verify.

Schnorr signatures on the ristretto255 prime-order group with Merlin
transcripts, wire-compatible with w3f schnorrkel / curve25519-voi as used
by the reference (crypto/sr25519/pubkey.go:49-61, privkey.go:44-66,
batch.go:15-47):

- signing context: ``Transcript("SigningContext")`` + empty context label,
  message appended under ``sign-bytes`` (privkey.go:18 ``signingCtx``).
- protocol: ``proto-name = "Schnorr-sig"``; commit pubkey under
  ``sign:pk``, R under ``sign:R``; 64-byte challenge under ``sign:c``
  reduced to a scalar.
- keys: 32-byte MiniSecretKey expanded ExpandEd25519-style
  (privkey.go:131): SHA-512, clamp, divide by cofactor; nonce = h[32:64].
- signatures: ``R || s`` with the schnorrkel marker bit (s[31] |= 0x80)
  set on encode and required on decode.

Transcript hashing is host-side (sequential Keccak duplex — SURVEY §7
"Hard parts"); batch verification reduces to one multiscalar equation,
checked with a random linear combination.
"""

from __future__ import annotations

import hashlib
import os
from typing import List, Optional, Sequence, Tuple

from tendermint_tpu.crypto import ristretto
from tendermint_tpu.crypto.batch import DEVICE_THRESHOLD, DeviceBatchVerifier, key_types
from tendermint_tpu.crypto.keys import (
    ADDRESS_LEN,
    SR25519_KEY_TYPE,
    PrivKey,
    PubKey,
)
from tendermint_tpu.crypto.merlin import MerlinTranscript
from tendermint_tpu.crypto.ristretto import (
    B_POINT,
    L,
    Point,
    compress,
    decompress,
    is_identity,
    pt_add,
    pt_mul,
    pt_neg,
    scalar_from_canonical,
    scalar_from_wide,
)

PUBKEY_SIZE = 32
SIGNATURE_SIZE = 64
SEED_SIZE = 32


def _signing_transcript(msg: bytes) -> MerlinTranscript:
    """signingCtx.NewTranscriptBytes(msg) with the empty signing context."""
    t = MerlinTranscript(b"SigningContext")
    t.append_message(b"", b"")
    t.append_message(b"sign-bytes", msg)
    return t


def _challenge(
    t: MerlinTranscript, pub_bytes: bytes, r_bytes: bytes
) -> int:
    t.append_message(b"proto-name", b"Schnorr-sig")
    t.append_message(b"sign:pk", pub_bytes)
    t.append_message(b"sign:R", r_bytes)
    return scalar_from_wide(t.challenge_bytes(b"sign:c", 64))


def expand_seed(seed: bytes) -> Tuple[int, bytes]:
    """MiniSecretKey.ExpandEd25519 → (secret scalar, 32-byte nonce)."""
    if len(seed) != SEED_SIZE:
        raise ValueError("sr25519 seed must be 32 bytes")
    h = hashlib.sha512(seed).digest()
    key = bytearray(h[:32])
    key[0] &= 248
    key[31] &= 63
    key[31] |= 64
    # divide by the cofactor: clamping zeroed the low 3 bits, so a 256-bit
    # right shift is exact
    scalar = int.from_bytes(bytes(key), "little") >> 3
    return scalar % L, h[32:64]


def pubkey_from_seed(seed: bytes) -> bytes:
    scalar, _ = expand_seed(seed)
    return compress(pt_mul(scalar, B_POINT))


def sign(
    seed: bytes,
    msg: bytes,
    _expanded: Optional[Tuple[int, bytes, bytes]] = None,
) -> bytes:
    """Sign msg under the Tendermint signing context; returns R || s(marked).

    ``_expanded`` lets keepers of a long-lived key (Sr25519PrivKey) skip
    re-deriving (scalar, nonce, pub_bytes) on every signature.
    """
    if _expanded is not None:
        scalar, nonce, pub_bytes = _expanded
    else:
        scalar, nonce = expand_seed(seed)
        pub_bytes = compress(pt_mul(scalar, B_POINT))
    t = _signing_transcript(msg)
    # Witness scalar via the transcript RNG, rekeyed with the secret nonce
    # and fresh OS entropy (merlin TranscriptRngBuilder — any r is valid,
    # verifiers never recompute it).
    rng = (
        t.build_rng()
        .rekey_with_witness_bytes(b"signing", nonce)
        .finalize(os.urandom(32))
    )
    r = scalar_from_wide(rng.fill_bytes(64))
    if r == 0:  # pragma: no cover - 2^-252 probability
        r = 1
    r_bytes = compress(pt_mul(r, B_POINT))
    k = _challenge(t, pub_bytes, r_bytes)
    s = (k * scalar + r) % L
    s_bytes = bytearray(s.to_bytes(32, "little"))
    s_bytes[31] |= 0x80  # schnorrkel marker
    return r_bytes + bytes(s_bytes)


def _parse_signature(sig: bytes) -> Optional[Tuple[bytes, int]]:
    """Split R-bytes and canonical s; None unless the marker bit is set."""
    if len(sig) != SIGNATURE_SIZE:
        return None
    if not sig[63] & 0x80:
        return None  # not marked as schnorrkel
    s_bytes = bytearray(sig[32:64])
    s_bytes[31] &= 0x7F
    s = scalar_from_canonical(bytes(s_bytes))
    if s is None:
        return None
    return sig[:32], s


def verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """Single verify: R == s·B − k·A (checked via ristretto equality)."""
    if len(pub) != PUBKEY_SIZE:
        return False
    a_point = decompress(pub)
    if a_point is None:
        return False
    parsed = _parse_signature(sig)
    if parsed is None:
        return False
    r_bytes, s = parsed
    r_point = decompress(r_bytes)
    if r_point is None:
        return False
    k = _challenge(_signing_transcript(msg), pub, r_bytes)
    # s·B − k·A − R must be the (ristretto) identity
    check = pt_add(
        pt_mul(s, B_POINT),
        pt_add(pt_mul((L - k) % L, a_point), pt_neg(r_point)),
    )
    return is_identity(check)


class Sr25519PubKey(PubKey):
    __slots__ = ("_bytes",)

    def __init__(self, data: bytes):
        if len(data) != PUBKEY_SIZE:
            raise ValueError("sr25519 pubkey must be 32 bytes")
        self._bytes = bytes(data)

    def address(self) -> bytes:
        return hashlib.sha256(self._bytes).digest()[:ADDRESS_LEN]

    def bytes(self) -> bytes:
        return self._bytes

    def verify_signature(self, msg: bytes, sig: bytes) -> bool:
        # Reachable from untrusted wire input via pubkey_from_proto:
        # must return bool, never raise.
        try:
            return verify(self._bytes, msg, sig)
        except Exception:
            return False

    @property
    def type(self) -> str:
        return SR25519_KEY_TYPE


class Sr25519PrivKey(PrivKey):
    """MiniSecretKey-seeded signer (reference crypto/sr25519/privkey.go)."""

    __slots__ = ("_seed", "_scalar", "_nonce", "_pub_bytes")

    def __init__(self, seed: bytes):
        if len(seed) != SEED_SIZE:
            raise ValueError("sr25519 seed must be 32 bytes")
        self._seed = bytes(seed)
        self._scalar, self._nonce = expand_seed(self._seed)
        self._pub_bytes = compress(pt_mul(self._scalar, B_POINT))

    @classmethod
    def generate(cls) -> "Sr25519PrivKey":
        return cls(os.urandom(SEED_SIZE))

    @classmethod
    def from_secret(cls, secret: bytes) -> "Sr25519PrivKey":
        """GenPrivKeyFromSecret: SHA-256 the secret into a seed."""
        return cls(hashlib.sha256(secret).digest())

    def bytes(self) -> bytes:
        return self._seed

    def sign(self, msg: bytes) -> bytes:
        return sign(
            self._seed,
            msg,
            _expanded=(self._scalar, self._nonce, self._pub_bytes),
        )

    def pub_key(self) -> Sr25519PubKey:
        return Sr25519PubKey(self._pub_bytes)

    @property
    def type(self) -> str:
        return SR25519_KEY_TYPE


_OPS_IMPORT_WARNED = False  # one warning per process for a jax-less install


class Sr25519BatchVerifier(DeviceBatchVerifier):
    """Batch verifier with a device path and a host fallback.

    Above ``device_threshold`` entries the batch rides the ristretto
    Straus kernel (ops/sr25519_batch.py — per-entry verdicts, no
    re-verify needed for attribution). Below it, or when the device is
    unusable, one random-linear-combination multiscalar check on host:
    Σ zᵢ·(sᵢ·B − kᵢ·Aᵢ − Rᵢ) = 0 with random 128-bit zᵢ
    (reference batch.go:46 → curve25519-voi BatchVerifier.Verify),
    falling back to per-entry verifies for attribution on failure
    (types/validation.go:244-251).
    """

    key_type = SR25519_KEY_TYPE

    def __init__(self, device_threshold: Optional[int] = None,
                 use_device: Optional[bool] = None):
        super().__init__(
            DEVICE_THRESHOLD if device_threshold is None else device_threshold, use_device
        )
        self._entries: List[Tuple[bytes, bytes, bytes]] = []

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        if pub_key.type != SR25519_KEY_TYPE:
            raise ValueError("sr25519 batch: pubkey is not sr25519")
        self._entries.append((pub_key.bytes(), msg, sig))
        if len(self._entries) >= self._look_at:
            self._look()

    def add_many(self, pub_keys: Sequence[PubKey], msgs: Sequence[bytes], sigs: Sequence[bytes]) -> None:
        if not (key_types(pub_keys) <= {SR25519_KEY_TYPE} and len(pub_keys) == len(msgs) == len(sigs)):
            return super().add_many(pub_keys, msgs, sigs)  # lane by lane, for add to say
        self._entries += zip([pub_key.bytes() for pub_key in pub_keys], msgs, sigs)
        if len(self._entries) >= self._look_at:
            self._look()

    def __len__(self) -> int:
        return len(self._entries)

    def _device_engine(self, lanes: Optional[int] = None):
        """``ops.sr25519_batch`` where this batch, or one of ``lanes``,
        goes to the device, else None (under the threshold, switched
        off, or no engine in this install)."""
        if self._wants_device(lanes):
            try:
                from tendermint_tpu.ops import sr25519_batch
            except ImportError:
                # No device engine in this install (jax absent): warn
                # once, then stop trying for the life of the process.
                global _OPS_IMPORT_WARNED
                if not _OPS_IMPORT_WARNED:
                    _OPS_IMPORT_WARNED = True
                    import warnings

                    warnings.warn(
                        "sr25519 device engine unavailable (ops import "
                        "failed); using host batch verification"
                    )
                self.use_device = False
            else:
                return sr25519_batch
        return None

    def _columns(self, lo: int = 0, hi: Optional[int] = None):
        return tuple([e[i] for e in self._entries[lo:hi]] for i in range(3))

    def _device_begin(self, lanes: Optional[int] = None):
        engine = self._device_engine(lanes)
        if engine is None:
            return None
        return lambda lo, hi, early: engine.begin_verify_batch_sr(
            *self._columns(lo, hi), early=early
        )

    def _verify(self, span) -> Tuple[bool, List[bool]]:
        n = len(self._entries)
        if n == 0:
            return False, []
        engine = self._device_engine()
        if engine is not None:
            # verify_batch_sr handles device failures itself
            # (warn + shared sticky policy) and returns host-oracle
            # verdicts on fallback.
            span.set(route="device")
            oks = engine.verify_batch_sr(*self._columns())
            return all(oks), list(oks)
        parsed = []
        for pub, msg, sig in self._entries:
            a_point = decompress(pub) if len(pub) == PUBKEY_SIZE else None
            sp = _parse_signature(sig)
            r_point = decompress(sp[0]) if sp else None
            if a_point is None or sp is None or r_point is None:
                parsed.append(None)
                continue
            k = _challenge(_signing_transcript(msg), pub, sp[0])
            parsed.append((a_point, r_point, sp[1], k))
        if all(p is not None for p in parsed):
            s_coeff = 0
            acc: Point = ristretto.IDENT
            for a_point, r_point, s, k in parsed:  # type: ignore[misc]
                z = int.from_bytes(os.urandom(16), "little") | 1
                s_coeff = (s_coeff + z * s) % L
                acc = pt_add(acc, pt_mul(z * k % L, a_point))
                acc = pt_add(acc, pt_mul(z, r_point))
            check = pt_add(pt_mul(s_coeff, B_POINT), pt_neg(acc))
            if is_identity(check):
                return True, [True] * n
        # Attribution path: re-check each entry from its already-parsed
        # points/challenge (transcript hashing and decompression are the
        # expensive host-side steps — don't redo them).
        oks = []
        for p in parsed:
            if p is None:
                oks.append(False)
                continue
            a_point, r_point, s, k = p
            check = pt_add(
                pt_mul(s, B_POINT),
                pt_add(pt_mul((L - k) % L, a_point), pt_neg(r_point)),
            )
            oks.append(is_identity(check))
        return all(oks), oks
