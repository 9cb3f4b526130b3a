"""Key interfaces and implementations.

Mirrors the reference crypto layer's contracts (crypto/crypto.go:38-76):
``PubKey`` (address, bytes, verify), ``PrivKey`` (sign, pubkey), and
20-byte addresses. Ed25519 addresses are SHA256(pubkey)[:20]
(crypto/crypto.go:27 AddressHash); secp256k1 uses RIPEMD160(SHA256(pub))
(crypto/secp256k1/secp256k1.go).

Ed25519 verification uses ZIP-215 semantics via the batch engine's host
oracle (crypto/ed25519/ed25519.go:24-31); signing follows RFC 8032.
"""

from __future__ import annotations

import hashlib
from abc import ABC, abstractmethod
from typing import List, Optional, Sequence

from tendermint_tpu.crypto import ed25519_ref, hashing

ADDRESS_LEN = 20

ED25519_KEY_TYPE = "ed25519"
SECP256K1_KEY_TYPE = "secp256k1"
SR25519_KEY_TYPE = "sr25519"

ED25519_PUBKEY_SIZE = 32
ED25519_PRIVKEY_SIZE = 64
ED25519_SIG_SIZE = 64


def address_hash(data: bytes) -> bytes:
    """crypto.AddressHash: first 20 bytes of SHA-256."""
    return hashlib.sha256(data).digest()[:ADDRESS_LEN]


class PubKey(ABC):
    @abstractmethod
    def address(self) -> bytes: ...

    @abstractmethod
    def bytes(self) -> bytes: ...

    @abstractmethod
    def verify_signature(self, msg: bytes, sig: bytes) -> bool: ...

    @property
    @abstractmethod
    def type(self) -> str: ...

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, PubKey)
            and self.type == other.type
            and self.bytes() == other.bytes()
        )

    def __hash__(self) -> int:
        return hash((self.type, self.bytes()))

    def __repr__(self) -> str:
        return f"PubKey{{{self.type}:{self.bytes().hex()[:16]}…}}"


class PrivKey(ABC):
    @abstractmethod
    def bytes(self) -> bytes: ...

    @abstractmethod
    def sign(self, msg: bytes) -> bytes: ...

    @abstractmethod
    def pub_key(self) -> PubKey: ...

    @property
    @abstractmethod
    def type(self) -> str: ...


# --- Ed25519 ----------------------------------------------------------------

try:
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey as _LibPriv,
    )

    _HAVE_LIB = True
except Exception:  # pragma: no cover
    _HAVE_LIB = False


class Ed25519PubKey(PubKey):
    __slots__ = ("_bytes",)

    def __init__(self, data: bytes):
        if len(data) != ED25519_PUBKEY_SIZE:
            raise ValueError(f"ed25519 pubkey must be 32 bytes, got {len(data)}")
        self._bytes = bytes(data)

    def address(self) -> bytes:
        return address_hash(self._bytes)

    def bytes(self) -> bytes:
        return self._bytes

    def verify_signature(self, msg: bytes, sig: bytes) -> bool:
        if len(sig) != ED25519_SIG_SIZE:
            return False
        return ed25519_ref.verify_zip215(self._bytes, msg, sig)

    @property
    def type(self) -> str:
        return ED25519_KEY_TYPE


class Ed25519PrivKey(PrivKey):
    """64-byte layout: seed || pubkey (crypto/ed25519/ed25519.go:76-82)."""

    __slots__ = ("_bytes", "_lib")

    def __init__(self, data: bytes):
        if len(data) == 32:  # bare seed
            data, _ = ed25519_ref.keypair_from_seed(data)
        if len(data) != ED25519_PRIVKEY_SIZE:
            raise ValueError(f"ed25519 privkey must be 64 bytes, got {len(data)}")
        self._bytes = bytes(data)
        self._lib = (
            _LibPriv.from_private_bytes(self._bytes[:32]) if _HAVE_LIB else None
        )

    @classmethod
    def generate(cls) -> "Ed25519PrivKey":
        priv, _ = ed25519_ref.generate_keypair()
        return cls(priv)

    @classmethod
    def from_seed(cls, seed: bytes) -> "Ed25519PrivKey":
        priv, _ = ed25519_ref.keypair_from_seed(seed)
        return cls(priv)

    def bytes(self) -> bytes:
        return self._bytes

    def sign(self, msg: bytes) -> bytes:
        if self._lib is not None:
            return self._lib.sign(msg)
        return ed25519_ref.sign(self._bytes, msg)

    def pub_key(self) -> Ed25519PubKey:
        return Ed25519PubKey(self._bytes[32:])

    @property
    def type(self) -> str:
        return ED25519_KEY_TYPE


# --- secp256k1 --------------------------------------------------------------

try:
    from cryptography.hazmat.primitives.asymmetric import ec as _ec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        decode_dss_signature as _decode_dss,
        encode_dss_signature as _encode_dss,
    )
    from cryptography.hazmat.primitives import hashes as _hashes
    from cryptography.exceptions import InvalidSignature as _InvalidSig

    _HAVE_SECP = True
except Exception:  # pragma: no cover
    _HAVE_SECP = False

SECP256K1_N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
_NO_SIG = bytes(64)


def _ripemd160_sha256(data: bytes) -> bytes:
    return hashlib.new("ripemd160", hashlib.sha256(data).digest()).digest()


class Secp256k1PubKey(PubKey):
    """33-byte compressed SEC1 pubkey; 64-byte r||s signatures with low-s
    requirement (crypto/secp256k1/secp256k1.go:38-217)."""

    __slots__ = ("_bytes",)

    def __init__(self, data: bytes):
        if len(data) != 33:
            raise ValueError(f"secp256k1 pubkey must be 33 bytes, got {len(data)}")
        self._bytes = bytes(data)

    def address(self) -> bytes:
        return _ripemd160_sha256(self._bytes)

    def bytes(self) -> bytes:
        return self._bytes

    def verify_signature(self, msg: bytes, sig: bytes) -> bool:
        return self.verify_many([self], [msg], [sig])[0]

    @staticmethod
    def verify_many(
        pub_keys: Sequence["Secp256k1PubKey"], msgs: Sequence[bytes], sigs: Sequence[bytes]
    ) -> List[bool]:
        """Each lane's verdict, all from one call into the native
        routine (``native/secp256k1_batch.c``); a single signature is a
        batch of one, so a vote, an evidence check and a commit's lane
        are answered by the same code. Upstream's rules
        (crypto/secp256k1/secp256k1.go): r || s of 64 bytes, 0 < r < n,
        0 < s <= n / 2, ECDSA over SHA-256. Where the library is not
        there (:func:`hashing.host_secp256k1_impl`), OpenSSL's lane by
        lane."""
        if not len(pub_keys) == len(msgs) == len(sigs):
            raise ValueError("as many keys, messages and signatures as lanes")
        # a signature of another length is refused as r = 0 is
        oks = hashing.secp256k1_verify_native(
            b"".join(pk._bytes for pk in pub_keys),
            b"".join(hashlib.sha256(msg).digest() for msg in msgs),
            b"".join(sig if len(sig) == 64 else _NO_SIG for sig in sigs),
            len(pub_keys),
        )
        if oks is None:
            return [pk._verify_openssl(msg, sig) for pk, msg, sig in zip(pub_keys, msgs, sigs)]
        return [ok == 1 for ok in oks]

    @staticmethod
    def verify_impl() -> str:
        """Whose code answers :meth:`verify_many` in this process:
        ``native`` or ``openssl``."""
        return hashing.host_secp256k1_impl()

    def _verify_openssl(self, msg: bytes, sig: bytes) -> bool:
        if not _HAVE_SECP or len(sig) != 64:
            return False
        r = int.from_bytes(sig[:32], "big")
        s = int.from_bytes(sig[32:], "big")
        if s > SECP256K1_N // 2:  # reject malleable high-s (reference does too)
            return False
        if r == 0 or s == 0:
            return False
        try:
            pub = _ec.EllipticCurvePublicKey.from_encoded_point(
                _ec.SECP256K1(), self._bytes
            )
            pub.verify(_encode_dss(r, s), msg, _ec.ECDSA(_hashes.SHA256()))
            return True
        except (_InvalidSig, ValueError):
            return False

    @property
    def type(self) -> str:
        return SECP256K1_KEY_TYPE


class Secp256k1PrivKey(PrivKey):
    __slots__ = ("_bytes", "_lib")

    def __init__(self, data: bytes):
        if len(data) != 32:
            raise ValueError("secp256k1 privkey must be 32 bytes")
        if not _HAVE_SECP:  # pragma: no cover
            raise RuntimeError("secp256k1 backend unavailable")
        self._bytes = bytes(data)
        self._lib = _ec.derive_private_key(
            int.from_bytes(data, "big"), _ec.SECP256K1()
        )

    @classmethod
    def generate(cls) -> "Secp256k1PrivKey":
        key = _ec.generate_private_key(_ec.SECP256K1())
        raw = key.private_numbers().private_value.to_bytes(32, "big")
        return cls(raw)

    def bytes(self) -> bytes:
        return self._bytes

    def sign(self, msg: bytes) -> bytes:
        der = self._lib.sign(msg, _ec.ECDSA(_hashes.SHA256()))
        r, s = _decode_dss(der)
        if s > SECP256K1_N // 2:  # normalize to low-s
            s = SECP256K1_N - s
        return r.to_bytes(32, "big") + s.to_bytes(32, "big")

    def pub_key(self) -> Secp256k1PubKey:
        from cryptography.hazmat.primitives import serialization

        raw = self._lib.public_key().public_bytes(
            serialization.Encoding.X962, serialization.PublicFormat.CompressedPoint
        )
        return Secp256k1PubKey(raw)

    @property
    def type(self) -> str:
        return SECP256K1_KEY_TYPE


# --- proto encoding of public keys (crypto/encoding/codec.go) ---------------

from tendermint_tpu.encoding.proto import Reader, encode_bytes_field, tag  # noqa: E402


def pubkey_to_proto(pub: PubKey) -> bytes:
    """tendermint.crypto.PublicKey: oneof {ed25519=1, secp256k1=2, sr25519=3}."""
    if pub.type == ED25519_KEY_TYPE:
        return encode_bytes_field(1, pub.bytes())
    if pub.type == SECP256K1_KEY_TYPE:
        return encode_bytes_field(2, pub.bytes())
    if pub.type == SR25519_KEY_TYPE:
        return encode_bytes_field(3, pub.bytes())
    raise ValueError(f"unknown key type {pub.type}")


def pubkey_from_proto(data: bytes) -> PubKey:
    r = Reader(data)
    for field, wire in r.fields():
        if field == 1 and wire == 2:
            return Ed25519PubKey(r.read_bytes())
        if field == 2 and wire == 2:
            return Secp256k1PubKey(r.read_bytes())
        if field == 3 and wire == 2:
            from tendermint_tpu.crypto.sr25519 import Sr25519PubKey

            return Sr25519PubKey(r.read_bytes())
        r.skip(wire)
    raise ValueError("empty PublicKey proto")


def pubkey_from_type_and_bytes(key_type: str, data: bytes) -> PubKey:
    if key_type == ED25519_KEY_TYPE:
        return Ed25519PubKey(data)
    if key_type == SECP256K1_KEY_TYPE:
        return Secp256k1PubKey(data)
    if key_type == SR25519_KEY_TYPE:
        from tendermint_tpu.crypto.sr25519 import Sr25519PubKey

        return Sr25519PubKey(data)
    raise ValueError(f"unknown key type {key_type}")


def privkey_from_type_and_bytes(key_type: str, data: bytes) -> PrivKey:
    if key_type == ED25519_KEY_TYPE:
        return Ed25519PrivKey(data)
    if key_type == SECP256K1_KEY_TYPE:
        return Secp256k1PrivKey(data)
    if key_type == SR25519_KEY_TYPE:
        from tendermint_tpu.crypto.sr25519 import Sr25519PrivKey

        return Sr25519PrivKey(data)
    raise ValueError(f"unknown key type {key_type}")
