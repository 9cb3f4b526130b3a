"""Commits of consecutive heights over one validator set of mixed key
types (ed25519, sr25519, secp256k1), verified one after another by one
caller through ``types.validation.verify_commit``: ``commits.py``'s
loop on a chain whose validators chose their consensus keys' type.

What the device is sent is the lanes of the two types that batch;
``lanes_per_call`` counts those (``run.py`` holds a traced run's
``dispatch_chunk`` lanes to it). The secp256k1 lanes are the host's by
design, every call. Only ed25519 verdicts enter the program's verdict
cache, so the cycle of commits is sized from the ed25519 lanes.

Signing is set-up and has to be quick: ed25519 and secp256k1 (ECDSA
over SHA-256, normalised to low s) with ``cryptography``; sr25519 by a
walk — key i's secret scalar is a0 + i and nonce j's is r0 + j, each
point the last one plus B and encoded once — with the challenges of a
commit's lanes from the program's batch Merlin in one call. The
signatures are ordinary schnorrkel ones (R, s = k a + r, marker bit):
``reference_mixed.py``, which shares nothing with the program, accepts
them, and would refuse them if that Merlin were wrong.
"""

from __future__ import annotations

import re

import numpy as np
from cryptography.hazmat.primitives import hashes, serialization
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import decode_dss_signature

from chipbench import reference_mixed, workload
from chipbench.generators import commits, cycle_length

KEY_TYPES = ("ed25519", "sr25519", "secp256k1")
BATCHED = ("ed25519", "sr25519")  # the types whose lanes the device is sent
SECP_N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
MARKER = 1 << 255  # schnorrkel's, on s
TAMPER_KINDS = {
    "ed25519": workload.TAMPER_KINDS,
    "sr25519": ("R", "s", "s>=L"),
    "secp256k1": ("r", "s", "high-s"),
}
SAMPLE_SECP = 8  # of the sampled lanes; the rest halves between the other two


class EdSigner(workload.Signer):
    key_type = "ed25519"


class SecpSigner:
    key_type = "secp256k1"
    __slots__ = ("pub", "_key")

    def __init__(self, seed32: bytes):
        self._key = ec.derive_private_key(
            int.from_bytes(seed32, "big") % (SECP_N - 1) + 1, ec.SECP256K1()
        )
        self.pub = self._key.public_key().public_bytes(
            serialization.Encoding.X962, serialization.PublicFormat.CompressedPoint
        )

    def sign(self, msg: bytes) -> bytes:
        r, s = decode_dss_signature(self._key.sign(msg, ec.ECDSA(hashes.SHA256())))
        return r.to_bytes(32, "big") + min(s, SECP_N - s).to_bytes(32, "big")


class SrSigner:
    key_type = "sr25519"
    __slots__ = ("pub", "scalar")

    def __init__(self, scalar: int, pub: bytes):
        self.scalar, self.pub = scalar, pub


class SrWalk:
    """Scalars w0, w0 + 1, ... with the ristretto encodings of their
    multiples of the base point, one point addition and one encoding a
    step."""

    def __init__(self, seed32: bytes):
        from tendermint_tpu.crypto import ristretto

        self._rs = ristretto
        self.scalar = int.from_bytes(seed32, "little") % ristretto.L
        self._point = ristretto.pt_mul(self.scalar, ristretto.B_POINT)

    def step(self):
        out = self.scalar, self._rs.compress(self._point)
        self.scalar = (self.scalar + 1) % self._rs.L
        self._point = self._rs.pt_add(self._point, self._rs.B_POINT)
        return out


def tamper(key_type: str, sig: bytes, kind: str) -> bytes:
    """A signature its type's rules refuse: a flipped bit of R (r), a
    flipped bit of s, or the one only a canonicity rule refuses (s + L,
    the marker bit kept; n - s, the high twin of a low s)."""
    if key_type == "ed25519":
        return workload.tamper_signature(sig, kind)
    out = bytearray(sig)
    if kind in ("R", "r"):
        out[3] ^= 0x01
    elif kind == "s":
        out[32 if key_type == "sr25519" else 60] ^= 0x01
    elif kind == "s>=L":
        s = (int.from_bytes(sig[32:], "little") & (MARKER - 1)) + workload.L
        out[32:] = (s | MARKER).to_bytes(32, "little")
    elif kind == "high-s":
        out[32:] = (SECP_N - int.from_bytes(sig[32:], "big")).to_bytes(32, "big")
    else:
        raise ValueError("unknown tamper kind %r" % kind)
    return bytes(out)


class CommitsMixed(commits.Commits):
    def __init__(self, ctx):
        try:
            from tendermint_tpu.crypto.hashing import sr25519_challenges_mod_l
        except ImportError:
            raise SystemExit(
                "chipbench: this program has no batch Merlin challenge "
                "(crypto/hashing.sr25519_challenges_mod_l) and verifies a committee "
                "that holds a secp256k1 key one signature at a time on the host: "
                "it cannot run the mixed committee. No result."
            )
        from tendermint_tpu.crypto import keys, sr25519
        from tendermint_tpu.ops import precompute
        from tendermint_tpu.types import Validator, ValidatorSet
        from tendermint_tpu.types.validation import InvalidCommitError, verify_commit

        self._challenges = sr25519_challenges_mod_l
        self._verify = verify_commit
        self._refused = InvalidCommitError
        self.seed = ctx.seed
        counts = {kt: int(ctx.config["key_types"][kt]) for kt in KEY_TYPES}
        n = int(ctx.config["validators"])
        if sum(counts.values()) != n:
            raise SystemExit("chipbench: key_types do not add up to %d validators" % n)
        self.lanes_per_call = sum(counts[kt] for kt in BATCHED)
        self.heights = cycle_length(ctx.traffic, counts["ed25519"], precompute.results.cap)

        # key types drawn over the seats; addresses are hashes of the
        # keys, so the types interleave in the set's order whatever the draw
        drawn = workload.rng_for(ctx.seed, "key-types").permutation(
            np.repeat(KEY_TYPES, [counts[kt] for kt in KEY_TYPES])
        )
        self._walk = SrWalk(workload._digest("chipbench-sr25519", ctx.seed))
        wrap = {
            "ed25519": keys.Ed25519PubKey,
            "sr25519": sr25519.Sr25519PubKey,
            "secp256k1": keys.Secp256k1PubKey,
        }
        signers = []
        for i, kt in enumerate(drawn):
            seed32 = workload._digest("chipbench-key", ctx.seed, "validators", i)
            if kt == "sr25519":
                signers.append(SrSigner(*self._walk.step()))
            else:
                signers.append((EdSigner if kt == "ed25519" else SecpSigner)(seed32))
        vals = [Validator(wrap[s.key_type](s.pub), 10) for s in signers]
        self.vset = ValidatorSet(vals)
        by_pub = {s.pub: s for s in signers}
        self.signers = [by_pub[v.pub_key.bytes()] for v in self.vset.validators]
        self.addresses = [v.address for v in self.vset.validators]
        self.pubs = [s.pub for s in self.signers]
        self.lanes_of = {
            kt: [i for i, s in enumerate(self.signers) if s.key_type == kt]
            for kt in KEY_TYPES
        }
        sr = self.lanes_of["sr25519"]
        self._sr_pubs = np.frombuffer(
            b"".join(self.pubs[i] for i in sr), dtype=np.uint8
        ).reshape(len(sr), 32)
        self.commits = [self._sign(h) for h in range(1, self.heights + 1)]
        ctx.say(
            "traffic: %d validators (%d ed25519, %d sr25519, %d secp256k1), %d of the "
            "%d signatures of a commit sent to the device; %d commits of consecutive "
            "heights cycled (%d ed25519 signatures between two visits of one commit; "
            "the verdict cache, which holds ed25519 verdicts only, holds %d)"
            % (n, counts["ed25519"], counts["sr25519"], counts["secp256k1"],
               self.lanes_per_call, n, self.heights,
               (self.heights - 1) * counts["ed25519"], precompute.results.cap)
        )

    def _sign(self, height: int):
        """A commit for ``height`` in which every validator signs a
        precommit for the block (``workload.make_commit`` over three
        kinds of signer)."""
        from tendermint_tpu.encoding.canonical import Timestamp
        from tendermint_tpu.types import BLOCK_ID_FLAG_COMMIT, Commit, CommitSig

        n = len(self.signers)
        times = workload.vote_times(self.seed, "chain", height, n)
        commit = Commit(
            height=height, round=0, block_id=workload.block_id(self.seed, "chain", height)
        )
        commit.signatures = [
            CommitSig(BLOCK_ID_FLAG_COMMIT, addr, Timestamp.from_unix_ns(int(t)), b"")
            for addr, t in zip(self.addresses, times)
        ]
        encoder = commit.sign_bytes_encoder(workload.CHAIN_ID)
        for kt in ("ed25519", "secp256k1"):
            for i in self.lanes_of[kt]:
                commit.signatures[i].signature = self.signers[i].sign(encoder.lane(i))
        sr = self.lanes_of["sr25519"]
        nonces = [self._walk.step() for _ in sr]
        r_encs = np.frombuffer(b"".join(enc for _, enc in nonces), dtype=np.uint8)
        ks = self._challenges(
            self._sr_pubs, r_encs.reshape(len(sr), 32), [encoder.lane(i) for i in sr]
        )
        for i, (r, r_enc), k in zip(sr, nonces, ks):
            s = (int.from_bytes(k.tobytes(), "little") * self.signers[i].scalar + r) % workload.L
            commit.signatures[i].signature = r_enc + (s | MARKER).to_bytes(32, "little")
        return commit

    def _lane(self, commit, lane: int):
        """(key type, key, sign-bytes, signature) of one lane."""
        return (
            self.signers[lane].key_type,
            self.pubs[lane],
            commit.vote_sign_bytes(workload.CHAIN_ID, lane),
            commit.signatures[lane].signature,
        )

    def check(self, outcomes, results) -> None:
        results.compare(
            "timed_commits_refused", sum(1 for o in outcomes if o is not None), 0
        )
        # three fresh heights, one tampered lane each, one of each key
        # type: refused, and the blame on the tampered lane
        rng = workload.rng_for(self.seed, "tamper", "commits_mixed")
        wrong = 0
        tampered = []
        for j, kt in enumerate(KEY_TYPES):
            lane = int(rng.choice(self.lanes_of[kt]))
            kind = TAMPER_KINDS[kt][int(rng.integers(len(TAMPER_KINDS[kt])))]
            commit = self._sign(self.heights + 1 + j)
            cs = commit.signatures[lane]
            cs.signature = tamper(kt, cs.signature, kind)
            refused = self._verify_commit(commit)
            m = re.search(r"#(\d+)", str(refused)) if refused is not None else None
            if m is None or int(m.group(1)) != lane:
                wrong += 1
            tampered.append(self._lane(commit, lane))
        results.compare("tampered_commits_not_blamed_on_their_lane", wrong, 0)
        # the plain reference on the tampered lanes and on a seeded
        # sample of the lanes the window accepted, each type in it
        bad = sum(1 for lane in tampered if reference_mixed.verify(*lane))
        rng = workload.rng_for(self.seed, "sample", "commits_mixed")
        rest = results.sample_lanes - SAMPLE_SECP
        share = {"secp256k1": SAMPLE_SECP, "ed25519": rest - rest // 2, "sr25519": rest // 2}
        for kt in KEY_TYPES:
            for _ in range(share[kt] if outcomes else 0):
                k = int(rng.integers(min(len(outcomes), self.heights)))
                commit = self.commits[(commits.WARM_CALLS + k) % self.heights]
                lane = self._lane(commit, int(rng.choice(self.lanes_of[kt])))
                if reference_mixed.verify(*lane) != (outcomes[k] is None):
                    bad += 1
        results.compare("lanes_where_reference_disagrees", bad, 0)


def build(ctx):
    return CommitsMixed(ctx)
