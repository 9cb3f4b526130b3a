"""Batch-verifier dispatch.

Mirrors crypto/batch/batch.go:11-33: only key types with batch support
(ed25519, sr25519) get a batch verifier; in a mixed set the lanes of
any other type (secp256k1) are verified on the host beside them
(MultiBatchVerifier). The ed25519 batch verifier routes
to the TPU engine (tendermint_tpu.ops) above a size threshold and to the
host oracle below it.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

from tendermint_tpu.crypto.keys import (
    ED25519_KEY_TYPE,
    SR25519_KEY_TYPE,
    PubKey,
)
from tendermint_tpu.libs import tracing


# Host/device crossover: below this many signatures a device launch
# costs more than it saves, so batches stay on the host (the analog of
# the reference's batchVerifyThreshold, types/validation.go:12-16).
# Shared by Ed25519BatchVerifier and the process-wide scheduler.
DEVICE_THRESHOLD = 16


def remote_verify_backend():
    """The verifyd remote backend's ``verify_fn`` when one is configured,
    else None. A shard federation (``TENDERMINT_TPU_VERIFY_SHARDS`` /
    ``verifyd.federation.set_federation``) outranks the single-remote
    config (``TENDERMINT_TPU_VERIFY_REMOTE`` / ``[ops] verify_remote``):
    when both are set the federation's digest router owns placement.
    Lazy import keeps crypto importable without the service."""
    try:
        from tendermint_tpu.verifyd import client as vclient
        from tendermint_tpu.verifyd import federation as vfederation
    except ImportError:
        return None
    try:
        fed = vfederation.federation_backend()
        if fed is not None:
            return fed
        return vclient.remote_backend()
    except Exception:
        return None


def host_verify_ed25519(pks, msgs, sigs) -> List[bool]:
    """Host ZIP-215 oracle over raw lanes — the universal fallback."""
    from tendermint_tpu.crypto.ed25519_ref import verify_zip215

    return [verify_zip215(p, m, s) for p, m, s in zip(pks, msgs, sigs)]


def tiered_verify_ed25519(pks, msgs, sigs) -> List[bool]:
    """The small-batch policy shared by Ed25519BatchVerifier, the
    process-wide scheduler, and verifyd's default flush target: below
    the device threshold a launch costs more than it saves — at
    steady-state vote rates flushes are 1-2 entries and must stay on
    the host; only floods hit the device."""
    if len(pks) < DEVICE_THRESHOLD:
        return host_verify_ed25519(pks, msgs, sigs)
    from tendermint_tpu.ops import verify_batch

    return list(verify_batch(pks, msgs, sigs))


def note_validator_set(
    vals, vhash: Optional[bytes] = None, span=None
) -> Tuple[bool, bool]:
    """Register the active validator set with the device precompute
    cache (ops/precompute.py): its ed25519 keys become eligible for
    per-validator table caching, and stale keys from rotated-out sets
    are dropped. When a verifyd federation is configured, the set's
    digest also becomes the routing key of every member key, so the
    whole committee's traffic pins tables on ONE shard (partitioned,
    not replicated). Never raises — cache warm-up must not be able to
    fail a verification — and stays a no-op when the ops engine is
    absent. Returns ``(newly_active, recognised)``: whether the cache
    had not seen the set before, and whether it knew a live set by its
    keys without hashing it (``precompute.activate_validator_set``).
    ``vhash`` is ``vals.hash()`` where the caller already holds it (the
    light client has checked it against the header): a set that is not
    recognised is then registered without being hashed again. ``span``
    is handed on to the cache, which tells it what a newly registered
    set pushed out (:func:`note_validator_set_traced`).
    """
    try:
        from tendermint_tpu.ops import precompute
    except ImportError:
        return False, False
    noted = (False, False)
    try:
        noted = precompute.activate_validator_set(vals, vhash, span)
    except Exception:
        pass  # cache warm-up must never fail a verification
    # federation routing hook: same best-effort contract; the key list
    # is built and sorted only where a client exists to take it
    try:
        from tendermint_tpu.verifyd import federation as vfederation

        client = vfederation.federation_client()
        if client is not None:
            keys = precompute._vset_ed25519_keys(vals)
            if keys:
                client.note_validator_set(sorted(keys))
    except Exception:
        pass  # routing locality is an optimization, never a failure
    return noted


def note_validator_set_traced(
    vals, vhash: Optional[bytes] = None
) -> Tuple[bool, bool]:
    """:func:`note_validator_set` under the ``note_validator_set`` span
    every caller records it with: ``validators``, ``newly_active``,
    ``recognised`` and, where the set was newly registered, ``retired``
    and ``tables_dropped`` (``precompute.activate_validator_set``)."""
    with tracing.span("note_validator_set", validators=len(vals)) as nsp:
        noted = note_validator_set(vals, vhash, nsp)
        nsp.set(newly_active=noted[0], recognised=noted[1])
    return noted


class PendingVerify:
    """What :meth:`BatchVerifier.begin` returns: a verify whose device
    work, if it has any, is dispatched and not yet collected.
    ``finish()`` returns what ``verify()`` would have, once;
    ``lanes_inflight`` is the lanes on the device until then (0 for a
    batch that was answered at ``begin``)."""

    def __init__(self, finish: Callable[[], Tuple[bool, List[bool]]], lanes_inflight: int = 0):
        self.finish = finish
        self.lanes_inflight = lanes_inflight


class BatchVerifier:
    """crypto.BatchVerifier contract (crypto/crypto.go:58-76): Add entries,
    then Verify once; returns (all_valid, per-entry validity)."""

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        raise NotImplementedError

    def verify(self) -> Tuple[bool, List[bool]]:
        raise NotImplementedError

    def begin(self) -> PendingVerify:
        """``verify()`` in two steps, for a caller with host work to do
        while the device runs: ``begin().finish()`` is ``verify()``.
        Here, as for every batch that stays on the host or goes to a
        remote: verify now, hand back the answer."""
        verdict = self.verify()
        return PendingVerify(lambda: verdict)

    def __len__(self) -> int:
        raise NotImplementedError


def begin_on_device(key_type: str, lanes: int, begin_batch) -> PendingVerify:
    """A device batch verifier's ``begin``: ``begin_batch()`` — the
    engine's ``ops.begin_verify_batch`` / ``begin_verify_batch_sr`` on
    the verifier's lanes, which it lays out under the span, as
    ``verify()`` does — and, later, its ``finish``, each under a
    ``batch_verify`` span as ``verify()`` opens one (``phase``
    ``dispatch`` / ``collect``). One caller's thread does one thing at
    a time, so the two spans of a batch never overlap what ran between
    them."""
    tags = dict(key_type=key_type, lanes=lanes, route="device")
    with tracing.span("batch_verify", phase="dispatch", **tags):
        pending = begin_batch()

    def finish() -> Tuple[bool, List[bool]]:
        with tracing.span("batch_verify", phase="collect", **tags):
            oks = pending.finish()
        return all(oks), list(oks)

    return PendingVerify(finish, pending.lanes_inflight)


class Ed25519BatchVerifier(BatchVerifier):
    """Accumulate-then-flush ed25519 batch verification.

    Above ``device_threshold`` entries the batch is verified on the
    accelerator via :func:`tendermint_tpu.ops.verify_batch`; below it, each
    signature is checked on host (device dispatch overhead dominates for
    tiny batches — the analog of the reference's batchVerifyThreshold at
    types/validation.go:12-16).
    """

    def __init__(
        self,
        device_threshold: int = DEVICE_THRESHOLD,
        use_device: Optional[bool] = None,
    ):
        self._pks: List[bytes] = []
        self._msgs: List[bytes] = []
        self._sigs: List[bytes] = []
        self.device_threshold = device_threshold
        self.use_device = use_device  # None = auto

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        if pub_key.type != ED25519_KEY_TYPE:
            raise ValueError(f"ed25519 batch got {pub_key.type} key")
        pk = pub_key.bytes()
        if len(pk) != 32 or len(sig) != 64:
            raise ValueError("malformed ed25519 entry")
        self._pks.append(pk)
        self._msgs.append(msg)
        self._sigs.append(sig)

    def __len__(self) -> int:
        return len(self._pks)

    def verify(self) -> Tuple[bool, List[bool]]:
        with tracing.span(
            "batch_verify",
            key_type=ED25519_KEY_TYPE,
            lanes=len(self._pks),
            route="host",
        ) as span:
            return self._verify(span)

    def _route(self):
        """``(route, how)``: ``device`` and the engine's module,
        ``remote`` and the backend's ``verify_fn``, or ``host``."""
        n = len(self._pks)
        use_device = self.use_device
        if use_device is None:
            use_device = n >= self.device_threshold
        if n and use_device:
            # A configured verifyd remote owns the accelerator for this
            # process: ship device-worthy batches to it (it amortizes
            # across clients; its client falls back to host verify on
            # transport failure, so verdicts never hang on the wire).
            remote = remote_verify_backend()
            if remote is not None:
                return "remote", remote
            try:
                from tendermint_tpu import ops
            except ImportError:  # device engine unavailable: fail safe to host
                pass
            else:
                return "device", ops
        return "host", None

    def _verify(self, span) -> Tuple[bool, List[bool]]:
        if not self._pks:
            return False, []
        route, how = self._route()
        span.set(route=route)
        if route == "remote":
            oks = how(self._pks, self._msgs, self._sigs)
        elif route == "device":
            oks = how.verify_batch(self._pks, self._msgs, self._sigs)
        else:
            oks = host_verify_ed25519(self._pks, self._msgs, self._sigs)
        return all(oks), list(oks)

    def begin(self) -> PendingVerify:
        route, ops = self._route()
        if route != "device":
            return super().begin()
        return begin_on_device(
            ED25519_KEY_TYPE,
            len(self._pks),
            lambda: ops.begin_verify_batch(self._pks, self._msgs, self._sigs),
        )


def supports_batch_verifier(pub_key: Optional[PubKey]) -> bool:
    """crypto/batch/batch.go:26-33: ed25519 and sr25519 batch."""
    return pub_key is not None and pub_key.type in (
        ED25519_KEY_TYPE,
        SR25519_KEY_TYPE,
    )


def create_batch_verifier(pub_key: PubKey) -> BatchVerifier:
    """crypto/batch/batch.go:11-22: dispatch on key type."""
    if pub_key.type == ED25519_KEY_TYPE:
        return Ed25519BatchVerifier()
    if pub_key.type == SR25519_KEY_TYPE:
        from tendermint_tpu.crypto.sr25519 import Sr25519BatchVerifier

        return Sr25519BatchVerifier()
    raise ValueError(f"key type {pub_key.type} does not support batching")


class HostLanesVerifier(BatchVerifier):
    """The lanes of one key type that has no batch verifier
    (secp256k1): each is verified on the host by its key's own
    ``verify_signature``, under the ``host_lanes`` span. Host by design
    and on every call: not the health machine's ``host_fallback``,
    which says that a device failed."""

    def __init__(self, key_type: str):
        self.key_type = key_type
        self._lanes: List[Tuple[PubKey, bytes, bytes]] = []

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        if pub_key.type != self.key_type:
            raise ValueError(f"{self.key_type} host lanes got {pub_key.type} key")
        self._lanes.append((pub_key, msg, sig))

    def __len__(self) -> int:
        return len(self._lanes)

    def verify(self, device_lanes_inflight: int = 0) -> Tuple[bool, List[bool]]:
        """``device_lanes_inflight``: the lanes the caller has on the
        device while these are verified (MultiBatchVerifier's dispatched
        sub-batches), for the span to say whether the host's work hid
        behind the device's."""
        if not self._lanes:
            return False, []
        n = len(self._lanes)
        # batch_verify as every sub-verifier opens it; host_lanes says
        # that these lanes are the host's by design
        with tracing.span(
            "batch_verify", key_type=self.key_type, lanes=n, route="host"
        ), tracing.span(
            "host_lanes",
            key_type=self.key_type,
            lanes=n,
            device_lanes_inflight=device_lanes_inflight,
        ):
            oks = [bool(pk.verify_signature(msg, sig)) for pk, msg, sig in self._lanes]
        return all(oks), oks


class MultiBatchVerifier(BatchVerifier):
    """Per-key-type sub-batching for MIXED validator sets.

    A 10k-validator commit with ed25519, sr25519 AND secp256k1 signers
    (BASELINE config 5) splits into one sub-verifier per key type — the
    two that batch each riding its own device kernel, a type with no
    batch support (secp256k1) on the host, lane by lane
    (:class:`HostLanesVerifier`) — and the verdicts merge back in
    submission order. A set of one type is that type's ``verify()``.
    A mixed batch is verified in three phases, all on the caller's
    thread: every device sub-verifier, in the order of its type's name
    (whatever seat each type first appeared in, so that a call's shape
    does not depend on the set's address order), does everything up to
    and including its last dispatch (``begin()``); the host-only lanes
    are verified while those kernels run; then each device sub-batch is
    collected, stored and merged (``finish()``), in the same order. Not
    a thread beside them: ``cryptography``'s ECDSA verify never gives
    the GIL up (PERF.md §6, PR 41), so a helper thread would make the
    caller wait out the switch interval each time it comes back from a
    put, a hash or a wait. Every sub-batch is verified whatever another found: the
    caller names the first bad lane across types (reference
    crypto/batch/batch.go:11-22 dispatches on ONE key type; this is the
    mixed-set generalisation)."""

    def __init__(self):
        self._subs: dict = {}
        self._order: List[Tuple[str, int]] = []  # (key type, idx in sub)

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        kt = pub_key.type
        sub = self._subs.get(kt)
        if sub is None:
            if supports_batch_verifier(pub_key):
                sub = create_batch_verifier(pub_key)
            else:
                sub = HostLanesVerifier(kt)
            self._subs[kt] = sub
        sub.add(pub_key, msg, sig)
        self._order.append((kt, len(sub) - 1))

    def __len__(self) -> int:
        return len(self._order)

    def _verify_in_phases(self) -> dict:
        """Key type -> verdicts of a batch of two sub-verifiers or more."""
        host = sorted(
            kt for kt, sub in self._subs.items() if isinstance(sub, HostLanesVerifier)
        )
        results = {}
        pending = []  # (key type, PendingVerify): begun and not finished
        try:
            for kt in sorted(set(self._subs) - set(host)):
                pending.append((kt, self._subs[kt].begin()))
            inflight = sum(p.lanes_inflight for _, p in pending)
            for kt in host:
                _, results[kt] = self._subs[kt].verify(device_lanes_inflight=inflight)
            while pending:
                kt, p = pending.pop(0)
                _, results[kt] = p.finish()
        finally:
            # a begin, the host lanes or a finish raised: what is still
            # in flight is collected all the same, so that no in-flight
            # gauge stays up and no probe stays latched
            for _, p in pending:
                try:
                    p.finish()
                except Exception:
                    pass  # the first exception is the caller's
        return results

    def verify(self) -> Tuple[bool, List[bool]]:
        if not self._order:
            return False, []  # same empty contract as every BatchVerifier
        if len(self._subs) == 1:
            ((kt, sub),) = self._subs.items()
            results = {kt: sub.verify()[1]}
        else:
            results = self._verify_in_phases()
        with tracing.span("merge_verdicts", lanes=len(self._order)):
            merged = [bool(results[kt][i]) for kt, i in self._order]
            return all(merged), merged


import threading as _threading

_shared_scheduler = None
_shared_scheduler_lock = _threading.Lock()


def get_shared_scheduler():
    """Process-wide accumulate-with-deadline scheduler fronting the
    device batch verifier (crypto/scheduler.py) — the seam for callers
    that ingest signatures from many concurrent sources (per-peer vote
    floods, RPC storms) and want device batching without paying a
    device launch per signature. Lazily started on first use."""
    global _shared_scheduler
    with _shared_scheduler_lock:
        if _shared_scheduler is None:
            from tendermint_tpu.crypto.scheduler import VerifyScheduler

            def _verify(pks, msgs, sigs):
                # A configured verifyd remote gets every flush — even
                # tiny ones: the whole point of the service is that
                # OTHER clients' lanes are coalescing there too.
                remote = remote_verify_backend()
                if remote is not None:
                    return remote(pks, msgs, sigs)
                return tiered_verify_ed25519(pks, msgs, sigs)

            def _host_fallback(pks, msgs, sigs):
                # verify_batch already degrades per-chunk via the device
                # health machine; this catches failures outside it (e.g.
                # engine import errors) so a flush never fails closed
                # when the host oracle can still answer it.
                return host_verify_ed25519(pks, msgs, sigs)

            _shared_scheduler = VerifyScheduler(
                _verify, fallback_fn=_host_fallback
            )
            _shared_scheduler.start()
        return _shared_scheduler
