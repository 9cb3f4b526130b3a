"""Batch-verifier dispatch.

Mirrors crypto/batch/batch.go:11-33: only key types with batch support
(ed25519, sr25519) get a batch verifier; in a mixed set the lanes of
any other type (secp256k1) are verified on the host beside them
(MultiBatchVerifier). The ed25519 batch verifier routes
to the TPU engine (tendermint_tpu.ops) above a size threshold and to the
host oracle below it.
"""

from __future__ import annotations

from itertools import repeat
from typing import Callable, List, Optional, Sequence, Tuple

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


_NEVER = 1 << 62  # lanes no batch holds


def key_types(pub_keys: Sequence[PubKey]) -> set:
    """The key types among ``pub_keys``."""
    return {pub_key.type for pub_key in pub_keys}


class BatchVerifier:
    """crypto.BatchVerifier contract (crypto/crypto.go:58-76): Add entries,
    then Verify once; returns (all_valid, per-entry validity)."""

    # True once the verifier holds a full engine job of lanes it has not
    # begun: what a caller's loop may look at, an attribute read a lane.
    # A verifier with no device path never has one.
    ready = False

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        raise NotImplementedError

    def add_many(self, pub_keys: Sequence[PubKey], msgs: Sequence[bytes], sigs: Sequence[bytes]) -> None:
        """``add`` of each lane in turn, a block of them in one call: a
        lane ``add`` refuses raises what it raises there, with the
        lanes before it taken. Here the loop itself; a verifier that
        keeps columns checks the block as a whole and extends them."""
        for lane in zip(pub_keys, msgs, sigs, strict=True):
            self.add(*lane)

    def room(self, pub_keys: Sequence[PubKey] = ()) -> int:
        """Where a caller's block ends: of the lanes to come, whose
        keys are ``pub_keys`` in that order, how many the verifier
        takes until it holds a full job ready to begin; more than there
        are where they fill none, as for every verifier with no device
        path. Only a verifier of several key types looks at the keys."""
        return _NEVER

    def verify(self) -> Tuple[bool, List[bool]]:
        raise NotImplementedError

    def begin(self) -> PendingVerify:
        """``verify()`` in two steps, for a caller with host work to do
        while the device runs: ``begin().finish()`` is ``verify()``.
        Here, as for every batch that stays on the host or goes to a
        remote: verify now, hand back the answer."""
        verdict = self.verify()
        return PendingVerify(lambda: verdict)

    def begin_ready(self) -> int:
        """Begin on the device, a job at a time, the full jobs of lanes
        held and not yet begun; returns the lanes so begun. ``add`` does
        it by itself one lane later, so a caller need not; one that
        looks at ``ready`` after each ``add`` starts the device that
        much sooner, and outside whatever it times around ``add``.
        Nothing to begin here, as for every verifier with no device
        path."""
        return 0

    def close(self) -> None:
        """Collect what was begun and never finished and drop the
        verdicts: for a caller that leaves without ``verify()``, so that
        no in-flight gauge stays up and no probe stays latched. Does
        nothing after ``verify()``, or twice."""

    def __len__(self) -> int:
        raise NotImplementedError


def begin_on_device(key_type: str, lanes: int, begin_batch, early: bool = False) -> PendingVerify:
    """A device batch verifier's ``begin``: ``begin_batch()`` — the
    engine's ``ops.begin_verify_batch`` / ``begin_verify_batch_sr`` on
    the verifier's lanes, which it lays out under the span, as
    ``verify()`` does — and, later, its ``finish``, each under a
    ``batch_verify`` span as ``verify()`` opens one (``phase``
    ``dispatch`` / ``collect``; ``early=1`` on the dispatch of a block
    begun before ``verify()``). One caller's thread does one thing at
    a time, so the two spans of a batch never overlap what ran between
    them."""
    tags = dict(key_type=key_type, lanes=lanes, route="device")
    with tracing.span("batch_verify", phase="dispatch", **(dict(tags, early=1) if early else tags)):
        pending = begin_batch()

    def finish() -> Tuple[bool, List[bool]]:
        with tracing.span("batch_verify", phase="collect", **tags):
            oks = pending.finish()
        return all(oks), list(oks)

    return PendingVerify(finish, pending.lanes_inflight)


def _drop(blocks: List[PendingVerify]) -> None:
    """Finish ``blocks`` for what finishing settles (the in-flight
    gauge, the health machine's probe) and drop the verdicts; an
    exception of theirs is not the caller's first, or the caller has
    left."""
    for block in blocks:
        try:
            block.finish()
        except Exception:
            pass  # the first exception is the caller's, or nobody is left to tell


def finish_in_order(blocks: List[PendingVerify]) -> PendingVerify:
    """The blocks of one batch as one ``PendingVerify``: each finished
    in the order begun, the verdicts concatenated. One that raises
    leaves the rest finished all the same."""

    def finish() -> Tuple[bool, List[bool]]:
        left = list(blocks)
        verdicts: List[bool] = []
        try:
            while left:
                verdicts += left.pop(0).finish()[1]
        finally:
            _drop(left)
        return all(verdicts), verdicts

    return PendingVerify(finish, sum(block.lanes_inflight for block in blocks))


class DeviceBatchVerifier(BatchVerifier):
    """What the two verifiers with a device engine share
    (:class:`Ed25519BatchVerifier`, ``crypto.sr25519.Sr25519BatchVerifier``):
    the route's rule, ``verify()``'s span, and the early begin.

    The early begin: the engine need not wait for ``verify()`` to hear
    of lanes the verifier already holds. Whenever it holds one full
    engine job of lanes not yet begun (``ops.ed25519_batch.job_lanes``:
    what one launch takes, on every device a batch that large would
    span) and the batch is this process's device's, those lanes are
    begun as a block of their own — lookup, gather, route, prep,
    dispatch — and the kernel runs while the caller goes on adding.
    ``verify()`` / ``begin()`` begin what is left, then finish every
    block in the order begun: the verdicts are those of one
    ``verify()`` of the whole batch, lane for lane, from the same
    kernels at the same widths (the engine cuts a batch at the same
    lanes). A batch under one job, a remote's or the host's begins
    nothing early. ``add`` keeps ``ready`` and begins a ready job when
    the next lane arrives; ``begin_ready()`` is for the caller that
    looked.

    A subclass names its ``key_type`` and gives ``__len__``,
    ``_verify(span)`` (the whole batch, now) and ``_device_begin()``.
    """

    key_type = ""

    def __init__(self, device_threshold: int, use_device: Optional[bool]):
        self.device_threshold = device_threshold
        self.use_device = use_device  # None = auto
        self.ready = False
        self._begun = 0  # lanes in the blocks begun early
        self._blocks: List[PendingVerify] = []  # those blocks, in the order begun, until finished
        self._job = 0  # lanes one engine job holds, once asked
        self._look_at = 1  # lanes held at which add looks next (_look)

    def _wants_device(self, lanes: Optional[int] = None) -> bool:
        """The route's rule for a batch of ``lanes`` (those held):
        ``use_device``, or by the threshold."""
        n = len(self) if lanes is None else lanes
        use_device = self.use_device
        if use_device is None:
            use_device = n >= self.device_threshold
        return bool(n and use_device)

    def _device_begin(self, lanes: Optional[int] = None):
        """``begin(lo, hi, early)`` -> the engine's ``PendingBatch`` of
        lanes ``lo:hi``, where this batch, or one of ``lanes``, is this
        process's device's; else None."""
        raise NotImplementedError

    def _verify(self, span) -> Tuple[bool, List[bool]]:
        raise NotImplementedError

    def _begins_warm(self, lo: int, hi: int) -> bool:
        """Whether beginning lanes ``lo:hi`` now is what ``verify()`` of
        the whole batch would do for them, only sooner: not before this
        engine's first full job in the process, whose launch compiles
        or loads the kernel (a process's first calls keep their order)."""
        from tendermint_tpu.ops.ed25519_batch import job_has_run

        return job_has_run(self.key_type)

    def _look(self) -> None:
        """``add``'s look, when the lanes held reach ``_look_at``. While
        the batch is not the device's (under the threshold, a remote's)
        it looks again at twice the lanes; from then on at the end of
        the next job, where it sets ``ready``, and one lane later, where
        it begins what a caller that never looked has left ready."""
        n = len(self)
        if self.ready:
            self.begin_ready()
        elif not self._job and not self._learn_job(n):
            self._look_at = 2 * n
            return
        if n >= self._look_at:
            self.ready = True
            self._look_at = n + 1

    def _learn_job(self, lanes: int) -> bool:
        """The engine's job, once: where a batch of ``lanes`` is this
        process's device's."""
        if self._device_begin(lanes) is None:
            return False
        from tendermint_tpu.ops.ed25519_batch import job_lanes

        self._look_at = self._job = job_lanes()
        return True

    def room(self, pub_keys: Sequence[PubKey] = ()) -> int:
        """The lanes the next job still takes; asked of a batch still
        under the threshold, what it will take once over it."""
        if self._look_at >= _NEVER:
            return _NEVER
        if not self._job and not self._learn_job(max(len(self), self.device_threshold)):
            return _NEVER
        return self._job - (len(self) - self._begun) % self._job

    def begin_ready(self) -> int:
        if not self.ready:
            return 0
        self.ready = False
        begin_lanes = self._device_begin()
        if begin_lanes is None:  # no longer the device's: verify() will say
            self._look_at = _NEVER
            return 0
        first = self._begun
        while len(self) - self._begun >= self._job:
            lo, hi = self._begun, self._begun + self._job
            if not self._begins_warm(lo, hi):
                self._look_at = _NEVER  # verify() takes them with the rest
                return self._begun - first
            self._blocks.append(
                begin_on_device(self.key_type, hi - lo, lambda: begin_lanes(lo, hi, True), early=True)
            )
            self._begun = hi
        self._look_at = self._begun + self._job
        return self._begun - first

    def verify(self) -> Tuple[bool, List[bool]]:
        if self._blocks:
            return self.begin().finish()
        with tracing.span(
            "batch_verify", key_type=self.key_type, lanes=len(self), route="host"
        ) as span:
            return self._verify(span)

    def begin(self) -> PendingVerify:
        begin_lanes = self._device_begin()
        blocks, self._blocks = self._blocks, []
        lo, n = self._begun, len(self)
        # from here the batch is verified as a whole, whoever asks again
        self.ready, self._begun, self._look_at = False, 0, _NEVER
        if begin_lanes is None:
            _drop(blocks)  # begun for a device this batch is no longer for
            return super().begin()
        if lo < n or not blocks:
            try:
                blocks.append(
                    begin_on_device(self.key_type, n - lo, lambda: begin_lanes(lo, n, False))
                )
            except BaseException:
                _drop(blocks)
                raise
        return blocks[0] if len(blocks) == 1 else finish_in_order(blocks)

    def close(self) -> None:
        blocks, self._blocks = self._blocks, []
        _drop(blocks)

    def __del__(self):
        # dropped with blocks in flight; nothing to do for one whose
        # __init__ never ran to its end
        if getattr(self, "_blocks", None):
            self.close()


class Ed25519BatchVerifier(DeviceBatchVerifier):
    """Accumulate-then-flush ed25519 batch verification.

    Above ``device_threshold`` entries the batch is verified on the
    accelerator via :func:`tendermint_tpu.ops.verify_batch`; below it, each
    signature is checked on host (device dispatch overhead dominates for
    tiny batches — the analog of the reference's batchVerifyThreshold at
    types/validation.go:12-16).
    """

    key_type = ED25519_KEY_TYPE

    def __init__(
        self,
        device_threshold: int = DEVICE_THRESHOLD,
        use_device: Optional[bool] = None,
    ):
        super().__init__(device_threshold, use_device)
        self._pks: List[bytes] = []
        self._msgs: List[bytes] = []
        self._sigs: List[bytes] = []

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        if pub_key.type != ED25519_KEY_TYPE:
            raise ValueError(f"ed25519 batch got {pub_key.type} key")
        pk = pub_key.bytes()
        if len(pk) != 32 or len(sig) != 64:
            raise ValueError("malformed ed25519 entry")
        self._pks.append(pk)
        self._msgs.append(msg)
        self._sigs.append(sig)
        if len(self._sigs) >= self._look_at:
            self._look()

    def add_many(self, pub_keys: Sequence[PubKey], msgs: Sequence[bytes], sigs: Sequence[bytes]) -> None:
        pks = [pub_key.bytes() for pub_key in pub_keys]
        if not (
            key_types(pub_keys) <= {ED25519_KEY_TYPE}
            and set(map(len, pks)) <= {32}
            and set(map(len, sigs)) <= {64}
            and len(pks) == len(msgs) == len(sigs)
        ):
            # a lane add refuses, or columns of unequal length: lane by lane, for add to say
            return super().add_many(pub_keys, msgs, sigs)
        self._pks += pks
        self._msgs += msgs
        self._sigs += sigs
        if len(self._sigs) >= self._look_at:
            self._look()

    def __len__(self) -> int:
        return len(self._pks)

    def _route(self, lanes: Optional[int] = None):
        """``(route, how)`` of this batch, or of one of ``lanes``:
        ``device`` and the engine's module, ``remote`` and the backend's
        ``verify_fn``, or ``host``."""
        if self._wants_device(lanes):
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

    def _begins_warm(self, lo: int, hi: int) -> bool:
        # not where the engine would build tables for the block: the
        # device store's width, and with it the kernel's compiled
        # shape, would follow the block (precompute.would_build)
        from tendermint_tpu.ops import precompute

        return super()._begins_warm(lo, hi) and not precompute.tables.would_build(
            self._pks[lo:hi]
        )

    def _device_begin(self, lanes: Optional[int] = None):
        route, ops = self._route(lanes)
        if route != "device":
            return None
        return lambda lo, hi, early: ops.begin_verify_batch(
            self._pks[lo:hi], self._msgs[lo:hi], self._sigs[lo:hi], early=early
        )


def supports_batch_verifier(pub_key: Optional[PubKey]) -> bool:
    """crypto/batch/batch.go:26-33: ed25519 and sr25519 batch."""
    return pub_key is not None and pub_key.type in (
        ED25519_KEY_TYPE,
        SR25519_KEY_TYPE,
    )


def create_batch_verifier(pub_key: PubKey, use_device: Optional[bool] = None) -> BatchVerifier:
    """crypto/batch/batch.go:11-22: dispatch on key type. ``use_device``
    is the verifier's own (None: by its threshold)."""
    if pub_key.type == ED25519_KEY_TYPE:
        return Ed25519BatchVerifier(use_device=use_device)
    if pub_key.type == SR25519_KEY_TYPE:
        from tendermint_tpu.crypto.sr25519 import Sr25519BatchVerifier

        return Sr25519BatchVerifier(use_device=use_device)
    raise ValueError(f"key type {pub_key.type} does not support batching")


class HostLanesVerifier(BatchVerifier):
    """The lanes of one key type that has no batch verifier
    (secp256k1), verified on the host under the ``host_lanes`` span: by
    one call of the key class's ``verify_many`` where it has one (the
    span then says whose code that is, ``impl``), else each by its
    key's own ``verify_signature``. Host by design and on every call:
    not the health machine's ``host_fallback``, which says that a
    device failed."""

    def __init__(self, key_type: str):
        self.key_type = key_type
        self._lanes: List[Tuple[PubKey, bytes, bytes]] = []

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        if pub_key.type != self.key_type:
            raise ValueError(f"{self.key_type} host lanes got {pub_key.type} key")
        self._lanes.append((pub_key, msg, sig))

    def add_many(self, pub_keys: Sequence[PubKey], msgs: Sequence[bytes], sigs: Sequence[bytes]) -> None:
        if not (key_types(pub_keys) <= {self.key_type} and len(pub_keys) == len(msgs) == len(sigs)):
            return super().add_many(pub_keys, msgs, sigs)
        self._lanes += zip(pub_keys, msgs, sigs)

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
        ) as span:
            cls = type(self._lanes[0][0])
            if getattr(cls, "verify_many", None) is not None and all(
                type(pk) is cls for pk, _, _ in self._lanes
            ):
                span.set(impl=cls.verify_impl())
                oks = cls.verify_many(*zip(*self._lanes))
            else:
                oks = [bool(pk.verify_signature(msg, sig)) for pk, msg, sig in self._lanes]
        return all(oks), oks


class MultiBatchVerifier(BatchVerifier):
    """Per-key-type sub-batching for MIXED validator sets.

    A 10k-validator commit with ed25519, sr25519 AND secp256k1 signers
    (BASELINE config 5) splits into one sub-verifier per key type — the
    two that batch each riding its own device kernel, a type with no
    batch support (secp256k1) on the host, in one call of its key
    class's native routine (:class:`HostLanesVerifier`) — and the
    verdicts merge back in submission order. A set of one type is that
    type's ``verify()``.
    A mixed batch is verified in three phases, all on the caller's
    thread: every device sub-verifier, in the order of its type's name
    (whatever seat each type first appeared in, so that a call's shape
    does not depend on the set's address order), does everything up to
    and including its last dispatch (``begin()``); the host-only lanes
    are verified while those kernels run; then each device sub-batch is
    collected, stored and merged (``finish()``), in the same order. Not
    a thread beside them: a hundred host lanes are ten milliseconds
    of one native call since PR 49 (they were 51 ms of OpenSSL's, which
    never gave the GIL up: PERF.md §6, PR 41 and PR 49), less than a
    helper thread's hand-offs would cost the caller. Every sub-batch is
    verified whatever another found: the caller names the first bad
    lane across types (reference crypto/batch/batch.go:11-22 dispatches
    on ONE key type; this is the mixed-set generalisation).

    The lanes need not be one commit's: ``parallel/pipeline`` hands it a
    blocksync window's — sixteen blocks' included lanes in one
    ``add_many``, grouped by key type in one pass — and slices the
    merged verdicts per block itself. ``use_device`` is handed to each
    device sub-verifier (None: by its threshold; False: every type on
    its host oracle)."""

    def __init__(self, use_device: Optional[bool] = None):
        self.use_device = use_device
        self._subs: dict = {}
        self._order: List[str] = []  # each lane's key type: a sub-verifier keeps its lanes in the order added
        self.ready = False  # a sub-verifier is

    def _sub(self, pub_key: PubKey) -> BatchVerifier:
        """The sub-verifier of ``pub_key``'s type, made when its first
        key is met."""
        kt = pub_key.type
        sub = self._subs.get(kt)
        if sub is None:
            if supports_batch_verifier(pub_key):
                sub = create_batch_verifier(pub_key, self.use_device)
            else:
                sub = HostLanesVerifier(kt)
            self._subs[kt] = sub
        return sub

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        kt = pub_key.type
        sub = self._subs.get(kt)
        if sub is None:
            sub = self._sub(pub_key)
        sub.add(pub_key, msg, sig)
        self._order.append(kt)
        if sub.ready:
            self.ready = True

    def _seats_by_type(self, pub_keys: Sequence[PubKey]) -> dict:
        """``pub_keys`` grouped by key type once: key type -> the seats
        its keys hold among them, in order; each type's sub-verifier
        made. By the keys' classes, a class being of one type: one
        read of ``type`` a class, not a lane."""
        if len(set(map(type, pub_keys))) == 1:
            self._sub(pub_keys[0])
            return {pub_keys[0].type: range(len(pub_keys))}
        by_class: dict = {}
        for seat, cls in enumerate(map(type, pub_keys)):
            try:
                by_class[cls].append(seat)
            except KeyError:
                by_class[cls] = [seat]
        seats: dict = {}
        for cls_seats in by_class.values():
            kt = pub_keys[cls_seats[0]].type
            self._sub(pub_keys[cls_seats[0]])
            # two classes of one type: their seats back in order
            seats[kt] = sorted(seats[kt] + cls_seats) if kt in seats else cls_seats
        return seats

    def add_many(self, pub_keys: Sequence[PubKey], msgs: Sequence[bytes], sigs: Sequence[bytes]) -> None:
        """A block one of whose lanes is refused is taken type by type,
        not up to that lane, and leaves ``len`` behind: the caller gives
        such a batch up, as the commit's loop does."""
        if not len(pub_keys) == len(msgs) == len(sigs):
            return super().add_many(pub_keys, msgs, sigs)
        order: List = [None] * len(pub_keys)
        for kt, seats in self._seats_by_type(pub_keys).items():
            sub = self._subs[kt]
            if len(seats) == len(pub_keys):
                sub.add_many(pub_keys, msgs, sigs)
                order = repeat(kt, len(seats))
            else:
                sub.add_many(*([column[seat] for seat in seats] for column in (pub_keys, msgs, sigs)))
                for seat in seats:
                    order[seat] = kt
            if sub.ready:
                self.ready = True
        self._order += order

    def room(self, pub_keys: Sequence[PubKey]) -> int:
        """The first lane that fills a sub-verifier's job, by the seats
        of each key type among ``pub_keys``: which sub-verifier that is
        depends on how the types interleave."""
        cut = _NEVER
        for kt, seats in self._seats_by_type(pub_keys).items():
            lanes = self._subs[kt].room()
            if lanes <= len(seats):
                cut = min(cut, seats[lanes - 1] + 1)
        return cut

    def __len__(self) -> int:
        return len(self._order)

    def lanes_by_type(self) -> dict:
        """Key type -> ``(lanes held, route)``, one entry a sub-batch:
        ``device`` for a type that batches (which a batch under the
        threshold, a remote's or ``use_device=False`` still sends
        elsewhere: its ``batch_verify`` span says where), ``host`` for
        one the host verifies by design."""
        return {
            kt: (len(sub), "host" if isinstance(sub, HostLanesVerifier) else "device")
            for kt, sub in self._subs.items()
        }

    def begin_ready(self) -> int:
        """Each sub-verifier's, in the order of its type's name."""
        self.ready = False
        return sum(self._subs[kt].begin_ready() for kt in sorted(self._subs))

    def close(self) -> None:
        for sub in self._subs.values():
            sub.close()

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
            _drop([p for _, p in pending])
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
            if len(results) == 1:
                ((_, verdicts),) = results.items()
                merged = list(map(bool, verdicts))
            else:
                lanes = {kt: iter(verdicts) for kt, verdicts in results.items()}
                merged = [bool(next(lanes[kt])) for kt in self._order]
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
