"""Validator-set-aware precompute and result caches for the verify hot path.

Consensus, blocksync, and the light client verify signatures from the
*same stable validator set* height after height.  The device kernel used
to re-decompress every pubkey and rebuild every lane's signed-window
cached-point table on every call; this module amortizes that work across
a committee's lifetime:

- :class:`PrecomputeCache` — a bounded, thread-safe LRU keyed by raw
  pubkey bytes, holding the host-built signed-window table column
  ``(8, 4, 32) uint8`` of ``[1..8](-A)`` in cached form ``(Y+X, Y-X, Z,
  2dT)`` plus the decompression verdict.  ``verify_batch`` gathers the
  cached columns into the ``(8, 4, 32, N)`` table input of the
  table-taking kernel entry point (ops/ed25519_batch.py
  ``verify_kernel_tables``), skipping pt_decompress-of-A and
  ``_build_lane_table`` entirely for hit lanes.
- :class:`ResultCache` — a bounded LRU over ``(pubkey, sign-bytes
  digest, sig)`` verdicts, so blocksync/light/consensus never re-verify
  the identical last-commit votes they verified one height ago. It has
  two batch entry points, which ``verify_batch`` calls once each a
  batch: ``results.get_many(pks, msgs, sigs)`` derives every lane's key
  once and looks them all up under one hold of the lock, returning the
  keys beside the verdicts; ``results.put_many(keys, verdicts)`` stores
  the verified lanes under those keys, again under one hold, and
  returns how many entries the cap pushed out. ``get`` / ``put`` are
  their one-lane calls.

Eligibility is validator-set aware: in the default ``auto`` mode only
keys that belong to an *activated* :class:`~tendermint_tpu.types.\
validator_set.ValidatorSet` (or were explicitly pinned) get host-built
tables, so one-off keys from ad-hoc batches cannot thrash the cache.
Activating a new set invalidates entries for keys that left every
active set (validator-set rotation).

A table is ~1.2 ms of host big-integer work and saves a fraction of a
microsecond a lane: it pays for a committee, whose keys a node verifies
height after height, and not for a key met once or twice (a light
client's pivot). So an eligible key gets its table in the
``BUILD_AT_SIGHTING``-th batch that carries it, and goes table-less on
the legacy kernel until then. The keys of the first set the cache meets
(the node's own committee: nothing else is live yet) and pinned keys are
built in the first.

Env knobs::

    TENDERMINT_TPU_PRECOMPUTE          auto (default) | all | off
    TENDERMINT_TPU_PRECOMPUTE_CAP      max cached keys (default 16384)
    TENDERMINT_TPU_RESULT_CACHE        1 (default) | 0
    TENDERMINT_TPU_RESULT_CACHE_CAP    max cached verdicts (default 65536)

This module imports neither jax nor field32 — table building runs on
host big-ints (crypto/ed25519_ref) and the radix-2^8 f32 limb encoding
is just the little-endian byte string — so the consensus layer can note
validator sets without paying for an accelerator import.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from collections import OrderedDict
from operator import attrgetter
from typing import Dict, FrozenSet, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from tendermint_tpu.libs import tracing

TABLE_WIDTH = 8  # signed 4-bit windows select from [1..8](-A)
NLIMBS = 32

_MODE_ENV = "TENDERMINT_TPU_PRECOMPUTE"
_CAP_ENV = "TENDERMINT_TPU_PRECOMPUTE_CAP"
_RESULT_ENV = "TENDERMINT_TPU_RESULT_CACHE"
_RESULT_CAP_ENV = "TENDERMINT_TPU_RESULT_CACHE_CAP"

_ACTIVE_SETS_CAP = 8  # distinct validator sets considered live at once

# The batch in which an eligible key's table is built, counted over the
# batches that carry it (module docstring). A light client's pivot key
# is carried by two batches at most (PERF.md section 6, PR 30).
BUILD_AT_SIGHTING = 3

# Cache-event observers (device-resident mirrors register here so host
# invalidation propagates to device copies in lockstep).  This module
# stays jax-free: observers are plain callables ``fn(kind, payload)``
# with kind in {"rotation", "evict", "clear"} and payload a tuple of the
# affected pubkeys (empty for "clear").  Callbacks fire OUTSIDE the
# cache lock — never call back into the cache from an observer without
# expecting fresh state.
_observers_lock = threading.Lock()
_observers: List = []  # guarded-by: _observers_lock


def register_observer(fn) -> None:
    """Subscribe ``fn(kind, payload)`` to table-cache invalidation events."""
    with _observers_lock:
        if fn not in _observers:
            _observers.append(fn)


def unregister_observer(fn) -> None:
    with _observers_lock:
        try:
            _observers.remove(fn)
        except ValueError:  # already gone — unsubscribe is idempotent
            pass


def _mode() -> str:
    return os.environ.get(_MODE_ENV, "auto").lower()


def table_cache_enabled() -> bool:
    return _mode() not in ("0", "off", "none", "false")


def result_cache_enabled() -> bool:
    return os.environ.get(_RESULT_ENV, "1").lower() not in (
        "0", "off", "none", "false",
    )


def _limbs(v: int) -> np.ndarray:
    """Canonical integer < 2^256 -> (32,) uint8 radix-2^8 limbs (LE)."""
    return np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8)


def _identity_table() -> np.ndarray:
    """(8, 4, 32) table of cached-form identities (1, 1, 1, 0)."""
    tab = np.zeros((TABLE_WIDTH, 4, NLIMBS), dtype=np.uint8)
    tab[:, 0, 0] = 1
    tab[:, 1, 0] = 1
    tab[:, 2, 0] = 1
    return tab


def build_table(pk: bytes) -> Tuple[np.ndarray, bool]:
    """Host-side builder: pubkey bytes -> ((8, 4, 32) uint8, decompress ok).

    Entry ``i`` is ``(i+1) * (-A)`` in cached form with Z normalized to 1
    — ``(y+x, y-x, 1, 2dxy)`` as canonical-integer limbs, which satisfies
    the kernel's loose limb invariant by construction and packs into
    uint8 (1 KiB per key; the kernel widens to f32 on device).  Invalid
    encodings get identity entries and ``ok=False`` (the kernel masks
    the lane).

    Cost is one liberal decompression plus 7 chained big-int point adds
    (~100 us), paid once per (validator, committee lifetime) instead of
    15 wide device point-adds per lane per batch.
    """
    from tendermint_tpu.crypto import ed25519_ref as ref

    p = ref.P
    a_pt = ref.pt_decompress_liberal(pk) if len(pk) == 32 else None
    if a_pt is None:
        return _identity_table(), False
    neg_a = ref.pt_neg(a_pt)
    tab = np.zeros((TABLE_WIDTH, 4, NLIMBS), dtype=np.uint8)
    acc = neg_a
    for i in range(TABLE_WIDTH):
        if i:
            acc = ref.pt_add(acc, neg_a)
        x_, y_, z_, _ = acc
        zinv = pow(z_, p - 2, p)
        x = x_ * zinv % p
        y = y_ * zinv % p
        tab[i, 0] = _limbs((y + x) % p)
        tab[i, 1] = _limbs((y - x) % p)
        tab[i, 2, 0] = 1
        tab[i, 3] = _limbs(2 * ref.D * x * y % p)
    return tab, True


_pub_key_of = attrgetter("pub_key")


def _vset_ed25519_keys(vset) -> FrozenSet[bytes]:
    """Raw 32-byte ed25519 pubkeys of a ValidatorSet (best effort)."""
    keys = set()
    for v in getattr(vset, "validators", ()):
        pk = getattr(v, "pub_key", None)
        if pk is None:
            continue
        try:
            raw = pk.bytes()
        except Exception:
            continue
        if isinstance(raw, (bytes, bytearray)) and len(raw) == 32:
            if getattr(pk, "type", "ed25519") == "ed25519":
                keys.add(bytes(raw))
    return frozenset(keys)


class PrecomputeCache:
    """Bounded thread-safe LRU of per-validator signed-window tables."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._entries: "OrderedDict[bytes, Tuple[np.ndarray, bool]]" = (
            OrderedDict()
        )  # guarded-by: _lock
        # set hash -> (the set's pub_key objects in order, or None where
        # the set could not be read; its raw ed25519 keys; whether no
        # other set was live when it was activated): one entry, so
        # eviction at the cap and clear() drop all three together.
        self._active_sets: (
            "OrderedDict[bytes, Tuple[Optional[tuple], FrozenSet[bytes], bool]]"
        ) = OrderedDict()  # guarded-by: _lock
        self._eligible: FrozenSet[bytes] = frozenset()  # guarded-by: _lock
        # the part of _eligible built at first sight: pinned keys and the
        # keys of the set activated while no other was live
        self._founders: FrozenSet[bytes] = frozenset()  # guarded-by: _lock
        # batches that carried an eligible key and built no table for it
        self._sightings: Dict[bytes, int] = {}  # guarded-by: _lock
        self._pinned: set = set()  # guarded-by: _lock
        self._metrics = None  # guarded-by: _lock
        self.hits = 0  # guarded-by: _lock
        self.misses = 0  # guarded-by: _lock
        self.builds = 0  # guarded-by: _lock
        self.evictions = 0  # guarded-by: _lock
        self.invalidations = 0  # guarded-by: _lock
        self.build_seconds = 0.0  # guarded-by: _lock
        self.active_set_recognised = 0  # guarded-by: _lock
        self.active_set_hashed = 0  # guarded-by: _lock
        self.sets_retired = 0  # guarded-by: _lock
        self.builds_deferred = 0  # guarded-by: _lock
        self._pending_events: List[Tuple[str, tuple]] = []  # guarded-by: _lock

    # --- configuration ------------------------------------------------------

    @property
    def cap(self) -> int:
        try:
            return max(1, int(os.environ.get(_CAP_ENV, "16384")))
        except ValueError:
            return 16384

    def bind_metrics(self, metrics) -> None:
        with self._lock:
            self._metrics = metrics

    def _flush_events(self) -> None:
        """Deliver queued invalidation events to registered observers.

        Events are appended under ``_lock`` but delivered outside it
        (same pattern as the metrics flush in :meth:`gather`): observers
        upload/drop device tensors, which must never run under the cache
        lock (lock-order sanitizer: no IO/device work under ``_lock``).
        """
        with self._lock:
            if not self._pending_events:
                return
            events = self._pending_events
            self._pending_events = []
        with _observers_lock:
            observers = list(_observers)
        for kind, payload in events:
            for fn in observers:
                try:
                    fn(kind, payload)
                except Exception:
                    # An observer failure must not poison the verify hot
                    # path; the resident store fails safe (lanes fall
                    # back to the gathered-table path).
                    pass

    # --- validator-set awareness -------------------------------------------

    def activate_validator_set(
        self, vset, vhash: Optional[bytes] = None, span=None
    ) -> Tuple[bool, bool]:
        """Mark a validator set live: its keys become table-eligible.
        Returns ``(newly_active, recognised)``. ``vhash`` is the set's
        ``hash()`` where the caller has just computed it (the light
        client checks it against the header); it is taken only for a
        set that is not recognised. ``span`` is the caller's
        ``note_validator_set`` span: it is told what this activation
        cost the cache, ``retired`` (live sets pushed out) and
        ``tables_dropped`` (host tables deleted with them).

        A live set is recognised by the tuple of its validators'
        ``pub_key`` objects, rebuilt on every call from what the set
        holds now and compared with the live sets' tuples, newest first:
        never by hashing it, never by a value remembered on the set.
        Tuple equality tries identity before ``PubKey.__eq__`` and
        ``Validator.copy()`` shares the key object (an immutable value),
        so a set, its ``copy()`` and a copy with other priorities or
        powers compare by pointers; a set decoded anew compares key by
        key. Recognising one is an LRU touch. Eligibility depends on the
        keys alone, so equal ordered keys under other powers touch the
        live entry.

        Any other set (a key replaced, added, removed or reordered; a
        set this pass cannot read) is hashed: a Merkle root over every
        validator's proto bytes, two orders of magnitude dearer than
        recognising it (PERF.md §6, PR 29). A new hash registers the key
        set, retires the oldest live set beyond the bound, and drops
        cached tables for keys that no longer belong to any live set
        (committee rotation). The keys of a set activated while no other
        is live are built at first sight (module docstring).
        """
        try:
            pub_keys = tuple(map(_pub_key_of, vset.validators))
        except (AttributeError, TypeError):  # only its hash() can tell
            pub_keys = None
        if pub_keys is not None:
            with self._lock:
                for known, (live, _, _) in reversed(self._active_sets.items()):
                    if live == pub_keys:
                        self._active_sets.move_to_end(known)
                        self.active_set_recognised += 1
                        return False, True
        if vhash is None:
            try:
                vhash = vset.hash()
            except Exception:
                return False, False
        with self._lock:
            self.active_set_hashed += 1
            if vhash in self._active_sets:
                self._active_sets.move_to_end(vhash)
                return False, False
            self._active_sets[vhash] = (
                pub_keys, _vset_ed25519_keys(vset), not self._active_sets
            )
            retired = 0
            while len(self._active_sets) > _ACTIVE_SETS_CAP:
                self._active_sets.popitem(last=False)
                retired += 1
            self.sets_retired += retired
            dropped = self._recompute_eligible_locked()
        if span is not None:
            span.set(retired=retired, tables_dropped=dropped)
        self._flush_events()
        return True, False

    def pin(self, pubkeys: Iterable[bytes]) -> None:
        """Make specific keys table-eligible outside any validator set."""
        with self._lock:
            self._pinned.update(bytes(pk) for pk in pubkeys)
            self._recompute_eligible_locked()
        self._flush_events()

    def _recompute_eligible_locked(self) -> int:
        """Eligibility from the live sets and the pins; returns how many
        host tables it deleted (keys that left every live set)."""
        eligible = set(self._pinned)
        founders = set(self._pinned)
        for _, keys, first in self._active_sets.values():
            eligible |= keys
            if first:
                founders |= keys
        self._eligible = frozenset(eligible)
        self._founders = frozenset(founders)
        self._sightings = {
            pk: n for pk, n in self._sightings.items() if pk in eligible
        }
        if _mode() != "auto":
            return 0
        stale = [pk for pk in self._entries if pk not in self._eligible]
        for pk in stale:
            del self._entries[pk]
        if stale:
            self.invalidations += len(stale)
            self._pending_events.append(("rotation", tuple(stale)))
            if self._metrics is not None:
                self._metrics.precompute_invalidations.inc(len(stale))
        return len(stale)

    def _due_for_build_locked(self, pk: bytes) -> bool:
        """Whether the batch that carries table-less ``pk`` builds its
        table (module docstring); counts the batch where it does not."""
        if _mode() == "all" or pk in self._founders:
            return True
        if pk not in self._eligible:
            return False
        sightings = self._sightings.get(pk, 0) + 1
        if sightings >= BUILD_AT_SIGHTING:
            self._sightings.pop(pk, None)
            return True
        self._sightings[pk] = sightings
        self.builds_deferred += 1
        return False

    def would_build(self, pubkeys: Sequence[bytes]) -> bool:
        """Whether a :meth:`gather` of ``pubkeys`` now would build a
        table, answered without building, counting a sighting or
        touching the LRU. For a caller that hands the engine a batch a
        block at a time (crypto/batch.DeviceBatchVerifier): a block that
        would build is not begun early, because the device store is
        sized, and its kernel compiled, for the tables that exist when
        it is uploaded — a committee's first commit sent in blocks would
        upload the store at the width of its first block, and compile
        for a width no other call has. Where every eligible key has its
        table — any warm committee, and a process that knows no set —
        nothing is looked up."""
        if not table_cache_enabled():
            return False
        with self._lock:
            build_all = _mode() == "all"
            # auto: the entries are eligible keys, so as many means all
            if not build_all and len(self._entries) >= len(self._eligible):
                return False
            for pk in pubkeys:
                if pk in self._entries:
                    continue
                if build_all or pk in self._founders:
                    return True
                if (
                    pk in self._eligible
                    and self._sightings.get(pk, 0) + 1 >= BUILD_AT_SIGHTING
                ):
                    return True
        return False

    # --- cache body ---------------------------------------------------------

    def _insert_locked(self, pk: bytes, table: np.ndarray, ok: bool) -> None:
        self._entries[pk] = (table, ok)
        self._entries.move_to_end(pk)
        cap = self.cap
        while len(self._entries) > cap:
            old_pk, _ = self._entries.popitem(last=False)
            self.evictions += 1
            self._pending_events.append(("evict", (old_pk,)))
            if self._metrics is not None:
                self._metrics.precompute_evictions.inc()

    def snapshot_eligible(self) -> List[Tuple[bytes, np.ndarray, bool]]:
        """(pk, table, ok) for every cached key of a live validator set.

        The device-resident mirror uploads exactly this slice: eligible
        keys whose host tables already exist.  No LRU touch and no
        hit/miss accounting — this is a replication read, not a lookup.
        """
        with self._lock:
            if _mode() == "all":
                keys = list(self._entries)
            else:
                keys = [pk for pk in self._entries if pk in self._eligible]
            return [
                (pk, self._entries[pk][0], self._entries[pk][1])
                for pk in keys
            ]

    def lookup(self, pk: bytes) -> Optional[Tuple[np.ndarray, bool]]:
        with self._lock:
            entry = self._entries.get(pk)
            if entry is not None:
                self._entries.move_to_end(pk)
            return entry

    def gather(
        self, pubkeys: Sequence[bytes]
    ) -> Tuple[Optional[List[Tuple[np.ndarray, bool]]], np.ndarray]:
        """Per-lane table lookup/build for a batch.

        Returns ``(entries, has_table)`` where ``entries[i]`` is the
        ``(table, ok)`` pair for lane i (None when the lane must take the
        legacy build-on-device path) and ``has_table`` is the (N,) bool
        partition mask.  Cache-hit lanes reuse the stored column;
        eligible miss lanes due for their table (module docstring) are
        built on host (timed + counted) and inserted; the others, like
        ineligible lanes, stay on the legacy kernel, so neither ad-hoc
        batches nor a light client's pivots evict the live committee or
        stall a call on table builds.
        """
        n = len(pubkeys)
        has_table = np.zeros(n, dtype=bool)
        if not table_cache_enabled():
            return None, has_table
        entries: List[Optional[Tuple[np.ndarray, bool]]] = [None] * n
        with tracing.span(
            "gather_tables", stage="gather", engine="ed25519", lanes=n
        ) as tspan:
            with self._lock:
                metrics = self._metrics
                hits = misses = builds = 0
                build_time = 0.0
                deferred = self.builds_deferred
                seen: Dict[bytes, int] = {}
                for i, pk in enumerate(pubkeys):
                    pk = bytes(pk)
                    entry = self._entries.get(pk)
                    if entry is not None:
                        self._entries.move_to_end(pk)
                        hits += 1
                    elif pk in seen:
                        # duplicate signer inside one batch: one build serves
                        # every lane, and only the first counts as a miss.
                        entry = entries[seen[pk]]
                        if entry is None:  # first occurrence got no table
                            continue
                    elif self._due_for_build_locked(pk):
                        misses += 1
                        t0 = time.perf_counter()
                        table, ok = build_table(pk)
                        build_time += time.perf_counter() - t0
                        builds += 1
                        entry = (table, ok)
                        self._insert_locked(pk, table, ok)
                    else:
                        misses += 1
                        has_table[i] = False
                        seen.setdefault(pk, i)
                        continue
                    entries[i] = entry
                    has_table[i] = True
                    seen.setdefault(pk, i)
                deferred = self.builds_deferred - deferred
                self.hits += hits
                self.misses += misses
                self.builds += builds
                self.build_seconds += build_time
            tspan.set(hits=hits, misses=misses, builds=builds, deferred=deferred)
            if metrics is not None:
                if hits:
                    metrics.precompute_hits.inc(hits)
                if misses:
                    metrics.precompute_misses.inc(misses)
                if builds:
                    metrics.precompute_builds.inc(builds)
                    metrics.table_build_seconds.observe(build_time)
        self._flush_events()
        if not has_table.any():
            return None, has_table
        return entries, has_table

    # --- introspection ------------------------------------------------------

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> Dict[str, float]:
        with self._lock:
            return {
                "entries": len(self._entries),
                "active_sets": len(self._active_sets),
                "hits": self.hits,
                "misses": self.misses,
                "builds": self.builds,
                "evictions": self.evictions,
                "invalidations": self.invalidations,
                "build_seconds": self.build_seconds,
                "active_set_recognised": self.active_set_recognised,
                "active_set_hashed": self.active_set_hashed,
                "sets_retired": self.sets_retired,
                "builds_deferred": self.builds_deferred,
            }

    def reset_stats(self) -> None:
        with self._lock:
            self.hits = self.misses = self.builds = 0
            self.evictions = self.invalidations = 0
            self.build_seconds = 0.0
            self.active_set_recognised = self.active_set_hashed = 0
            self.sets_retired = self.builds_deferred = 0

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._active_sets.clear()
            self._pinned.clear()
            self._eligible = self._founders = frozenset()
            self._sightings.clear()
            self._pending_events.append(("clear", ()))
        self._flush_events()
        self.reset_stats()


class ResultCache:
    """Bounded LRU of (pubkey, sign-bytes digest, sig) -> bool verdicts.

    Verification is a pure function of the triple, so both verdicts are
    cacheable; the digest keeps arbitrarily large sign-bytes out of the
    key. Consulted before enqueueing lanes so a vote verified at height
    H never costs device time again at H+1 (last-commit re-verification)
    or when flooded in from N peers.

    The cache works a batch at a time: :meth:`get_many` derives each
    lane's key once and hands the keys back, :meth:`put_many` stores the
    verdicts under them; each reads the switch (and the cap) once and
    takes the lock once, whatever the lane count. What they leave
    behind — contents, LRU order, counters — is what a lane-by-lane
    ``get`` / ``put`` loop over the same lanes leaves; ``get`` and
    ``put`` are the one-lane calls of them.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: "OrderedDict[bytes, bool]" = OrderedDict()  # guarded-by: _lock
        self._metrics = None  # guarded-by: _lock
        self.hits = 0  # guarded-by: _lock
        self.misses = 0  # guarded-by: _lock
        self.evictions = 0  # guarded-by: _lock

    @property
    def cap(self) -> int:
        try:
            return max(1, int(os.environ.get(_RESULT_CAP_ENV, "65536")))
        except ValueError:
            return 65536

    def bind_metrics(self, metrics) -> None:
        with self._lock:
            self._metrics = metrics

    @staticmethod
    def _keys(
        pks: Sequence[bytes], msgs: Sequence[bytes], sigs: Sequence[bytes]
    ) -> List[bytes]:
        sha256 = hashlib.sha256
        return [pk + sha256(msg).digest() + sig for pk, msg, sig in zip(pks, msgs, sigs)]

    def get_many(
        self, pks: Sequence[bytes], msgs: Sequence[bytes], sigs: Sequence[bytes]
    ) -> Tuple[Optional[List[bytes]], Optional[List[Optional[bool]]]]:
        """Look a batch up: ``(keys, verdicts)``.

        ``keys`` are the lanes' cache keys, to hand to :meth:`put_many`
        once the missed lanes are verified, or None with the cache
        switched off. ``verdicts`` holds a lane's cached verdict or None
        where it missed, and is None itself where every lane missed.
        """
        if not result_cache_enabled():
            return None, None
        keys = self._keys(pks, msgs, sigs)
        verdicts = None
        hits = 0
        with self._lock:
            metrics = self._metrics
            entries = self._entries
            if not entries.keys().isdisjoint(keys):
                verdicts = [entries.get(key) for key in keys]
                for key, verdict in zip(keys, verdicts):
                    if verdict is not None:
                        entries.move_to_end(key)
                        hits += 1
            self.hits += hits
            self.misses += len(keys) - hits
        if metrics is not None:
            if hits:
                metrics.result_cache_hits.inc(hits)
            if len(keys) > hits:
                metrics.result_cache_misses.inc(len(keys) - hits)
        return keys, verdicts

    def put_many(self, keys: Sequence[bytes], verdicts) -> int:
        """Store ``verdicts`` under the ``keys`` :meth:`get_many` gave
        for those lanes; returns how many entries the cap pushed out.
        The whole store runs under one hold of the lock."""
        if not result_cache_enabled():
            return 0
        cap = self.cap
        evicted = 0
        with self._lock:
            entries = self._entries
            for key, verdict in zip(keys, np.asarray(verdicts, dtype=bool).tolist()):
                entries[key] = verdict
                entries.move_to_end(key)
                while len(entries) > cap:
                    entries.popitem(last=False)
                    evicted += 1
            self.evictions += evicted
        return evicted

    def get(self, pk: bytes, msg: bytes, sig: bytes) -> Optional[bool]:
        _, verdicts = self.get_many((pk,), (msg,), (sig,))
        return None if verdicts is None else verdicts[0]

    def put(self, pk: bytes, msg: bytes, sig: bytes, verdict: bool) -> None:
        self.put_many(self._keys((pk,), (msg,), (sig,)), (verdict,))

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> Dict[str, float]:
        with self._lock:
            return {
                "entries": len(self._entries),
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
            }

    def reset_stats(self) -> None:
        with self._lock:
            self.hits = self.misses = self.evictions = 0

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
        self.reset_stats()


# --- process-wide singletons -------------------------------------------------

tables = PrecomputeCache()
results = ResultCache()


def activate_validator_set(
    vset, vhash: Optional[bytes] = None, span=None
) -> Tuple[bool, bool]:
    return tables.activate_validator_set(vset, vhash, span)


def pin_pubkeys(pubkeys: Iterable[bytes]) -> None:
    tables.pin(pubkeys)


def bind_metrics(metrics) -> None:
    tables.bind_metrics(metrics)
    results.bind_metrics(metrics)


def stats() -> Dict[str, Dict[str, float]]:
    return {"precompute": tables.stats(), "result_cache": results.stats()}


def reset() -> None:
    """Drop all cached state and counters (tests, bench isolation)."""
    tables.clear()
    results.clear()
