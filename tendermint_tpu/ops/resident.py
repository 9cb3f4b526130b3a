"""Device-resident precompute table store for the verify hot path.

ops/precompute.py keeps the per-validator ``[1..8](-A)`` signed-window
tables on the *host*; until now every batch re-gathered the cached
columns and re-shipped a fresh ``(8, 4, 32, N)`` uint8 tensor to the
device — ~1 KiB per lane per call, even when the same 100-validator
committee signs every commit. This module closes that loop: the live
validator set's tables are uploaded **once** as a ``(8, 4, 32, K)``
device tensor, and steady-state batches ship only per-lane ``int32``
gather indices into it (ops/ed25519_batch.verify_kernel_resident does
the ``jnp.take`` on device). Rotation and LRU eviction invalidate the
device copy in lockstep with the host cache via the observer hook
(:func:`precompute.register_observer`) — a stale tensor can never
verify a rotated-out key because any change to the host entries drops
the device copy wholesale.

Sharding: when a mesh is planned the store is uploaded **replicated**
across the plan's devices (``P(None, None, None, None)``): the store
axis is *distinct keys*, not lanes, and a replicated store makes the
per-lane gather device-local, so the in-kernel gathered table tensor
comes out lane-sharded ``P(None, None, None, 'sig')`` with zero
collectives — same layout the sharded table kernel always used. A
committee's worth of tables is ~100 KiB; replication is cheaper than
one cross-device gather.

Column 0 is reserved for the pad-lane table so padded lanes index
something valid; real keys start at column 1.

Env knob / config::

    TENDERMINT_TPU_RESIDENT   auto (default: on for tpu) | on | off
    [ops] resident_tables     same values, via node config -> configure()

This module fails safe everywhere: any trouble (no device, upload
failure, mesh mismatch) returns None from :func:`acquire` and the
caller keeps the per-batch gathered-table path.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Iterable, Optional, Sequence, Tuple

import numpy as np

from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.sanitizer import instrument_attrs

_ENV = "TENDERMINT_TPU_RESIDENT"

# Keys seen this many times via note_hot_keys get pinned in the host
# cache (verifyd traffic has no validator-set activation to ride).
_HOT_PIN_THRESHOLD = 2
_HOT_TRACK_CAP = 4096

# Host-staged footprint of one key's signed-window table: the
# ``(8, 4, 32)`` uint8 column that joins the resident upload. Pinned
# keys hold this much host memory whether or not a device copy exists,
# so the partitioned-fleet ledger can show per-shard table placement
# even on CPU (where the device upload never happens).
TABLE_BYTES_PER_KEY = 8 * 4 * 32

# Narrowest device tensor :meth:`ResidentTableStore.refresh` uploads, in
# columns (the narrowest kernel bucket's lanes).
_MIN_STORE_WIDTH = 64


@instrument_attrs
class ResidentTableStore:
    """Thread-safe device mirror of the host precompute cache."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._mode_override: Optional[str] = None  # guarded-by: _lock
        self._index: Dict[bytes, int] = {}  # guarded-by: _lock
        self._tab_dev = None  # guarded-by: _lock  (8,4,32,K) device uint8
        self._ok_host: Optional[np.ndarray] = None  # guarded-by: _lock
        self._mesh_key: Optional[tuple] = None  # guarded-by: _lock
        self._backend_key: Optional[str] = None  # guarded-by: _lock
        self._version = 0  # guarded-by: _lock
        self._metrics = None  # guarded-by: _lock
        self.hits = 0  # guarded-by: _lock
        self.misses = 0  # guarded-by: _lock
        self.uploads = 0  # guarded-by: _lock
        self.h2d_bytes = 0  # guarded-by: _lock
        self.gathered_h2d_bytes = 0  # guarded-by: _lock
        self.invalidations = 0  # guarded-by: _lock
        self._hot_counts: Dict[bytes, int] = {}  # guarded-by: _lock
        self._tenant_pins: Dict[str, int] = {}  # guarded-by: _lock
        self.pin_quota_denials = 0  # guarded-by: _lock
        # keys THIS process pinned via note_hot_keys — the shard's
        # slice of the partitioned fleet. Mirrored to the introspect
        # ledger as host-staged bytes so `verifyd stats` shows table
        # placement per shard (disjoint across a federation) even on
        # CPU, where the device upload never happens.
        self._pinned: set = set()  # guarded-by: _lock

    # --- configuration ------------------------------------------------------

    def configure(self, mode: Optional[str]) -> None:
        """Config-file override of the env knob (``[ops] resident_tables``)."""
        with self._lock:
            self._mode_override = mode.lower() if mode else None

    def mode(self) -> str:
        with self._lock:
            override = self._mode_override
        if override:
            return override
        return os.environ.get(_ENV, "auto").lower()

    def enabled(self, backend: Optional[str] = None) -> bool:
        # auto: the accelerator only — CPU ships tables per batch
        # exactly as before, so tier-1 behavior is unchanged.
        from tendermint_tpu.ops import backend as backend_mod

        return backend_mod.auto_on(self.mode(), backend)

    def bind_metrics(self, metrics) -> None:
        with self._lock:
            self._metrics = metrics

    # --- upload / invalidate ------------------------------------------------

    def _context_key(self, plan, backend: Optional[str]) -> Tuple[Optional[tuple], Optional[str]]:
        if plan is not None:
            return tuple(plan.device_ids), None
        return None, backend

    def refresh(
        self, plan=None, backend: Optional[str] = None, reason: str = "first"
    ) -> bool:
        """Upload the host cache's live-committee slice to the device.

        Builds the ``(8, 4, 32, K)`` tensor on host (column 0 = pad
        table), ships it once, and installs it unless an invalidation
        raced the upload (version check). Returns True when a usable
        device copy is installed. ``reason`` goes on the
        ``resident_upload`` span: ``first`` (no copy yet), ``dropped``
        (an invalidation took the copy), ``joined`` (a batch carried a
        host-cached key the copy lacks) or ``context`` (the copy was
        uploaded for another mesh or backend).
        """
        from tendermint_tpu.ops import ed25519_batch, precompute

        snap = precompute.tables.snapshot_eligible()
        if not snap:
            return False
        mesh_key, backend_key = self._context_key(plan, backend)
        with self._lock:
            version = self._version
        pad = ed25519_batch._pad_table()
        cols = [pad]
        oks = [True]
        index: Dict[bytes, int] = {}
        for pk, table, ok in snap:
            index[pk] = len(cols)
            cols.append(table)
            oks.append(ok)
        # The kernels are compiled for the store's width, and the host
        # cache holds a table only for keys some batch has carried: a
        # light commit stops at 2/3, so a validator past that point
        # shows up heights later, one key at a time. Widths are powers
        # of two (pad-table columns behind the real ones), so that a
        # key joining the store re-uploads it but recompiles nothing
        # until the width doubles.
        width = max(_MIN_STORE_WIDTH, 1 << (len(cols) - 1).bit_length())
        cols += [pad] * (width - len(cols))
        oks += [True] * (width - len(oks))
        host_tab = np.ascontiguousarray(
            np.stack(cols).transpose(1, 2, 3, 0)
        )  # (8, 4, 32, width)
        nbytes = int(host_tab.nbytes)
        try:
            with tracing.span(
                "resident_upload",
                stage="resident_upload",
                engine="ed25519",
                keys=len(index),
                bytes=nbytes,
                width=width,
                reason=reason,
            ):
                tab_dev = self._device_put(host_tab, plan, backend)
        except Exception:  # upload is an optimization; fail safe to gather
            return False
        with self._lock:
            if self._version != version:
                # an invalidation raced the upload: the snapshot may be
                # stale, drop it and let the next batch retry
                return False
            self._index = index
            self._tab_dev = tab_dev
            self._ok_host = np.asarray(oks, dtype=np.uint8)
            self._mesh_key = mesh_key
            self._backend_key = backend_key
            self.uploads += 1
            self.h2d_bytes += nbytes
            metrics = self._metrics
            pins = dict(self._tenant_pins)
        if metrics is not None:
            metrics.table_h2d_bytes.inc(nbytes)
        # Device-tier ledger (ops/introspect.py): the installed tensor
        # is THE resident_tables allocation — absolute-set keeps the
        # ledger exact across rotation (drop zeroes it, the re-upload
        # sets the new size).
        from tendermint_tpu.ops import introspect

        introspect.set_bytes("resident_tables", nbytes)
        introspect.accountant.set_tenant_bytes(nbytes, pins)
        return True

    @staticmethod
    def _device_put(host_tab: np.ndarray, plan, backend: Optional[str]):
        import jax

        if plan is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            return jax.device_put(
                host_tab,
                NamedSharding(plan.mesh, PartitionSpec(None, None, None, None)),
            )
        dev = jax.local_devices(backend=backend)[0] if backend else None
        if dev is not None:
            return jax.device_put(host_tab, dev)
        return jax.device_put(host_tab)

    def invalidate(self, pubkeys: Iterable[bytes], reason: str = "rotation") -> None:
        """Host cache dropped these keys (``reason``: ``rotation`` or
        ``evict``): the device copy dies with them."""
        keys = [bytes(pk) for pk in pubkeys]
        with self._lock:
            # an evicted key leaves the shard's pinned slice whether or
            # not a device copy exists — the host column is gone
            if any(pk in self._pinned for pk in keys):
                self._pinned.difference_update(keys)
                self._account_host_locked()
            if self._tab_dev is None:
                return
            if not any(pk in self._index for pk in keys):
                return
            self._drop_locked(reason, departed=len(keys))

    def clear(self) -> None:
        with self._lock:
            self._drop_locked("clear")
            self._hot_counts.clear()
            self._pinned.clear()
            self._account_host_locked()

    def _drop_locked(self, reason: str, departed: int = 0) -> None:
        """Forget the device copy, whole. Where there was one, under a
        ``resident_drop`` span: ``keys`` the copy held, ``departed``
        the keys the host cache named, ``reason`` =
        ``rotation|evict|clear``."""
        if self._tab_dev is None:
            self._forget_locked()
            return
        with tracing.span(
            "resident_drop",
            engine="ed25519",
            keys=len(self._index),
            departed=departed,
            reason=reason,
        ):
            self.invalidations += 1
            self._forget_locked()

    def _forget_locked(self) -> None:
        self._index = {}
        self._tab_dev = None
        self._ok_host = None
        self._mesh_key = None
        self._backend_key = None
        self._version += 1
        # the introspect ledger holds its own (leaf) lock, never ours
        from tendermint_tpu.ops import introspect

        introspect.set_bytes("resident_tables", 0)
        introspect.accountant.set_tenant_bytes(0, {})

    def _account_host_locked(self) -> None:
        """Mirror the pinned slice to the introspect ledger under its
        own owner label ("resident_tables_host"): host-staged bytes,
        distinct from the device tensor, so a federation's per-shard
        memstats show the PARTITIONED placement — each shard's entry is
        its slice, and the fleet aggregate grows linearly."""
        from tendermint_tpu.ops import introspect

        introspect.set_bytes(
            "resident_tables_host", len(self._pinned) * TABLE_BYTES_PER_KEY
        )

    # --- lookup -------------------------------------------------------------

    def acquire(
        self,
        pubkeys: Sequence[bytes],
        has_table: np.ndarray,
        plan=None,
        backend: Optional[str] = None,
    ):
        """Resident routing for one batch.

        For lanes with a host-cached table (``has_table``), answers
        which can ride the resident kernel: returns ``(res_mask, idx,
        ok, tab_dev, mesh_key)`` where ``res_mask`` is the (N,) bool
        lane partition, ``idx``/``ok`` are full-length per-lane arrays
        (garbage outside the mask), and ``tab_dev`` is the device
        tensor. Returns None when the resident path is off, empty, or
        uploaded for a different mesh/backend context.
        """
        if not self.enabled(backend):
            return None
        with tracing.span(
            "resident_acquire",
            engine="ed25519",
            lanes=len(pubkeys),
            hits=0,
            misses=0,
        ) as sp:
            return self._acquire(pubkeys, has_table, plan, backend, sp)

    def _acquire(self, pubkeys, has_table, plan, backend, sp):
        n = len(pubkeys)
        want_key = self._context_key(plan, backend)
        # why the device copy cannot serve this batch, if it cannot
        stale = None
        with self._lock:
            if self._tab_dev is None:
                # no copy: none was sent yet, or a drop took it
                stale = "dropped" if self.invalidations else "first"
            elif (self._mesh_key, self._backend_key) != want_key:
                stale = "context"
            else:
                # committee growth: a host-cached key the store has not
                # seen yet means the upload predates it — refresh once
                # so new validators join the resident tensor
                index = self._index
                if any(
                    has_table[i] and bytes(pubkeys[i]) not in index
                    for i in range(n)
                ):
                    stale = "joined"
        if stale:
            if not self.refresh(plan=plan, backend=backend, reason=stale):
                return None
        with self._lock:
            tab_dev = self._tab_dev
            ok_host = self._ok_host
            index = self._index
            if tab_dev is None or (
                (self._mesh_key, self._backend_key) != want_key
            ):
                return None
            idx = np.zeros(n, dtype=np.int32)
            res_mask = np.zeros(n, dtype=bool)
            hits = misses = 0
            for i in range(n):
                if not has_table[i]:
                    continue
                col = index.get(bytes(pubkeys[i]))
                if col is None:
                    misses += 1
                    continue
                idx[i] = col
                res_mask[i] = True
                hits += 1
            self.hits += hits
            self.misses += misses
            metrics = self._metrics
        sp.set(hits=hits, misses=misses)
        if metrics is not None:
            if hits:
                metrics.table_resident_hits.inc(hits)
            if misses:
                metrics.table_resident_misses.inc(misses)
        if not res_mask.any():
            return None
        return res_mask, idx, ok_host, tab_dev, want_key[0]

    # --- verifyd / accounting hooks ----------------------------------------

    def note_hot_keys(
        self,
        pubkeys: Iterable[bytes],
        tenant: Optional[str] = None,
        quota: int = 0,
    ) -> None:
        """Count repeat signers from set-less traffic (verifyd): a key
        seen ``_HOT_PIN_THRESHOLD`` times gets pinned in the host cache
        so it joins the next resident upload.

        ``tenant``/``quota`` cap how many pins one namespace may hold
        (multi-tenant verifyd): past ``quota`` pins, a tenant's further
        hot keys are counted as ``pin_quota_denials`` instead of pinned,
        so one chain's validator universe can't monopolize the resident
        tensor. ``quota=0`` (or no tenant) keeps the unlimited behavior.
        """
        to_pin = []
        with self._lock:
            for pk in pubkeys:
                pk = bytes(pk)
                if len(pk) != 32:
                    continue
                c = self._hot_counts.get(pk, 0) + 1
                if c >= _HOT_PIN_THRESHOLD:
                    if tenant is not None and quota > 0:
                        used = self._tenant_pins.get(tenant, 0)
                        if used >= quota:
                            self.pin_quota_denials += 1
                            self._hot_counts.pop(pk, None)
                            continue
                        self._tenant_pins[tenant] = used + 1
                    self._hot_counts.pop(pk, None)
                    to_pin.append(pk)
                elif len(self._hot_counts) < _HOT_TRACK_CAP:
                    self._hot_counts[pk] = c
            if to_pin:
                self._pinned.update(to_pin)
                self._account_host_locked()
        if to_pin:
            from tendermint_tpu.ops import precompute

            precompute.pin_pubkeys(to_pin)

    def note_table_h2d(self, nbytes: int) -> None:
        """Account a gathered-table (non-resident) per-batch upload."""
        with self._lock:
            self.gathered_h2d_bytes += int(nbytes)
            metrics = self._metrics
        if metrics is not None:
            metrics.table_h2d_bytes.inc(int(nbytes))

    # --- introspection ------------------------------------------------------

    def stats(self) -> Dict[str, float]:
        with self._lock:
            return {
                "resident_keys": len(self._index),
                "hits": self.hits,
                "misses": self.misses,
                "uploads": self.uploads,
                "h2d_bytes": self.h2d_bytes,
                "gathered_h2d_bytes": self.gathered_h2d_bytes,
                "invalidations": self.invalidations,
                "pin_quota_denials": self.pin_quota_denials,
                "pinned_keys": len(self._pinned),
                "host_staged_bytes": len(self._pinned) * TABLE_BYTES_PER_KEY,
            }

    def pinned_keys(self) -> list:
        """Hex identities of this process's pinned slice (sorted). The
        verifyd_fleet bench compares these across shards to prove the
        federation PARTITIONS tables instead of replicating them."""
        with self._lock:
            return sorted(pk.hex() for pk in self._pinned)

    def tenant_pins(self) -> Dict[str, int]:
        """Pins held per tenant namespace (quota introspection)."""
        with self._lock:
            return dict(self._tenant_pins)

    def reset(self) -> None:
        with self._lock:
            self._drop_locked("clear")
            self._hot_counts.clear()
            self._tenant_pins.clear()
            self._pinned.clear()
            self._account_host_locked()
            self.hits = self.misses = self.uploads = 0
            self.h2d_bytes = self.gathered_h2d_bytes = 0
            self.invalidations = 0
            self.pin_quota_denials = 0


# --- process-wide singleton --------------------------------------------------

store = ResidentTableStore()


def _on_cache_event(kind: str, payload: tuple) -> None:
    """precompute.py observer: host invalidation -> device invalidation."""
    if kind in ("rotation", "evict"):
        store.invalidate(payload, reason=kind)
    elif kind == "clear":
        store.clear()


def _install_observer() -> None:
    from tendermint_tpu.ops import precompute

    precompute.register_observer(_on_cache_event)


_install_observer()


def acquire(pubkeys, has_table, plan=None, backend=None):
    return store.acquire(pubkeys, has_table, plan=plan, backend=backend)


def enabled(backend: Optional[str] = None) -> bool:
    return store.enabled(backend)


def configure(mode: Optional[str]) -> None:
    store.configure(mode)


def bind_metrics(metrics) -> None:
    store.bind_metrics(metrics)


def note_hot_keys(
    pubkeys: Iterable[bytes],
    tenant: Optional[str] = None,
    quota: int = 0,
) -> None:
    store.note_hot_keys(pubkeys, tenant=tenant, quota=quota)


def note_table_h2d(nbytes: int) -> None:
    store.note_table_h2d(nbytes)


def note_validator_rotation() -> None:
    """Consensus noticed a validator-set change before the host cache
    did (crypto/batch.note_validator_set): drop the device copy now so
    the next batch re-uploads against the fresh committee."""
    store.clear()


def stats() -> Dict[str, float]:
    return store.stats()


def pinned_keys() -> list:
    return store.pinned_keys()


def reset() -> None:
    store.reset()
