"""Device-tier introspection: memory accounting + continuous profiling.

PR 15 made the *fleet* observable (traces, latency attribution, flight
recorder); this module makes the *device tier* observable. Two units,
one process-wide singleton each:

``DeviceMemAccountant``
    Tracks device-resident bytes by **owner** — ``resident_tables``
    (the installed (8,4,32,K) tensor, exact nbytes, hooked from
    ops/resident.py install/drop), ``resident_tables/<tenant>``
    (pro-rata share from the store's pin table), ``shm_slabs`` (live
    slab-ring segment bytes, hooked from verifyd/shm.py register/
    unregister on both ends), and ``exec_cache`` (compiled-executable
    cache entries, counted not sized — XLA does not expose executable
    HBM footprints, so the entry count + compile counter is the honest
    signal). Mirrored into ``tendermint_ops_device_bytes{owner}`` /
    ``tendermint_ops_compile_events_total{engine}`` when metrics are
    bound, and snapshotted by :func:`memstats` for ``/debug/memstats``,
    ``verifyd stats``, and every flight-recorder dump.

``KernelProfiler``
    A continuous low-overhead profiler fed from the tracer's third
    sink slot (:func:`tendermint_tpu.libs.tracing.Tracer.
    set_profile_sink`): per-(engine, batch-bucket) rolling windows of
    ``dispatch`` (``dispatch_chunk`` spans: the host's time to ENQUEUE
    a chunk — JAX dispatch is asynchronous, so this is not how long
    the kernel runs), ``device_wait`` (``collect_chunk`` spans: the
    host's wait for the chunk's verdicts, which is where the kernel's
    run time shows) and ``compile`` (``kernel_compile`` spans),
    exported as p50/p95/p99 digests in the ``profile`` fragment
    bench/child.py attaches to every section. A kernel's own time on
    the device comes only from a profiler trace (``chipbench``).
    Buckets are power-of-two lane counts only, capped with an
    ``other`` overflow (:func:`bucket_label`), so the metric-label
    cardinality is bounded by construction — tpulint TPM004 audits
    that every ``bucket=`` label site routes through that helper.

Env knob::

    TENDERMINT_TPU_PROFILE   on (default) | off

Everything here fails safe: accounting hooks never raise into the op
that triggered them, and with the profiler off the tracer sink slot
stays None so the hot path pays nothing.
"""

from __future__ import annotations

import functools
import json
import os
import threading
from collections import deque
from typing import Any, Callable, Dict, Optional, Tuple

from tendermint_tpu.libs.sanitizer import instrument_attrs

# Power-of-two lane buckets only: 1, 2, 4, ... up to this cap; larger
# batches collapse into "other". 2^14 covers the largest bench lane
# counts (BENCH_MULTICHIP_LANES=8192) with headroom, for at most
# 15 + 1 label values per engine.
_BUCKET_CAP = 1 << 14
_WINDOW = 512  # rolling samples kept per (engine, bucket) series


def bucket_label(lanes: Any) -> str:
    """The ONE bounded batch-bucket labeler: rounds a lane count up to
    the next power of two, capped at ``other``. Every ``bucket=`` metric
    label and profiler series key must come from here (tpulint TPM004
    enforces the metric-label half), so per-bucket cardinality can
    never exceed 16 values per engine."""
    try:
        n = int(lanes)
    except (TypeError, ValueError):
        return "other"
    if n <= 0:
        return "other"
    b = 1
    while b < n:
        b <<= 1
    if b > _BUCKET_CAP:
        return "other"
    return str(b)


def _percentile(sorted_vals, q: float) -> float:
    """Nearest-rank percentile over an already-sorted list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, int(q * len(sorted_vals))))
    return sorted_vals[idx]


class _Series:
    """One rolling timing window. Not thread-safe on its own; the
    profiler's lock guards every touch."""

    __slots__ = ("samples", "count", "total_s")

    def __init__(self) -> None:
        self.samples: deque = deque(maxlen=_WINDOW)
        self.count = 0
        self.total_s = 0.0

    def add(self, seconds: float) -> None:
        self.samples.append(seconds)
        self.count += 1
        self.total_s += seconds

    def digest(self) -> Dict[str, float]:
        vals = sorted(self.samples)
        return {
            "count": self.count,
            "total_ms": round(self.total_s * 1e3, 3),
            "p50_ms": round(_percentile(vals, 0.50) * 1e3, 4),
            "p95_ms": round(_percentile(vals, 0.95) * 1e3, 4),
            "p99_ms": round(_percentile(vals, 0.99) * 1e3, 4),
        }


@instrument_attrs
class KernelProfiler:
    """Rolling per-(engine, bucket) dispatch (= enqueue), device-wait
    and compile digests.

    Installed as the tracer's profile sink (a third slot beside the
    metrics observer and the flight sink); the sink call is the whole
    hot-path cost: one dict lookup + deque append under a lock, only
    for ``dispatch_chunk`` / ``collect_chunk`` / ``kernel_compile``
    spans. The bench harness keeps it on by default and proves the
    overhead ≤5% in CI.
    """

    # span name -> digest it feeds
    _DIGEST_OF = {
        "dispatch_chunk": "dispatch",
        "collect_chunk": "device_wait",
        "kernel_compile": "compile",
    }

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._series: Dict[str, Dict[Tuple[str, str], _Series]] = {  # guarded-by: _lock
            digest: {} for digest in self._DIGEST_OF.values()
        }
        self._enabled = _env_on()  # guarded-by: none(racy bool read)
        self._metrics = None  # guarded-by: none(racy hot-path read)

    # --- wiring --------------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self._enabled

    def configure(self, mode: Optional[str]) -> None:
        """``on``/``off`` (anything else falls back to the env knob)."""
        if mode == "on":
            self._enabled = True
        elif mode == "off":
            self._enabled = False
        else:
            self._enabled = _env_on()
        _sync_tracer_sink()

    def bind_metrics(self, metrics) -> None:
        self._metrics = metrics

    # --- the tracer sink ------------------------------------------------------

    def sink(self, name: str, args: Dict[str, Any], seconds: float) -> None:
        """(name, args, seconds) for every completed span — same shape
        as the metrics observer. Anything that is not a dispatch,
        collect or compile span returns after one dict probe."""
        digest = self._DIGEST_OF.get(name)
        if digest is None:
            return
        engine = str(args.get("engine", "unknown"))
        bucket = bucket_label(args.get("lanes"))
        key = (engine, bucket)
        with self._lock:
            table = self._series[digest]
            series = table.get(key)
            if series is None:
                series = table[key] = _Series()
            series.add(seconds)
        metrics = self._metrics
        if metrics is not None and name == "dispatch_chunk":
            try:
                metrics.kernel_bucket_seconds.labels(
                    engine=engine, bucket=bucket
                ).observe(seconds)
            except Exception:
                pass  # a broken metrics binding must not fail the dispatch

    # --- export --------------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """The ``profile`` fragment: per-series digests keyed
        ``<engine>/b<bucket>``."""
        with self._lock:
            digests = {
                digest: {k: s.digest() for k, s in table.items()}
                for digest, table in self._series.items()
            }
        out: Dict[str, Any] = {"enabled": self._enabled}
        for digest, table in digests.items():
            out[digest] = {
                "%s/b%s" % key: d for key, d in sorted(table.items())
            }
        return out

    def clear(self) -> None:
        with self._lock:
            for table in self._series.values():
                table.clear()


@instrument_attrs
class DeviceMemAccountant:
    """Process-wide device-resident byte ledger, by owner string.

    Owners are *set*, not incremented, by the subsystems that know the
    exact size (resident table install, shm segment register), so the
    ledger can never drift from the real allocation the way a +=/-=
    pair interleaved with an exception could. Compile events and
    exec-cache entries ride along because they are the same question
    ("what is sitting on the device and why") asked of XLA.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._bytes: Dict[str, int] = {}  # guarded-by: _lock
        self._compiles: Dict[str, int] = {}  # guarded-by: _lock
        self._exec_entries: Dict[str, int] = {}  # guarded-by: _lock
        self._stored: Dict[str, int] = {}  # guarded-by: _lock
        self._metrics = None  # guarded-by: none(racy hot-path read)

    def bind_metrics(self, metrics) -> None:
        """Last binder wins (device_policy.bind_metrics convention);
        re-mirrors the current ledger so a late binding starts true."""
        self._metrics = metrics
        with self._lock:
            snap = dict(self._bytes)
            compiles = dict(self._compiles)
        for owner, n in snap.items():
            self._mirror(owner, n)
        if metrics is not None:
            for engine, c in compiles.items():
                try:
                    metrics.compile_events.labels(engine=engine).inc(0)
                except Exception:
                    pass  # pre-binding counts are cosmetic; never fail bind

    def _mirror(self, owner: str, nbytes: int) -> None:
        metrics = self._metrics
        if metrics is None:
            return
        try:
            metrics.device_bytes.labels(owner=owner).set(nbytes)
        except Exception:
            pass  # accounting must never fail the op that allocated

    # --- byte ledger ----------------------------------------------------------

    def set_bytes(self, owner: str, nbytes: int) -> None:
        """Absolute-set the owner's ledger entry (0 removes it from the
        snapshot but keeps the gauge at 0 so scrapes see the release)."""
        nbytes = max(0, int(nbytes))
        with self._lock:
            if nbytes == 0:
                self._bytes.pop(owner, None)
            else:
                self._bytes[owner] = nbytes
        self._mirror(owner, nbytes)

    def add_bytes(self, owner: str, delta: int) -> None:
        """Delta accounting for owners with many live allocations
        (shm slab segments attach/retire independently)."""
        with self._lock:
            n = max(0, self._bytes.get(owner, 0) + int(delta))
            if n == 0:
                self._bytes.pop(owner, None)
            else:
                self._bytes[owner] = n
        self._mirror(owner, n)

    def bytes_for(self, owner: str) -> int:
        with self._lock:
            return self._bytes.get(owner, 0)

    def set_tenant_bytes(self, total: int, pins: Dict[str, int]) -> None:
        """Pro-rata ``resident_tables/<tenant>`` owners from the pin
        table: pinned columns are the tenant's declared stake in the
        shared tensor. Tenants that lost all pins are zeroed."""
        total = max(0, int(total))
        pinned = sum(pins.values())
        with self._lock:
            stale = [
                o
                for o in self._bytes
                if o.startswith("resident_tables/")
                and o.split("/", 1)[1] not in pins
            ]
        for owner in stale:
            self.set_bytes(owner, 0)
        for tenant, count in pins.items():
            share = total * count // pinned if pinned else 0
            self.set_bytes("resident_tables/%s" % tenant, share)

    # --- compile ledger -------------------------------------------------------

    def note_compile(self, engine: str, entries: Optional[int] = None) -> None:
        """One XLA (re)compile on ``engine``; ``entries`` is the
        caller's current compiled-executable cache size when known."""
        engine = str(engine)
        with self._lock:
            self._compiles[engine] = self._compiles.get(engine, 0) + 1
            if entries is not None:
                self._exec_entries[engine] = int(entries)
        metrics = self._metrics
        if metrics is not None:
            try:
                metrics.compile_events.labels(engine=engine).inc()
            except Exception:
                pass  # accounting must never fail the compiling op

    def set_exec_entries(self, engine: str, entries: int) -> None:
        with self._lock:
            self._exec_entries[str(engine)] = int(entries)

    def note_stored_program(self, outcome: str) -> None:
        """One fetch from ops/kernel_store: a ``hit`` staged a stored
        lowered program, a ``miss`` traced and lowered the kernel."""
        with self._lock:
            self._stored[outcome] = self._stored.get(outcome, 0) + 1

    # --- export --------------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "device_bytes": dict(sorted(self._bytes.items())),
                "device_bytes_total": sum(self._bytes.values()),
                "compile_events": dict(sorted(self._compiles.items())),
                "exec_cache_entries": dict(sorted(self._exec_entries.items())),
                "stored_programs": dict(sorted(self._stored.items())),
            }

    def clear(self) -> None:
        """Test hook: forget everything (gauges are left behind — the
        registry is per-test anyway)."""
        with self._lock:
            self._bytes.clear()
            self._compiles.clear()
            self._exec_entries.clear()
            self._stored.clear()


def _env_on() -> bool:
    return os.environ.get("TENDERMINT_TPU_PROFILE", "on").lower() not in (
        "off",
        "0",
        "false",
    )


accountant = DeviceMemAccountant()
profiler = KernelProfiler()


def _sync_tracer_sink() -> None:
    """Install (or remove) the profiler as the tracer's profile sink so
    a disabled profiler costs the hot path nothing — the tracer's span
    gate returns NOP_SPAN when every sink slot is None."""
    from tendermint_tpu.libs import tracing

    tracing.tracer.set_profile_sink(
        profiler.sink if profiler.enabled else None
    )


def install() -> None:
    """Wire the profiler into the process tracer. Idempotent; called
    from node assembly, verifyd serve, and bench children."""
    _sync_tracer_sink()


def bind_metrics(metrics) -> None:
    accountant.bind_metrics(metrics)
    profiler.bind_metrics(metrics)


def set_bytes(owner: str, nbytes: int) -> None:
    accountant.set_bytes(owner, nbytes)


def add_bytes(owner: str, delta: int) -> None:
    accountant.add_bytes(owner, delta)


def note_compile(engine: str, entries: Optional[int] = None) -> None:
    accountant.note_compile(engine, entries)


def note_stored_program(outcome: str) -> None:
    accountant.note_stored_program(outcome)


def traced_first_call(fn: Callable, engine: str, kernel: str, lanes: int, **span_args):
    """Wrap a freshly jitted callable so its FIRST invocation — the one
    that traces and compiles — runs under a ``kernel_compile`` span
    (feeding the profiler's compile digests; ``span_args`` are further
    arguments of it) and lands one ``note_compile`` tick. Steady-state
    calls pay one bool check. Every kernel factory wraps what it
    jitted in this: the XLA graphs', the Pallas entry points'
    (ops/pallas_verify) and the mesh's (parallel/sharding)."""
    state = {"first": True}

    @functools.wraps(fn)  # keeps the jitted program's name; ``__wrapped__`` is it
    def wrapper(*args, **kwargs):
        if state["first"]:
            state["first"] = False
            from tendermint_tpu.libs import tracing

            note_compile(engine)
            with tracing.tracer.span(
                "kernel_compile", engine=engine, kernel=kernel, lanes=lanes, **span_args
            ):
                return fn(*args, **kwargs)
        return fn(*args, **kwargs)

    return wrapper


def _exec_cache_entries() -> Dict[str, int]:
    """Compiled-executable cache entries per engine, read from the
    factories' lru_cache stats — only for engine modules that are
    already imported (reading must never be the thing that pulls jax
    into a process that wasn't using it)."""
    import sys

    out: Dict[str, int] = {}
    ed = sys.modules.get("tendermint_tpu.ops.ed25519_batch")
    if ed is not None:
        try:
            # the one XLA factory, serving every chunk kind of both engines
            out["xla"] = ed._compiled_kernel.cache_info().currsize
        except Exception:
            pass  # cache introspection is best-effort; report what we can
    pl = sys.modules.get("tendermint_tpu.ops.pallas_verify")
    if pl is not None:
        try:
            out["pallas"] = (
                pl.compiled_verify.cache_info().currsize
                + pl.compiled_verify_tables.cache_info().currsize
                + pl.compiled_verify_resident.cache_info().currsize
            )
        except Exception:
            pass  # cache introspection is best-effort; report what we can
    return out


# --- federation fleet roll-up -------------------------------------------------
# A federated process (verifyd serve --shards, or a node routing to a
# fleet) installs a provider returning per-shard ledger rows; memstats
# then carries a "fleet" section — per-shard device bytes under the
# SAME owner labels as the local ledger, plus the owner-wise aggregate,
# so partitioned vs replicated table placement is visible at a glance
# (each shard's resident_tables entry disjoint => sum grows linearly).

_fleet_mtx = threading.Lock()
_fleet_provider: Optional[Callable[[], Dict[str, Dict[str, Any]]]] = None
_shard_id = -1


def set_shard_identity(shard_id: int) -> None:
    """Stamp this process's federation shard id into memstats (-1 =
    standalone, omitted from the snapshot)."""
    global _shard_id
    with _fleet_mtx:
        _shard_id = int(shard_id)


def shard_identity() -> int:
    with _fleet_mtx:
        return _shard_id


def set_fleet_provider(
    fn: Optional[Callable[[], Dict[str, Dict[str, Any]]]]
) -> None:
    """Install (or clear, with None) the fleet roll-up source: a
    callable returning ``{shard_label: {"device_bytes": {owner: n},
    ...}}`` rows. Must be cheap or internally rate-limited — memstats
    is polled by /debug/memstats and the flight recorder."""
    global _fleet_provider
    with _fleet_mtx:
        _fleet_provider = fn


def fleet_rollup() -> Optional[Dict[str, Any]]:
    """The fleet section, or None when unfederated/unavailable: the
    provider's per-shard rows plus the owner-wise byte aggregate."""
    with _fleet_mtx:
        provider = _fleet_provider
    if provider is None:
        return None
    try:
        rows = provider() or {}
    except Exception:
        return None
    if not isinstance(rows, dict) or not rows:
        return None
    agg: Dict[str, int] = {}
    for row in rows.values():
        if not isinstance(row, dict):
            continue
        owners = row.get("device_bytes")
        if not isinstance(owners, dict):
            continue
        for owner, n in owners.items():
            try:
                agg[owner] = agg.get(owner, 0) + int(n)
            except (TypeError, ValueError):
                continue
    return {
        "shards": rows,
        "aggregate_bytes": dict(sorted(agg.items())),
        "aggregate_total": sum(agg.values()),
    }


def memstats() -> Dict[str, Any]:
    """The full device-tier snapshot: the accountant's ledger, the
    resident store's own counters (so byte claims are cross-checkable
    against uploads), and the profiler digests. This is the payload of
    ``GET /debug/memstats``, the ``verifyd stats`` memstats field, and
    the flight-recorder ``memstats`` section. Federated processes grow
    a ``fleet`` section (per-shard rows + owner-wise aggregate) and a
    ``shard_id`` stamp."""
    out = accountant.snapshot()
    sid = shard_identity()
    if sid >= 0:
        out["shard_id"] = sid
    fleet = fleet_rollup()
    if fleet is not None:
        out["fleet"] = fleet
    live = _exec_cache_entries()
    if live:
        merged = dict(out.get("exec_cache_entries", {}))
        merged.update(live)
        out["exec_cache_entries"] = dict(sorted(merged.items()))
    try:
        from tendermint_tpu.ops import resident

        out["resident"] = resident.stats()
    except Exception:
        out["resident"] = {}
    out["profile"] = profiler.snapshot()
    return out


def memstats_json(limit_bytes: Optional[int] = None) -> str:
    """Serialized memstats, optionally size-bounded: when the compact
    JSON exceeds ``limit_bytes`` the profiler digests are dropped
    first, then the snapshot collapses to totals — callers with a hard
    budget (the flight recorder's atomic dump) always get *something*
    that fits."""
    doc = memstats()
    blob = json.dumps(doc, sort_keys=True, separators=(",", ":"))
    if limit_bytes is None or len(blob) <= limit_bytes:
        return blob
    doc.pop("profile", None)
    blob = json.dumps(doc, sort_keys=True, separators=(",", ":"))
    if len(blob) <= limit_bytes:
        return blob
    slim = {
        "device_bytes_total": doc.get("device_bytes_total", 0),
        "truncated": True,
    }
    return json.dumps(slim, sort_keys=True, separators=(",", ":"))
