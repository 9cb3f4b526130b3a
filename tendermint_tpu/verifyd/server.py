"""verifyd server: one shared scheduler, many tenants, many connections.

The daemon owns the accelerator and serves batched verification over
the zero-dependency gRPC transport. Every connection's lanes funnel
into ONE ``VerifyScheduler`` per algorithm, so batches form ACROSS
clients — a lone light client's header check rides the same device
launch as a validator's commit flood. Scheduling behavior:

- continuous batching: the scheduler's dispatch workers overlap batch
  prep with the in-flight kernel (``crypto/scheduler.py``), so newly
  arrived lanes join the NEXT dispatch instead of waiting behind a
  flush barrier; ``verifyd_dispatch_occupancy`` observes the pipeline
  depth at every hand-off;
- deadline-aware flush: each lane carries ``flush_by`` derived from the
  request's wire deadline (minus a respond margin), so the accumulator
  flushes early rather than letting a lane's deadline expire in queue;
- priority-ordered dequeue: when more lanes are pending than one batch
  holds, consensus < blocksync < light/rpc decides who flushes first;
- multi-tenant namespaces: requests carry a tenant/chain id
  (``protocol`` field 6; absent = ``default``). Admission budgets,
  resident-table pin quotas, and ``tendermint_verifyd_*{tenant=...}``
  metrics are kept per tenant, so one chain's spike exhausts its own
  budget, not the fleet's. Label cardinality is bounded: at most
  ``max_tenants`` distinct labels; later tenants collapse into
  ``other`` (one shared budget bucket);
- admission control: ``light``/``rpc`` requests are shed with an
  explicit RESOURCE_EXHAUSTED response — never a silent drop — when
  the tenant budget, queue depth, or estimated service time exceeds
  budget. ``consensus``/``blocksync`` are never shed by admission
  (losing them stalls the chain, not just a reader); they land in the
  scheduler's own ``max_pending`` backstop instead;
- per-tenant SLO budgets: a tenant may declare a p99 latency target
  (``--tenant-slo name=ms`` server-side, or protocol field 8 from the
  client — the tightest wins, operator config beats the wire). The
  server keeps a bounded sketch of each tenant's attributed latency
  (the same wall the stage vector tiles) and, on a sustained p99
  breach, sheds that tenant's sheddable classes — scoped to the
  tenant, BEFORE the load-based ladder moves — releasing on the same
  hysteresis-clock shape the ladder uses;
- adaptive serving: schedulers run with deadline-aware dynamic
  batching (``crypto/adaptive.py``) unless ``TENDERMINT_TPU_DYN_BATCH=off``
  (or ``dyn_batch=False``) pins the static config; ``stats()`` reports
  the knobs actually in force under ``"scheduler"``.

Brownout ladder (the documented degradation contract, see README):
under SUSTAINED overload — or device COOLDOWN — the server walks an
explicit ladder, one rung per ``escalate_after`` of continuous
pressure, back down one rung per ``recover_after`` of calm:

    0 normal          everything admitted (per-tenant budgets apply)
    1 shed_rpc        rpc requests shed (brownout)
    2 shed_light      + light shed
    3 shed_blocksync  + blocksync shed
    4 shrink_shares   per-tenant budgets shrink to 1/4; consensus past
                      a tenant's shrunken dispatch share verifies on
                      the HOST oracle instead of the device
    5 host_consensus  ALL consensus verifies host-direct (the device is
                      out of the loop, e.g. COOLDOWN); everything else
                      sheds

Consensus is NEVER shed at any rung — its worst case is the host
oracle, which is slower but sound (same ZIP-215 ground truth).
"""

from __future__ import annotations

import hashlib
import json
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto.adaptive import dyn_batch_default
from tendermint_tpu.crypto.scheduler import (
    DEFAULT_PIPELINE_DEPTH,
    SchedulerSaturatedError,
    VerifyScheduler,
)
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.grpc import GrpcServer, current_conn_tag
from tendermint_tpu.libs.sanitizer import instrument_attrs
from tendermint_tpu.libs.metrics import VerifydMetrics
from tendermint_tpu.verifyd import protocol
from tendermint_tpu.verifyd import shm as shm_transport
from tendermint_tpu.verifyd.protocol import (
    ALGO_ED25519,
    ALGO_SR25519,
    CLASS_BLOCKSYNC,
    CLASS_CONSENSUS,
    CLASS_LIGHT,
    CLASS_NAMES,
    CLASS_RPC,
    DEFAULT_TENANT,
    KIND_NAMES,
    SHEDDABLE_CLASSES,
    STATUS_DEADLINE_EXCEEDED,
    STATUS_INTERNAL,
    STATUS_INVALID,
    STATUS_NAMES,
    STATUS_OK,
    STATUS_RESOURCE_EXHAUSTED,
    STATS_PATH,
    VERIFY_PATH,
)

DEFAULT_ADMISSION_CAP = 1024  # pending-lane ceiling for sheddable classes
DEFAULT_MAX_PENDING = 4096  # hard scheduler cap (all classes)
DEFAULT_SERVICE_BUDGET = 0.5  # seconds of estimated queue service time
DEFAULT_WAIT = 10.0  # verdict wait for requests without a deadline
DEFAULT_TENANT_CAP = 512  # outstanding sheddable lanes per tenant
DEFAULT_PIN_QUOTA = 256  # resident-table pins per tenant
DEFAULT_MAX_TENANTS = 16  # distinct tenant label/budget buckets
_EWMA_ALPHA = 0.2
_SHRINK_DIVISOR = 4  # tenant share divisor at the shrink_shares rung

# --- per-tenant SLO budgets --------------------------------------------------
# A tenant may declare a p99 latency target (``--tenant-slo name=ms`` or
# protocol field 8). The server keeps a bounded ring of attributed
# server-side latencies per tenant (the same wall the stage vector
# tiles) and, when the tenant's p99 drifts past its target for
# ``slo_breach_after`` seconds, sheds that tenant's SHEDDABLE classes
# scoped to the tenant — BEFORE the load-based brownout ladder would
# move, and without touching any other tenant. Release rides the same
# hysteresis-clock shape as the ladder: after ``slo_recover_after`` of
# shedding the gate opens and the sample ring resets, so the verdict on
# re-breach comes from fresh post-recovery samples, not the stale storm.
SLO_BREACH_AFTER = 0.25  # sustained p99 breach before the scoped shed
SLO_RECOVER_AFTER = 1.0  # shed dwell before release (ring resets)
_SLO_RING = 512  # latency samples kept per tenant
_SLO_RECOMPUTE = 16  # recompute the cached p99 every N samples
_SLO_MIN_SAMPLES = 20  # no verdicts from a cold sketch

# --- brownout ladder ---------------------------------------------------------

LEVEL_NORMAL = 0
LEVEL_SHED_RPC = 1
LEVEL_SHED_LIGHT = 2
LEVEL_SHED_BLOCKSYNC = 3
LEVEL_SHRINK_SHARES = 4
LEVEL_HOST_CONSENSUS = 5
LEVEL_NAMES = {
    LEVEL_NORMAL: "normal",
    LEVEL_SHED_RPC: "shed_rpc",
    LEVEL_SHED_LIGHT: "shed_light",
    LEVEL_SHED_BLOCKSYNC: "shed_blocksync",
    LEVEL_SHRINK_SHARES: "shrink_shares",
    LEVEL_HOST_CONSENSUS: "host_consensus",
}
# the declared shed order: rpc first, light next, blocksync last;
# consensus has NO entry — no rung ever sheds it
_CLASS_SHED_LEVEL = {
    CLASS_RPC: LEVEL_SHED_RPC,
    CLASS_LIGHT: LEVEL_SHED_LIGHT,
    CLASS_BLOCKSYNC: LEVEL_SHED_BLOCKSYNC,
}


def level_sheds_class(level: int, klass: int) -> bool:
    """True when the ladder rung ``level`` sheds priority class
    ``klass``. Consensus is never shed at any level."""
    at = _CLASS_SHED_LEVEL.get(klass)
    return at is not None and level >= at


def _device_cooling() -> bool:
    """Process-wide device health says the accelerator is cooling down
    (or terminally disabled): pin the ladder at host_consensus."""
    try:
        from tendermint_tpu.ops.device_policy import (
            COOLDOWN,
            DISABLED,
            shared,
        )

        return shared.state in (COOLDOWN, DISABLED)
    except Exception:
        # health machinery unavailable (host-only build): never escalate
        return False


@instrument_attrs
class BrownoutController:
    """Walks the degradation ladder on sustained pressure.

    Fed one boolean load sample per request (``observe``): pressure
    sustained for ``escalate_after`` seconds climbs one rung (and
    restarts the clock); calm sustained for ``recover_after`` descends
    one. ``cooldown_fn`` (default: the process-wide device health
    machine) pins the EFFECTIVE level at host_consensus while the
    device is in COOLDOWN/DISABLED, regardless of load. ``force``
    overrides the level outright (tests, operator override).
    """

    def __init__(
        self,
        escalate_after: float = 0.25,
        recover_after: float = 1.0,
        cooldown_fn: Optional[Callable[[], bool]] = _device_cooling,
    ):
        self.escalate_after = escalate_after
        self.recover_after = recover_after
        self._cooldown_fn = cooldown_fn
        self._mtx = threading.Lock()
        self._level = LEVEL_NORMAL  # guarded-by: _mtx
        self._forced: Optional[int] = None  # guarded-by: _mtx
        self._pressure_since: Optional[float] = None  # guarded-by: _mtx
        self._calm_since: Optional[float] = None  # guarded-by: _mtx
        self.transitions = {"up": 0, "down": 0}  # guarded-by: _mtx

    def force(self, level: Optional[int]) -> None:
        """Pin the effective level (None releases the pin)."""
        with self._mtx:
            self._forced = level

    @property
    def level(self) -> int:
        """The organic (load-driven) level, ignoring force/cooldown."""
        with self._mtx:
            return self._level

    def effective(self) -> int:
        with self._mtx:
            return self._effective_locked()

    def _effective_locked(self) -> int:
        lvl = self._level if self._forced is None else self._forced
        if self._cooldown_fn is not None:
            try:
                cooling = self._cooldown_fn()
            except Exception:
                cooling = False  # a broken probe must not change policy
            if cooling:
                lvl = max(lvl, LEVEL_HOST_CONSENSUS)
        return lvl

    def snapshot(self) -> dict:
        """Locked view of the ladder state for monitors and tests —
        reading ``transitions`` raw races every in-flight ``observe``."""
        with self._mtx:
            return {
                "level": self._level,
                "forced": self._forced,
                "effective": self._effective_locked(),
                "transitions": dict(self.transitions),
            }

    def observe(
        self, pressure: bool, now: Optional[float] = None
    ) -> Tuple[int, int]:
        """Feed one load sample; returns ``(effective_level, delta)``
        where delta is +1/-1 when this sample moved the organic level."""
        now = time.monotonic() if now is None else now
        delta = 0
        with self._mtx:
            if pressure:
                self._calm_since = None
                if self._pressure_since is None:
                    self._pressure_since = now
                elif (
                    now - self._pressure_since >= self.escalate_after
                    and self._level < LEVEL_HOST_CONSENSUS
                ):
                    self._level += 1
                    self.transitions["up"] += 1
                    self._pressure_since = now
                    delta = 1
            else:
                self._pressure_since = None
                if self._level == LEVEL_NORMAL:
                    self._calm_since = None
                elif self._calm_since is None:
                    self._calm_since = now
                elif now - self._calm_since >= self.recover_after:
                    self._level -= 1
                    self.transitions["down"] += 1
                    self._calm_since = now
                    delta = -1
            return self._effective_locked(), delta


# --- tenants -----------------------------------------------------------------

TENANT_OVERFLOW_LABEL = "other"


def sanitize_tenant_label(name: str) -> str:
    """Metrics-safe tenant label: alnum/dash/underscore/dot, max 32
    chars. Names that don't survive sanitization intact become a stable
    hash so distinct ugly ids don't collide with each other."""
    safe = "".join(c for c in name if c.isalnum() or c in "-_.")[:32]
    if safe == name and safe:
        return safe
    return "t" + hashlib.sha1(name.encode("utf-8")).hexdigest()[:8]


class _TenantState:
    """Per-tenant accounting. All fields guarded by the server's
    ``_tenant_mtx`` (one lock for the whole registry: tenant counts are
    bounded and the critical sections are tiny)."""

    __slots__ = (
        "label", "depth", "lanes", "sheds", "host_direct",
        "slo_ms", "slo_pinned", "lat_ring", "lat_idx", "lat_new",
        "p99", "slo_breach_since", "slo_shed_since", "slo_shedding",
        "slo_sheds",
    )

    def __init__(self, label: str):
        self.label = label
        self.depth = 0  # outstanding (admitted, unresolved) lanes
        self.lanes = 0  # total lanes admitted
        self.sheds = 0  # total requests shed
        self.host_direct = 0  # lanes verified on the host oracle
        # SLO budget: declared p99 target (0 = none) and the bounded
        # attributed-latency sketch that polices it
        self.slo_ms = 0  # declared p99 target; 0 = no SLO
        self.slo_pinned = False  # server-config target beats the wire's
        self.lat_ring: List[float] = []  # bounded latency samples (s)
        self.lat_idx = 0  # ring write cursor
        self.lat_new = 0  # samples since the last p99 recompute
        self.p99 = 0.0  # cached ring p99 (seconds)
        self.slo_breach_since: Optional[float] = None
        self.slo_shed_since: Optional[float] = None
        self.slo_shedding = False
        self.slo_sheds = 0  # requests shed by the SLO gate


# --- admission ---------------------------------------------------------------


def _introspect_bytes() -> Dict[str, int]:
    """Device-byte ledger for stats(); never fails the stats call."""
    try:
        from tendermint_tpu.ops import introspect

        return introspect.accountant.snapshot()["device_bytes"]
    except Exception:
        return {}


def _introspect_compiles() -> Dict[str, int]:
    try:
        from tendermint_tpu.ops import introspect

        return introspect.accountant.snapshot()["compile_events"]
    except Exception:
        return {}


def _default_sr25519_verify(pks, msgs, sigs) -> List[bool]:
    """Tiered sr25519 dispatch, mirroring the ed25519 policy."""
    if len(pks) < crypto_batch.DEVICE_THRESHOLD:
        return _host_sr25519_verify(pks, msgs, sigs)
    from tendermint_tpu.ops.sr25519_batch import verify_batch_sr

    return list(verify_batch_sr(pks, msgs, sigs))


def _host_sr25519_verify(pks, msgs, sigs) -> List[bool]:
    from tendermint_tpu.crypto.sr25519 import verify as sr_verify

    return [sr_verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)]


@instrument_attrs
class AdmissionController:
    """Sheds sheddable-class load when the queue is past budget.

    Two trip-wires, both checked at enqueue time: unresolved depth past
    ``cap`` lanes, or estimated service time for the queue (EWMA
    per-lane flush cost x depth) past ``service_budget`` seconds. The
    estimate learns from real flushes via ``observe_flush``.
    """

    def __init__(
        self,
        cap: int = DEFAULT_ADMISSION_CAP,
        service_budget: float = DEFAULT_SERVICE_BUDGET,
    ):
        self.cap = cap
        self.service_budget = service_budget
        self._lane_ewma = 0.0  # seconds per lane, learned  # guarded-by: _mtx
        self._mtx = threading.Lock()

    def observe_flush(self, lanes: int, seconds: float) -> None:
        if lanes <= 0 or seconds <= 0:
            return
        per_lane = seconds / lanes
        with self._mtx:
            if self._lane_ewma == 0.0:
                self._lane_ewma = per_lane
            else:
                self._lane_ewma += _EWMA_ALPHA * (per_lane - self._lane_ewma)

    def estimated_service_time(self, depth: int) -> float:
        with self._mtx:
            return depth * self._lane_ewma

    def pressure(self, depth: int) -> bool:
        """Load sample for the brownout controller: is the queue past
        either budget right now?"""
        if depth > self.cap:
            return True
        return self.estimated_service_time(depth) > self.service_budget

    def admit(self, klass: int, lanes: int, depth: int) -> Optional[str]:
        """None = admitted; else the shed reason. Only sheddable
        classes (light/rpc) are ever refused here."""
        if klass not in SHEDDABLE_CLASSES:
            return None
        if depth + lanes > self.cap:
            return "queue_depth"
        if self.estimated_service_time(depth + lanes) > self.service_budget:
            return "service_time"
        return None


@instrument_attrs
class VerifydServer:
    """The verification daemon. ``verify_fn`` defaults to the tiered
    host/device ed25519 dispatch; tests inject a host oracle."""

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        max_batch: Optional[int] = None,
        max_delay: float = 0.002,
        admission_cap: int = DEFAULT_ADMISSION_CAP,
        max_pending: int = DEFAULT_MAX_PENDING,
        service_budget: float = DEFAULT_SERVICE_BUDGET,
        verify_fn: Optional[Callable[..., List[bool]]] = None,
        sr25519_verify_fn: Optional[Callable[..., List[bool]]] = None,
        metrics: Optional[VerifydMetrics] = None,
        evloop_metrics=None,
        continuous: Optional[bool] = None,
        pipeline_depth: int = DEFAULT_PIPELINE_DEPTH,
        tenant_cap: int = DEFAULT_TENANT_CAP,
        tenant_pin_quota: int = DEFAULT_PIN_QUOTA,
        max_tenants: int = DEFAULT_MAX_TENANTS,
        brownout: Optional[BrownoutController] = None,
        shm: Optional[str] = None,
        dyn_batch: Optional[bool] = None,
        tenant_slos: Optional[Dict[str, int]] = None,
        slo_breach_after: float = SLO_BREACH_AFTER,
        slo_recover_after: float = SLO_RECOVER_AFTER,
        shard_id: int = -1,
    ):
        self.metrics = metrics or VerifydMetrics.nop()
        # federation identity: -1 = standalone (pre-federation wire
        # behaviour: response field 6 is omitted entirely)
        self.shard_id = int(shard_id)
        if self.shard_id > protocol.MAX_SHARD_ID:
            raise ValueError(f"shard id too large: {self.shard_id}")
        self.max_delay = max_delay
        self.admission = AdmissionController(admission_cap, service_budget)
        self.brownout = brownout or BrownoutController()
        self.tenant_cap = tenant_cap
        self.tenant_pin_quota = tenant_pin_quota
        self.max_tenants = max(1, max_tenants)
        self.slo_breach_after = slo_breach_after
        self.slo_recover_after = slo_recover_after
        # None = env default: the serving tier is adaptive unless
        # TENDERMINT_TPU_DYN_BATCH=off pins the static scheduler
        self.dyn_batch = (
            dyn_batch_default() if dyn_batch is None else bool(dyn_batch)
        )
        # What this process got, for anyone looking from outside
        # (stats(), the CLI banner): filled by start() when the server
        # verifies on the real engine. An injected verify_fn (tests,
        # modeled bench shards) never touches a backend — and must not:
        # a chip belongs to one process — so it reports None.
        self._owns_engine = verify_fn is None
        self._device: Optional[Dict[str, object]] = None
        self._verify_fns = {
            ALGO_ED25519: (
                verify_fn or crypto_batch.tiered_verify_ed25519,
                crypto_batch.host_verify_ed25519,
            ),
            ALGO_SR25519: (
                sr25519_verify_fn or _default_sr25519_verify,
                _host_sr25519_verify,
            ),
        }
        # None = mesh-aware default, resolved LAZILY by the scheduler
        # against the mesh config generation — a server built before
        # MeshManager.configure() no longer bakes the pre-config device
        # count into max_batch (the stale-default fix).
        self._sched_args = dict(
            max_batch=max_batch,
            max_delay=max_delay,
            max_pending=max_pending,
            continuous=continuous,
            pipeline_depth=pipeline_depth,
            dyn_batch=self.dyn_batch,
        )
        self._schedulers: Dict[int, VerifyScheduler] = {}  # guarded-by: _sched_mtx
        self._sched_mtx = threading.Lock()
        self._depth_mtx = threading.Lock()
        self._class_depth: Dict[int, int] = {}  # guarded-by: _depth_mtx
        self._tenant_mtx = threading.Lock()
        self._tenants: Dict[str, _TenantState] = {}  # guarded-by: _tenant_mtx
        # plain counters for tests and bench (metrics-free introspection).
        # Handler threads and the schedulers' dispatch threads all write
        # these, so they take their own mutex.
        self._stats_mtx = threading.Lock()
        self.cross_client_flushes: Dict[str, int] = {
            "size": 0, "deadline": 0, "shutdown": 0,
        }  # guarded-by: _stats_mtx
        self.admission_rejections = 0  # guarded-by: _stats_mtx
        self.deadline_expired = 0  # guarded-by: _stats_mtx
        self.requests_served = 0  # guarded-by: _stats_mtx
        self.host_direct_lanes = 0  # guarded-by: _stats_mtx
        self.shm_lanes = 0  # guarded-by: _stats_mtx
        self.shm_torn_slabs = 0  # guarded-by: _stats_mtx
        self.shm_fallbacks = 0  # guarded-by: _stats_mtx
        # requests stamped for a DIFFERENT shard (stale client shard
        # map); served anyway — routing is placement advice, not an
        # authorization boundary — but counted so operators see churn
        self.misroutes = 0  # guarded-by: _stats_mtx
        self.route_epoch_seen = 0  # guarded-by: _stats_mtx
        self._evloop_metrics = evloop_metrics
        # zero-copy ingress: the slab-ring endpoint starts beside the
        # TCP listener unless the mode (param beats config/env) is off
        self._shm_mode = shm if shm is not None else shm_transport.shm_mode()
        if self._shm_mode not in ("auto", "on", "off"):
            raise ValueError(f"bad shm mode {self._shm_mode!r}")
        # _shm_endpoint is published by start() and retired by stop()
        # while handler threads read it per-request; _shm_mtx guards the
        # reference (methods on a snapshot are called outside the lock)
        self._shm_mtx = threading.Lock()
        self._shm_endpoint: Optional[shm_transport.ShmEndpoint] = None
        self._grpc = GrpcServer(
            {VERIFY_PATH: self._handle, STATS_PATH: self._handle_stats},
            host, port,
            evloop_metrics=evloop_metrics,
        )
        # operator-declared p99 targets (--tenant-slo name=ms): pinned,
        # so a wire-declared target (protocol field 8) never loosens them
        for name, slo_ms in (tenant_slos or {}).items():
            ts = self._tenant_for(name)
            with self._tenant_mtx:
                ts.slo_ms = max(0, int(slo_ms))
                ts.slo_pinned = True

    # --- lifecycle ----------------------------------------------------------

    @property
    def address(self) -> Tuple[str, int]:
        return self._grpc.address

    @property
    def max_batch(self) -> int:
        """Resolved size-flush threshold (mesh-aware when defaulted) —
        delegated to the scheduler, which re-resolves the mesh-aware
        default whenever the mesh configuration generation moves."""
        return self.scheduler.max_batch

    @property
    def scheduler(self) -> VerifyScheduler:
        """The ed25519 scheduler (the common case; tests poke it)."""
        return self._scheduler_for(ALGO_ED25519)

    def start(self) -> None:
        if self._owns_engine:
            # Take the device now, not at the first device-worthy batch:
            # a daemon that cannot get its backend fails to start, and
            # one that came up on the CPU says so in stats().
            from tendermint_tpu.ops import backend as ops_backend

            self._device = ops_backend.device_identity()
        self._scheduler_for(ALGO_ED25519)  # eager: first request is hot
        self._grpc.start()
        with self._shm_mtx:
            want_shm = self._shm_mode != "off" and self._shm_endpoint is None
        if want_shm:
            ep = shm_transport.ShmEndpoint(
                self._serve,
                metrics=self.metrics,
                evloop_metrics=self._evloop_metrics,
                on_stat=self._shm_stat,
            )
            try:
                ep.start(self.address[1])
            except OSError:
                # no AF_UNIX / unwritable tempdir: TCP-only serving is
                # strictly correct, so degrade instead of failing start
                self._shm_stat("shm_fallbacks", 1)
                ep = None
            with self._shm_mtx:
                self._shm_endpoint = ep

    def stop(self) -> None:
        self._grpc.stop()
        # doorbells close before the schedulers so no NEW slab drains
        # race scheduler teardown; drains already in flight resolve
        # against the shutdown flush below
        with self._shm_mtx:
            ep, self._shm_endpoint = self._shm_endpoint, None
        if ep is not None:
            ep.stop()
        with self._sched_mtx:
            scheds, self._schedulers = dict(self._schedulers), {}
        for sched in scheds.values():
            sched.stop()

    @property
    def shm_socket_path(self) -> str:
        """Doorbell socket path when the shm endpoint is live ('' when
        negotiation is off or the endpoint failed to start)."""
        with self._shm_mtx:
            ep = self._shm_endpoint
        return ep.socket_path if ep is not None else ""

    def shm_backlog(self) -> int:
        """Lanes committed to slab rings but not yet in the scheduler —
        added to ``load_depth`` so admission and the brownout ladder see
        shm pressure exactly like TCP pressure."""
        with self._shm_mtx:
            ep = self._shm_endpoint
        return ep.backlog_lanes() if ep is not None else 0

    def _shm_stat(self, field: str, n: int) -> None:
        with self._stats_mtx:
            setattr(self, field, getattr(self, field) + n)

    def _scheduler_for(self, algo: int) -> VerifyScheduler:
        with self._sched_mtx:
            sched = self._schedulers.get(algo)
            if sched is None:
                verify_fn, fallback_fn = self._verify_fns[algo]
                sched = VerifyScheduler(
                    verify_fn,
                    fallback_fn=fallback_fn,
                    on_flush=(
                        lambda reason, batch, seconds, _algo=algo: (
                            self._on_flush(reason, batch, seconds, _algo)
                        )
                    ),
                    on_dispatch=self._on_dispatch,
                    **self._sched_args,
                )
                sched.start()
                self._schedulers[algo] = sched
            return sched

    # --- tenants ------------------------------------------------------------

    def _tenant_for(self, name: str) -> _TenantState:
        """Registry lookup with bounded cardinality: once
        ``max_tenants`` distinct states exist, every UNSEEN tenant maps
        to one shared ``other`` bucket (label and budget both)."""
        with self._tenant_mtx:
            ts = self._tenants.get(name)
            if ts is not None:
                return ts
            distinct = len(set(id(t) for t in self._tenants.values()))
            if distinct >= self.max_tenants:
                ts = self._tenants.get(TENANT_OVERFLOW_LABEL)
                if ts is None:
                    ts = _TenantState(TENANT_OVERFLOW_LABEL)
                    self._tenants[TENANT_OVERFLOW_LABEL] = ts
            else:
                ts = _TenantState(sanitize_tenant_label(name))
            self._tenants[name] = ts
            return ts

    def stats(self) -> Dict[str, object]:
        """Locked snapshot of the wire counters. Handler threads write
        these under ``_stats_mtx`` while requests are in flight, so live
        monitors (tests polling mid-run, bench sections) must read here
        — a raw attribute read races the serving path even after a
        client got its response, because the TCP round-trip is not a
        synchronization edge the counters ride on."""
        with self._shm_mtx:
            ep = self._shm_endpoint
        # resolved scheduler knobs (the config actually under test):
        # snapshot the LIVE scheduler if one exists — stats() must not
        # resurrect a scheduler after stop()
        with self._sched_mtx:
            sched = self._schedulers.get(ALGO_ED25519)
        knobs = sched.resolved_knobs() if sched is not None else None
        with self._stats_mtx:
            return {
                "shard_id": self.shard_id,
                # platform / device kind / device count as jax reported
                # them at start(); None when no real engine is attached
                "device": self._device,
                "misroutes": self.misroutes,
                "route_epoch_seen": self.route_epoch_seen,
                "requests_served": self.requests_served,
                "admission_rejections": self.admission_rejections,
                "deadline_expired": self.deadline_expired,
                "host_direct_lanes": self.host_direct_lanes,
                "cross_client_flushes": dict(self.cross_client_flushes),
                "shm_lanes": self.shm_lanes,
                "shm_torn_slabs": self.shm_torn_slabs,
                "shm_fallbacks": self.shm_fallbacks,
                "shm_sessions": ep.session_count() if ep is not None else 0,
                "scheduler": knobs,
                # device-tier ledger (ops/introspect.py): resident /
                # slab bytes by owner + compile counters, so `verifyd
                # stats` answers "what is sitting on the device" too
                "device_bytes": _introspect_bytes(),
                "compile_events": _introspect_compiles(),
            }

    def tenant_stats(self) -> Dict[str, Dict[str, int]]:
        """Per-label accounting snapshot (bench/chaos introspection)."""
        out: Dict[str, Dict[str, int]] = {}
        with self._tenant_mtx:
            for ts in self._tenants.values():
                if ts.label not in out:
                    out[ts.label] = {
                        "depth": ts.depth,
                        "lanes": ts.lanes,
                        "sheds": ts.sheds,
                        "host_direct": ts.host_direct,
                        "slo_ms": ts.slo_ms,
                        "slo_sheds": ts.slo_sheds,
                        "slo_shedding": ts.slo_shedding,
                        "p99_ms": round(ts.p99 * 1000.0, 3),
                    }
        return out

    def _tenant_shed(self, ts: _TenantState, reason: str) -> None:
        with self._tenant_mtx:
            ts.sheds += 1
        self.metrics.tenant_rejections.labels(
            tenant=ts.label, reason=reason
        ).inc()

    def _tenant_admit(self, ts: _TenantState, n: int) -> None:
        with self._tenant_mtx:
            ts.depth += n
            ts.lanes += n
            depth = ts.depth
        self.metrics.tenant_lanes.labels(tenant=ts.label).inc(n)
        self.metrics.tenant_queue_depth.labels(tenant=ts.label).set(depth)

    def _tenant_release(self, ts: _TenantState, n: int) -> None:
        with self._tenant_mtx:
            ts.depth = max(0, ts.depth - n)
            depth = ts.depth
        self.metrics.tenant_queue_depth.labels(tenant=ts.label).set(depth)

    def _tenant_budget(self, level: int) -> int:
        """Effective per-tenant outstanding-lane budget at this rung."""
        if level >= LEVEL_SHRINK_SHARES:
            return max(1, self.tenant_cap // _SHRINK_DIVISOR)
        return self.tenant_cap

    # --- per-tenant SLO budgets ---------------------------------------------

    def _tenant_declare_slo(self, ts: _TenantState, slo_ms: int) -> None:
        """Wire-declared target (protocol field 8): adopted unless the
        operator pinned one via --tenant-slo; the TIGHTEST wire value
        wins so one lax client can't loosen its tenant's budget."""
        if slo_ms <= 0:
            return
        with self._tenant_mtx:
            if ts.slo_pinned:
                return
            if ts.slo_ms == 0 or slo_ms < ts.slo_ms:
                ts.slo_ms = slo_ms

    def _tenant_observe_latency(
        self, ts: _TenantState, seconds: float, now: Optional[float] = None
    ) -> None:
        """Fold one attributed server-side latency (the wall the stage
        vector tiles) into the tenant's sketch and run the breach
        hysteresis. ``now`` is injectable for synthetic-clock tests."""
        now = time.monotonic() if now is None else now
        with self._tenant_mtx:
            if len(ts.lat_ring) < _SLO_RING:
                ts.lat_ring.append(seconds)
            else:
                ts.lat_ring[ts.lat_idx] = seconds
                ts.lat_idx = (ts.lat_idx + 1) % _SLO_RING
            ts.lat_new += 1
            if ts.lat_new >= _SLO_RECOMPUTE or ts.p99 == 0.0:
                ts.lat_new = 0
                ordered = sorted(ts.lat_ring)
                ts.p99 = ordered[max(0, int(len(ordered) * 0.99) - 1)]
            if ts.slo_ms <= 0 or ts.slo_shedding:
                return
            if (
                len(ts.lat_ring) >= _SLO_MIN_SAMPLES
                and ts.p99 > ts.slo_ms / 1000.0
            ):
                if ts.slo_breach_since is None:
                    ts.slo_breach_since = now
                elif now - ts.slo_breach_since >= self.slo_breach_after:
                    # sustained breach: tenant-scoped brownout, BEFORE
                    # the load-based ladder has any reason to move
                    ts.slo_shedding = True
                    ts.slo_shed_since = now
                    ts.slo_breach_since = None
                    tracing.instant(
                        "verifyd_tenant_slo_breach",
                        tenant=ts.label,
                        p99_ms=round(ts.p99 * 1000.0, 3),
                        slo_ms=ts.slo_ms,
                    )
            else:
                ts.slo_breach_since = None

    def _tenant_slo_gate(
        self, ts: _TenantState, now: Optional[float] = None
    ) -> bool:
        """True while the tenant's sheddable classes are SLO-shed.
        Release is the existing hysteresis-clock shape: after
        ``slo_recover_after`` of shedding the gate opens and the sample
        ring resets, so re-breach verdicts come from fresh samples."""
        now = time.monotonic() if now is None else now
        with self._tenant_mtx:
            if not ts.slo_shedding:
                return False
            if (
                ts.slo_shed_since is not None
                and now - ts.slo_shed_since >= self.slo_recover_after
            ):
                ts.slo_shedding = False
                ts.slo_shed_since = None
                ts.lat_ring = []
                ts.lat_idx = 0
                ts.lat_new = 0
                ts.p99 = 0.0
                return False
            ts.slo_sheds += 1
            return True

    # --- flush / dispatch observers -----------------------------------------

    def _on_dispatch(self, depth: int, lanes: int, reason: str) -> None:
        """Scheduler hand-off hook: depth = outstanding dispatches
        (queued + in flight) — the continuous-batching occupancy."""
        self.metrics.dispatch_occupancy.observe(depth)

    def _on_flush(
        self, reason: str, batch: list, seconds: float, algo: int = ALGO_ED25519
    ) -> None:
        lanes = len(batch)
        self.admission.observe_flush(lanes, seconds)
        self.metrics.flushes.labels(reason=reason).inc()
        self.metrics.batch_occupancy.observe(lanes)
        if algo == ALGO_ED25519:
            # Repeat signers from set-less verifyd traffic feed the
            # device-resident table store's hot-key pinning
            # (ops/resident.py), capped per tenant so one chain's
            # validator universe can't evict everyone else's; the
            # import stays lazy + guarded so a host-only daemon config
            # never pays for the ops engine.
            try:
                from tendermint_tpu.ops import resident

                by_tenant: Dict[Optional[str], list] = {}
                for p in batch:
                    by_tenant.setdefault(p.tenant, []).append(p.pubkey)
                for tname, pks in by_tenant.items():
                    resident.note_hot_keys(
                        pks,
                        tenant=tname or DEFAULT_TENANT,
                        quota=self.tenant_pin_quota,
                    )
            except Exception:
                # accounting hook only — a broken ops import must never
                # touch the serving path
                pass
        if len({p.tag for p in batch}) > 1:
            with self._stats_mtx:
                self.cross_client_flushes[reason] = (
                    self.cross_client_flushes.get(reason, 0) + 1
                )
            self.metrics.cross_client_flushes.labels(reason=reason).inc()

    # --- per-class depth gauge ----------------------------------------------

    def _track_depth(self, klass: int, delta: int) -> None:
        with self._depth_mtx:
            depth = self._class_depth.get(klass, 0) + delta
            self._class_depth[klass] = max(0, depth)
            self.metrics.queue_depth.labels(klass=CLASS_NAMES[klass]).set(
                self._class_depth[klass]
            )

    # --- request handler ----------------------------------------------------

    def _respond(
        self,
        status: int,
        verdicts: List[bool],
        message: str,
        t0: float,
        kind_name: str,
        queue_depth: int = 0,
        tenant_label: str = "",
        stages: Optional[Dict[str, float]] = None,
    ) -> protocol.VerifyResponse:
        with tracing.span("verifyd_respond", status=STATUS_NAMES[status]):
            with self._stats_mtx:
                self.requests_served += 1
            self.metrics.requests.labels(
                kind=kind_name, status=STATUS_NAMES[status]
            ).inc()
            self.metrics.request_seconds.labels(kind=kind_name).observe(
                time.monotonic() - t0
            )
            if tenant_label:
                self.metrics.tenant_request_seconds.labels(
                    tenant=tenant_label
                ).observe(time.monotonic() - t0)
            return protocol.VerifyResponse(
                status=status,
                verdicts=verdicts,
                message=message,
                queue_depth=queue_depth,
                stages=protocol.pack_stages(stages) if stages else b"",
                shard_id=self.shard_id,
            )

    def _shed(
        self,
        ts: _TenantState,
        klass_name: str,
        reason: str,
        n: int,
        message: str,
        t0: float,
        kind_name: str,
        depth: int,
    ) -> protocol.VerifyResponse:
        """Every shed path funnels here: explicit RESOURCE_EXHAUSTED on
        the wire, a reasoned rejection metric per class AND per tenant —
        never a silent drop."""
        with self._stats_mtx:
            self.admission_rejections += 1
        self._tenant_shed(ts, reason)
        self.metrics.admission_rejections.labels(
            klass=klass_name, reason=reason
        ).inc()
        tracing.instant(
            "verifyd_shed",
            klass=klass_name,
            reason=reason,
            lanes=n,
            tenant=ts.label,
        )
        return self._respond(
            STATUS_RESOURCE_EXHAUSTED,
            [],
            message,
            t0,
            kind_name,
            depth,
            tenant_label=ts.label,
        )

    def _host_direct(
        self,
        req,
        ts: _TenantState,
        t0: float,
        kind_name: str,
        level: int,
    ) -> protocol.VerifyResponse:
        """host_consensus rung: consensus lanes bypass the device
        scheduler and verify on the host oracle — slower, sound, and
        immune to whatever took the device out."""
        n = len(req)
        _verify_fn, host_fn = self._verify_fns[req.algo]
        # shm requests hand msgs over as slab memoryviews; the host
        # oracle path bypasses the scheduler's flush-assembly (where
        # they normally materialise), so copy them out here
        msgs = [
            m.tobytes() if type(m) is memoryview else m for m in req.msgs
        ]
        t_dev0 = time.monotonic()
        with tracing.span(
            "verifyd_host_direct", lanes=n, tenant=ts.label, level=level
        ):
            verdicts = list(host_fn(req.pks, msgs, req.sigs))
        t_dev1 = time.monotonic()
        with self._stats_mtx:
            self.host_direct_lanes += n
        with self._tenant_mtx:
            ts.host_direct += n
            ts.lanes += n
        self.metrics.host_direct_lanes.inc(n)
        self.metrics.tenant_lanes.labels(tenant=ts.label).inc(n)
        return self._respond(
            STATUS_OK, verdicts, "", t0, kind_name, 0, tenant_label=ts.label,
            stages={
                "admission": t_dev0 - t0,
                "device": t_dev1 - t_dev0,
                "collect": time.monotonic() - t_dev1,
            },
        )

    def _handle_stats(self, payload: bytes) -> bytes:
        """STATS_PATH unary: one JSON snapshot of everything a
        federation client (or ``verifyd stats``) needs to gossip — wire
        counters (with the device this daemon got), per-tenant SLO view,
        brownout level, the device health machine's failure/fallback
        counters, and this shard's pinned resident-table slice. The
        request payload is ignored (reserved), so any client version
        can poll any server version."""
        del payload
        from tendermint_tpu.ops import device_policy, hash512, resident

        snap = {
            "shard_id": self.shard_id,
            "stats": self.stats(),
            "tenants": self.tenant_stats(),
            "brownout": self.brownout.snapshot(),
            # lanes the engine answered from the host oracle after a
            # device failure never show in the wire counters above;
            # the health machine's own counters do
            "device_health": device_policy.shared.snapshot(),
            "hash512": hash512.stats(),
            "resident": resident.stats(),
            "pinned_keys": resident.pinned_keys(),
        }
        return json.dumps(snap, sort_keys=True).encode("utf-8")

    def _handle(self, payload: bytes) -> bytes:
        """TCP entry point: decode the wire frame, serve, re-encode.
        The shm drain path skips both codec halves and enters
        ``_serve`` directly — that is the entire zero-copy win."""
        t0 = time.monotonic()
        with tracing.span("verifyd_decode", nbytes=len(payload)):
            try:
                req = protocol.decode_request(payload)
            except ValueError as exc:
                return protocol.encode_response(
                    self._respond(STATUS_INVALID, [], str(exc), t0, "raw")
                )
        # Connection identity for cross-client batching stats. Under
        # the event loop many connections share few worker threads,
        # so the transport's per-connection tag is authoritative;
        # the thread ident covers direct (non-gRPC) handler calls.
        tag = current_conn_tag(threading.get_ident())
        return protocol.encode_response(self._serve(req, t0, tag=tag))

    def _serve(
        self,
        req: protocol.VerifyRequest,
        t0: float,
        tag: Optional[object] = None,
        on_entries: Optional[Callable[[List[object]], None]] = None,
    ) -> protocol.VerifyResponse:
        """Transport-independent serving path: admission, brownout,
        tenant budgets, enqueue, wait. ``on_entries`` (shm drain) gets
        the scheduler entries right after submit so the caller can tell
        whether a deadline response left lanes still holding slab
        memoryviews (the held-slab reclaim protocol).

        When the request carries a trace context (protocol field 7 /
        slab header trace words) every span this handler opens links
        under the CLIENT's span, so a fleet-merged timeline shows the
        client's ``verifyd_call`` as ancestor of the server's enqueue,
        dispatch, and chunk spans."""
        ctx = (
            tracing.TraceContext.from_bytes(req.trace) if req.trace else None
        )
        if ctx is None:
            return self._serve_inner(req, t0, tag, on_entries, None)
        with tracing.attach(ctx):
            return self._serve_inner(req, t0, tag, on_entries, ctx)

    def _serve_inner(
        self,
        req: protocol.VerifyRequest,
        t0: float,
        tag: Optional[object],
        on_entries: Optional[Callable[[List[object]], None]],
        ctx: Optional[tracing.TraceContext],
    ) -> protocol.VerifyResponse:
        kind_name = "raw"
        t_entry = time.monotonic()  # decode/transport hand-off boundary
        try:
            kind_name = KIND_NAMES[req.kind]
            klass_name = CLASS_NAMES[req.klass]
            # federation bookkeeping: a request stamped for another
            # shard means the client's shard map is stale — serve it
            # anyway (any shard verifies correctly; only table locality
            # suffers) but count it and leave a trace breadcrumb
            if req.route_epoch:
                with self._stats_mtx:
                    if req.route_epoch > self.route_epoch_seen:
                        self.route_epoch_seen = req.route_epoch
            if (
                req.shard_id >= 0
                and self.shard_id >= 0
                and req.shard_id != self.shard_id
            ):
                with self._stats_mtx:
                    self.misroutes += 1
                tracing.instant(
                    "verifyd_misroute",
                    want=req.shard_id,
                    got=self.shard_id,
                    epoch=req.route_epoch,
                )
            ts = self._tenant_for(req.tenant)
            if req.slo_ms:
                self._tenant_declare_slo(ts, req.slo_ms)
            n = len(req)
            if n == 0:
                return self._respond(
                    STATUS_OK, [], "", t0, kind_name, tenant_label=ts.label
                )
            sched = self._scheduler_for(req.algo)
            # the caller-observed wire/decode wait is the adaptive
            # controller's shrink signal (queueing ahead of the
            # accumulator dominating the flush deadline)
            sched.note_queue_wait(t_entry - t0)
            deadline_s = req.deadline_ms / 1000.0 if req.deadline_ms else 0.0

            # load_depth counts in-flight lanes too: on the continuous
            # path lanes leave the accumulator while their dispatch
            # still occupies the device, and admission must see them.
            # Committed-but-undrained slab-ring lanes ride on top, so
            # shm pressure moves the brownout ladder like TCP pressure.
            depth = sched.load_depth() + self.shm_backlog()
            level, moved = self.brownout.observe(
                self.admission.pressure(depth)
            )
            self.metrics.brownout_level.set(level)
            if moved:
                direction = "up" if moved > 0 else "down"
                self.metrics.brownout_transitions.labels(
                    direction=direction
                ).inc()
                tracing.instant(
                    "verifyd_brownout",
                    level=LEVEL_NAMES[level],
                    direction=direction,
                )

            # per-tenant SLO gate, BEFORE the load-based ladder: a
            # tenant whose attributed p99 drifted past its declared
            # budget sheds ITS OWN sheddable classes while every other
            # tenant — and the global ladder — is untouched. Consensus
            # and blocksync are exempt exactly as on the ladder.
            if req.klass in SHEDDABLE_CLASSES and self._tenant_slo_gate(ts):
                return self._shed(
                    ts, klass_name, "slo", n,
                    f"tenant {ts.label} over SLO budget"
                    f" ({ts.slo_ms}ms p99 target)",
                    t0, kind_name, depth,
                )

            # ladder rungs 1-3: whole-class sheds (rpc -> light ->
            # blocksync; consensus never)
            if level_sheds_class(level, req.klass):
                return self._shed(
                    ts, klass_name, "brownout", n,
                    f"{klass_name} shed (brownout {LEVEL_NAMES[level]})",
                    t0, kind_name, depth,
                )
            # ladder rung 5: device out of the loop — consensus goes
            # host-direct (rung 3 already shed everything else)
            if level >= LEVEL_HOST_CONSENSUS and req.klass == CLASS_CONSENSUS:
                return self._host_direct(req, ts, t0, kind_name, level)

            # per-tenant budget: all-or-nothing for the WHOLE request —
            # an atomic lane group never splits on the budget boundary
            budget = self._tenant_budget(level)
            if req.klass in SHEDDABLE_CLASSES:
                with self._tenant_mtx:
                    over = ts.depth + n > budget
                if over:
                    return self._shed(
                        ts, klass_name, "tenant_budget", n,
                        f"tenant {ts.label} over budget ({budget} lanes)",
                        t0, kind_name, depth,
                    )
            elif (
                level >= LEVEL_SHRINK_SHARES
                and req.klass == CLASS_CONSENSUS
            ):
                # shrink_shares rung: consensus past the tenant's
                # shrunken dispatch share rides the host oracle instead
                # of the device — never shed, never silently dropped
                with self._tenant_mtx:
                    over = ts.depth + n > budget
                if over:
                    return self._host_direct(req, ts, t0, kind_name, level)

            shed = self.admission.admit(req.klass, n, depth)
            if shed is not None:
                return self._shed(
                    ts, klass_name, shed, n,
                    f"{klass_name} load shed ({shed}, {depth} pending)",
                    t0, kind_name, depth,
                )

            # enqueue: the wire deadline (minus a respond margin) becomes
            # the lane's flush_by so the scheduler flushes early instead
            # of letting the deadline lapse inside the accumulator
            flush_by = None
            if deadline_s:
                margin = max(0.001, 0.2 * deadline_s)
                flush_by = t0 + max(0.0, deadline_s - margin)
            if tag is None:
                tag = threading.get_ident()
            try:
                with tracing.span(
                    "verifyd_enqueue", lanes=n, klass=klass_name,
                    tenant=ts.label,
                ):
                    # submit_many is atomic against max_pending: the
                    # group lands whole or not at all, even while the
                    # continuous dispatcher is draining concurrently
                    entries = sched.submit_many(
                        list(zip(req.pks, req.msgs, req.sigs)),
                        priority=req.klass,
                        flush_by=flush_by,
                        tag=tag,
                        tenant=ts.label,
                        # inside the enqueue span the current context IS
                        # the enqueue span (deepest linkage); when tracing
                        # is off locally, propagate the client's context
                        # so coalesced waiters still link in the merge
                        trace=tracing.current_context() or ctx,
                    )
            except SchedulerSaturatedError as exc:
                return self._shed(
                    ts, klass_name, "saturated", n,
                    str(exc), t0, kind_name, sched.pending_depth(),
                )
            t_submit = time.monotonic()
            self._track_depth(req.klass, n)
            self._tenant_admit(ts, n)
            self.metrics.lanes.labels(klass=klass_name).inc(n)
            if on_entries is not None:
                on_entries(entries)

            try:
                verdicts: List[bool] = []
                with tracing.span("verifyd_wait", lanes=n):
                    for entry in entries:
                        if deadline_s:
                            left = deadline_s - (time.monotonic() - t0)
                            if left <= 0 or not entry.done.wait(timeout=left):
                                with self._stats_mtx:
                                    self.deadline_expired += 1
                                return self._respond(
                                    STATUS_DEADLINE_EXCEEDED,
                                    [],
                                    f"deadline ({req.deadline_ms}ms) expired"
                                    " awaiting flush",
                                    t0,
                                    kind_name,
                                    sched.pending_depth(),
                                    tenant_label=ts.label,
                                )
                            verdicts.append(entry.ok)
                        else:
                            verdicts.append(
                                sched.wait(entry, timeout=DEFAULT_WAIT)
                            )
            finally:
                self._track_depth(req.klass, -n)
                self._tenant_release(ts, n)
            # latency attribution: the stage vector tiles the full
            # server wall t0 -> now with REAL span boundaries, so the
            # client can see where its round trip went (any gap between
            # the client's observed wall and this sum is transport).
            disp = [e.t_dispatch for e in entries if e.t_dispatch > 0.0]
            fin = [e.t_done for e in entries if e.t_done > 0.0]
            t_disp = min(disp) if disp else t_submit
            t_fin = max(fin) if fin else t_disp
            now = time.monotonic()
            stages = {
                "wire_wait": t_entry - t0,
                "admission": t_submit - t_entry,
                "batch_residency": t_disp - t_submit,
                "device": t_fin - t_disp,
                "collect": now - t_fin,
            }
            # the SLO sketch eats the same wall the stage vector tiles
            self._tenant_observe_latency(ts, now - t0, now)
            return self._respond(
                STATUS_OK, verdicts, "", t0, kind_name,
                sched.pending_depth(), tenant_label=ts.label,
                stages=stages,
            )
        except Exception as exc:  # never tear the stream on a handler bug
            return self._respond(
                STATUS_INTERNAL, [], repr(exc), t0, kind_name
            )
