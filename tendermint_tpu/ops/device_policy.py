"""Device health state machine for the batch verification kernels.

One process-wide answer to "is the accelerator usable?", shared by both
signature engines (ops/ed25519_batch.py, ops/sr25519_batch.py) so a
backend declared broken by one path is immediately known to the other.

Unlike the sticky boolean this replaces, the policy degrades gracefully
and RECOVERS — the crash-recovery discipline the p2p layer already
applies to flaky peers (p2p/peermanager.py retry backoff), applied to
the accelerator boundary:

    HEALTHY ──transient──▶ DEGRADED ──budget spent──▶ COOLDOWN
       ▲                      │                          │
       │◀────── success ──────┘            backoff expires: ONE caller
       │                                   becomes the half-open probe
       └──────── probe batch succeeds ◀──────────────────┘

    any state ──permanent error signature──▶ DISABLED (terminal)

- **Classification** is by specific backend-initialization error
  signatures (and an explicit ``permanent`` attribute for injected
  faults), never by substring-matching arbitrary RuntimeErrors: one
  transient XLA hiccup mentioning "platform" must not disable the
  device path for the process lifetime.
- **Retry budget**: transient failures ride through DEGRADED until
  ``retry_budget`` consecutive failures, then the path enters COOLDOWN.
- **Exponential backoff**: each COOLDOWN entry doubles the next
  cooldown up to ``cooldown_max``; a successful batch resets it.
- **Circuit breaker / half-open probe**: during COOLDOWN callers are
  answered instantly (no device attempt, no blocking). Once the
  backoff expires exactly ONE caller's batch is admitted as the probe;
  its success re-promotes the device path for everyone, its failure
  re-arms the cooldown. A flapping device can therefore never stall
  callers — the worst case is one probe batch per backoff window.

Every transition is recorded (``transitions``) and mirrored to
libs/metrics.OpsMetrics when a node binds one, so a dead device is
loudly visible instead of silently misreported.
"""

from __future__ import annotations

import re
import threading
import time
from typing import Callable, List, Optional, Tuple

from tendermint_tpu.libs import tracing

# --- states ------------------------------------------------------------------

HEALTHY = "healthy"
DEGRADED = "degraded"
COOLDOWN = "cooldown"
DISABLED = "disabled"

# Numeric codes for the state gauge (monotone in severity).
STATE_CODES = {HEALTHY: 0, DEGRADED: 1, COOLDOWN: 2, DISABLED: 3}

# --- failure classification --------------------------------------------------

TRANSIENT = "transient"
PERMANENT = "permanent"

# Specific backend-initialization signatures that mean no jax backend
# can come up in this process at all (e.g. the TPU plugin failing to
# register, or the chip held by another process). Anything else — OOMs, flaky launches,
# transport resets — is transient and consumes the retry budget. Each
# pattern pins the *shape* jax actually raises with, not a keyword: a
# transient hiccup that merely mentions "backend" or "platform"
# ("unknown backend configuration flag", "transfer to platform device
# timed out") must never disable the device path for the process
# lifetime (ROADMAP known debt; regression tests in
# tests/test_device_policy.py).
_PERMANENT_PATTERNS = [
    re.compile(p)
    for p in (
        r"unable to initialize backend",
        r"backend '[\w-]+' failed to initialize",
        # jax's xla_bridge raises "Unknown backend: 'tpu' requested, ..."
        # / "Unknown backend tpu" — the backend NAME must follow, so
        # prose that happens to contain "unknown backend" stays transient.
        r"unknown backend:? '[\w-]+'",
        r"^unknown backend [\w-]+$",
        r"no devices? found for platform",
        r"platform '[\w-]+' is not registered",
    )
]


class DeviceStallError(RuntimeError):
    """A device call that never returned (wedge, not an exception) —
    reported by watchdogs like the VotePreverifier's deadline tracking
    so other callers stop feeding a hung device. Always transient."""


def classify_failure_text(text: str) -> str:
    """TRANSIENT or PERMANENT for a failure only known by its text —
    e.g. the stderr tail of a dead bench section child (bench/runner.py),
    where the exception object died with the subprocess. Permanent iff
    the text carries one of the specific backend-init signatures."""
    lowered = text.lower()
    if any(p.search(lowered) for p in _PERMANENT_PATTERNS):
        return PERMANENT
    return TRANSIENT


def classify_failure(exc: BaseException) -> str:
    """TRANSIENT or PERMANENT for a device-path exception.

    An explicit boolean ``permanent`` attribute wins (the fault
    injection harness and any future backend shim set it); otherwise
    only an ImportError (engine can't even load) or a RuntimeError
    matching a known backend-init signature is permanent.
    """
    flagged = getattr(exc, "permanent", None)
    if isinstance(flagged, bool):
        return PERMANENT if flagged else TRANSIENT
    if isinstance(exc, ImportError):
        return PERMANENT
    if isinstance(exc, RuntimeError):
        return classify_failure_text(str(exc))
    return TRANSIENT


# --- attempts ----------------------------------------------------------------


class Attempt:
    """Token for one admitted device attempt; carries whether this
    attempt is the half-open probe (so its outcome re-arms or clears
    the cooldown) and its start time for probe-latency metrics."""

    __slots__ = ("engine", "probe", "started")

    def __init__(self, engine: str, probe: bool, started: float):
        self.engine = engine
        self.probe = probe
        self.started = started


class DeviceHealth:
    """Thread-safe device health state machine (see module docstring)."""

    def __init__(
        self,
        retry_budget: int = 3,
        cooldown_base: float = 0.25,
        cooldown_max: float = 30.0,
        clock: Callable[[], float] = time.monotonic,
    ):
        self._mtx = threading.Lock()
        self._clock = clock
        self.retry_budget = retry_budget
        self.cooldown_base = cooldown_base
        self.cooldown_max = cooldown_max
        self._state = HEALTHY  # guarded-by: _mtx
        self._consecutive_failures = 0  # guarded-by: _mtx
        self._cooldown = cooldown_base  # next cooldown duration  # guarded-by: _mtx
        self._cooldown_until = 0.0  # guarded-by: _mtx
        self._probe_inflight = False  # guarded-by: _mtx
        # observability (all monotone; tests read these directly)
        self.transitions: List[Tuple[str, str]] = []  # guarded-by: _mtx
        self.fallback_batches = 0  # guarded-by: _mtx
        self.failure_counts = {TRANSIENT: 0, PERMANENT: 0}  # guarded-by: _mtx
        self._metrics = None  # OpsMetrics, bound by the node  # guarded-by: _mtx

    # --- wiring --------------------------------------------------------------

    def bind_metrics(self, metrics) -> None:
        """Mirror state into a libs/metrics.OpsMetrics. Process-global
        policy, per-node registries: the last binder wins (one node per
        process outside tests)."""
        with self._mtx:
            self._metrics = metrics
            state = self._state
        if metrics is not None:
            metrics.device_health_state.set(STATE_CODES[state])

    def reset(self) -> None:
        """Back to a pristine HEALTHY machine (tests / operator reset)."""
        with self._mtx:
            self._state = HEALTHY
            self._consecutive_failures = 0
            self._cooldown = self.cooldown_base
            self._cooldown_until = 0.0
            self._probe_inflight = False
            self.transitions.clear()
            self.fallback_batches = 0
            self.failure_counts = {TRANSIENT: 0, PERMANENT: 0}
            metrics = self._metrics
        if metrics is not None:
            metrics.device_health_state.set(STATE_CODES[HEALTHY])

    # --- inspection ----------------------------------------------------------

    @property
    def state(self) -> str:
        with self._mtx:
            return self._state

    @property
    def broken(self) -> bool:
        """Back-compat view of the old sticky boolean: only a terminal
        DISABLED device is 'broken'; everything else may recover."""
        return self.state == DISABLED

    def snapshot(self) -> dict:
        with self._mtx:
            return {
                "state": self._state,
                "consecutive_failures": self._consecutive_failures,
                "cooldown_until": self._cooldown_until,
                "next_cooldown": self._cooldown,
                "probe_inflight": self._probe_inflight,
                "transitions": list(self.transitions),
                "fallback_batches": self.fallback_batches,
                "failures": dict(self.failure_counts),
            }

    # --- the state machine ---------------------------------------------------

    def _transition_locked(self, to: str) -> Optional[Tuple[str, str]]:
        if self._state == to:
            return None
        edge = (self._state, to)
        self._state = to
        self.transitions.append(edge)
        return edge

    def _emit(self, edge: Optional[Tuple[str, str]], metrics) -> None:
        if edge is None:
            return
        # Instant trace event: health transitions line up against the
        # verify-stage spans in the same Chrome-trace timeline.
        tracing.instant(
            "device_health_transition", from_state=edge[0], to_state=edge[1]
        )
        if metrics is None:
            return
        metrics.device_health_state.set(STATE_CODES[edge[1]])
        metrics.device_transitions.labels(
            from_state=edge[0], to_state=edge[1]
        ).inc()

    def begin_attempt(self, engine: str = "ed25519") -> Optional[Attempt]:
        """Admission control for one device batch. Returns an Attempt
        token to pass back to record_success/record_failure, or None
        when the caller must go straight to the CPU path (DISABLED, or
        cooling down with the backoff not yet expired / another probe
        already in flight). Never blocks."""
        now = self._clock()
        with self._mtx:
            if self._state in (HEALTHY, DEGRADED):
                return Attempt(engine, probe=False, started=now)
            if self._state == DISABLED:
                return None
            # COOLDOWN: half-open once the backoff expires, one prober.
            if now < self._cooldown_until or self._probe_inflight:
                return None
            self._probe_inflight = True
            return Attempt(engine, probe=True, started=now)

    def record_success(self, attempt: Optional[Attempt] = None) -> None:
        """A device batch (or probe) completed: re-promote to HEALTHY
        and reset the retry budget and backoff."""
        edge = None
        with self._mtx:
            if attempt is not None and attempt.probe:
                self._probe_inflight = False
            if self._state == DISABLED:
                return  # terminal; a stray late success changes nothing
            self._consecutive_failures = 0
            self._cooldown = self.cooldown_base
            edge = self._transition_locked(HEALTHY)
            metrics = self._metrics
        self._emit(edge, metrics)
        if metrics is not None and attempt is not None and attempt.probe:
            metrics.device_probe_seconds.observe(
                max(0.0, self._clock() - attempt.started)
            )

    def release_probe(self, attempt: Optional[Attempt]) -> None:
        """Give back a half-open probe reservation WITHOUT recording an
        outcome: the admitted attempt was never actually dispatched
        (e.g. the mesh planner reserved a probe slot but the batch took
        another path). Without this the one-prober latch would stay set
        forever and the device could never be re-admitted."""
        if attempt is None or not attempt.probe:
            return
        with self._mtx:
            self._probe_inflight = False

    def record_failure(
        self, exc: BaseException, attempt: Optional[Attempt] = None
    ) -> str:
        """Classify and absorb one device failure; returns the
        classification. Permanent -> DISABLED. Transient -> DEGRADED
        until the retry budget is spent (or the failure was the
        half-open probe), then COOLDOWN with doubled backoff."""
        kind = classify_failure(exc)
        edge = None
        probe_latency = None
        with self._mtx:
            was_probe = attempt is not None and attempt.probe
            if was_probe:
                self._probe_inflight = False
                probe_latency = max(0.0, self._clock() - attempt.started)
            self.failure_counts[kind] += 1
            metrics = self._metrics
            if self._state == DISABLED:
                edge = None  # terminal: count the failure, no transition
            elif kind == PERMANENT:
                edge = self._transition_locked(DISABLED)
            else:
                self._consecutive_failures += 1
                budget_spent = self._consecutive_failures >= self.retry_budget
                if was_probe or budget_spent:
                    self._cooldown_until = self._clock() + self._cooldown
                    self._cooldown = min(self._cooldown * 2, self.cooldown_max)
                    self._consecutive_failures = 0
                    edge = self._transition_locked(COOLDOWN)
                else:
                    edge = self._transition_locked(DEGRADED)
        self._emit(edge, metrics)
        if metrics is not None:
            metrics.device_failures.labels(kind=kind).inc()
            if probe_latency is not None:
                metrics.device_probe_seconds.observe(probe_latency)
        return kind

    def count_fallback(self, engine: str, lanes: int) -> None:
        """One batch (or chunk) of ``lanes`` signatures served by the
        CPU path because the device path failed or is unavailable."""
        with self._mtx:
            self.fallback_batches += 1
            metrics = self._metrics
        if metrics is not None:
            metrics.device_fallbacks.labels(engine=engine).inc()
            metrics.device_fallback_lanes.labels(engine=engine).inc(lanes)

    def note_inflight(self, engine: str, delta: int) -> None:
        """Adjust the in-flight-lanes gauge: +lanes at chunk dispatch,
        -lanes once the result is materialized (or fails to)."""
        with self._mtx:
            metrics = self._metrics
        if metrics is not None:
            metrics.inflight_lanes.labels(engine=engine).inc(delta)


# The process-wide instance both engines share.
shared = DeviceHealth()
