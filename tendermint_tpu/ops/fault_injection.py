"""Fault-injection hooks for the accelerator verification path.

Jepsen-style injected faults prove the device health state machine
(ops/device_policy.py) actually degrades and recovers: the signature
engines call :func:`fire` at each device dispatch site, and an
installed :class:`FaultPlan` decides — per call — whether to inject
latency, raise a transient error shape, or raise a permanent one.

Sites currently instrumented:

- ``ed25519.chunk`` / ``sr25519.chunk`` — one chunk's kernel dispatch
  in ops/ed25519_batch._run_chunk (one device) or
  parallel/sharding.run_chunk_mesh (a mesh)
- ``ed25519.collect`` / ``sr25519.collect`` — materialization of a
  dispatched chunk's result in ops/ed25519_batch._PendingJobs.collect

When no plan is installed the hook is a single global read — zero
overhead on the hot path. Plans are process-global and thread-safe
(device dispatch happens from scheduler threads, the consensus state
loop, and tests concurrently).

Plans can be driven three ways:

- declaratively: ``FaultPlan(fail_from=3, fail_count=2)`` fails the 3rd
  and 4th matching calls (raise-on-Nth-call);
- imperatively: ``plan.kill()`` / ``plan.revive()`` flip a switch so a
  chaos driver can take the device down and bring it back mid-run;
- from the environment: ``TENDERMINT_TPU_FAULTS="site=ed25519;
  fail_from=1;fail_count=5;permanent=0;latency=0.01"`` installs a plan
  at import — the seam the e2e harness uses to inject faults into
  subprocess nodes.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from typing import Callable, Iterable, Optional, Set


class DeviceFault(RuntimeError):
    """Injected device error. ``permanent`` mirrors the shape of a
    backend-init failure vs a flaky launch; device_policy classifies on
    the attribute, so injected faults never depend on message text.
    ``device`` (an optional device id) mirrors a fault attributable to
    one chip of a mesh; parallel/mesh.attribute_device reads it."""

    def __init__(
        self,
        message: str = "injected device fault",
        permanent: bool = False,
        device: Optional[int] = None,
    ):
        super().__init__(message)
        self.permanent = permanent
        self.device = device


class FaultPlan:
    """One installed fault schedule.

    ``site`` is a prefix filter (``"ed25519"`` matches both the chunk
    and collect sites; None matches every site). Matching calls are
    counted; a call fails when its 1-based index is in ``fail_calls``,
    falls in [``fail_from``, ``fail_from + fail_count``), or the plan
    has been imperatively :meth:`kill`-ed. ``latency`` seconds are
    injected before every matching call, failing or not.
    """

    def __init__(
        self,
        site: Optional[str] = None,
        fail_calls: Iterable[int] = (),
        fail_from: Optional[int] = None,
        fail_count: int = 0,
        permanent: bool = False,
        latency: float = 0.0,
        error_factory: Optional[Callable[[], BaseException]] = None,
    ):
        self.site = site
        self.fail_calls: Set[int] = set(fail_calls)
        self.fail_from = fail_from
        self.fail_count = fail_count
        self.permanent = permanent
        self.latency = latency
        self.error_factory = error_factory
        self._mtx = threading.Lock()
        self._failing = False  # imperative kill/revive switch
        self.calls = 0
        self.faults_raised = 0

    # --- imperative chaos driver ---------------------------------------------

    def kill(self) -> None:
        """Every matching call fails until revive()."""
        with self._mtx:
            self._failing = True

    def revive(self) -> None:
        with self._mtx:
            self._failing = False

    @property
    def killed(self) -> bool:
        with self._mtx:
            return self._failing

    # --- hook ---------------------------------------------------------------

    def _matches(self, site: str) -> bool:
        return self.site is None or site.startswith(self.site)

    def on_call(self, site: str) -> None:
        if not self._matches(site):
            return
        with self._mtx:
            self.calls += 1
            idx = self.calls
            fail = self._failing or idx in self.fail_calls
            if (
                not fail
                and self.fail_from is not None
                and self.fail_from <= idx < self.fail_from + self.fail_count
            ):
                fail = True
            if fail:
                self.faults_raised += 1
        if self.latency > 0:
            time.sleep(self.latency)
        if fail:
            if self.error_factory is not None:
                raise self.error_factory()
            raise DeviceFault(
                f"injected {'permanent' if self.permanent else 'transient'} "
                f"fault at {site} call #{idx}",
                permanent=self.permanent,
            )


_PLAN: Optional[FaultPlan] = None
_PLAN_MTX = threading.Lock()


def install(plan: FaultPlan) -> FaultPlan:
    global _PLAN
    with _PLAN_MTX:
        _PLAN = plan
    return plan


def uninstall() -> None:
    global _PLAN
    with _PLAN_MTX:
        _PLAN = None


def active() -> Optional[FaultPlan]:
    return _PLAN


def fire(site: str) -> None:
    """The per-dispatch hook the engines call. No-op without a plan."""
    plan = _PLAN
    if plan is not None:
        plan.on_call(site)


@contextmanager
def inject(**plan_kwargs):
    """Scoped installation for tests::

        with fault_injection.inject(site="ed25519", fail_from=1,
                                    fail_count=2) as plan:
            ...
    """
    plan = install(FaultPlan(**plan_kwargs))
    try:
        yield plan
    finally:
        uninstall()


def _parse_env_plan(spec: str) -> FaultPlan:
    """``key=value`` pairs separated by ``;`` (see module docstring)."""
    kwargs: dict = {}
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        key, _, value = part.partition("=")
        key = key.strip()
        value = value.strip()
        if key == "site":
            kwargs["site"] = value or None
        elif key == "fail_calls":
            kwargs["fail_calls"] = [int(v) for v in value.split(",") if v]
        elif key == "fail_from":
            kwargs["fail_from"] = int(value)
        elif key == "fail_count":
            kwargs["fail_count"] = int(value)
        elif key == "permanent":
            kwargs["permanent"] = value not in ("0", "false", "")
        elif key == "latency":
            kwargs["latency"] = float(value)
        else:
            raise ValueError(f"unknown fault-plan key {key!r}")
    return FaultPlan(**kwargs)


def install_from_env(env_var: str = "TENDERMINT_TPU_FAULTS") -> Optional[FaultPlan]:
    spec = os.environ.get(env_var, "")
    if not spec:
        return None
    return install(_parse_env_plan(spec))


install_from_env()
