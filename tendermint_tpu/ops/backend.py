"""Which JAX backend this process got — asked in one place.

Every device path that defaults on for an accelerator gates here: the
verify implementation choice on :func:`platform`; device hashing, the
resident table store and the field-multiply autotuner on
:func:`auto_on`. The verifyd banner, ``VerifydServer.stats()`` and the
node's start-up line report :func:`device_identity`.

Nothing here catches. A backend that cannot initialise raises to the
caller — on the verify path that is the health machine
(ops/device_policy.py), which counts it — and is never read as "behave
as on a CPU".
"""

from __future__ import annotations

from typing import Dict, Optional

import jax


def platform(backend: Optional[str] = None) -> str:
    """``jax``'s platform name for ``backend`` (default backend if None)."""
    if backend:
        return jax.local_devices(backend=backend)[0].platform
    return jax.default_backend()


def auto_on(mode: str, backend: Optional[str] = None) -> bool:
    """Resolve an ``auto | on | off`` switch of a device path (device
    hashing, resident tables, the autotuner): ``auto`` means on for the
    tpu backend only, so CPU tier-1 behaviour never changes."""
    mode = mode.lower()
    if mode in ("1", "on", "true", "yes", "all"):
        return True
    if mode in ("0", "off", "none", "false"):
        return False
    return platform(backend) == "tpu"


def device_identity() -> Dict[str, object]:
    """Platform, device kind and device count as JAX reports them.
    Initialises the backend: call it only from a process that owns the
    device."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
