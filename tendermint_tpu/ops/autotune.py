"""MXU-first field-multiply autotuner.

The batch verifier's hot loop is nothing but field multiplies, and the
repo carries two implementations: the f32 VPU shift schoolbook
(ops/field32.py, the historical default) and the int8 dot_general MXU
contraction (ops/field_mxu.py), which per its own analysis is the only
unit with the arithmetic throughput for the 50x target — but until now
it was an env opt-in (``TENDERMINT_TPU_FIELD_MUL=mxu``) nobody flips in
production.

This module makes the *measured* winner the default: on first use per
(platform, batch-bucket) it compiles and times a short ``fe_mul`` chain
under both impls on the target backend, adopts the faster one, and
persists the verdict to a JSON cache so later processes skip the timing
entirely. The engines (ops/ed25519_batch, ops/sr25519_batch) consult
:func:`mul_impl_for` wherever they previously read
``field32.get_mul_impl()``.

Precedence (first match wins):

1. ``TENDERMINT_TPU_FIELD_MUL`` set in the environment — the operator's
   explicit choice always beats the tuner.
2. ``TENDERMINT_TPU_VERIFY_IMPL=mxu`` — handled by the engines before
   they ever call in here.
3. Autotuned winner for (platform, bucket) — in-memory, then the JSON
   cache file, then a fresh measurement.
4. Tuner disabled (``TENDERMINT_TPU_AUTOTUNE=off``, or ``auto`` on a
   non-accelerator backend): ``field32.get_mul_impl()``, unchanged
   behavior.

The winner file changes which kernel later processes compile, so it is
git-ignored: a checkout never carries another machine's verdict.

Env knobs::

    TENDERMINT_TPU_AUTOTUNE        auto (default: on for tpu) | on | off
    TENDERMINT_TPU_AUTOTUNE_CACHE  winner-cache JSON path
                                   (default: <repo>/.autotune_cache.json)
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.ops import backend as backend_mod, field32 as field

_ENV = "TENDERMINT_TPU_AUTOTUNE"
_CACHE_ENV = "TENDERMINT_TPU_AUTOTUNE_CACHE"
_FIELD_ENV = "TENDERMINT_TPU_FIELD_MUL"

_IMPLS = ("vpu", "mxu")
# Mirrors ops/ed25519_batch._BUCKETS: compiled kernel widths are padded
# to these, so winners keyed the same way map 1:1 onto real kernels.
_BUCKETS = (64, 256, 1024, 4096)
_CHAIN_MULS = 8  # multiplies per timed kernel call
_TIMING_ROUNDS = 3  # best-of-k wall times per impl

_lock = threading.Lock()
_selected: Dict[str, str] = {}  # guarded-by: _lock  "platform:bucket" -> impl
_timings: Dict[str, Dict[str, float]] = {}  # guarded-by: _lock  key -> impl -> ms
_file_loaded = False  # guarded-by: _lock
_metrics = None  # guarded-by: _lock
_selection_counts: Dict[str, int] = {"vpu": 0, "mxu": 0}  # guarded-by: _lock
_counted: set = set()  # guarded-by: _lock  keys already counted this process


def mode() -> str:
    return os.environ.get(_ENV, "auto").lower()


def enabled(backend: Optional[str] = None) -> bool:
    """Whether the tuner may pick the field-mul impl for this backend."""
    # auto: only the accelerator — CPU tier-1 runs keep the
    # deterministic field32 default and never pay a timing pass.
    return backend_mod.auto_on(mode(), backend)


def cache_path() -> str:
    return os.environ.get(
        _CACHE_ENV,
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(__file__))),
            ".autotune_cache.json",
        ),
    )


def bucket(lanes: int) -> int:
    """Bucket key for a lane count (kernel widths are padded the same
    way, so one winner per compiled kernel width)."""
    for b in _BUCKETS:
        if lanes <= b:
            return b
    return _BUCKETS[-1]


def bind_metrics(metrics) -> None:
    global _metrics
    with _lock:
        _metrics = metrics


# --- measurement -------------------------------------------------------------


def _timing_inputs(lanes: int) -> np.ndarray:
    """(32, lanes) f32 limb vectors inside fe_mul's loose invariant;
    deterministic so the compiled timing kernel is cache-friendly."""
    vals = (np.arange(32 * lanes, dtype=np.float32) * 7.0) % 251.0
    return vals.reshape(32, lanes)


def _chain_fn(impl: str):
    def chain(a, b):
        with field.pinned_mul_impl(impl):
            out = a
            for _ in range(_CHAIN_MULS):
                out = field.fe_mul(out, b)
            return out

    return chain


def _measure(backend: Optional[str], lanes: int) -> Dict[str, float]:
    """Best-of-k wall ms for the fe_mul chain under each impl."""
    a = _timing_inputs(lanes)
    b = _timing_inputs(lanes)[:, ::-1].copy()
    out: Dict[str, float] = {}
    for impl in _IMPLS:
        fn = jax.jit(_chain_fn(impl), backend=backend)
        da, db = jnp.asarray(a), jnp.asarray(b)
        fn(da, db).block_until_ready()  # compile + warm
        best = None
        for _ in range(_TIMING_ROUNDS):
            t0 = time.perf_counter()
            fn(da, db).block_until_ready()
            dt = (time.perf_counter() - t0) * 1000.0
            best = dt if best is None or dt < best else best
        out[impl] = best
    return out


# --- winner cache ------------------------------------------------------------


def _load_file_locked() -> None:
    global _file_loaded
    if _file_loaded:
        return
    _file_loaded = True
    try:
        with open(cache_path(), "r", encoding="utf-8") as fh:
            data = json.load(fh)
        for key, entry in data.get("selections", {}).items():
            impl = entry.get("impl")
            if impl in _IMPLS and key not in _selected:
                _selected[key] = impl
                _timings[key] = dict(entry.get("ms", {}))
    except Exception:  # missing/corrupt cache file just means re-time
        pass


def _persist_locked() -> None:
    path = cache_path()
    payload = {
        "version": 1,
        "selections": {
            key: {"impl": impl, "ms": _timings.get(key, {})}
            for key, impl in sorted(_selected.items())
        },
    }
    tmp = path + ".tmp"
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except Exception:  # persistence is best-effort; in-memory still wins
        try:
            os.unlink(tmp)
        except OSError:
            pass  # tmp may never have been created; nothing to clean


def _count_selection_locked(key: str, impl: str) -> None:
    """Each (platform, bucket) winner counts once per process — whether
    it came from a fresh timing pass or the persisted cache file."""
    if key in _counted:
        return
    _counted.add(key)
    _selection_counts[impl] = _selection_counts.get(impl, 0) + 1
    if _metrics is not None:
        _metrics.autotune_selections.labels(impl=impl).inc()


def mul_impl_for(backend: Optional[str], lanes: int) -> str:
    """The field-mul impl the engines should compile this chunk with.

    Explicit ``TENDERMINT_TPU_FIELD_MUL`` wins; with the tuner disabled
    this is exactly ``field32.get_mul_impl()`` (the pre-autotune
    behavior). Otherwise the per-(platform, bucket) measured winner —
    resolved from memory, then the JSON cache, then one timing pass
    whose verdict is persisted for every later process.
    """
    if os.environ.get(_FIELD_ENV):
        return field.get_mul_impl()
    if not enabled(backend):
        return field.get_mul_impl()
    key = "%s:%d" % (backend_mod.platform(backend), bucket(lanes))
    with _lock:
        _load_file_locked()
        impl = _selected.get(key)
        if impl is not None:
            _count_selection_locked(key, impl)
            return impl
    # Time outside the lock: compiling two kernels can take seconds and
    # must not serialize concurrent verify paths behind it. A kernel
    # that fails to compile or run here is a device failure and raises
    # to the dispatching engine, which hands it to the health machine.
    ms = _measure(backend, bucket(lanes))
    winner = min(ms, key=lambda k: ms[k])
    with _lock:
        if key not in _selected:  # lost a race: first measurement wins
            _selected[key] = winner
            _timings[key] = ms
            _persist_locked()
        _count_selection_locked(key, _selected[key])
        return _selected[key]


# --- introspection -----------------------------------------------------------


def stats() -> Dict[str, object]:
    with _lock:
        return {
            "selections": dict(_selected),
            "timings_ms": {k: dict(v) for k, v in _timings.items()},
            "selection_counts": dict(_selection_counts),
            "cache_path": cache_path(),
        }


def reset() -> None:
    """Drop in-memory winners (tests); the JSON cache file survives and
    is re-read on the next resolution."""
    global _file_loaded
    with _lock:
        _selected.clear()
        _timings.clear()
        _selection_counts.clear()
        _selection_counts.update({"vpu": 0, "mxu": 0})
        _counted.clear()
        _file_loaded = False
