"""Sharded batch verification over a device mesh.

The TPU analog of the reference's task-level concurrency inventory
(SURVEY.md §2.4): signature lanes are the data-parallel axis. Every
verify kernel — the ed25519 chunk kinds of ops/ed25519_batch.KINDS and
sr25519's (ops/sr25519_batch.SR25519) — is lane-local with no
cross-signature communication, so sharding the lane axis over an ICI
mesh partitions with zero collectives; every device runs its own slab
and the only sync is the final per-lane bool gather. What a kernel takes
and where its lanes lie comes from its :class:`ChunkKind` record
(ops/chunk_kinds.py); nothing here names a kind.

A mesh has the implementations one device has (:func:`_sharded_kernel`):
the kind's XLA graph partitioned by GSPMD, or, for ``pallas`` and a kind
with a Pallas entry point, that entry point's program run per shard
under ``shard_map`` — the same Mosaic kernel one device runs, taken
from ops/kernel_store.py so that a process which finds the store warm
never walks the kernel body.

This module is the mechanism half of the mesh engine: compile-cached
sharded kernels, slab padding to a device multiple, the
dispatch-with-degradation loop (:func:`run_chunk_mesh`), and per-device
collection (:func:`collect_sharded`). Policy — which devices, per-device
health, COOLDOWN re-admission — lives in
:mod:`tendermint_tpu.parallel.mesh`; the engines (ops/ed25519_batch,
ops/sr25519_batch) call in here per chunk, so scheduler and verifyd
super-batches span devices without their callers changing at all.

Failure semantics: a dispatch failure attributable to one chip excludes
that chip and retries the chunk on a rebuilt smaller mesh (7-way, not
host); only when no usable mesh remains does :class:`MeshUnavailableError`
hand the chunk back to the engine's single-device path. Unattributed
failures propagate to the engine's ordinary per-chunk host fallback.
"""

from __future__ import annotations

import warnings
from functools import lru_cache
from typing import List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tendermint_tpu.libs import tracing
from tendermint_tpu.ops import fault_injection, field32 as field
from tendermint_tpu.ops.chunk_kinds import ChunkKind
from tendermint_tpu.parallel import mesh as mesh_mod
from tendermint_tpu.parallel.mesh import SIG_AXIS


class MeshUnavailableError(RuntimeError):
    """No usable multi-device mesh remains for this chunk; the caller
    should take its single-device path (NOT the host oracle)."""


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the first ``n_devices`` devices (default: all)."""
    devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(
                f"requested {n_devices} devices, only {len(devices)} available"
            )
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (SIG_AXIS,))


def _lane_spec(axis: Optional[int]) -> P:
    """Lanes along ``axis`` over the mesh; None (an input without
    lanes: the resident store) is replicated."""
    return P() if axis is None else P(*(None,) * axis, SIG_AXIS)


def _shard_shape(shape: tuple, axis: Optional[int], n_dev: int) -> tuple:
    """One device's part of ``shape`` under :func:`_lane_spec`."""
    if axis is None:
        return tuple(shape)
    return (*shape[:axis], shape[axis] // n_dev, *shape[axis + 1 :])


def _runs_pallas(kind: ChunkKind, impl: str) -> bool:
    return impl == "pallas" and kind.pallas is not None


def shard_program(kind: ChunkKind) -> str:
    """What a kind's sharded program is called, so its module on a
    device trace (``jit_<this>``): the one-chip program's name
    (``kind.program``: ``run``, ``run_sr25519``) with ``_shard`` behind
    its ``run`` — ``run_shard``, ``run_shard_sr25519``. It is another
    program than the one-chip one — lowered at one shard's shapes, under
    ``shard_map`` or GSPMD — and a process whose mesh degrades to one
    device runs both; ``run*`` still matches, which is what the
    benchmark's kernel metrics look for."""
    return "run_shard" + kind.program.removeprefix("run")


@lru_cache(maxsize=32)
def _sharded_kernel(
    mesh: Mesh, kind: ChunkKind, impl: str, mul_impl: str, avals: Optional[tuple] = None
):
    """Jitted lane-sharded kernel per (mesh, chunk kind, implementation,
    field-mul impl), and for ``pallas`` per argument shapes. The mul
    impl is a trace-time switch on field32, pinned inside the traced fn
    (same rules as ops/ed25519_batch._compiled_kernel) and therefore
    part of the cache key.

    The jitted function is named by :func:`shard_program`, so each
    kind's sharded program is a module of its own on a device trace.

    Each input is sharded along its lane axis, so a device holds only
    its own lanes' rows (and, for the gathered ``(8, 4, 32, N)`` table
    input, its own lanes' tables). An input without lanes — the
    resident store, keyed by distinct pubkey (a committee is ~100 KiB)
    — is replicated, so the per-lane take inside the kernel is device-
    local and comes out lane-sharded.

    ``impl`` ``pallas`` (for a kind that has a Pallas entry point) runs
    that entry point's program on every device's slab under
    ``shard_map``: no collective, nothing for GSPMD to partition.
    ``avals`` — the ``(shape, dtype)`` of every argument — is part of
    the key there, because the program is not traced here but fetched,
    already lowered, from ops/kernel_store.py at one shard's shapes
    (``pallas_verify.stored_program``, which a single device jits);
    the fetch and the first call run under a ``kernel_compile`` span
    whose ``stored`` says whether the store had it (``hit``) or the
    kernel body was walked (``miss``). Anything else is the kind's XLA
    graph under GSPMD."""
    specs = tuple(_lane_spec(i.lane_axis) for i in kind.inputs)
    shardings = dict(
        in_shardings=tuple(NamedSharding(mesh, p) for p in specs),
        out_shardings=NamedSharding(mesh, _lane_spec(0)),
    )
    if _runs_pallas(kind, impl):
        from tendermint_tpu.ops import introspect, pallas_verify

        n_dev = mesh.devices.size
        shard_avals = tuple(
            jax.ShapeDtypeStruct(_shard_shape(shape, i.lane_axis, n_dev), np.dtype(dtype))
            for i, (shape, dtype) in zip(kind.inputs, avals)
        )
        program = []

        def first_then(*args):
            if not program:
                shard, stored = pallas_verify.stored_program(
                    kind.pallas, shard_avals, mesh.devices.flat[0]
                )
                tracing.tag(stored=stored)

                def run(*a):
                    return jax.shard_map(
                        shard,
                        mesh=mesh,
                        in_specs=specs,
                        out_specs=_lane_spec(0),
                        check_vma=False,
                    )(*a)

                run.__name__ = shard_program(kind)
                program.append(jax.jit(run, **shardings))
            return program[0](*args)

        return introspect.traced_first_call(
            first_then,
            "pallas",
            kind.kernel_name,
            shard_avals[-1].shape[0],
            devices=n_dev,
        )

    def run(*args):
        with field.pinned_mul_impl(mul_impl):
            return kind.kernel(*args)

    run.__name__ = shard_program(kind)
    return jax.jit(run, **shardings)


def sharded_verify_fn(mesh: Mesh):
    """Jitted ed25519 verify kernel with lane-axis sharding over
    ``mesh`` (back-compat entry point; see :func:`_sharded_kernel`)."""
    from tendermint_tpu.ops import ed25519_batch

    return _sharded_kernel(
        mesh, ed25519_batch.KINDS["legacy"], "xla", field.get_mul_impl()
    )


# --- dispatch / collect -------------------------------------------------------


def run_chunk_mesh(
    kind: ChunkKind,
    inputs: dict,
    impl: str,
    mul_impl: str,
    plan: "mesh_mod.MeshPlan",
    sp=tracing.NOP_SPAN,
):
    """Dispatch one prepped chunk lane-sharded across ``plan``'s mesh,
    on the mesh's kernel for ``impl`` (:func:`_sharded_kernel`).

    Returns ``(device_result, plan_used)`` — ``plan_used`` may be a
    smaller rebuilt plan if a device was excluded mid-dispatch. A
    failure attributable to one chip excludes it (its DeviceHealth
    enters COOLDOWN), rebuilds an (n-1)-device mesh, and retries the
    chunk there: a sick chip degrades the mesh, never to host. Raises
    :class:`MeshUnavailableError` when no multi-device mesh remains,
    and re-raises unattributed failures for the engine's ordinary
    per-chunk handling.

    ``sp`` (the engine's ``dispatch_chunk`` span) gets the sharded
    call's time as its ``launch`` phase, every try of a degrading mesh
    in the one total. The call takes host arrays and transfers them
    inside itself, so there is no ``h2d`` phase on a mesh.
    """
    mgr = mesh_mod.manager
    lanes = kind.lanes(inputs)
    launch = sp.timed("launch", lambda fn, *args: fn(*args))
    pallas = _runs_pallas(kind, impl)
    if pallas:
        from tendermint_tpu.ops import pallas_verify
    while True:
        # Every device gets an identical slab. The engines already pad to
        # ``_mesh_bucket`` multiples for the planned mesh; this re-pad
        # covers dispatch on a DEGRADED mesh (8-way prep retried 7-way:
        # 512 -> 518), and for the Pallas kernels makes a slab above one
        # block whole blocks.
        slab = -(-lanes // plan.n_dev)
        if pallas:
            slab = pallas_verify.shard_lanes(slab)
        m = slab * plan.n_dev
        padded = kind.pad_lanes(inputs, m - lanes)
        args = kind.args(padded)
        avals = tuple((a.shape, str(a.dtype)) for a in args) if pallas else None
        fn = _sharded_kernel(plan.mesh, kind, impl, mul_impl, avals)
        try:
            with tracing.span(
                "mesh_dispatch",
                stage="mesh_dispatch",
                engine=kind.engine,
                kind=kind.name,
                devices=plan.n_dev,
                lanes=m,
                impl=impl,
            ):
                fault_injection.fire(kind.engine + ".chunk")
                out = launch(fn, *args)
        except Exception as exc:
            culprit = mgr.on_failure(plan, exc)
            if culprit is None:
                raise
            nxt = mgr.replan(plan)
            if nxt is None:
                raise MeshUnavailableError(
                    f"device {culprit} excluded and no usable mesh remains"
                ) from exc
            if kind.store_bound:
                # the resident store tensor is committed to THIS mesh;
                # a rebuilt smaller mesh can't consume it — hand back so
                # the engine re-ships this chunk's columns explicitly
                raise MeshUnavailableError(
                    f"device {culprit} excluded; resident store is bound "
                    "to the dead mesh"
                ) from exc
            warnings.warn(
                f"sharded {kind.name} chunk failed on device {culprit} ({exc!r}); "
                f"retrying on a {nxt.n_dev}-device mesh"
            )
            plan = nxt
            continue
        mgr.note_dispatch(plan, m)
        for did in plan.device_ids:
            tracing.instant(
                "mesh_device_dispatch", device=did, engine=kind.engine, lanes=slab
            )
        return out, plan


def collect_sharded(out, engine: str) -> np.ndarray:
    """Materialize a sharded lane result device by device, one
    ``collect_device`` span per shard so per-device D2H time lands in
    the trace ring. Shards are stitched in lane order."""
    shards = getattr(out, "addressable_shards", None)
    if not shards or len(shards) <= 1:
        return np.asarray(out)

    def lane_start(sh) -> int:
        idx = sh.index[0] if sh.index else slice(None)
        return idx.start or 0

    parts = []
    for sh in sorted(shards, key=lane_start):
        with tracing.span(
            "collect_device",
            stage="collect_device",
            engine=engine,
            device=str(getattr(sh.device, "id", "?")),
            lanes=int(sh.data.shape[0]),
        ):
            parts.append(np.asarray(sh.data))
    return np.concatenate(parts)


# --- whole-batch entry points -------------------------------------------------


def verify_batch_sharded(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    mesh: Optional[Mesh] = None,
    min_lanes: Optional[int] = None,
) -> List[bool]:
    """Like ops.verify_batch but lane-sharded across ``mesh``.

    Routes through the full ops pipeline — digest-keyed result cache,
    OpsMetrics, chunking, per-chunk fallback — with the mesh forced for
    the call's scope, so sharded verification is observable exactly
    like single-device verification. Batches below ``min_lanes``
    (default :data:`mesh.MIN_MESH_LANES`) take the single-device path:
    tiny batches lose more to ``n_dev``-way padding and dispatch fan-out
    than they gain (pass ``min_lanes=0`` to force sharding, e.g. for
    parity tests and warmup). With ``mesh=None`` the engines plan
    against the configured mesh themselves.
    """
    from tendermint_tpu.ops import ed25519_batch

    n = len(pubkeys)
    if n == 0:
        return []
    floor = mesh_mod.MIN_MESH_LANES if min_lanes is None else min_lanes
    if mesh is None or n < floor:
        return ed25519_batch.verify_batch(pubkeys, msgs, sigs)
    with mesh_mod.manager.forced(mesh):
        return ed25519_batch.verify_batch(pubkeys, msgs, sigs)


def verify_batch_sharded_sr(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    mesh: Optional[Mesh] = None,
    min_lanes: Optional[int] = None,
) -> List[bool]:
    """sr25519 counterpart of :func:`verify_batch_sharded`."""
    from tendermint_tpu.ops import sr25519_batch

    n = len(pubkeys)
    if n == 0:
        return []
    floor = mesh_mod.MIN_MESH_LANES if min_lanes is None else min_lanes
    if mesh is None or n < floor:
        return sr25519_batch.verify_batch_sr(pubkeys, msgs, sigs)
    with mesh_mod.manager.forced(mesh):
        return sr25519_batch.verify_batch_sr(pubkeys, msgs, sigs)
