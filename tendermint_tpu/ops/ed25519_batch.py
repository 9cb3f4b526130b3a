"""Batched Ed25519 ZIP-215 verification on TPU (f32 limb engine).

The device kernel verifies, for each lane i, the cofactored equation

    [8]([s_i]B - R_i - [k_i]A_i) == identity

with a shared-doubling (Straus) double-scalar multiplication: 64
*signed* 4-bit windows (digits in [-8, 8)), per-window additions from a
constant Niels basepoint table of [1..8]B (7-mul mixed adds plus a
conditional negation at select) and a per-lane table of [1..8](-A_i).
Signed windows halve both the per-lane table build (7 chained adds
instead of 14) and the broadcast-select bandwidth of the window loop —
the per-window memory hot spot. All lanes execute the same 64-step
loop, so the computation is pure SIMD over the batch — the TPU analog
of the reference's CPU multi-scalar batch verify
(crypto/ed25519/ed25519.go:198-233, types/validation.go:154).

Three kernel entry points, one chunk kind each (:data:`KINDS`):
:func:`verify_kernel` decompresses A and builds the lane tables on
device; :func:`verify_kernel_tables` accepts a gathered
``(8, 4, 32, N)`` table input from the validator-set-aware precompute
cache (ops/precompute.py) and skips both; :func:`verify_kernel_resident`
gathers that input on device from the resident store. verify_batch
consults the digest-keyed result cache first, partitions lanes between
the kinds, and hands the chunks to :func:`_dispatch_jobs`, the dispatch
loop it shares with sr25519, which double-buffers (host prep of chunk
i+1 overlaps the kernel of chunk i) and reaches every kernel through
:func:`_run_chunk`; ``collect()`` of what that returns is the loop's
second half. ``begin_verify_batch`` hands the caller the batch between
the two (crypto/batch.MultiBatchVerifier has host work for that time).

Layout is transfer-minimal: the host uploads only the raw 32-byte
strings (A, R, S, and the SHA-512 challenge k reduced mod L) as uint8;
limb conversion, sign-bit stripping, and 4-bit windowing all happen on
device, where radix 2^8 f32 limbs make a 32-byte string its own limb
vector (see :mod:`field32`). Host work is the SHA-512 challenge hash
(batched in the C extension when available), the s < L canonicity
check (vectorized byte compare), and padding.

Large batches are split into fixed-size chunks whose kernel calls are
enqueued back-to-back: JAX's async dispatch overlaps each chunk's H2D
transfer with the previous chunk's compute.
"""

from __future__ import annotations

from functools import lru_cache
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.crypto.hashing import (
    L,
    host_hash_impl,
    sha512_batch_mod_l,
    sha512_batch_prefixed_mod_l,
)
from tendermint_tpu.libs import tracing
from tendermint_tpu.ops import (
    curve32 as curve,
    device_policy,
    fault_injection,
    field32 as field,
    introspect,
    resident,
)
from tendermint_tpu.ops.chunk_kinds import ChunkInput, ChunkKind
from tendermint_tpu.parallel import mesh as mesh_mod, sharding as mesh_sharding

_L_BYTES_BE = np.frombuffer(L.to_bytes(32, "big"), dtype=np.uint8)

NWINDOWS = 64  # 256 bits / 4

# Chunk size for pipelined dispatch; also the largest compiled kernel.
CHUNK = 4096
_BUCKETS = [64, 256, 1024, CHUNK]


# --- constant basepoint table (host precompute, Niels form) -----------------


def _build_b_niels_table(width: int = 8) -> np.ndarray:
    """(width, 3, 32) f32: [1..width]B as (Y+X, Y-X, 2dT), Z=1.

    Signed windows select |digit| from the positive multiples and
    negate at select time; digit 0 is an identity fixup, so no row is
    spent on it.
    """
    from tendermint_tpu.crypto import ed25519_ref as ref

    out = np.zeros((width, 3, field.NLIMBS), dtype=np.float32)
    p_mod = field.P

    def affine(pt):
        x_, y_, z_, _ = pt
        zinv = pow(z_, p_mod - 2, p_mod)
        return (x_ * zinv % p_mod, y_ * zinv % p_mod)

    acc = ref.B_POINT
    for i in range(width):
        if i:
            acc = ref.pt_add(acc, ref.B_POINT)
        x, y = affine(acc)
        out[i, 0] = field.int_to_limbs((y + x) % p_mod)
        out[i, 1] = field.int_to_limbs((y - x) % p_mod)
        out[i, 2] = field.int_to_limbs(2 * field.D * x * y % p_mod)
    return out


B_NIELS = _build_b_niels_table()


# --- device kernel ----------------------------------------------------------


def _bytes_to_fe(raw: jnp.ndarray) -> jnp.ndarray:
    """(N, 32) uint8 -> (32, N) f32 limbs (radix 2^8 == raw bytes)."""
    return raw.astype(jnp.float32).T


def _strip_sign(y: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(32, N) limbs with bit 255 set-or-not -> (limbs, sign (N,))."""
    sign = jnp.floor(y[31] * (1.0 / 128.0))
    y = y.at[31].add(-128.0 * sign)
    return y, sign


def _to_windows(raw: jnp.ndarray) -> jnp.ndarray:
    """(N, 32) uint8 scalars (LE) -> (64, N) f32 4-bit digits, MSB first.

    Unsigned digit split; the window loop itself runs on the signed
    recode (:func:`_to_windows_signed`) — this stays as the layout
    primitive and documentation of the MSB-first interleave.
    """
    b = raw.astype(jnp.float32).T  # (32, N)
    hi = jnp.floor(b * (1.0 / 16.0))
    lo = b - 16.0 * hi
    # MSB-first interleave: hi[31], lo[31], hi[30], ...
    return jnp.stack([hi[::-1], lo[::-1]], axis=1).reshape(2 * field.NLIMBS, -1)


def _to_windows_signed(raw: jnp.ndarray) -> jnp.ndarray:
    """(N, 32) uint8 scalars (LE) -> (64, N) f32 signed digits in [-8, 8).

    Recoding: z = x + 0x88...88 (add 136 to every byte, ripple the
    carries), then digit_i = window_i(z) - 8, so x = sum d_i 16^i with
    every d_i in [-8, 7] — no carry chain inside the window loop. Exact
    for x < 2^253 (both s and the reduced challenge k are < L < 2^253);
    a non-canonical s >= 2^253 drops its carry-out and yields a
    wrong-but-well-defined verdict that the host-side s < L check
    already rejects. All intermediates stay exact in f32 (<= 392).
    """
    b = raw.astype(jnp.float32).T  # (32, N)
    carry = jnp.zeros_like(b[0])
    z = []
    for i in range(field.NLIMBS):  # 32-step ripple, unrolled at trace
        t = b[i] + 136.0 + carry
        carry = jnp.floor(t * (1.0 / 256.0))
        z.append(t - 256.0 * carry)
    zb = jnp.stack(z)  # (32, N), carry-out dropped
    hi = jnp.floor(zb * (1.0 / 16.0))
    lo = zb - 16.0 * hi
    win = jnp.stack([hi[::-1], lo[::-1]], axis=1).reshape(
        2 * field.NLIMBS, -1
    )
    return win - 8.0


def _select_b_niels(digit: jnp.ndarray, table: jnp.ndarray) -> curve.NielsPoint:
    """digit: (N,) f32 in [-8, 8); table: (8, 3, 32) const [1..8]B.

    One-hot on |digit| against half the rows of the unsigned scheme,
    identity fixup for digit 0 (Niels identity is (1, 1, 0): add the
    miss mask into limb 0), conditional negation for digit < 0.
    """
    absd = jnp.abs(digit)
    onehot = (
        jnp.arange(1.0, 9.0, dtype=jnp.float32)[:, None] == absd[None, :]
    ).astype(jnp.float32)  # (8, N)
    sel = jnp.einsum("tn,tcl->cln", onehot, table)
    miss = (absd == 0.0).astype(jnp.float32)
    yplusx = sel[0].at[0].add(miss)
    yminusx = sel[1].at[0].add(miss)
    return curve.niels_cneg(digit < 0.0, (yplusx, yminusx, sel[2]))


def _select_lane_cached(digit: jnp.ndarray, table: jnp.ndarray) -> curve.CachedPoint:
    """digit: (N,) in [-8, 8); table: (8, 4, 32, N) cached [1..8]p.

    The broadcast select over the per-lane table is the window loop's
    memory hot spot — signed digits halve the rows it reads. Cached
    identity is (1, 1, 1, 0), restored via the digit-0 fixup.
    """
    absd = jnp.abs(digit)
    onehot = (
        jnp.arange(1.0, 9.0, dtype=jnp.float32)[:, None] == absd[None, :]
    ).astype(jnp.float32)
    sel = (onehot[:, None, None, :] * table).sum(axis=0)
    miss = (absd == 0.0).astype(jnp.float32)
    yplusx = sel[0].at[0].add(miss)
    yminusx = sel[1].at[0].add(miss)
    z = sel[2].at[0].add(miss)
    return curve.cached_cneg(digit < 0.0, (yplusx, yminusx, z, sel[3]))


TABLE_WIDTH = 8  # rows of the per-lane signed-window table: [1..8](-A)


def _build_lane_table(p: curve.Point) -> jnp.ndarray:
    """(8, 4, 32, N) cached-form table of [1..8]p.

    Chained complete additions build the extended multiples (lax.scan
    keeps the traced graph to one pt_add); the conversion to cached form
    (Y+X, Y-X, Z, 2dT) batches the 2d pre-scale of all 8 entries into a
    single wide multiply so the window loop's adds need none. Signed
    windows spend no rows on 0 or the negative multiples, halving the
    14-add chain of the unsigned scheme.
    """
    n = p[0].shape[1]
    w = TABLE_WIDTH
    cached_p = curve.pt_to_cached(p)
    p_stacked = jnp.stack(p)

    def step(acc, _):
        nxt = jnp.stack(
            curve.pt_add_cached((acc[0], acc[1], acc[2], acc[3]), cached_p)
        )
        return nxt, nxt

    _, rows = jax.lax.scan(step, p_stacked, None, length=w - 1)
    ext = jnp.concatenate([p_stacked[None], rows], axis=0)
    # (8, 4, 32, N) extended
    x, y, z, t = ext[:, 0], ext[:, 1], ext[:, 2], ext[:, 3]
    # one wide 2d*T multiply across all 8 entries (lanes folded in)
    t_flat = t.transpose(1, 0, 2).reshape(field.NLIMBS, w * n)
    td2 = field.fe_mul_const(t_flat, field.D2_FE).reshape(field.NLIMBS, w, n)
    td2 = td2.transpose(1, 0, 2)
    yplusx = field.fe_add(
        y.transpose(1, 0, 2).reshape(field.NLIMBS, w * n),
        x.transpose(1, 0, 2).reshape(field.NLIMBS, w * n),
    ).reshape(field.NLIMBS, w, n).transpose(1, 0, 2)
    yminusx = field.fe_sub(
        y.transpose(1, 0, 2).reshape(field.NLIMBS, w * n),
        x.transpose(1, 0, 2).reshape(field.NLIMBS, w * n),
    ).reshape(field.NLIMBS, w, n).transpose(1, 0, 2)
    return jnp.stack([yplusx, yminusx, z, td2], axis=1)


def _dbl_step(_, acc_stacked):
    return jnp.stack(
        curve.pt_double(
            (acc_stacked[0], acc_stacked[1], acc_stacked[2], acc_stacked[3])
        )
    )


def _straus_core(
    a_table: jnp.ndarray, s_win: jnp.ndarray, k_win: jnp.ndarray
) -> curve.Point:
    """64-step shared-doubling window loop over a prebuilt lane table.

    a_table: (8, 4, 32, N) cached-form [1..8](-A) — either built on
    device (:func:`straus_sb_minus_ka`) or gathered from the host-side
    precompute cache (:func:`verify_kernel_tables`).
    """
    nn = a_table.shape[3]
    b_table = jnp.asarray(B_NIELS)
    init = jnp.stack(curve.pt_identity(nn))

    def body(i, acc_stacked):
        acc_stacked = jax.lax.fori_loop(0, 4, _dbl_step, acc_stacked)
        acc = (acc_stacked[0], acc_stacked[1], acc_stacked[2], acc_stacked[3])
        sd = jax.lax.dynamic_index_in_dim(s_win, i, keepdims=False)
        kd = jax.lax.dynamic_index_in_dim(k_win, i, keepdims=False)
        acc = curve.pt_madd(acc, _select_b_niels(sd, b_table))
        acc = curve.pt_add_cached(acc, _select_lane_cached(kd, a_table))
        return jnp.stack(acc)

    acc_stacked = jax.lax.fori_loop(0, NWINDOWS, body, init)
    return (acc_stacked[0], acc_stacked[1], acc_stacked[2], acc_stacked[3])


def straus_sb_minus_ka(
    a_pt: curve.Point, s_win: jnp.ndarray, k_win: jnp.ndarray
) -> curve.Point:
    """Shared-doubling double-scalar core: [s]B - [k]A per lane.

    The same 64-step window loop serves both signature schemes on this
    curve — ed25519 (below) and the schnorrkel/ristretto verifier
    (ops/sr25519_batch.py): their verification equations are both
    instances of [s]B - [k]A - R == identity-class. s_win/k_win are
    signed digits from :func:`_to_windows_signed`.
    """
    neg_a = curve.pt_neg(a_pt)
    return _straus_core(_build_lane_table(neg_a), s_win, k_win)


def _finish_verify(
    acc: curve.Point, r_pt: curve.Point, ok: jnp.ndarray
) -> jnp.ndarray:
    """[s]B - [k]A computed; subtract R, multiply by cofactor 8, test
    identity, mask structurally-invalid lanes."""
    acc = curve.pt_add(acc, curve.pt_neg(r_pt))
    acc_stacked = jax.lax.fori_loop(0, 3, _dbl_step, jnp.stack(acc))
    acc = (acc_stacked[0], acc_stacked[1], acc_stacked[2], acc_stacked[3])
    return curve.pt_is_identity(acc) & ok


def verify_kernel(
    pk_bytes: jnp.ndarray,
    r_bytes: jnp.ndarray,
    s_bytes: jnp.ndarray,
    k_bytes: jnp.ndarray,
) -> jnp.ndarray:
    """(N,32)x4 uint8 -> (N,) bool."""
    a_y, a_sign = _strip_sign(_bytes_to_fe(pk_bytes))
    r_y, r_sign = _strip_sign(_bytes_to_fe(r_bytes))
    s_win = _to_windows_signed(s_bytes)
    k_win = _to_windows_signed(k_bytes)

    # Decompress A and R as one 2N batch: halves the decompression HLO
    # and doubles its SIMD width.
    nn = a_y.shape[1]
    both_pt, both_ok = curve.pt_decompress(
        jnp.concatenate([a_y, r_y], axis=1),
        jnp.concatenate([a_sign, r_sign], axis=0),
    )
    a_pt = tuple(c[:, :nn] for c in both_pt)
    r_pt = tuple(c[:, nn:] for c in both_pt)
    a_ok, r_ok = both_ok[:nn], both_ok[nn:]

    acc = straus_sb_minus_ka(a_pt, s_win, k_win)
    return _finish_verify(acc, r_pt, a_ok & r_ok)


def verify_kernel_tables(
    a_table: jnp.ndarray,
    a_ok: jnp.ndarray,
    r_bytes: jnp.ndarray,
    s_bytes: jnp.ndarray,
    k_bytes: jnp.ndarray,
) -> jnp.ndarray:
    """Cache-hit entry point: the lane tables arrive prebuilt.

    a_table: (8, 4, 32, N) uint8 — gathered [1..8](-A) cached-form
    columns from ops/precompute.py (canonical limbs, so uint8 on the
    wire: 1/4 the H2D bytes of f32). a_ok: (N,) uint8 decompression
    verdicts from the same cache. Skips pt_decompress-of-A and
    _build_lane_table entirely; only R is decompressed on device.
    """
    r_y, r_sign = _strip_sign(_bytes_to_fe(r_bytes))
    s_win = _to_windows_signed(s_bytes)
    k_win = _to_windows_signed(k_bytes)
    r_pt, r_ok = curve.pt_decompress(r_y, r_sign)
    acc = _straus_core(a_table.astype(jnp.float32), s_win, k_win)
    return _finish_verify(acc, r_pt, (a_ok != 0) & r_ok)


def verify_kernel_resident(
    tab_store: jnp.ndarray,
    idx: jnp.ndarray,
    a_ok: jnp.ndarray,
    r_bytes: jnp.ndarray,
    s_bytes: jnp.ndarray,
    k_bytes: jnp.ndarray,
) -> jnp.ndarray:
    """Device-resident entry point: tables stay on device across calls.

    tab_store: (8, 4, 32, K) uint8 — the resident store's device tensor
    (ops/resident.py), uploaded once per validator-set activation.
    idx: (N,) int32 per-lane column indices into it. The gather runs on
    device, so steady-state batches ship 4 bytes per lane where the
    gathered path ships ~1 KiB. Under the mesh the store is replicated
    and ``idx`` lane-sharded, so the take is device-local and the
    gathered table tensor comes out lane-sharded exactly like
    :func:`verify_kernel_tables` always saw it.
    """
    tab = jnp.take(tab_store, idx, axis=3)
    return verify_kernel_tables(tab, a_ok, r_bytes, s_bytes, k_bytes)


def _enable_persistent_cache() -> None:
    """First compilation of the verifier is expensive; persist it across
    processes (driver, tests, bench, chip_smoke.py's phases).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax honours it by itself
    and this sets nothing at all. Otherwise the cache is
    ``<checkout>/.jax_cache``: a fixed path, because the directory is
    part of what a later process must repeat to hit. Thresholds (which
    compiles are worth keeping) stay jax's own, so its
    ``JAX_PERSISTENT_CACHE_*`` variables work from outside too.
    """
    import os

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.dirname(__file__))),
                ".jax_cache",
            ),
        )


_enable_persistent_cache()


@lru_cache(maxsize=64)
def _compiled_kernel(kind: ChunkKind, n: int, backend: Optional[str], mul_impl: str):
    """One compiled XLA verifier per (chunk kind, padded size, backend,
    field-mul impl), for both engines.

    The field-mul impl ("vpu" f32 shifts vs "mxu" int8 dot_general —
    see ops/field_mxu.py) is a trace-time switch on field32, so it is
    pinned here around the trace — under field32's trace lock, so
    concurrent first compilations can't interleave their set/restore —
    and must be part of the cache key. A kind whose store input has no
    lanes re-traces per store width K inside jit.
    """

    def run(*args):
        with field.pinned_mul_impl(mul_impl):
            return kind.kernel(*args)

    run.__name__ = kind.program
    return introspect.traced_first_call(
        jax.jit(run, backend=backend), kind.engine, kind.kernel_name, n
    )


# --- implementation dispatch (XLA graph vs Pallas kernel) -------------------
#
# The Pallas kernel (ops/pallas_verify.py) keeps every field-op
# intermediate in VMEM; the XLA graph materializes them to HBM. Each
# platform has ONE implementation that ``auto`` resolves to, stated
# here; TENDERMINT_TPU_VERIFY_IMPL=pallas|xla|mxu overrides it. "mxu"
# is the XLA graph with field multiplies as int8 dot_general
# contractions (ops/field_mxu.py) instead of f32 VPU shifts. Whichever
# implementation is chosen, a failure in it raises into the health
# machine (ops/device_policy.py) like any device failure and is
# counted; nothing switches to another implementation behind the
# caller's back.

_IMPL_ENV = "TENDERMINT_TPU_VERIFY_IMPL"
# What ``auto`` means per platform. tpu: the Pallas entry points
# compiled on a TPU v5e at 64/256/1024/4096 lanes and agreed with the
# host oracle lane for lane (PR 21's chip run; chip_smoke.py repeats
# it). CPU stays on the XLA graph (Pallas interpret mode is a test
# vehicle, far too slow for real batches). ``pallas`` covers legacy,
# gathered-table and resident-store chunks, on one device and per shard
# of a mesh (parallel/sharding.py; PR 36's chip run); a kind without a
# Pallas entry point runs its XLA graph whatever this says (every kind
# has one since the sr25519 kernel of PR 40).
_AUTO_IMPL = {"tpu": "pallas", "cpu": "xla"}
# Device-vs-host fallback state lives in ops/device_policy.py, shared
# with the sr25519 engine so a broken backend is broken once.


def active_impl(backend: Optional[str] = None) -> str:
    """Which verifier implementation verify_batch will dispatch to."""
    import os

    from tendermint_tpu.ops import backend as backend_mod

    mode = os.environ.get(_IMPL_ENV, "auto").lower()
    if mode in ("mxu", "xla", "pallas"):
        return mode
    return _AUTO_IMPL.get(backend_mod.platform(backend), "xla")


def _mul_impl_for_chunk(impl: str, backend: Optional[str], lanes: int) -> str:
    """Field-mul impl for one padded chunk: the explicit ``mxu`` verify
    impl forces the contraction; otherwise the autotuner's measured
    winner for (platform, bucket) — which is the plain
    ``field32.get_mul_impl()`` default whenever the tuner is off or
    overridden by env."""
    if impl == "mxu":
        return "mxu"
    from tendermint_tpu.ops import autotune

    return autotune.mul_impl_for(backend, lanes)


def _run_chunk(
    kind: ChunkKind,
    inputs: dict,
    backend: Optional[str],
    plan=None,
    sp=tracing.NOP_SPAN,
):
    """Dispatch one padded chunk: the one place a kernel is chosen.

    Returns ``(result, plan_used, impl)``: ``plan_used`` is the (possibly
    degraded) mesh plan when the chunk went out lane-sharded, else None;
    ``impl`` is what the chunk was actually handed to. ``pallas`` takes
    the kind's Pallas entry point, anything else (and a kind without
    one) the XLA graph — on one device and, a usable plan given, on the
    mesh alike (parallel/sharding._sharded_kernel). A mesh that loses
    all usable devices falls through to the single-device dispatch
    below — never to host.

    A resident chunk's store is committed to the context it was
    uploaded for (``mesh_key``: one mesh's devices, or None for one
    single device). When that context is gone — mesh degraded mid-
    batch, or run_chunk_mesh gave up — the store's columns are pulled
    to host, gathered per lane, and the chunk re-enters as a gathered-
    table chunk (rare, and still device compute).

    ``sp`` is the caller's ``dispatch_chunk`` span, which gets the
    chunk's two phases: ``h2d`` (the puts of the per-batch arrays) and
    ``launch`` (the kernel's entry point returning). A sharded call
    takes host arrays and transfers inside itself: ``launch`` alone.
    """
    # TENDERMINT_TPU_VERIFY_IMPL=mxu forces the int8 contraction; the
    # autotuned (or field-level default) impl is honored otherwise.
    impl = active_impl(backend)
    if impl == "pallas" and kind.pallas is None:
        impl = "xla"
    m = kind.lanes(inputs)
    mul_impl = _mul_impl_for_chunk(impl, backend, m)
    bound = kind.store_bound
    if plan is not None and (
        not bound or inputs["mesh_key"] == tuple(plan.device_ids)
    ):
        try:
            out, used = mesh_sharding.run_chunk_mesh(
                kind, inputs, impl, mul_impl, plan, sp
            )
            return out, used, impl
        except mesh_sharding.MeshUnavailableError:
            # Every device excluded: degrade to THIS backend's single-
            # device dispatch below; host fallback stays with the caller.
            pass
    fault_injection.fire(kind.engine + ".chunk")
    if bound and (plan is not None or inputs["mesh_key"] is not None):
        # Context mismatch: materialize the needed columns and take the
        # gathered-table kernel (counted as real per-batch table H2D).
        tab_host = np.asarray(inputs["store"])
        tab = np.ascontiguousarray(tab_host[:, :, :, np.asarray(inputs["idx"])])
        resident.note_table_h2d(tab.nbytes)
        gathered = dict(
            tab=tab, ok=inputs["ok"], r=inputs["r"], s=inputs["s"], k=inputs["k"]
        )
        return _run_chunk(KINDS["tables"], gathered, backend, None, sp)
    put = sp.timed("h2d", jnp.asarray)
    args = tuple(
        inputs[i.name] if i.lane_axis is None else put(inputs[i.name])
        for i in kind.inputs
    )
    if impl == "pallas":
        from tendermint_tpu.ops import pallas_verify

        fn = getattr(pallas_verify, kind.pallas)(m)
    else:
        fn = _compiled_kernel(kind, m, backend, mul_impl)
    return sp.timed("launch", fn)(*args), None, impl


# --- host-side preparation --------------------------------------------------


def _bucket(n: int) -> int:
    """Padded size for n lanes: next bucket, or the next CHUNK multiple
    above CHUNK (large batches are dispatched CHUNK at a time)."""
    for b in _BUCKETS:
        if n <= b:
            return b
    return ((n + CHUNK - 1) // CHUNK) * CHUNK


def _mesh_bucket(n: int, n_dev: int) -> int:
    """Padded size for n lanes sharded over n_dev devices: the
    per-device slab stays in the bucket table so the sharded compile
    cache hits (512 lanes on 8 devices -> 64-lane slabs -> 512)."""
    return _bucket(max(1, -(-n // n_dev))) * n_dev


def _mesh_plan(lanes: int):
    """A mesh plan (parallel/mesh.MeshPlan) when the sharded path
    should serve this batch, else None. Any trouble building one (no
    backend) means 'unsharded', never a verification error."""
    try:
        return mesh_mod.plan_for_lanes(lanes)
    except Exception:  # sharding is an optimization; never block verify
        return None


def _mesh_on_success(plan) -> None:
    try:
        mesh_mod.manager.on_success(plan)
    except Exception:  # health bookkeeping must never fail verification
        pass


def _mesh_abandon(plan) -> None:
    try:
        mesh_mod.manager.abandon(plan)
    except Exception:  # health bookkeeping must never fail verification
        pass


# A known-good padding triple so padded lanes verify true and never mask
# real failures (they are sliced off anyway).
def _make_pad_entry() -> Tuple[bytes, bytes, bytes]:
    from tendermint_tpu.crypto import ed25519_ref as ref

    priv, pub = ref.keypair_from_seed(b"\x42" * 32)
    msg = b"tendermint-tpu-pad"
    return pub, msg, ref.sign(priv, msg)


_PAD_PK, _PAD_MSG, _PAD_SIG = _make_pad_entry()
_PAD_K: Optional[bytes] = None


def _pad_k() -> bytes:
    global _PAD_K
    if _PAD_K is None:
        _PAD_K = sha512_batch_mod_l(
            [_PAD_SIG[:32] + _PAD_PK + _PAD_MSG]
        )[0].tobytes()
    return _PAD_K


# Pad lanes as ready-made arrays, decoded once instead of np.frombuffer
# over the pad triple on every padded prepare call.
_PAD_ROWS: Optional[dict] = None
_PAD_TABLE: Optional[np.ndarray] = None


def _pad_row(name: str) -> np.ndarray:
    """(32,) uint8 pad lane of the input ``pk`` / ``r`` / ``s`` / ``k``."""
    global _PAD_ROWS
    if _PAD_ROWS is None:
        raw = (_PAD_PK, _PAD_SIG[:32], _PAD_SIG[32:], _pad_k())
        _PAD_ROWS = {
            n: np.frombuffer(b, dtype=np.uint8) for n, b in zip(("pk", "r", "s", "k"), raw)
        }
    return _PAD_ROWS[name]


def _pad_table() -> np.ndarray:
    """(8, 4, 32) uint8 signed-window table of the pad pubkey."""
    global _PAD_TABLE
    if _PAD_TABLE is None:
        from tendermint_tpu.ops import precompute

        _PAD_TABLE = precompute.build_table(_PAD_PK)[0]
    return _PAD_TABLE


# --- the chunk kinds of this engine -----------------------------------------
#
# What each kernel takes, in its argument order, and what a pad lane of
# each input is (ops/chunk_kinds.py says what the fields mean). A pad
# lane of ``idx`` is column 0, the pad-key table reserved at upload.


def _byte_rows(*names: str) -> Tuple[ChunkInput, ...]:
    return tuple(ChunkInput(n, 0, lambda n=n: _pad_row(n)) for n in names)


_OK = ChunkInput("ok", 0, lambda: np.uint8(1))
KINDS = {
    kind.name: kind
    for kind in (
        ChunkKind(
            "legacy", "ed25519", "verify", verify_kernel, "compiled_verify",
            _byte_rows("pk", "r", "s", "k"),
        ),
        ChunkKind(
            "tables", "ed25519", "verify_tables", verify_kernel_tables,
            "compiled_verify_tables",
            (ChunkInput("tab", 3, _pad_table), _OK) + _byte_rows("r", "s", "k"),
        ),
        ChunkKind(
            "resident", "ed25519", "verify_resident", verify_kernel_resident,
            "compiled_verify_resident",
            (ChunkInput("store", None), ChunkInput("idx", 0, lambda: np.int32(0)), _OK)
            + _byte_rows("r", "s", "k"),
        ),
    )
}


def canonical_lt(arr_le: np.ndarray, bound_be: np.ndarray) -> np.ndarray:
    """(N, 32) little-endian values -> (N,) bool value < bound, no
    Python loop (shared by the ed25519 s < L and the ristretto
    encoding < p checks; equality is non-canonical -> False)."""
    be = arr_le[:, ::-1].astype(np.int16)
    diff = be - bound_be.astype(np.int16)[None, :]
    nz = diff != 0
    first = np.argmax(nz, axis=1)
    rows = np.arange(arr_le.shape[0])
    val = diff[rows, first]
    return np.where(nz.any(axis=1), val < 0, False)


def _s_canonical(s_arr: np.ndarray) -> np.ndarray:
    """(N, 32) little-endian s -> (N,) bool s < L."""
    return canonical_lt(s_arr, _L_BYTES_BE)


def _challenge_k(
    prefix: np.ndarray, msgs: Sequence[bytes], backend: Optional[str]
) -> np.ndarray:
    """Challenge scalars k = SHA-512(R‖A‖M) mod L for well-formed lanes.

    Where device hashing is on and applies (ops/hash512: fixed-width
    vote batches) the fused kernel hashes on the accelerator and the
    host's share of prep shrinks to byte packing; batches it does not
    apply to take the host path, hashed and reduced mod L in one pass of
    the C extension (hashlib where that has no compiler). A failing
    device kernel raises — it does not become host hashing. The open
    span (the engine's ``prep_chunk``) is tagged with the path that ran,
    ``hash="device"|"native"|"hashlib"``, and ``_prep_rows`` times this
    call as that span's ``hash`` phase: ``hash_us`` against the span's
    time splits prep into hashing and packing.
    """
    from tendermint_tpu.ops import hash512

    k_dev = hash512.try_challenge_device(prefix, msgs, backend)
    if k_dev is not None:
        tracing.tag(hash="device")
        return np.asarray(k_dev)
    tracing.tag(hash=host_hash_impl())
    return sha512_batch_prefixed_mod_l(prefix, msgs)


def _prep_rows(
    pks: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    backend: Optional[str],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """The head every kind's prep shares, for well-formed lanes: two
    joins + one prefixed C hash call, no per-signature Python work.
    Returns ``(pk, r, s, k, host_ok)``: (n, 32) uint8 each and the
    (n,) bool s < L verdicts."""
    n = len(pks)
    pk_arr = np.frombuffer(b"".join(pks), dtype=np.uint8).reshape(n, 32)
    sig_arr = np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(n, 64)
    r_arr, s_arr = sig_arr[:, :32], sig_arr[:, 32:]
    prefix = np.concatenate([r_arr, pk_arr], axis=1)  # (n, 64) = R || A
    k_arr = tracing.timed("hash", _challenge_k)(prefix, msgs, backend)
    return pk_arr, r_arr, s_arr, k_arr, _s_canonical(s_arr)


def prepare_batch(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    pad_to: Optional[int] = None,
    backend: Optional[str] = None,
) -> Tuple[dict, np.ndarray]:
    """Host prep: batch-hash challenges, stack raw bytes, pad to bucket.

    Returns (device inputs dict of (M,32) uint8 arrays, host_ok (N,)
    bool of structural checks: lengths and s < L canonicity)."""
    n = len(pubkeys)
    if all(len(pk) == 32 and len(sg) == 64 for pk, sg in zip(pubkeys, sigs)):
        # Fast path (every batch from commit verification).
        pk_arr, r_arr, s_arr, k_arr, host_ok = _prep_rows(pubkeys, msgs, sigs, backend)
    else:
        host_ok = np.ones(n, dtype=bool)
        pk_arr = np.zeros((n, 32), dtype=np.uint8)
        r_arr = np.zeros((n, 32), dtype=np.uint8)
        s_arr = np.zeros((n, 32), dtype=np.uint8)
        hash_inputs: List[bytes] = []
        hash_rows: List[int] = []
        for i, (pk, msg, sig) in enumerate(zip(pubkeys, msgs, sigs)):
            if len(pk) != 32 or len(sig) != 64:
                host_ok[i] = False
                continue
            pk_arr[i] = np.frombuffer(pk, dtype=np.uint8)
            r_arr[i] = np.frombuffer(sig[:32], dtype=np.uint8)
            s_arr[i] = np.frombuffer(sig[32:], dtype=np.uint8)
            hash_inputs.append(sig[:32] + pk + msg)
            hash_rows.append(i)
        host_ok &= _s_canonical(s_arr)
        k_arr = np.zeros((n, 32), dtype=np.uint8)
        if hash_inputs:
            k_arr[np.asarray(hash_rows)] = sha512_batch_mod_l(hash_inputs)
            tracing.tag(hash=host_hash_impl())

    inputs = dict(pk=pk_arr, r=r_arr, s=s_arr, k=k_arr)
    m = pad_to if pad_to is not None else _bucket(n)
    return KINDS["legacy"].pad_lanes(inputs, m - n), host_ok


def _prep_table_chunk(
    pks: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    tabs: Sequence[np.ndarray],
    oks: Sequence[bool],
    pad_to: int,
    backend: Optional[str] = None,
) -> Tuple[dict, np.ndarray]:
    """Host prep for a cache-hit chunk: hash challenges, stack the
    gathered per-key table columns into the kernel's (8, 4, 32, M)
    uint8 input. Lengths are pre-validated by the caller (ill-formed
    lanes stay on the legacy path)."""
    n = len(pks)
    _, r_arr, s_arr, k_arr, host_ok = _prep_rows(pks, msgs, sigs, backend)
    inputs = dict(
        tab=np.stack(tabs).transpose(1, 2, 3, 0),  # (n, 8, 4, 32) -> lanes last
        ok=np.fromiter(oks, dtype=bool, count=n).astype(np.uint8),
        r=r_arr,
        s=s_arr,
        k=k_arr,
    )
    inputs = KINDS["tables"].pad_lanes(inputs, pad_to - n)
    inputs["tab"] = np.ascontiguousarray(inputs["tab"])
    # every gathered chunk re-ships its table tensor; the resident store
    # accounts it so benches can prove the steady-state delta
    resident.note_table_h2d(inputs["tab"].nbytes)
    return inputs, host_ok


def _prep_resident_chunk(
    pks: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    idxs: np.ndarray,
    oks: np.ndarray,
    store_tab,
    mesh_key,
    pad_to: int,
    backend: Optional[str] = None,
) -> Tuple[dict, np.ndarray]:
    """Host prep for a resident-store chunk: the table tensor is already
    on device, so the per-batch payload is the (M,) int32 gather index
    vector plus the usual r/s/k rows."""
    _, r_arr, s_arr, k_arr, host_ok = _prep_rows(pks, msgs, sigs, backend)
    inputs = dict(
        store=store_tab,
        mesh_key=mesh_key,
        idx=np.asarray(idxs, dtype=np.int32),
        ok=np.asarray(oks, dtype=np.uint8),
        r=r_arr,
        s=s_arr,
        k=k_arr,
    )
    return KINDS["resident"].pad_lanes(inputs, pad_to - len(pks)), host_ok


def _host_verify_rows(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    rows,
) -> np.ndarray:
    """CPU oracle over a row subset of the original (unpadded) batch."""
    from tendermint_tpu.crypto.ed25519_ref import verify_zip215

    return np.array(
        [verify_zip215(pubkeys[i], msgs[i], sigs[i]) for i in rows],
        dtype=bool,
    )


# --- the dispatch loop (both engines) ---------------------------------------


class _Job:
    """One padded chunk of a batch. ``rows`` are original batch indices;
    the padded tail is sliced off at scatter time."""

    __slots__ = ("kind", "rows", "prepped", "out", "plan")

    def __init__(self, kind: ChunkKind, rows: np.ndarray):
        self.kind = kind
        self.rows = rows
        self.prepped = None  # (inputs dict, host_ok) once prep ran
        self.out = None  # in-flight device result
        self.plan = None  # mesh plan this chunk dispatched on (or None)


def _chunk_rows(rows: np.ndarray, span: int) -> List[np.ndarray]:
    return [rows[lo : lo + span] for lo in range(0, len(rows), span)]


def _mesh_span(lanes: int):
    """``(plan, span)`` for a batch: the mesh plan serving it (or None)
    and the lanes one job may hold. With a plan, chunks span all its
    devices — span and padding scale by the device count so each chip
    still sees bucket-size slabs."""
    plan = _mesh_plan(lanes)
    return plan, CHUNK * (plan.n_dev if plan is not None else 1)


# Engines that have dispatched a full job in this process: the kernel
# of a job's width is compiled, its first call behind it (job_has_run).
_ENGINES_WITH_A_JOB_RUN: set = set()


def job_has_run(engine: str) -> bool:
    """Whether ``engine`` (``ed25519`` | ``sr25519``) has dispatched a
    chunk of a full job's lanes in this process. Until it has, a
    job's first launch compiles, or loads, its kernel for seconds: a
    caller that hands the engine a batch a block at a time
    (crypto/batch.DeviceBatchVerifier) sends that batch whole, so that
    a process's first calls come in the order, and from the stack, they
    always did (the compile cache's key holds the stack)."""
    return engine in _ENGINES_WITH_A_JOB_RUN


def job_lanes() -> int:
    """The lanes one job holds in a batch of a job's lanes or more:
    ``CHUNK`` a device a plan for that batch would span — what
    :func:`_mesh_span` answers there, asked without ``manager.plan()``,
    which reserves the probe attempts of devices cooling down. For a
    caller that cuts its own batch at the engine's seams
    (crypto/batch.DeviceBatchVerifier). A device the mesh has excluded
    is counted all the same: its plan's jobs are then narrower than
    this, and a block of these lanes is cut in two."""
    try:
        forced = mesh_mod.manager.forced_mesh()
        n_dev = forced.devices.size if forced is not None else mesh_mod.manager.device_count()
    except Exception:  # as _mesh_plan: any trouble means 'unsharded'
        n_dev = 1
    return CHUNK * max(1, n_dev)


def _mesh_collect_retry(job: _Job, backend: Optional[str], exc: Exception):
    """A sharded chunk died at materialization. If the failure is
    attributable to one device, exclude it, rebuild a smaller mesh, and
    re-dispatch THIS chunk on it — 'a sick chip degrades the mesh, not
    to host' holds for collect-time failures too. Returns the chunk's
    verdict array, or None so the caller keeps its ordinary host
    fallback (unattributed failure, or the retry failed as well)."""
    try:
        culprit = mesh_mod.manager.on_failure(job.plan, exc)
        if culprit is None:
            return None
        nxt = mesh_mod.manager.replan(job.plan)
        if nxt is None:
            return None
        import warnings

        warnings.warn(
            f"sharded chunk ({job.kind.name}) failed at collect ({exc!r}); "
            f"device {culprit} excluded, retrying on a {nxt.n_dev}-device mesh"
        )
        out, used, _ = _run_chunk(job.kind, job.prepped[0], backend, nxt)
        if used is None:
            ok = np.asarray(out)
        else:
            ok = mesh_sharding.collect_sharded(out, job.kind.engine)
            _mesh_on_success(used)
        job.plan = used
        return ok
    except Exception:  # retry is best-effort; host fallback covers the chunk
        return None


def _dispatch_jobs(
    engine: str, n: int, jobs: List[_Job], prep_job, host_verify, backend, plan, attempt
) -> "_PendingJobs":
    """Prepare and dispatch ``jobs``: the first half of the one loop both
    engines reach the device through. Returns the batch as it stands
    after its last dispatch, its chunks in flight; ``collect()`` of that
    is the second half.

    ``prep_job(job, pad_to)`` is the engine's host prep: it returns
    ``(inputs, host_ok)``, the chunk's kernel inputs padded to
    ``pad_to`` lanes and the structural verdicts of its rows.
    ``host_verify(rows)`` is the engine's CPU oracle. ``plan`` is the
    batch's mesh plan (a plan degraded mid-batch replaces it, so later
    chunks ride the smaller mesh) and ``attempt`` the health machine's
    admission of this call.

    Dispatch is double-buffered: job j's kernel is enqueued (JAX async
    dispatch), then job j+1's host prep runs while the device crunches
    job j. A job whose prep or dispatch fails is left for the oracle at
    collect time while the rest stay on the device, if the health
    machine (ops/device_policy.py) still admits them.
    """
    import warnings

    health = device_policy.shared
    host_ok_all = np.ones(n, dtype=bool)
    mesh_used = False

    def prep(job: _Job) -> None:
        nonlocal attempt
        lanes = len(job.rows)
        try:
            with tracing.span(
                "prep_chunk", stage="prep", engine=engine, kind=job.kind.name, lanes=lanes
            ):
                pad_to = (
                    _mesh_bucket(lanes, plan.n_dev) if plan is not None else _bucket(lanes)
                )
                job.prepped = prep_job(job, pad_to)
        except Exception as exc:
            # Host prep failed before any device work for this job. Never
            # take the node down over infrastructure — its lanes degrade to
            # the host oracle at collect time.
            health.record_failure(exc, attempt)
            attempt = None
            warnings.warn(
                f"chunk prepare failed ({exc!r}); CPU fallback for "
                f"{lanes} lanes (device state={health.state})"
            )

    for j, job in enumerate(jobs):
        if j == 0:
            prep(job)
        if job.prepped is not None:
            inputs, host_ok = job.prepped
            host_ok_all[job.rows] = host_ok[: len(job.rows)]
            if attempt is None:
                attempt = health.begin_attempt(engine)
            if attempt is not None:
                try:
                    with tracing.span(
                        "dispatch_chunk",
                        stage="dispatch",
                        engine=engine,
                        kind=job.kind.name,
                        lanes=len(job.rows),
                        chunk=j,
                        chunks=len(jobs),
                    ) as dsp:
                        if dsp.live:
                            dsp.set(
                                padded_lanes=job.kind.lanes(inputs),
                                h2d_bytes=job.kind.h2d_bytes(inputs),
                            )
                        job.out, job.plan, impl = _run_chunk(
                            job.kind, inputs, backend, plan, dsp
                        )
                        if dsp.live:
                            dsp.set(impl=impl)
                    if job.plan is not None:
                        mesh_used = True
                        plan = job.plan  # degraded: later chunks follow
                    health.note_inflight(engine, len(job.rows))
                    if engine not in _ENGINES_WITH_A_JOB_RUN and len(job.rows) >= job_lanes():
                        _ENGINES_WITH_A_JOB_RUN.add(engine)
                except Exception as exc:
                    health.record_failure(exc, attempt)
                    attempt = None
                    warnings.warn(
                        f"device chunk ({job.kind.name}, {len(job.rows)} lanes) "
                        f"dispatch failed ({exc!r}); CPU fallback for the "
                        f"chunk (device state={health.state})"
                    )
        if j + 1 < len(jobs):
            prep(jobs[j + 1])

    if plan is not None and not mesh_used:
        # Planned but never dispatched sharded (e.g. the shared health
        # machine denied every chunk): release probe reservations.
        _mesh_abandon(plan)
    return _PendingJobs(engine, n, jobs, host_verify, backend, attempt, host_ok_all)


class _PendingJobs:
    """A batch between its last dispatch and its first collect: what
    :func:`_dispatch_jobs` returns. The caller may do other host work
    while the chunks run (crypto/batch.MultiBatchVerifier dispatches a
    second engine's and verifies its host-only lanes); ``collect()``
    then finishes the batch, once."""

    def __init__(self, engine, n, jobs, host_verify, backend, attempt, host_ok_all):
        self.engine = engine
        self.n = n
        self.jobs = jobs
        self.host_verify = host_verify
        self.backend = backend
        self.attempt = attempt
        self.host_ok_all = host_ok_all  # (n,) bool structural verdicts from prep

    @property
    def lanes_inflight(self) -> int:
        """Lanes dispatched and not yet collected."""
        return sum(len(job.rows) for job in self.jobs if job.out is not None)

    def collect(self) -> np.ndarray:
        """Collect every chunk; returns the (n,) bool verdicts. JAX
        dispatch is async, so runtime errors can surface at
        materialization: a chunk that fails here, as one that was never
        dispatched, is re-verified on the oracle alone."""
        import warnings

        engine, attempt, host_ok_all = self.engine, self.attempt, self.host_ok_all
        health = device_policy.shared
        results = np.ones(self.n, dtype=bool)
        fallback_lanes = 0
        device_chunks_ok = 0
        for j, job in enumerate(self.jobs):
            ok = None
            if job.out is not None:
                try:
                    with tracing.span(
                        "collect_chunk",
                        stage="collect",
                        engine=engine,
                        kind=job.kind.name,
                        lanes=len(job.rows),
                        chunk=j,
                    ) as csp:
                        fault_injection.fire(engine + ".collect")
                        if csp.live:
                            # the one blocking line, cut in two: until the
                            # device is done, then what is left of the copy
                            # back, which starts now, as np.asarray starts it
                            job.out.copy_to_host_async()
                            csp.timed("wait", jax.block_until_ready)(job.out)
                        if job.plan is not None:
                            ok = csp.timed("d2h", mesh_sharding.collect_sharded)(
                                job.out, engine
                            )
                        else:
                            ok = csp.timed("d2h", np.asarray)(job.out)
                        if csp.live:
                            csp.set(d2h_bytes=int(ok.nbytes))
                    device_chunks_ok += 1
                    if job.plan is not None:
                        _mesh_on_success(job.plan)
                except Exception as exc:
                    if job.plan is not None:
                        ok = _mesh_collect_retry(job, self.backend, exc)
                    if ok is not None:
                        device_chunks_ok += 1
                    else:
                        health.record_failure(exc, attempt)
                        attempt = None
                        warnings.warn(
                            f"device chunk ({job.kind.name}, {len(job.rows)} lanes) "
                            f"failed at collect ({exc!r}); CPU fallback for the "
                            f"chunk (device state={health.state})"
                        )
                finally:
                    health.note_inflight(engine, -len(job.rows))
            if not len(job.rows):
                continue
            if ok is None:
                fallback_lanes += len(job.rows)
                with tracing.span(
                    "host_fallback", stage="fallback", engine=engine, lanes=len(job.rows)
                ):
                    results[job.rows] = self.host_verify(job.rows)
                host_ok_all[job.rows] = True  # oracle verdicts are final
            else:
                results[job.rows] = ok[: len(job.rows)]

        if fallback_lanes:
            health.count_fallback(engine, fallback_lanes)
        if attempt is not None and device_chunks_ok:
            # No failure consumed the attempt and device work round-tripped:
            # re-promote (clears DEGRADED, completes a half-open probe).
            health.record_success(attempt)
        return np.logical_and(results, host_ok_all)


def _undispatched(engine: str, n: int, host_verify) -> _PendingJobs:
    """The batch the health machine did not admit (DISABLED, or cooling
    down while another caller holds the probe slot): one chunk that was
    never dispatched, so ``collect()`` hands every lane to the oracle
    under ``host_fallback`` and counts it — the circuit breaker never
    blocks, and nothing is prepared for a device that will not be
    asked."""
    jobs = [_Job(None, np.arange(n))]
    return _PendingJobs(engine, n, jobs, host_verify, None, None, np.ones(n, dtype=bool))


class PendingBatch:
    """An engine's batch whose chunks are dispatched and not collected:
    what ``begin_verify_batch`` / ``begin_verify_batch_sr`` return.
    ``finish()`` collects them and returns the per-entry verdicts, under
    a ``verify_batch`` span of its own (``phase`` ``collect``; the begin
    ran under one with ``phase`` ``dispatch``), so that the two never
    hold what the caller did in between."""

    def __init__(self, engine: str, lanes: int, pending: Optional[_PendingJobs], settle):
        self.engine = engine
        self.lanes = lanes
        self._pending = pending  # None: no lane was left for the device
        self._settle = settle  # (n,) bool verdicts of the pending lanes -> List[bool]

    @property
    def lanes_inflight(self) -> int:
        return 0 if self._pending is None else self._pending.lanes_inflight

    def finish(self) -> List[bool]:
        with tracing.span(
            "verify_batch", engine=self.engine, lanes=self.lanes, phase="collect"
        ) as vsp:
            vsp.process_cpu()
            pending, self._pending = self._pending, None
            return self._settle(None if pending is None else pending.collect())


def _early_tag(early: bool) -> dict:
    """The dispatch span's mark of a block begun before ``verify()``."""
    return {"early": 1} if early else {}


class _CacheFront:
    """The verdict cache around one batch (precompute.ResultCache),
    asked and filled once each: the constructor's lookup derives the
    keys, ``settle`` takes them back with the verdicts of ``lanes``,
    the ``(pubkeys, msgs, sigs)`` no entry answered (None where every
    lane hit). With no hit at all, or the cache off, ``lanes`` is the
    whole batch (what a batch of unseen votes sends)."""

    def __init__(self, pubkeys, msgs, sigs):
        from tendermint_tpu.ops import precompute

        self._results = precompute.results
        self.n = n = len(pubkeys)
        self.keys = self.cached = self.missed = None
        if precompute.result_cache_enabled():
            with tracing.span(
                "cache_lookup", stage="cache_lookup", engine="ed25519", lanes=n
            ) as csp:
                self.keys, self.cached = self._results.get_many(pubkeys, msgs, sigs)
                self.missed = [i for i, v in enumerate(self.cached or ()) if v is None]
                csp.set(hits=0 if self.cached is None else n - len(self.missed))
        if self.cached is None:
            self.lanes = (pubkeys, msgs, sigs)
        elif self.missed:
            self.lanes = tuple([seq[i] for i in self.missed] for seq in (pubkeys, msgs, sigs))
        else:
            self.lanes = None

    def settle(self, out: Optional[np.ndarray]) -> List[bool]:
        """Store the engine's verdicts of ``lanes`` (the store stays
        behind the device's or the oracle's answer), merge them with
        the hits, and return the batch's verdicts in its own order."""
        if self.cached is None:
            verdicts = out
            if self.keys is not None:
                with tracing.span("cache_store", lanes=self.n) as ssp:
                    ssp.set(evicted=self._results.put_many(self.keys, verdicts))
        else:
            verdicts = np.array([v is True for v in self.cached], dtype=bool)
            if self.missed:
                with tracing.span("cache_store", lanes=len(self.missed)) as ssp:
                    ssp.set(
                        evicted=self._results.put_many(
                            [self.keys[i] for i in self.missed], out
                        )
                    )
                verdicts[self.missed] = out
        with tracing.span("merge_results", lanes=self.n):
            return verdicts.tolist()


def verify_batch(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    backend: Optional[str] = None,
) -> List[bool]:
    """Batch ZIP-215 verification; returns per-entry validity.

    The entry point behind crypto.Ed25519BatchVerifier — reference
    contract crypto/crypto.go:58-76 / crypto/ed25519/ed25519.go:198-233.

    The amortized pipeline (ops/precompute.py):

    1. The digest-keyed result cache answers lanes verified before
       (identical last-commit votes at height H+1, vote floods).
    2. Remaining lanes are partitioned: keys with a cached (or
       eligible-to-build) signed-window table take the table kernel,
       which skips per-lane decompression and table building; the rest
       take the legacy build-on-device kernel.
    3. Chunks are double-buffered: the kernel for chunk i is enqueued
       (JAX async dispatch), then chunk i+1's host prep — challenge
       hashing and table gather — runs while the device crunches
       chunk i, so host prep and H2D overlap device compute.

    Device failures degrade per chunk, not per process: a chunk whose
    dispatch or materialization fails is re-verified on the CPU oracle
    while the rest of the batch stays on the device (if the health
    state machine — ops/device_policy.py — still admits it). A batch
    that completes on the device re-promotes a degraded path; the
    state machine alone decides when the device is cooling down or
    disabled, and it recovers via half-open probe batches.
    """
    n = len(pubkeys)
    if n == 0:
        return []
    with tracing.span("verify_batch", engine="ed25519", lanes=n) as vsp:
        vsp.process_cpu()
        front = _CacheFront(pubkeys, msgs, sigs)
        # by its name: the seam tests and chipbench/breaks.py stand in
        return front.settle(
            _verify_uncached(*front.lanes, backend) if front.lanes else None
        )


def begin_verify_batch(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    backend: Optional[str] = None,
    early: bool = False,
) -> PendingBatch:
    """:func:`verify_batch` in two steps, for a caller with host work to
    do while the device runs (crypto/batch.MultiBatchVerifier): this one
    does everything up to and including the last ``dispatch_chunk``
    (lookup, route, gather, the double-buffered prep/dispatch loop),
    ``finish()`` of what it returns the collects, the cache store and
    the merge. ``begin_verify_batch(...).finish()`` is ``verify_batch``
    statement for statement, under two ``verify_batch`` spans (``phase``
    ``dispatch`` / ``collect``) where that opens one. ``early`` says
    that the lanes are a block of a batch still being added to
    (crypto/batch.DeviceBatchVerifier): ``early=1`` on the dispatch
    span, and nothing else."""
    n = len(pubkeys)
    if n == 0:
        return PendingBatch("ed25519", 0, None, lambda out: [])
    with tracing.span(
        "verify_batch", engine="ed25519", lanes=n, phase="dispatch", **_early_tag(early)
    ) as vsp:
        vsp.process_cpu()
        front = _CacheFront(pubkeys, msgs, sigs)
        pending = _begin_uncached(*front.lanes, backend) if front.lanes else None
    return PendingBatch("ed25519", n, pending, front.settle)


def _verify_uncached(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    backend: Optional[str] = None,
) -> np.ndarray:
    """Device verification of lanes the result cache could not answer."""
    return _begin_uncached(pubkeys, msgs, sigs, backend).collect()


def _begin_uncached(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    backend: Optional[str] = None,
) -> _PendingJobs:
    """:func:`_verify_uncached` up to and including its last dispatch."""
    from tendermint_tpu.ops import precompute

    health = device_policy.shared
    n = len(pubkeys)

    def host_verify(rows) -> np.ndarray:
        return _host_verify_rows(pubkeys, msgs, sigs, rows)

    attempt = health.begin_attempt("ed25519")
    if attempt is None:
        return _undispatched("ed25519", n, host_verify)

    # Partition: lanes whose key has a cached (or eligible, host-built)
    # table take the table kernel; ill-formed lanes must stay on the
    # legacy path, whose slow-path prep handles bad lengths.
    try:
        entries, has_table = precompute.tables.gather(pubkeys)
    except Exception:  # cache trouble never blocks verification
        entries, has_table = None, np.zeros(n, dtype=bool)
    # From the gather's answer to the job list: which lanes ride which
    # kernel, on which devices, in which chunks.
    with tracing.span("route_lanes", lanes=n) as rsp:
        if entries is not None:
            well_formed = np.fromiter(
                (len(pk) == 32 and len(sg) == 64 for pk, sg in zip(pubkeys, sigs)),
                dtype=bool,
                count=n,
            )
            has_table &= well_formed
        if entries is None or not has_table.any():
            has_table = np.zeros(n, dtype=bool)
            entries = None

        plan, span = _mesh_span(n)

        # Resident routing: lanes whose key already lives in the device-
        # resident store ship only gather indices — zero per-batch table
        # H2D. Any trouble leaves every cached lane on the gathered path.
        res_idx = res_ok_cols = res_tab = res_mesh_key = None
        res_mask = np.zeros(n, dtype=bool)
        if entries is not None:
            try:
                res = resident.acquire(
                    pubkeys, has_table, plan=plan, backend=backend
                )
            except Exception:  # resident path is an optimization, never a gate
                res = None
            if res is not None:
                res_mask, res_idx, res_ok_cols, res_tab, res_mesh_key = res
        table_mask = has_table & ~res_mask

        jobs = [
            _Job(KINDS[name], rows)
            for name, mask in (
                ("resident", res_mask),
                ("tables", table_mask),
                ("legacy", ~has_table),
            )
            for rows in _chunk_rows(np.nonzero(mask)[0], span)
        ]
        if rsp.live:
            rsp.set(
                resident=int(res_mask.sum()),
                tables=int(table_mask.sum()),
                legacy=int(n - has_table.sum()),
                jobs=len(jobs),
            )

    def prep_job(job: _Job, pad_to: int) -> Tuple[dict, np.ndarray]:
        pks = [pubkeys[i] for i in job.rows]
        ms = [msgs[i] for i in job.rows]
        sgs = [sigs[i] for i in job.rows]
        if job.kind.name == "resident":
            idxs = res_idx[job.rows]
            return _prep_resident_chunk(
                pks, ms, sgs, idxs, res_ok_cols[idxs], res_tab, res_mesh_key,
                pad_to, backend=backend,
            )
        if job.kind.name == "tables":
            return _prep_table_chunk(
                pks, ms, sgs,
                [entries[i][0] for i in job.rows],
                [entries[i][1] for i in job.rows],
                pad_to, backend=backend,
            )
        return prepare_batch(pks, ms, sgs, pad_to=pad_to, backend=backend)

    return _dispatch_jobs(
        "ed25519", n, jobs, prep_job, host_verify, backend, plan, attempt
    )
