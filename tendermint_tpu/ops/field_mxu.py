"""GF(2^255 - 19) multiply as an int8 x int8 -> int32 MXU contraction.

The f32 engine (:mod:`field32`) runs the schoolbook limb product on the
VPU: 32 shifted multiply-adds of (32, N) f32 arrays, ~1024 f32 MACs per
lane per multiply. What bounds that path on the chip is not measured;
the v5e MXU's int8 path (int8 x int8 accumulating in int32) has the
higher published arithmetic peak, which is why this formulation exists.
Whether it wins is the autotuner's measurement (ops/autotune.py), per
bucket, on the chip it runs on.

This module reformulates the product as a *batched matrix contraction*
the MXU executes:

- operands are split limb-wise into 64 radix-16 digits ("nibbles"):
  a radix-256 limb v <= 450 (the loose invariant of field32) becomes
  lo = v mod 16 <= 15 and hi = v div 16 <= 28 — both comfortably int8;
- the schoolbook convolution ``cols16[k] = sum_i anib[i] * bnib[k-i]``
  becomes ONE ``lax.dot_general`` between the Toeplitz expansion of the
  a-digits, shape (127, 64, N) int8, and the b-digits (64, N) int8,
  contracting the 64-digit axis with the lane axis as a batch dimension
  and ``preferred_element_type=int32`` — the canonical quantized-matmul
  pattern XLA lowers to the MXU's int8 systolic path;
- the 127 radix-16 columns (each <= 64 * 28^2 < 2^16) repack in int32
  into 64 radix-256 columns (< 2^20), which are exact in f32, so the
  2^256 = 38 fold and the carry tail reuse :mod:`field32`'s proven
  machinery; the output satisfies the same loose invariant (limbs
  <= 293) as ``field32.fe_mul``.

The formulation is selected per compiled kernel via
``field32.set_mul_impl("mxu")`` (env ``TENDERMINT_TPU_FIELD_MUL``) and
benchmarked with ``bench.py --impl=mxu``; parity with the f32 engine
and with the host oracle is pinned by tests/test_mxu_field.py on the
CPU backend. Reference contract unchanged: batched verification semantics
of crypto/ed25519/ed25519.go:198-233.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.ops import field32 as field

NLIMBS = field.NLIMBS  # 32 radix-256 limbs
NDIGITS = 2 * NLIMBS  # 64 radix-16 digits
NCOLS16 = 2 * NDIGITS - 1  # 127 product columns in radix 16

# Toeplitz gather indices: T[c, j] = digits[c - j], with out-of-range
# entries pointing at a zero row appended at index NDIGITS.
_TOEP_IDX = np.full((NCOLS16, NDIGITS), NDIGITS, dtype=np.int32)
for _c in range(NCOLS16):
    for _j in range(NDIGITS):
        if 0 <= _c - _j < NDIGITS:
            _TOEP_IDX[_c, _j] = _c - _j


def _to_digits(a: jnp.ndarray) -> jnp.ndarray:
    """(32, N) f32 limbs (loose, <= 450) -> (64, N) int8 radix-16 digits.

    Split in f32 (exact for these magnitudes), then narrow: lo <= 15,
    hi <= 450/16 < 29 — both inside int8.
    """
    hi = jnp.floor(a * (1.0 / 16.0))
    lo = a - 16.0 * hi
    inter = jnp.stack([lo, hi], axis=1).reshape(NDIGITS, -1)
    return inter.astype(jnp.int8)


def fe_mul_mxu(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Exact field multiply with the product columns on the MXU.

    Same contract as :func:`field32.fe_mul`: loose inputs (limbs in
    [0, 450]) -> loose output (limbs <= 293).
    """
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape)
    b = jnp.broadcast_to(b, shape)

    a_dig = _to_digits(a)  # (64, N) int8
    b_dig = _to_digits(b)  # (64, N) int8
    n = a_dig.shape[1]

    a_pad = jnp.concatenate([a_dig, jnp.zeros((1, n), dtype=jnp.int8)], axis=0)
    toep = a_pad[jnp.asarray(_TOEP_IDX)]  # (127, 64, N) int8

    # Contract the digit axis, batch over lanes: int8 x int8 -> int32.
    cols16 = jax.lax.dot_general(
        toep,
        b_dig,
        dimension_numbers=(((1,), (0,)), ((2,), (1,))),
        preferred_element_type=jnp.int32,
    )  # (N, 127) int32, each column <= 64 * 29^2 < 2^16

    cols16 = cols16.T  # (127, N)
    cols16 = jnp.concatenate(
        [cols16, jnp.zeros((1, n), dtype=jnp.int32)], axis=0
    )  # pad to 128 = 2 * 64
    pairs = cols16.reshape(NDIGITS, 2, n)
    col256 = pairs[:, 0] + 16 * pairs[:, 1]  # (64, N) int32, < 2^21

    # Fold 256^32 = 38 (mod p). Columns 32..63 carry weights 38 * 256^j
    # for j = 0..31; splitting each into 8-bit digit + carry keeps every
    # folded term < 2^18. The carry of column 63 lands on limb 32 and
    # folds once more: 256^32 = 38 -> weight 38 * 38 at limb 0 (its
    # magnitude is tiny: col 63 = hi_a[31] * hi_b[31] <= 29^2).
    lo = col256[:NLIMBS]
    hi = col256[NLIMBS:]
    hi_hi = hi >> 8
    hi_lo = hi & 255
    lo = lo + 38 * hi_lo
    lo = lo.at[1:].add(38 * hi_hi[: NLIMBS - 1])
    lo = lo.at[0].add((38 * 38) * hi_hi[NLIMBS - 1])

    # All limbs < 2^22 — exact in f32; finish with the proven carry tail.
    return field.fe_carry(lo.astype(jnp.float32))
