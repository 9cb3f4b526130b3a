"""Operations and bytes of one sr25519 lane, counted as ``opcount.py``
counts ed25519's: from the algorithm, whatever implements it, in field
multiplications of 2,048 operations each, useful lanes only.

One lane is one schnorrkel verification by the Straus double-scalar
multiplication the ed25519 kernels run: ristretto DECODE (RFC 9496
4.3.1) of A and of R, the lane's table of [1..8](-A), 64 signed 4-bit
windows, the subtraction of R. Ristretto255 is the prime-order
quotient, so there are no cofactor doublings; the accept test
(X = 0 or Y = 0) is comparisons, which the count leaves out.
"""

from __future__ import annotations

from chipbench import opcount

# s^2, u1^2, d * u1^2, u2^2, w = v * u2^2, w^3 (2), w^7 (2), the power,
# w^3 times the power, w * r^2 (2), r * sqrt(-1), den_x = r * u2,
# den_y = r * den_x * v (2), x = 2s * den_x, y = u1 * den_y, t = x * y
RISTRETTO_DECODE = 1 + 1 + 1 + 1 + 1 + 2 + 2 + opcount.POW22523 + 1 + 2 + 1 + 1 + 2 + 1 + 1 + 1
# subtract R: to cached form, one add
FINISH = opcount.TO_CACHED + opcount.PT_ADD_CACHED

FE_MUL = 2 * RISTRETTO_DECODE + opcount.LANE_TABLE + opcount.WINDOW_LOOP + FINISH
# in: A, R, s, k as 32 bytes each; out: one verdict byte
BYTES = 4 * 32 + 1


def least_seconds(lanes: int, peak: dict) -> dict:
    """``opcount.least_seconds`` for ``lanes`` useful sr25519 lanes."""
    ops = lanes * FE_MUL * opcount.OPS_PER_FE_MUL
    nbytes = lanes * BYTES
    t_ops = ops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {
        "ops": ops,
        "bytes": nbytes,
        "seconds": max(t_ops, t_bytes),
        "bound": "compute" if t_ops >= t_bytes else "memory",
    }
