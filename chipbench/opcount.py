"""Operations and bytes the verify kernels need, from shapes.

Counted from the algorithm, not from what a kernel happens to execute:
one lane is one ZIP-215 verification by the Straus double-scalar
multiplication that every kernel of the program implements (the XLA
graphs and the Pallas kernel share the math: 64 signed 4-bit windows,
radix-2^8 limbs). Only *useful* lanes are counted; pad lanes are work
the chip does and nobody asked for.

The unit is the field multiplication (``fe_mul``; a squaring is one):
a 32 x 32 limb schoolbook product, 1024 multiply-adds, counted as 2048
operations. The fold of the high columns, the carries, additions,
selects and comparisons are left out, so the count is a floor on what
any 32-limb formulation executes, and a roofline share worked from it
is if anything too low, never too high.
"""

from __future__ import annotations

NLIMBS = 32
MACS_PER_FE_MUL = NLIMBS * NLIMBS  # schoolbook product of two 32-limb numbers
OPS_PER_FE_MUL = 2 * MACS_PER_FE_MUL

NWINDOWS = 64
TABLE_ROWS = 8  # [1..8](-A), signed windows

# field multiplications per point operation (extended coordinates,
# a = -1; curve25519 folklore)
PT_DOUBLE = 8  # dbl-2008-hwcd: 4 squarings + 4 products
PT_ADD_CACHED = 8  # add against (Y+X, Y-X, Z, 2dT)
PT_MADD_NIELS = 7  # add against (Y+X, Y-X, 2dT), Z = 1
TO_CACHED = 1  # the 2d * T pre-scale

# z^(2^252 - 3): 251 squarings and 11 products
POW22523 = 251 + 11
# y^2, d*y^2, v^3 (2), v^7 (2), u*v^7, the power, u*v^3, times the
# power, v*x^2 (2), x*sqrt(-1), x*y
DECOMPRESS = 1 + 1 + 2 + 2 + 1 + POW22523 + 1 + 1 + 2 + 1 + 1

WINDOW_LOOP = NWINDOWS * (4 * PT_DOUBLE + PT_MADD_NIELS + PT_ADD_CACHED)
# subtract R (to cached form, one add), three doublings for the cofactor
FINISH = TO_CACHED + PT_ADD_CACHED + 3 * PT_DOUBLE
# to cached form, seven chained adds, the 2d pre-scale of eight rows
LANE_TABLE = TO_CACHED + (TABLE_ROWS - 1) * PT_ADD_CACHED + TABLE_ROWS

TABLE_BYTES_PER_KEY = TABLE_ROWS * 4 * NLIMBS  # uint8 cached-form rows

KERNELS = {
    # decompress A and R, build the lane table on the device
    "legacy": {
        "fe_mul": 2 * DECOMPRESS + LANE_TABLE + WINDOW_LOOP + FINISH,
        # in: A, R, s, k as 32 bytes each; out: one verdict byte
        "bytes": 4 * 32 + 1,
    },
    # the lane's table arrives from the host with the batch
    "tables": {
        "fe_mul": DECOMPRESS + WINDOW_LOOP + FINISH,
        "bytes": 3 * 32 + 1 + 1 + TABLE_BYTES_PER_KEY,
    },
    # the table is on the device; the lane ships an index and reads
    # its 1 KiB of table from device memory
    "resident": {
        "fe_mul": DECOMPRESS + WINDOW_LOOP + FINISH,
        "bytes": 3 * 32 + 4 + 1 + 1 + TABLE_BYTES_PER_KEY,
    },
}


def fe_mul_per_lane(kind: str) -> int:
    return KERNELS[kind]["fe_mul"]


def ops_per_lane(kind: str) -> int:
    return KERNELS[kind]["fe_mul"] * OPS_PER_FE_MUL


def bytes_per_lane(kind: str) -> int:
    return KERNELS[kind]["bytes"]


def least_seconds(lanes_by_kind: dict, peak: dict) -> dict:
    """The least time the chip could take for these useful lanes: the
    larger of operations over peak operations and bytes over peak
    bytes. Says which of the two held."""
    ops = sum(n * ops_per_lane(k) for k, n in lanes_by_kind.items())
    nbytes = sum(n * bytes_per_lane(k) for k, n in lanes_by_kind.items())
    t_ops = ops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {
        "ops": ops,
        "bytes": nbytes,
        "seconds": max(t_ops, t_bytes),
        "bound": "compute" if t_ops >= t_bytes else "memory",
    }
