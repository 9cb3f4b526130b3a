"""Device kernels: batched Ed25519 verification on TPU via JAX/XLA.

This package is the TPU-native replacement for the reference's native
crypto dependency (curve25519-voi; SURVEY.md §2.9): GF(2^255-19) limb
arithmetic shaped for the TPU VPU, complete Edwards point ops, and a
vmap-free hand-batched ZIP-215 verifier, shardable over device meshes
(see tendermint_tpu.parallel).
"""

from tendermint_tpu.ops.ed25519_batch import (  # noqa: F401
    begin_verify_batch,
    prepare_batch,
    verify_batch,
    verify_kernel,
)
