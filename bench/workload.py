"""Shared benchmark fixtures: signature workloads, header chains, and
the tests/helpers.py loader.

Every builder here is imported lazily by the section bodies in
bench/sections.py so a section child only pays for the dependencies its
own measurement needs (the host_ref and chaos sections never touch
jax at all — see bench/sections.py Section.needs_jax).
"""

import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, str(default)))
    except ValueError:
        return default


def env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, str(default)))
    except ValueError:
        return default


def make_workload(rng, batch):
    """pks/msgs/sigs with 256 distinct signers cycled (commit-like)."""
    from tendermint_tpu.crypto.keys import Ed25519PrivKey

    n_keys = 256
    privs = [
        Ed25519PrivKey.from_seed(bytes(rng.integers(0, 256, 32, dtype="uint8")))
        for _ in range(n_keys)
    ]
    pubs = [p.pub_key().bytes() for p in privs]
    msgs = [bytes(rng.integers(0, 256, 120, dtype="uint8")) for _ in range(batch)]
    pks = [pubs[i % n_keys] for i in range(batch)]
    sigs = [privs[i % n_keys].sign(msgs[i]) for i in range(batch)]
    return pks, msgs, sigs


def load_helpers():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_helpers", os.path.join(REPO, "tests", "helpers.py")
    )
    helpers = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(helpers)
    return helpers


def mixed_key_factory(i: int):
    """Alternating ed25519 / sr25519 keys: the two key types of
    BASELINE config 5's mix that batch, and not its secp256k1 (the
    benchmark's ``mixed10k`` cell holds all three); verification
    sub-batches per key type (crypto/batch MultiBatchVerifier ->
    ops/ed25519_batch + ops/sr25519_batch)."""
    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.crypto.sr25519 import Sr25519PrivKey

    if i % 2 == 0:
        return Ed25519PrivKey.from_seed(i.to_bytes(32, "big"))
    return Sr25519PrivKey.from_secret(b"bench-sr" + i.to_bytes(4, "big"))


def build_light_block_chain(n_heights, n_vals):
    """LightBlock chain over build_header_chain (constant valset) — the
    fixture the light_serve section feeds a MemoryProvider."""
    from tendermint_tpu.types import LightBlock

    chain, vset, chain_id = build_header_chain(n_heights, n_vals)
    blocks = [
        LightBlock(signed_header=sh, validator_set=vset.copy())
        for sh in chain
    ]
    return blocks, chain_id


def build_header_chain(n_heights, n_vals):
    """Signed-header chain with a constant validator set (the shape of
    light/client_benchmark_test.go's fixture)."""
    import hashlib

    from tendermint_tpu.encoding.canonical import Timestamp
    from tendermint_tpu.types import (
        BlockID,
        Consensus,
        Header,
        PartSetHeader,
        SignedHeader,
    )

    helpers = load_helpers()
    base_ns = 1_700_000_000_000_000_000
    privs, vset = helpers.make_validators(n_vals)
    chain = []
    last_bid = BlockID()
    for h in range(1, n_heights + 1):
        header = Header(
            version=Consensus(block=11),
            chain_id=helpers.CHAIN_ID,
            height=h,
            time=Timestamp.from_unix_ns(base_ns + h * 1_000_000_000),
            last_block_id=last_bid,
            last_commit_hash=hashlib.sha256(b"lc%d" % h).digest(),
            data_hash=hashlib.sha256(b"d%d" % h).digest(),
            validators_hash=vset.hash(),
            next_validators_hash=vset.hash(),
            consensus_hash=hashlib.sha256(b"cp").digest(),
            app_hash=hashlib.sha256(b"app%d" % h).digest(),
            last_results_hash=b"",
            evidence_hash=b"",
            proposer_address=vset.validators[0].address,
        )
        bid = BlockID(
            header.hash(), PartSetHeader(1, hashlib.sha256(b"p%d" % h).digest())
        )
        commit = helpers.make_commit(
            bid, h, 0, vset, privs, time_ns=base_ns + h * 1_000_000_000
        )
        chain.append(SignedHeader(header=header, commit=commit))
        last_bid = bid
    return chain, vset, helpers.CHAIN_ID
