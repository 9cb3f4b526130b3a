"""The Pallas sr25519 kernel (ops/pallas_verify.compiled_verify_sr)
interpreted on the CPU, lane for lane against the host schnorrkel
oracle (crypto/sr25519.verify) and against the XLA graph
(ops/sr25519_batch.verify_kernel_sr), on valid, tampered and
non-canonical lanes.

Interpret mode traces the kernel body as ordinary JAX ops and XLA:CPU
compiles what that unrolled to: one compile a bucket, shared by the
bucket's three cases, and minutes of it on a cold ``.jax_cache`` (a
fresh checkout's is always cold: the cache's key holds the checkout's
path). Tier-1 therefore interprets the 64-lane bucket alone, which is
the whole program in one grid step, as the 256-lane bucket is; the
wider buckets are ``slow`` here and held against the oracle on the
chip, compiled for real, by ``chip_smoke.py`` (``_run_sr25519`` at
every width of its ``SR_BUCKETS``). A
grid of several steps costs the interpreter by the step, not by the
width (64 lanes in two steps of 32: 431 s to compile, PR 46), so
tier-1 holds the grid with the kernel's body stood in
(``test_a_grid_of_several_steps_...``) and the chip holds it for real.
The 64 distinct lanes are made and judged by the oracle once; a bucket
spreads them over its lanes."""

from functools import lru_cache

import jax.numpy as jnp
import numpy as np
import pytest

from tendermint_tpu.crypto import ristretto
from tendermint_tpu.crypto.hashing import sr25519_challenges_mod_l
from tendermint_tpu.crypto.sr25519 import Sr25519PrivKey, verify as verify_host
from tendermint_tpu.ops import ed25519_batch, pallas_verify, sr25519_batch

BUCKETS = [
    64,
    *(
        # a cold trace and compile of minutes (the 256-lane bucket: 4 min 54 s, the 4,096-lane one
        # never inside tier-1's limit); chip_smoke.py's _run_sr25519 holds these widths on the chip
        pytest.param(n, marks=pytest.mark.slow)
        for n in (256, 1024, 4096)
    ),
]
MARKER = 1 << 255


def _undecodable() -> bytes:
    """A canonical, non-negative encoding that is no ristretto element."""
    s = 2
    while ristretto.decompress(s.to_bytes(32, "little")) is not None:
        s += 2
    return s.to_bytes(32, "little")


@lru_cache(maxsize=None)
def distinct_lanes():
    """64 lanes: ``(pubs, msgs, sigs, case of each, oracle verdicts)``."""
    pubs, msgs, sigs, cases = [], [], [], []
    for i in range(64):
        priv = Sr25519PrivKey.from_secret(b"pallas-sr %d" % i)
        msg = b"vote %d " % i + bytes(i)  # lengths 7 .. 72
        pubs.append(priv.pub_key().bytes())
        msgs.append(msg)
        sigs.append(priv.sign(msg))
        cases.append("valid")

    def flip(data, at, bit=1):
        return data[:at] + bytes([data[at] ^ bit]) + data[at + 1 :]

    s_plus_l = (int.from_bytes(sigs[5][32:], "little") & (MARKER - 1)) + ristretto.L
    tampered = {
        1: ("sig", flip(sigs[1], 2)),  # R
        2: ("sig", flip(sigs[2], 40)),  # s
        3: ("msg", b"another vote"),
        4: ("pub", pubs[9]),  # another validator's key
    }
    non_canonical = {
        5: ("sig", sigs[5][:32] + (s_plus_l | MARKER).to_bytes(32, "little")),  # s >= L
        6: ("sig", flip(sigs[6], 63, 0x80)),  # marker bit cleared
        7: ("pub", (ristretto.P + 2).to_bytes(32, "little")),  # A >= p
        8: ("pub", flip(pubs[8], 0)),  # A negative (odd)
        10: ("sig", (ristretto.P + 4).to_bytes(32, "little") + sigs[10][32:]),  # R >= p
        11: ("sig", flip(sigs[11], 0)),  # R negative
        12: ("pub", _undecodable()),
        13: ("sig", _undecodable() + sigs[13][32:]),
    }
    for case, edits in (("tampered", tampered), ("non_canonical", non_canonical)):
        for lane, (what, value) in edits.items():
            {"pub": pubs, "msg": msgs, "sig": sigs}[what][lane] = value
            cases[lane] = case
    oracle = [verify_host(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
    assert oracle == [c == "valid" for c in cases]
    return pubs, msgs, sigs, cases, oracle


@lru_cache(maxsize=None)
def bucket_verdicts(n: int):
    """``(distinct lane, case, oracle verdict, host_ok, pallas, xla)`` of
    each of the n lanes of one bucket; the kernels' verdicts before the
    host's structural mask."""
    pubs, msgs, sigs, cases, oracle = distinct_lanes()
    pick = (np.arange(n) * 5 + np.arange(n) // 64) % 64
    lane = lambda xs: [xs[i] for i in pick]
    pk, r, s, host_ok = sr25519_batch._lane_arrays(lane(pubs), lane(sigs))
    r = np.ascontiguousarray(r)
    k = sr25519_challenges_mod_l(pk, r, lane(msgs))
    args = tuple(jnp.asarray(a) for a in (pk, r, s, k))
    pallas = pallas_verify.compiled_verify_sr(n, interpret=True)(*args)
    xla = ed25519_batch._compiled_kernel(sr25519_batch.SR25519, n, None, "vpu")(*args)
    return pick, np.array(lane(cases)), np.array(lane(oracle)), host_ok, np.asarray(pallas), np.asarray(xla)


# whichever test asks for a bucket's verdicts first compiles its program: 5 min 30 s for the 64-lane
# bucket alone on a cold cache (PR 46), and longer beside five busy workers
compiles_a_bucket = pytest.mark.limit(1200)


@compiles_a_bucket
@pytest.mark.parametrize("case", ["valid", "tampered", "non_canonical"])
@pytest.mark.parametrize("n", BUCKETS)
def test_pallas_sr25519_agrees_with_the_oracle_and_the_xla_graph(n, case):
    _, cases, oracle, host_ok, pallas, xla = bucket_verdicts(n)
    mine = cases == case
    assert mine.sum() >= n // 64 * 4
    np.testing.assert_array_equal((pallas & host_ok)[mine], oracle[mine])
    np.testing.assert_array_equal((xla & host_ok)[mine], oracle[mine])
    assert oracle[mine].all() == (case == "valid")


@pytest.mark.parametrize("block", [8, 16, 32])
def test_a_grid_of_several_steps_keeps_every_lane_in_its_place(monkeypatch, block):
    """``verify_sr_fn``'s grid with the kernel's body stood in: 64 lanes
    in 8, 4 and 2 steps. The stand-in's verdict is a function of a
    lane's own four rows, so every lane must have met its own A, R, s
    and k, whichever step it was in, and its verdict must have come
    back in its own place. (The real body in a grid of several steps is
    minutes of compile when interpreted; the chip runs it at 1,024 and
    4,096 lanes, four and sixteen steps.)"""

    def lane_local(a_ref, r_ref, swin_ref, kwin_ref, byp_ref, bym_ref, bt2_ref, consts_ref, out_ref, tab_ref):
        assert a_ref.shape == (32, block) and swin_ref.shape == (64, block) and out_ref.shape == (1, block)
        same = jnp.all(a_ref[:, :] == r_ref[:, :], axis=0, keepdims=True)
        same &= jnp.all(swin_ref[:, :] == kwin_ref[:, :], axis=0, keepdims=True)
        out_ref[:, :] = same.astype(jnp.float32)

    monkeypatch.setattr(pallas_verify, "_verify_sr_kernel", lane_local)
    rng = np.random.default_rng(block)
    pk = rng.integers(0, 256, (64, 32), dtype=np.uint8)
    s = rng.integers(0, 256, (64, 32), dtype=np.uint8)
    s[:, 31] &= 0x0F  # a scalar below 2^252, as the host hands it over
    r, k = pk.copy(), s.copy()
    want = np.ones(64, bool)
    for lane in range(0, 64, 3):  # in every step, at no stride a block has
        (r if lane % 2 else k)[lane, lane % 31] ^= 1
        want[lane] = False
    got = pallas_verify.verify_sr_fn(*map(jnp.asarray, (pk, r, s, k)), block=block, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)


@compiles_a_bucket
def test_the_kernel_alone_refuses_what_is_no_element():
    """The device half of the decode rules, without the host's mask: an
    encoding the host checks pass (canonical, even) that decodes to no
    element is refused by the kernel itself, as A and as R."""
    pick, _, _, host_ok, pallas, xla = bucket_verdicts(64)
    undecodable = np.isin(pick, (12, 13))
    assert undecodable.sum() == 2 and host_ok[undecodable].all()
    assert not pallas[undecodable].any() and not xla[undecodable].any()


@compiles_a_bucket
def test_a_restarted_process_runs_the_real_kernel_from_the_store(monkeypatch):
    """chip_smoke.py's sr25519 lanes (valid, a flipped R, a flipped s,
    ``s >= L``, a non-canonical A) at the 64-lane bucket through the real
    kernel, then again as a process that finds the kernel store warm
    does: the factory has forgotten its program, the body raises if it
    is walked, and lane for lane the verdicts are the first's and the
    host oracle's. The real store, beside the compile cache that holds
    the executable."""
    import chip_smoke
    from tendermint_tpu.ops import kernel_store

    assert kernel_store.directory()
    pubs, msgs, sigs = chip_smoke._sr25519_lanes(64)
    want = [verify_host(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
    assert True in want and want.count(False) >= 16
    pk, r, s, host_ok = sr25519_batch._lane_arrays(pubs, sigs)
    r = np.ascontiguousarray(r)
    args = tuple(jnp.asarray(a) for a in (pk, r, s, sr25519_challenges_mod_l(pk, r, msgs)))

    def verdicts():
        out = pallas_verify.compiled_verify_sr(64, interpret=True)(*args)
        return list(np.asarray(out) & host_ok)

    try:
        assert verdicts() == want
        pallas_verify.compiled_verify_sr.cache_clear()

        def boom(*args, **kwargs):
            raise AssertionError("the kernel body was walked in a process that found the store warm")

        monkeypatch.setattr(pallas_verify, "verify_sr_fn", boom)
        assert verdicts() == want
    finally:
        pallas_verify.compiled_verify_sr.cache_clear()


def test_the_sr25519_programs_are_called_run_sr25519():
    """``jit_run_sr25519``: what ``kernel_ms.sr`` matches on a device
    trace, and ``kernel_ms.commit``'s ``jit_run*`` with it."""
    import fnmatch
    import re

    import jax

    def run_sr25519(pk, r, s, k):
        return pallas_verify.verify_sr_fn(pk, r, s, k, block=8, interpret=True)

    avals = [jax.ShapeDtypeStruct((8, 32), jnp.uint8)] * 4
    name = re.search(r"module @(\S+)", jax.jit(run_sr25519).lower(*avals).as_text()).group(1)
    assert sr25519_batch.SR25519.program == "run_sr25519"
    assert fnmatch.fnmatch(name, "jit_run_sr25519*") and fnmatch.fnmatch(name, "jit_run*")
    xla = ed25519_batch._compiled_kernel(sr25519_batch.SR25519, 64, None, "vpu")
    assert xla.__wrapped__.__name__ == "run_sr25519"
    assert ed25519_batch._compiled_kernel(
        ed25519_batch.KINDS["legacy"], 64, None, "vpu"
    ).__wrapped__.__name__ == "run"


def test_run_chunk_hands_an_sr25519_chunk_to_its_pallas_entry(monkeypatch):
    calls = []

    def factory(n):
        def kernel(*args):
            calls.append((n, args))
            return "verdicts"

        return kernel

    monkeypatch.setattr(ed25519_batch, "active_impl", lambda backend=None: "pallas")
    monkeypatch.setattr(ed25519_batch, "_mul_impl_for_chunk", lambda impl, backend, lanes: "vpu")
    monkeypatch.setattr(pallas_verify, "compiled_verify_sr", factory)
    kind = sr25519_batch.SR25519
    inputs = kind.pad_lanes({name: np.zeros((0, 32), np.uint8) for name in ("pk", "r", "s", "k")}, 64)
    out, used, impl = ed25519_batch._run_chunk(kind, inputs, None)
    ((n, args),) = calls
    assert (out, used, impl, n, len(args)) == ("verdicts", None, "pallas", 64, 4)
