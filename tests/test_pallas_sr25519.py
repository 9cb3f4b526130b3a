"""The Pallas sr25519 kernel (ops/pallas_verify.compiled_verify_sr)
interpreted on the CPU, lane for lane against the host schnorrkel
oracle (crypto/sr25519.verify) and against the XLA graph
(ops/sr25519_batch.verify_kernel_sr), at the four buckets a chunk is
padded to and on valid, tampered and non-canonical lanes.

Interpret mode traces the kernel body as ordinary JAX ops: one compile
a bucket (minutes on a cold ``.jax_cache``, a second from a warm one),
shared by the bucket's three cases. The 64 distinct lanes are made and
judged by the oracle once; a bucket spreads them over its lanes."""

from functools import lru_cache

import jax.numpy as jnp
import numpy as np
import pytest

from tendermint_tpu.crypto import ristretto
from tendermint_tpu.crypto.hashing import sr25519_challenges_mod_l
from tendermint_tpu.crypto.sr25519 import Sr25519PrivKey, verify as verify_host
from tendermint_tpu.ops import ed25519_batch, pallas_verify, sr25519_batch

BUCKETS = (64, 256, 1024, 4096)
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


@pytest.mark.parametrize("case", ["valid", "tampered", "non_canonical"])
@pytest.mark.parametrize("n", BUCKETS)
def test_pallas_sr25519_agrees_with_the_oracle_and_the_xla_graph(n, case):
    _, cases, oracle, host_ok, pallas, xla = bucket_verdicts(n)
    mine = cases == case
    assert mine.sum() >= n // 64 * 4
    np.testing.assert_array_equal((pallas & host_ok)[mine], oracle[mine])
    np.testing.assert_array_equal((xla & host_ok)[mine], oracle[mine])
    assert oracle[mine].all() == (case == "valid")


def test_the_kernel_alone_refuses_what_is_no_element():
    """The device half of the decode rules, without the host's mask: an
    encoding the host checks pass (canonical, even) that decodes to no
    element is refused by the kernel itself, as A and as R."""
    pick, _, _, host_ok, pallas, xla = bucket_verdicts(64)
    undecodable = np.isin(pick, (12, 13))
    assert undecodable.sum() == 2 and host_ok[undecodable].all()
    assert not pallas[undecodable].any() and not xla[undecodable].any()


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
