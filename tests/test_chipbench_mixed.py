"""The benchmark's part of the ``mixed10k`` deployment, without a chip:
the plain reference of the three key types on published vectors and
against the program, the sr25519 lane's count of operations, the cell's
files, the new readers on hand-made spans and the generator's committee.
The cell's tiny twin is rehearsed end to end, sound and with a fault
planted, in ``tests/test_chipbench_rehearsals.py``."""

from __future__ import annotations

import collections
import hashlib
import json
import os

import numpy as np
import pytest

from chipbench import opcount, opcount_sr25519, reference_mixed, selftest, spec
from chipbench.run import Context, Evidence
from tests.helpers import (
    DEFINITION_KEYS, REAL_BENCH, definitions, read, span,
)

BENCH = os.path.join(spec.HERE, "testdata", "tiny-mixed-benchmark.json")
CELL = "tiny-mixed"
SEED = 2**31 + 40
REAL = (REAL_BENCH, "mixed10k")  # the cell a hand-made reading is named through
# the cell reports two entries under each of two stems: both engines' and sr25519's alone
SR_PROGRAMS, ALL_PROGRAMS = ({"line": "modules", "patterns": p} for p in (["jit_run_sr25519*"], ["jit_run*", "jit__lambda*"]))
SR_PREP, ALL_PREP = {"where": {"engine": "sr25519"}}, {"reader": "span_time_per_call"}


# --- the plain reference --------------------------------------------------------


def test_reference_merlin_gives_the_published_transcript_vector():
    # the Merlin crate's transcript equivalence test
    t = reference_mixed.Transcript(b"test protocol")
    t.append(b"some label", b"some data")
    assert t.challenge(b"challenge", 32).hex() == (
        "d5a21972d0d5fe320c0d263fac7fffb8145aa640af6e9bca177c03c7efcf0615"
    )


def test_reference_schnorrkel_on_a_known_keypair_and_signature():
    """polkadot-js wasm-crypto's known pair pins the key expansion and
    the ristretto encoding the reference decodes. No signature under the
    empty signing context is published where this repository can reach
    it, so the signature is one this program made of that key, frozen:
    what pins the verification itself beyond it is the transcript vector
    above and the 200 lanes held against the program below."""
    seed = bytes.fromhex("fac7959dbfe72f052e5a0c3c8d6530f202b02fd8f9f5ca3580ec8deb7797479e")
    pub = bytes.fromhex("46ebddef8cd9bb167dc30878d7113b7e168e6f0646beffd77d69d39bad76b47a")
    # ExpandEd25519: SHA-512, clamp, divide by the cofactor
    h = bytearray(hashlib.sha512(seed).digest()[:32])
    h[0] &= 248
    h[31] = h[31] & 63 | 64
    scalar = (int.from_bytes(h, "little") >> 3) % reference_mixed.L
    point = reference_mixed.scalar_mult(scalar, reference_mixed.reference.BASE)
    assert reference_mixed.ristretto_equal(point, reference_mixed.ristretto_decode(pub))
    sig = bytes.fromhex(FROZEN_SIGNATURE)
    assert reference_mixed.verify_sr25519(pub, b"chipbench mixed10k", sig)
    assert not reference_mixed.verify_sr25519(pub, b"chipbench mixed10K", sig)
    assert not reference_mixed.verify_sr25519(pub, b"chipbench mixed10k", sig, context=b"substrate")
    unmarked = sig[:63] + bytes([sig[63] & 0x7F])
    assert not reference_mixed.verify_sr25519(pub, b"chipbench mixed10k", unmarked)


FROZEN_SIGNATURE = "f48fe1210320c0a840008897104c5e12201fb91c7298e67d95e00588b8d7d302745e4fad06dae7c1f2a43943e4aca32a7ef5e2c3c6d96926dd920c0173d71086"


def test_reference_ristretto_refuses_the_rfc_9496_bad_encodings():
    for bad in (
        "edffffffffffffffffffffffffffffffffffffffffffffffffffffffffffff7f",  # s = p
        "ecffffffffffffffffffffffffffffffffffffffffffffffffffffffffffff7f",  # negative
        "0100000000000000000000000000000000000000000000000000000000000000",  # negative
        # A.3: non-square x^2; negative x * y
        "26948d35ca62e643e26a83177332e6b6afeb9d08e4268b650f1f5bbd8d81d371",
        "3eb858e78f5a7254d8c9731174a94f76755fd3941c0ac93735c07ba14579630e",
    ):
        assert reference_mixed.ristretto_decode(bytes.fromhex(bad)) is None, bad
    # the generator and its small multiples' encodings (A.1) decode
    base = bytes.fromhex("e2f2ae0a6abc4e71a884a961c500515f58e30b6aa582dd8db6a65945e08d2d76")
    two = bytes.fromhex("6a493210f7499cd17fecb510ae0cea23a110e8d5b901f8acadd3095c73a3b919")
    b = reference_mixed.ristretto_decode(base)
    assert reference_mixed.ristretto_equal(b, reference_mixed.reference.BASE)
    assert reference_mixed.ristretto_equal(
        reference_mixed.point_add(b, b), reference_mixed.ristretto_decode(two)
    )


def test_reference_ecdsa_on_a_published_secp256k1_vector():
    # RFC 6979 deterministic ECDSA over secp256k1 and SHA-256, private
    # key 1 (the public key is the generator), "Satoshi Nakamoto": the
    # vector bitcoinj, Trezor and python-ecdsa carry; its s is low
    pub = bytes.fromhex("0279be667ef9dcbbac55a06295ce870b07029bfcdb2dce28d959f2815b16f81798")
    sig = bytes.fromhex(
        "934b1ea10a4b3c1757e2b0c017d0b6143ce3c9a7e6a4a49860d7a6ab210ee3d8"
        "2442ce9d2b916064108014783e923ec36b49743e2ffa1c4496f01a512aafd9e5"
    )
    assert reference_mixed.verify_secp256k1(pub, b"Satoshi Nakamoto", sig)
    assert not reference_mixed.verify_secp256k1(pub, b"Satoshi Nakamotp", sig)
    high = sig[:32] + (reference_mixed._KN - int.from_bytes(sig[32:], "big")).to_bytes(32, "big")
    assert not reference_mixed.verify_secp256k1(pub, b"Satoshi Nakamoto", high)  # the low-s rule
    assert not reference_mixed.verify_secp256k1(b"\x04" + pub[1:], b"Satoshi Nakamoto", sig)
    assert not reference_mixed.verify_secp256k1(pub, b"Satoshi Nakamoto", bytes(32) + sig[32:])


def test_reference_mixed_imports_nothing_of_the_program():
    with open(reference_mixed.__file__, encoding="utf-8") as fh:
        source = fh.read()
    assert "tendermint_tpu" not in source and "import jax" not in source


@pytest.mark.parametrize("key_type", ["ed25519", "sr25519", "secp256k1"])
def test_reference_agrees_with_the_program_on_200_seeded_lanes(key_type):
    """Valid and tampered lanes alike: the plain reference and the
    program's own host verification give one verdict."""
    from chipbench.generators import commits_mixed
    from tendermint_tpu.crypto.keys import Ed25519PrivKey, Secp256k1PrivKey
    from tendermint_tpu.crypto.sr25519 import Sr25519PrivKey

    make = {
        "ed25519": Ed25519PrivKey.from_seed,
        "sr25519": Sr25519PrivKey,
        "secp256k1": Secp256k1PrivKey,
    }[key_type]
    rng = np.random.default_rng(40)
    kinds = (None,) + commits_mixed.TAMPER_KINDS[key_type] + ("message",)
    accepted = 0
    for i in range(200):
        priv = make(hashlib.sha256(b"%s %d" % (key_type.encode(), i // 4)).digest())
        msg = rng.bytes(int(rng.integers(0, 200)))
        sig = priv.sign(msg)
        kind = kinds[i % len(kinds)]
        if kind == "message":
            msg += b"!"
        elif kind is not None:
            sig = commits_mixed.tamper(key_type, sig, kind)
        pub = priv.pub_key()
        mine = reference_mixed.verify(key_type, pub.bytes(), msg, sig)
        assert mine == pub.verify_signature(msg, sig), (i, kind)
        assert mine == (kind is None), (i, kind)
        accepted += mine
    assert accepted == 40


# --- the count of operations ------------------------------------------------------


def test_opcount_sr25519():
    # one ristretto DECODE: 19 products around the power's 262
    assert opcount_sr25519.RISTRETTO_DECODE == 262 + 19 == 281
    # two decodes, the lane table (65), 64 windows (3,008), subtract R (9):
    # no cofactor doublings
    assert opcount_sr25519.FINISH == 9
    assert opcount_sr25519.FE_MUL == 2 * 281 + 65 + 3008 + 9 == 3644
    assert opcount_sr25519.FE_MUL == opcount.fe_mul_per_lane("legacy") + 2 * 6 - 3 * opcount.PT_DOUBLE
    assert opcount_sr25519.BYTES == 129
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = opcount_sr25519.least_seconds(4950, peak)
    assert least["bound"] == "compute" and least["ops"] == 4950 * 3644 * 2048
    assert abs(least["seconds"] - 4950 * 3644 * 2048 / 197e12) < 1e-15
    fast = opcount_sr25519.least_seconds(1, {"bf16_flops_per_s": 1e30, "hbm_bytes_per_s": 819e9})
    assert fast["bound"] == "memory" and fast["bytes"] == 129


# --- the cell's files ---------------------------------------------------------------


def test_benchmark_files_agree():
    selftest.test_files()
    real = spec.Spec(REAL_BENCH)
    assert len(real.doc["per_layer"]) <= 128
    cell = real.cell("mixed10k")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("mixed10k", "warm-mixed-commits", 1)
    config = real.config("mixed10k")
    assert config["validators"] == sum(config["key_types"].values()) == 10000
    assert config["key_types"] == {"ed25519": 4950, "sr25519": 4950, "secp256k1": 100}
    entry = [c for c in real.doc["configs"] if c["name"] == "mixed10k"][0]
    assert entry["reduced"] == list(config["reduced"]) == ["chips"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert config["env"] == real.config("big10k")["env"]
    assert [m["name"] for m in real.metrics_for("end_to_end", "mixed10k")] == ["commit_p50_ms", "setup_s"]


MS, SHARE, SETUP = ("ms", "lower"), ("%", "lower"), ("s", "lower", "program_span", "setup_s")
SPAN, TRACE = ("program_span", "commit_p50_ms"), ("device_trace", "commit_p50_ms")
# what ``mixed10k`` brought (PR 40) as definitions (``DEFINITION_KEYS``: reader, args, layer, unit, better,
# source, moves), under whatever names. Seven new with the cell ...
NEW = [
    ["trace_kernel_time", SR_PROGRAMS, "Kernels", *MS, *TRACE],
    ["roofline_sr25519", SR_PROGRAMS, "Kernels", "%", "higher", *TRACE],
    ["span_time_per_call", {"spans": ["merlin_challenge"]}, "Hashing", *MS, *SPAN],
    ["span_time_matching_per_call", {"span": "prep_chunk", **SR_PREP}, "Engine", *MS, *SPAN],
    ["span_time_per_call", {"spans": ["host_lanes"]}, "Entry", *MS, *SPAN],
    ["span_arg_share", {"span": "host_lanes", "arg": "lanes", "of_span": "build_lanes", "of_arg": "lanes"},
     "Entry", *SHARE, *SPAN],
    ["span_arg_share", {"span": "dispatch_chunk", "arg": "lanes", "where": {"kind": "sr25519"},
                        "of_span": "dispatch_chunk", "of_arg": "lanes"}, "Engine", "%", "higher", *SPAN],
]
# ... six its elders have too, the first four ``ELDERS``', and the padding counted over a map of kinds
ELDERS = ["device_chain_gap_ms", "pre_dispatch_ms", "chain_ms", "post_collect_ms"]
KINDS = {"legacy": "verify", "tables": "verify_tables", "resident": "verify_resident", "sr25519": "verify_sr"}
OWN = NEW + [
    ["device_call_path", {"part": "gap", "patterns": ALL_PROGRAMS["patterns"]}, "Device", *MS, *TRACE],
    *(["call_path", {"part": part}, "Engine", *MS, *SPAN] for part in ("pre", "chain", "post")),
    ["setup_span_sum", {"span": "kernel_compile"}, "Kernels", *SETUP],
    ["setup_span_sum", {"span": "gather_tables", "min_args": {"builds": 1}}, "Tables", *SETUP],
    ["pad_lane_share_kinds", {"kernel_of_kind": KINDS}, "Engine", *SHARE, *SPAN],
]
# and what every commit cell reports (PERF.md section 3), held to ``big10k-warm``'s
SHARED = [
    "entry_host_ms", "entry_unnamed_ms", "sign_bytes_ms", "batch_add_ms", "note_set_ms", "prep_ms", "device_wait_ms",
    "cache_store_ms", "route_ms", "engine_unnamed_ms", "h2d_bytes", "d2h_ms", "engine_proc_cpu_ms", "gc_pause_ms",
    "kernel_ms", "device_idle", "resident_hit_share", "device_hash_share", "mesh_lane_share",
]
FROZEN = spec.load_json(os.path.join(spec.HERE, "testdata", "definitions_at_pr36.json"))  # seven cells, as of PR 36


def as_counted(rows):
    return collections.Counter(json.dumps(row, sort_keys=True) for row in rows)


@pytest.mark.parametrize("cell", [*FROZEN["cells"], "mixed10k"])
def test_a_cell_reports_every_definition_it_is_held_to(cell):
    """Merging, renaming or sharing entries loses no cell a definition: the seven cells
    of PR 36 are held to what they reported then (as ``selftest.test_files`` holds
    them), ``mixed10k`` to what it brought and to its elders' shared ones."""
    assert tuple(FROZEN["keys"]) == DEFINITION_KEYS
    if cell == "mixed10k":
        want = as_counted(OWN) + definitions(REAL_BENCH, "big10k-warm", SHARED)
        assert all(definitions(REAL_BENCH, "big10k-warm", [stem]) for stem in SHARED)
    else:
        want = as_counted(FROZEN["definitions"][i] for i in FROZEN["cells"][cell])
    lost = want - definitions(REAL_BENCH, cell)
    assert not lost, "%s no longer reports %s" % (cell, sorted(lost.elements()))


@pytest.mark.parametrize("cell", FROZEN["cells"])
def test_no_other_cell_reports_what_is_new_with_mixed10k(cell):
    """No cell older than it, and of the seven new definitions alone: the copies are what a merge lets cells share."""
    assert not definitions(REAL_BENCH, cell) & as_counted(NEW), cell


@pytest.mark.parametrize("stem", ELDERS)
def test_mixed10k_reports_its_elders_definition_of_the_call_path(stem):
    """In a copy of its own or from the elder's entry: reader, arguments and the rest letter for letter."""
    mine = definitions(*REAL, [stem])
    assert mine and mine == definitions(REAL_BENCH, "big10k-warm", [stem])


# --- the new readers, on hand-made spans ---------------------------------------------


def one_commit():
    ED, SR = {"engine": "ed25519", "kind": "resident"}, {"engine": "sr25519", "kind": "sr25519"}
    ev = Evidence()
    ev.calls = [{"start_ns": 0, "end_ns": 1}, {"start_ns": 2, "end_ns": 3}]
    ev.setup_spans = [
        span("kernel_compile", 0, 9, engine="pallas", kernel="verify_resident", lanes=4096),
        span("kernel_compile", 0, 9, engine="pallas", kernel="verify_sr", lanes=4096),
        span("kernel_compile", 0, 9, engine="pallas", kernel="verify_sr", lanes=1024),
    ]
    ev.spans = [
        span("build_lanes", 0, 100, lanes=10000),
        span("prep_chunk", 100, 700, lanes=4096, **ED), span("dispatch_chunk", 800, 50, lanes=4096, **ED),
        span("prep_chunk", 900, 3000, lanes=4096, **SR), span("merlin_challenge", 950, 2500, lanes=4096),
        span("dispatch_chunk", 4000, 50, lanes=4096, **SR),
        span("prep_chunk", 4100, 1000, lanes=854, **SR), span("merlin_challenge", 4150, 500, lanes=854),
        span("dispatch_chunk", 5200, 50, lanes=854, **SR),
        span("host_lanes", 6000, 40000, key_type="secp256k1", lanes=100),
    ]
    return ev


@pytest.mark.parametrize(
    "stem,like,want",
    [
        ("merlin_ms", {}, 1.5), ("prep_ms", SR_PREP, 2.0), ("prep_ms", ALL_PREP, 2.35),  # both engines' prep
        ("host_lanes_ms", {}, 20.0), ("host_lane_share", {}, 1.0), ("sr25519_lane_share", {}, 100.0 * 4950 / 9046),
        ("pad_lane_share", {}, 100.0 * (1 - 9046 / (3 * 4096 + 0 + 1024 - 4096))),
        ("kernel_ms", SR_PROGRAMS, None), ("sr25519_roofline", {}, None),  # no device trace
    ],
)
def test_mixed_metric_on_hand_made_spans(stem, like, want):
    got = read(one_commit(), *REAL, stem, **like)
    assert got is None if want is None else got == pytest.approx(want)


def test_mixed_metrics_on_hand_made_spans():
    """A program without the spans (the parent): nothing to read, no error."""
    ev = one_commit()
    ev.spans = [s for s in ev.spans if s["name"] in ("build_lanes",) or s["args"].get("engine") == "ed25519"]
    assert read(ev, *REAL, "prep_ms", **SR_PREP) is None and read(ev, *REAL, "host_lane_share") is None
    assert read(ev, *REAL, "sr25519_lane_share") is None and read(ev, *REAL, "merlin_ms") == 0.0
    ev.setup_spans = []
    assert read(ev, *REAL, "pad_lane_share") is None


# --- the generator ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    from chipbench.generators import commits_mixed

    bench = spec.Spec(BENCH)
    cell = bench.cell(CELL)
    config = bench.config(cell["config"])
    os.environ["TENDERMINT_TPU_RESULT_CACHE_CAP"] = config["env"]["TENDERMINT_TPU_RESULT_CACHE_CAP"]
    try:
        from tendermint_tpu.ops import precompute

        precompute.reset()
        said = []
        traffic = commits_mixed.build(
            Context(cell, config, bench.traffic(cell["traffic"]), SEED, said.append)
        )
    finally:
        del os.environ["TENDERMINT_TPU_RESULT_CACHE_CAP"]
        precompute.reset()
    return traffic, said


def test_the_generators_committee_holds_the_three_types_interleaved(tiny):
    traffic, said = tiny
    types = [s.key_type for s in traffic.signers]
    assert {kt: types.count(kt) for kt in set(types)} == {"ed25519": 16, "sr25519": 16, "secp256k1": 4}
    assert [v.pub_key.type for v in traffic.vset.validators] == types
    assert traffic.lanes_per_call == 32  # what the device is sent
    # in address order the types interleave: no type sits in one block
    runs = 1 + sum(1 for a, b in zip(types, types[1:]) if a != b)
    assert runs > 8
    # the cycle is sized from the lanes that enter the verdict cache
    assert (traffic.heights - 1) * 16 >= 1.05 * 256 > (traffic.heights - 2) * 16
    assert "16 ed25519, 16 sr25519, 4 secp256k1" in said[0] and "32 of the 36" in said[0]


def test_the_generators_signatures_are_ones_the_reference_and_the_program_accept(tiny):
    traffic, _ = tiny
    commit = traffic.commits[3]
    for lane in range(len(traffic.signers)):
        key_type, pub, msg, sig = traffic._lane(commit, lane)
        assert reference_mixed.verify(key_type, pub, msg, sig), (lane, key_type)
        assert traffic.vset.validators[lane].pub_key.verify_signature(msg, sig), (lane, key_type)
    assert traffic._verify_commit(commit) is None
    # and a tampered lane of each kind is one both refuse
    from chipbench.generators import commits_mixed

    for lane in (traffic.lanes_of[kt][2] for kt in commits_mixed.KEY_TYPES):
        key_type, pub, msg, sig = traffic._lane(commit, lane)
        for kind in commits_mixed.TAMPER_KINDS[key_type]:
            bad = commits_mixed.tamper(key_type, sig, kind)
            assert not reference_mixed.verify(key_type, pub, msg, bad), (key_type, kind)
            assert not traffic.vset.validators[lane].pub_key.verify_signature(msg, bad), (key_type, kind)


def test_a_program_without_the_batch_merlin_stops_at_once(monkeypatch):
    """The parent of PR 40 under this PR's benchmark files: no result,
    and no commit verified one signature at a time on the host."""
    from chipbench.generators import commits_mixed
    from tendermint_tpu.crypto import hashing

    monkeypatch.delattr(hashing, "sr25519_challenges_mod_l")
    bench = spec.Spec(BENCH)
    cell = bench.cell(CELL)
    ctx = Context(cell, bench.config(cell["config"]), bench.traffic(cell["traffic"]), 1, print)
    with pytest.raises(SystemExit, match="cannot run the mixed committee"):
        commits_mixed.build(ctx)
