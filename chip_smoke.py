#!/usr/bin/env python3
"""chip_smoke.py — does the verify path still start on the chip?

    python3 chip_smoke.py

drives the system's main path once on a TPU, through the entry points a
user calls, at the sizes ``BASELINE.json`` takes from upstream, and
fails unless the DEVICE did the work. It is the quickest proof that the
program still runs on the chip; it measures nothing (compile and wall
seconds are printed as set-up information only).

Two phases, each a process that owns the chip alone, one after the
other. The parent never initialises a JAX backend: it builds commits,
starts children and talks to the daemon over ``verifyd.client`` (whose
shm transport imports the package's device-byte ledger, and with it
jax, but touches no device).

- **library** (a child, run twice): 150- and 10,000-validator commits
  checked with ``types.validation.verify_commit`` -> ``crypto.batch``
  -> ``ops.verify_batch``: the lanes once with no validator set known
  (legacy kernel), a few heights over the same set (resident tables,
  device hashing on fixed-width sign-bytes), then a commit with a
  flipped ``R``, a flipped ``s`` byte and an ``s >= L`` whose failing
  lanes must be attributed exactly; beside them one direct
  ``ops.verify_batch`` call on ZIP-215 edge vectors; then one engine
  job + 204 lanes of keys of no set added lane by lane to a
  ``crypto.BatchVerifier``, which begins the full job before
  ``verify()``, tampered on both sides of that seam; then two blocksync
  windows of 16 commits at 500 validators through
  ``parallel/pipeline.verify_commits_pipelined`` (one sound, one with a
  block tampered on both sides of its early exit); then sr25519 lanes
  (valid, tampered R, tampered s, ``s >= L``, a non-canonical ``A``)
  through ``ops/sr25519_batch.verify_batch_sr`` at each of its kernel's
  four buckets, and a 150-validator committee of ed25519, sr25519 and
  secp256k1 keys through ``verify_commit``, sound and with one lane of
  each type tampered; then one blocksync window of 16 commits over such
  a committee, validators absent and voting nil at each height, through
  ``verify_commits_pipelined`` with one included lane of each type
  tampered: planned by key type into one sub-batch a device type and one
  host call, each block's verdict the light rule's over the oracles.
  Verdicts are compared with the host oracles
  (``crypto/ed25519_ref.py``, ``crypto/sr25519.verify``, the keys' own
  ``verify_signature``). Where more
  than one chip is present the edge vectors also go through the sharded
  path (``parallel/sharding.verify_batch_sharded``), which under
  ``pallas`` runs the Pallas kernel per shard from a stored lowered
  program (``ops/kernel_store.py``), as one device does since PR 50:
  every Pallas first call carries its ``stored`` (``miss``: the kernel
  body was walked; ``hit``: not), counted in each run's report line, and
  each sharded one is printed with its seconds, cold in the first run
  and warm in the second; there the sr25519 buckets from the mesh floor
  (256 lanes) up and a 4,950-lane batch (``BASELINE.json`` config 5's sr25519 half: a
  4,096-lane slab a device on four) must go out sharded, on the sr25519
  shard program, and the mixed committee is 600 validators, so that both
  of its device sub-batches pass the floor and one ``verify_commit``
  sends two sharded chunks with the host's lanes between
  (``mesh_dispatch`` by kind; PR 48). The second run proves the compile
  cache and the kernel store:
  it may add no entry, and every Pallas first call, on one device and
  sharded, must be a ``hit``.
- **served** (driven from the parent): ``python -m tendermint_tpu
  verifyd`` started through the CLI, warmed one request at a time, then
  four concurrent ``verifyd.client`` clients built with
  ``fallback=False`` send commit requests in class consensus, one with a
  tampered lane; then the daemon's stats, SIGTERM and a clean exit.

What may hide the device is counted and checked: platform must be
``tpu``; the health machine healthy with zero failures and zero host
fallbacks; lanes dispatched == lanes sent; device hashing, the resident
store and (on more than one chip) sharding must have served lanes where
``auto`` turns them on; server ``host_direct_lanes`` /
``admission_rejections`` / ``deadline_expired`` and client
``fallback_calls`` all zero. Python warnings are errors in every
process.

Exit code 0 and, as the last line of stdout, one JSON object
``{"ok": true, "device": {...}}`` only if every phase passed. Any
failure — no accelerator, a phase raising, a check failing — exits
non-zero, says why on stderr, and prints no result.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import warnings

HERE = os.path.dirname(os.path.abspath(__file__))

SEED = 21
# BASELINE.json: config 2 (upstream's VerifyCommit size; pads to the 256
# bucket, below the mesh floor) and the size BASELINE.md tracks
# VerifyCommit p50 at (three 4096-lane chunks, ~10 MiB of resident tables).
SIZES = (150, 10_000)
HEIGHTS = 3  # heights verified over the same set after the cold pass
EARLY_TAIL = 204  # lanes past one full engine job in the early-begin batch (pads to 256)
SYNC_VALS = 500  # the blocksync window: BASELINE.json config 4's committee
SYNC_WINDOW = 16  # blocksync/syncer.DEFAULT_VERIFY_WINDOW
SR_BUCKETS = (64, 256, 1024, 4096)  # every width an sr25519 chunk is padded to
SR_MESH_LANES = 4_950  # config 5's sr25519 half: on two to four devices a 4,096-lane slab each
MIXED_VALS = 150  # BASELINE.json config 5's three key types at config 2's size
MIXED_MESH_VALS = 600  # on a mesh: 280 lanes of each type that batches, over the mesh floor
MIXED_SYNC = (150, 16)  # a blocksync window over that committee: ~750 lanes a device type, one 1,024-lane chunk each
SERVED_VALS = 150
CLIENTS = 4
REQUESTS_PER_CLIENT = 3
# One request at a time before the concurrent ones. The first few
# compile their shapes inside the call; the rest let the admission
# controller's per-lane service-time average, which counted those
# compiles, come back down (x0.8 per flush) so that it does not read
# four clients' worth of queue as overload.
WARMUP_REQUESTS = 32
DEADLINE_S = 300.0  # compile is inside the call; protocol ceiling is 600 s
BUDGET_S = 1150.0  # whole smoke, under the contract's 1200 s

# Every warning is an error, in every process of the smoke, so that a
# ``warnings.warn("... falling back ...")`` cannot pass. The one let
# through is jax's own start-up note about the TPU VM's image.
_HUGEPAGES = "Transparent hugepages are not enabled"
WARNING_FLAGS = ["-W", "error", "-W", "ignore:%s:UserWarning" % _HUGEPAGES]

# Vote timestamps whose nanoseconds all encode as a 5-byte varint
# (2^28 <= nanos < 2^29 for every validator index), so a commit's
# sign-bytes are fixed-width and device hashing applies to it.
TIME_NS = 1_700_000_000_000_000_000 + 500_000_000


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond: bool, msg: str, *args) -> None:
    if not cond:
        raise SmokeFailure(msg % args if args else msg)


def say(msg: str) -> None:
    print("chip_smoke: " + msg, flush=True)


def _auto_on(env_name: str, platform: str) -> bool:
    """What an ``auto (on for tpu) | on | off`` switch of ops/ resolves
    to — for the parent, which cannot ask ops/ without initialising a
    backend."""
    mode = os.environ.get(env_name, "auto").lower()
    if mode in ("1", "on", "true", "yes", "all"):
        return True
    if mode in ("0", "off", "none", "false"):
        return False
    return platform == "tpu"


# --- workload ----------------------------------------------------------------


def build_set(n_vals: int, heights: int):
    """Seeded ed25519 validator set + one signed commit per height,
    fixed-width sign-bytes. Keys differ between sizes so that no size
    finds the other's tables."""
    from bench.workload import load_helpers
    from tendermint_tpu.crypto.keys import Ed25519PrivKey

    helpers = load_helpers()
    offset = n_vals * 1000
    privs, vset = helpers.make_validators(
        n_vals,
        key_factory=lambda i: Ed25519PrivKey.from_seed(
            (offset + i).to_bytes(32, "big")
        ),
    )
    commits = {
        h: helpers.make_commit(
            helpers.make_block_id(b"chip-smoke-%d-%d-%d" % (SEED, n_vals, h)),
            h, 0, vset, privs, time_ns=TIME_NS,
        )
        for h in range(1, heights + 1)
    }
    return helpers, vset, commits


def commit_lanes(helpers, vset, commit):
    pks = [v.pub_key.bytes() for v in vset.validators]
    msgs = [
        commit.vote_sign_bytes(helpers.CHAIN_ID, i) for i in range(len(pks))
    ]
    sigs = [cs.signature for cs in commit.signatures]
    check(
        len({len(m) for m in msgs}) == 1,
        "sign-bytes are not fixed-width: device hashing would not apply",
    )
    return pks, msgs, sigs


def tamper(commit) -> dict:
    """Break three lanes of ``commit`` in place: a flipped R bit, a
    flipped s bit, and s + L (the curve equation still holds; only the
    canonicity check refuses it). Returns {lane index: kind}."""
    from tendermint_tpu.crypto.ed25519_ref import L

    n = len(commit.signatures)
    picks = {7 % n: "R", n // 2: "s", n - 3: "s>=L"}
    check(len(picks) == 3, "commit too small to tamper three lanes")
    for idx, kind in picks.items():
        sig = bytearray(commit.signatures[idx].signature)
        if kind == "R":
            sig[3] ^= 0x01
        elif kind == "s":
            sig[32] ^= 0x01
        else:
            s = int.from_bytes(sig[32:], "little") + L
            sig[32:] = s.to_bytes(32, "little")
        commit.signatures[idx].signature = bytes(sig)
    return picks


def _blame_block_edges(helpers, vset, commit, height: int, edges, what: str) -> None:
    """``commit``, sound, with one lane at a time refused, each lane of
    ``edges`` (the first and last lane of a block of the entry's loop):
    ``verify_commit`` names that lane and no other. The commit is left
    as it was."""
    from tendermint_tpu.types.validation import InvalidCommitError, verify_commit

    for idx in edges:
        sound = commit.signatures[idx].signature
        sig = bytearray(sound)
        sig[32] ^= 0x01
        commit.signatures[idx].signature = bytes(sig)
        try:
            verify_commit(helpers.CHAIN_ID, vset, commit.block_id, height, commit)
        except InvalidCommitError as exc:
            check("(#%d)" % idx in str(exc), "%s: blame %s, want the block's edge, lane %d", what, exc, idx)
        else:
            raise SmokeFailure("%s: accepted a commit with lane %d tampered" % (what, idx))
        finally:
            commit.signatures[idx].signature = sound


def _check_blocks(spans: list, what: str) -> int:
    """Every ``verify_commit`` among ``spans`` built its lanes a block
    at a time: each ``build_lanes`` span handed all its lanes over by
    one ``add_many`` and the call counts its spans. Returns the most
    blocks a call was made of."""
    loops = [e for e in spans if e["name"] == "build_lanes"]
    check(
        loops and all(e["args"]["block_lanes"] == e["args"]["lanes"] for e in loops),
        "%s: lanes that went through add: %r",
        what, [(e["args"]["lanes"], e["args"].get("block_lanes")) for e in loops][:8],
    )
    calls = [e["args"]["blocks"] for e in spans if e["name"] == "verify_commit"]
    check(sum(calls) == len(loops), "%s: blocks %r, build_lanes spans %d", what, calls, len(loops))
    return max(calls)


def edge_vectors():
    """The ZIP-215 edge cases tests/test_ed25519_ref.py and
    tests/test_ops_ed25519.py hold (small-order and identity keys,
    non-canonical y, s >= L, off-curve), as one well-formed-length batch
    with ordinary lanes between them. Not among them: x = 0 with the
    sign bit set, on which the oracle's two layers disagree with each
    other (PERF.md, open questions)."""
    from tendermint_tpu.crypto import ed25519_ref as ref

    s = 12345
    r_sb = ref.pt_compress(ref.pt_mul(s, ref.B_POINT))
    sig_sb = r_sb + s.to_bytes(32, "little")
    le = lambda v: v.to_bytes(32, "little")
    ident, order4 = le(1), le(0)
    lanes = [
        (ident, b"x", sig_sb),  # identity key: R = [s]B verifies
        (le(ref.P + 1), b"x", sig_sb),  # the same point, y >= p
        (order4, b"x", sig_sb),  # small-order key (y = 0)
        (le(ref.P), b"x", sig_sb),  # the same point, y >= p
        (ident, b"x", r_sb + le(s + ref.L)),  # s >= L
        (ident, b"y", order4 + le(0)),  # small-order R, s = 0
        (ident, b"y", le(ref.P) + le(0)),  # the same R, y >= p
        (le(2), b"x", sig_sb),  # not on the curve
    ]
    for i in range(12):
        priv, pub = ref.keypair_from_seed(bytes([i + 1]) * 32)
        msg = b"chip-smoke edge filler %d" % i
        sig = ref.sign(priv, msg)
        if i % 4 == 3:
            msg = b"tampered"
        lanes.append((pub, msg, sig))
    return [list(col) for col in zip(*lanes)]


# --- library phase ------------------------------------------------------------


def _counters() -> dict:
    from tendermint_tpu.ops import hash512, resident
    from tendermint_tpu.ops.device_policy import shared as health
    from tendermint_tpu.parallel import mesh

    h = health.snapshot()
    r = resident.stats()
    return {
        "fallback_batches": h["fallback_batches"],
        "failures": sum(h["failures"].values()),
        "hash_device_lanes": hash512.stats()["device_lanes"],
        "resident_hits": r["hits"],
        "resident_misses": r["misses"],
        "resident_uploads": r["uploads"],
        "gathered_h2d_bytes": r["gathered_h2d_bytes"],
        "mesh_dispatches": mesh.manager.snapshot()["dispatches"],
    }


def _delta(before: dict) -> dict:
    after = _counters()
    return {k: after[k] - before[k] for k in after}


def _check_health(d: dict, what: str) -> None:
    check(
        d["fallback_batches"] == 0 and d["failures"] == 0,
        "%s: the health machine counted failures or host fallbacks: %r",
        what, d,
    )


def _drain_spans() -> list:
    from tendermint_tpu.libs import tracing

    doc = tracing.tracer.export(clear=True)
    check(
        doc["otherData"]["dropped"] == 0,
        "trace ring overflowed: span counts would be wrong",
    )
    return [e for e in doc["traceEvents"] if e.get("ph") == "X"]


def _check_dispatch(spans: list, sent: dict, what: str) -> None:
    """Lanes dispatched to and collected from the device, per job kind,
    equal the lanes sent; nothing went to the host oracle."""
    check(
        not [e for e in spans if e["name"] == "host_fallback"],
        "%s: lanes were answered by the host oracle", what,
    )
    for stage in ("dispatch_chunk", "collect_chunk"):
        got = {}
        for e in spans:
            if e["name"] == stage:
                kind = e["args"]["kind"]
                got[kind] = got.get(kind, 0) + int(e["args"]["lanes"])
        check(
            got == sent,
            "%s: %s lanes by kind %r != lanes sent %r", what, stage, got, sent,
        )


def _compiles(spans: list, impl: str) -> list:
    """(implementation, kernel, lanes, seconds) for each kernel the
    window compiled (or loaded from the cache), and for a Pallas kernel,
    whose program comes from the kernel store on one device and per
    shard of a mesh alike, two more: the devices, and what the store
    did (``hit`` | ``miss``). The legacy, table and
    resident kernels must be the implementation ``auto`` resolved to, on
    one device and per shard of a mesh, and so must sr25519's. A mesh's XLA-graph kernels
    record no ``kernel_compile`` span (they show under ``sharded``)."""
    out = []
    for e in spans:
        if e["name"] != "kernel_compile":
            continue
        a = e["args"]
        ran = "pallas" if a.get("engine") == "pallas" else "xla"
        if a.get("kernel") in ("verify", "verify_tables", "verify_resident", "verify_sr"):
            check(
                ran == ("pallas" if impl == "pallas" else "xla"),
                "active_impl() is %r but the %s kernel at %s lanes ran %r",
                impl, a.get("kernel"), a.get("lanes"), ran,
            )
        row = [ran, a.get("kernel"), a.get("lanes"), round(e["dur"] / 1e6, 2)]
        if "stored" in a:
            row += [a.get("devices", 1), a["stored"]]
        out.append(row)
    return out


def _first_calls(report: dict) -> list:
    """Every row of :func:`_compiles` in a library run's report."""
    parts = [report["edge"], report.get("sharded_edge", {})] + report["sizes"]
    parts += [report.get("early_begin", {}), report.get("pipelined", {})]
    parts += report.get("sr25519", []) + [report.get("mixed_committee") or {}]
    parts += [report.get("mixed_window") or {}]
    return [c for part in parts for c in part.get("compiles", ())]


def _stored_first_calls(report: dict) -> list:
    """The rows among them whose program came through the kernel store."""
    return [c for c in _first_calls(report) if len(c) > 4]


def _sharded_first_calls(report: dict) -> list:
    """The rows among those that are a mesh's kernels."""
    return [c for c in _stored_first_calls(report) if c[4] > 1]


def _stored_counts(report: dict) -> tuple:
    """(hits, misses) over a library run's first calls."""
    stored = [c[5] for c in _stored_first_calls(report)]
    return stored.count("hit"), stored.count("miss")


def _check_store_warm(run: int, report: dict) -> None:
    """A process that finds the kernel store warm walks no kernel body:
    every first call of a second run, on one device and sharded, is a
    ``hit``."""
    cold = [c for c in _stored_first_calls(report) if c[5] != "hit"]
    check(
        not cold,
        "library run %d traced a kernel the store should have held: %r",
        run, cold,
    )


def _sharded(spans: list, impl: str) -> list:
    """(job kind, devices, padded lanes, implementation) of the sharded
    dispatches. A mesh has the implementations one device has
    (parallel/sharding._sharded_kernel): every ed25519 kind has a Pallas
    entry point, so each must have run what ``auto`` resolved to."""
    out = sorted(
        {
            (e["args"]["kind"], e["args"]["devices"], e["args"]["lanes"],
             e["args"].get("impl"))
            for e in spans
            if e["name"] == "mesh_dispatch"
        }
    )
    for kind, devices, lanes, ran in out:
        check(
            ran == impl,
            "active_impl() is %r but the sharded %s chunk (%s lanes over %s "
            "devices) ran %r", impl, kind, lanes, devices, ran,
        )
    return out


def _run_sharded_edge(dev: dict, impl: str) -> dict:
    """The oracle's lanes through the sharded path, whatever their
    number (the mesh floor would keep 20 lanes on one device): the
    legacy kernel on every device's slab, lane for lane against the
    oracle."""
    from tendermint_tpu.parallel import sharding

    _drain_spans()
    before = _counters()
    pks, msgs, sigs = edge_vectors()
    t0 = time.monotonic()
    verdicts = sharding.verify_batch_sharded(
        pks, msgs, sigs, mesh=sharding.make_mesh(), min_lanes=0
    )
    wall = time.monotonic() - t0
    _check_oracle(pks, msgs, sigs, verdicts, range(len(pks)), "sharded edge vectors")
    spans = _drain_spans()
    _check_dispatch(spans, {"legacy": len(pks)}, "sharded edge vectors")
    d = _delta(before)
    _check_health(d, "sharded edge vectors")
    sharded = _sharded(spans, impl)
    check(
        d["mesh_dispatches"] > 0 and sharded
        and all(row[1] == dev["count"] for row in sharded),
        "sharded edge vectors: dispatches %r over %d devices",
        sharded, dev["count"],
    )
    return {
        "lanes": len(pks),
        "accepted": int(sum(map(bool, verdicts))),
        "wall_s": round(wall, 2),
        "compiles": _compiles(spans, impl),
        "sharded": sharded,
    }


def _batch_verify(pub_key, pks, msgs, sigs) -> list:
    """crypto.BatchVerifier as a caller without a validator set uses it."""
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.crypto.keys import Ed25519PubKey

    bv = crypto_batch.create_batch_verifier(pub_key)
    for pk, msg, sig in zip(pks, msgs, sigs):
        bv.add(Ed25519PubKey(pk), msg, sig)
    _, verdicts = bv.verify()
    return verdicts


def _check_oracle(pks, msgs, sigs, verdicts, rows, what: str) -> None:
    from tendermint_tpu.crypto.ed25519_ref import verify_zip215

    wrong = [
        i for i in rows
        if bool(verdicts[i]) != verify_zip215(pks[i], msgs[i], sigs[i])
    ]
    check(not wrong, "%s: device and oracle disagree on lanes %r", what, wrong[:16])


def _fresh_node() -> None:
    """Every committee below is a node's own, the first set its process
    meets: the table cache builds that one at first sight and any later
    one only in the third batch that carries a key
    (``precompute.BUILD_AT_SIGHTING``). Dropping the cache (and with it
    the device store, its observer) starts the next node."""
    from tendermint_tpu.ops import precompute

    precompute.reset()


def _run_size(n: int, heights: int, dev: dict, impl: str, paths: dict) -> dict:
    import numpy as np

    from tendermint_tpu.ops import ed25519_batch
    from tendermint_tpu.parallel import mesh
    from tendermint_tpu.types.validation import InvalidCommitError, verify_commit

    _fresh_node()
    what = "%d validators" % n
    t0 = time.monotonic()
    helpers, vset, commits = build_set(n, heights + 1)
    setup_s = time.monotonic() - t0
    before = _counters()
    _drain_spans()
    proposer = vset.validators[0].pub_key
    walls = {}

    # Cold: the lanes through crypto.BatchVerifier before any validator
    # set is known -> no tables -> the legacy kernel builds them on device.
    t0 = time.monotonic()
    pks, msgs, sigs = commit_lanes(helpers, vset, commits[1])
    check(
        all(_batch_verify(proposer, pks, msgs, sigs)),
        what + ": cold pass refused a valid lane",
    )
    walls["cold_s"] = round(time.monotonic() - t0, 2)

    # The same set, height after height, through verify_commit.
    walls["heights_s"] = []
    gathered_after_first = None
    for h in range(1, heights + 1):
        t0 = time.monotonic()
        verify_commit(helpers.CHAIN_ID, vset, commits[h].block_id, h, commits[h])
        walls["heights_s"].append(round(time.monotonic() - t0, 2))
        if gathered_after_first is None:
            gathered_after_first = _counters()["gathered_h2d_bytes"]

    # The loop's blocks: a lane refused at each edge of each is named.
    job = ed25519_batch.job_lanes()
    edges = sorted({0, n - 1} | {lane for lane in (job - 1, job) if lane < n})
    t0 = time.monotonic()
    _blame_block_edges(helpers, vset, commits[heights], heights, edges, what)
    walls["edges_s"] = round(time.monotonic() - t0, 2)

    # Tampered commit: verify_commit names the first bad signature; the
    # per-lane verdicts name all of them, and agree with the oracle.
    t0 = time.monotonic()
    bad_h = heights + 1
    bad = commits[bad_h]
    picks = tamper(bad)
    try:
        verify_commit(helpers.CHAIN_ID, vset, bad.block_id, bad_h, bad)
    except InvalidCommitError as exc:
        m = re.search(r"#(\d+)", str(exc))
        check(
            m is not None and int(m.group(1)) == min(picks),
            "%s: verify_commit blamed %r, first tampered lane is %d",
            what, str(exc)[:60], min(picks),
        )
    else:
        raise SmokeFailure(what + ": verify_commit accepted a tampered commit")
    pks, msgs, sigs = commit_lanes(helpers, vset, bad)
    verdicts = _batch_verify(proposer, pks, msgs, sigs)
    refused = [i for i, v in enumerate(verdicts) if not v]
    check(
        refused == sorted(picks),
        "%s: refused lanes %r, tampered lanes %r",
        what, refused[:16], sorted(picks),
    )
    rng = np.random.default_rng(SEED)
    rows = sorted(set(picks) | set(rng.permutation(n)[:512].tolist()))
    _check_oracle(pks, msgs, sigs, verdicts, rows, what)
    walls["tampered_s"] = round(time.monotonic() - t0, 2)

    # What served the lanes.
    spans = _drain_spans()
    d = _delta(before)
    table_lanes = n * (heights + 2 + len(edges))
    table_kind = "resident" if paths["resident"] else "tables"
    _check_dispatch(spans, {"legacy": n, table_kind: table_lanes}, what)
    _check_health(d, what)
    blocks = _check_blocks(spans, what)
    check(blocks == -(-n // job), "%s: a call of %d blocks, the job is %d lanes", what, blocks, job)
    if paths["device_hash"]:
        check(
            d["hash_device_lanes"] == n + table_lanes,
            "%s: device hashing served %d of %d lanes",
            what, d["hash_device_lanes"], n + table_lanes,
        )
    if paths["resident"]:
        check(
            d["resident_hits"] == table_lanes and d["resident_misses"] == 0,
            "%s: resident store hit %d / missed %d of %d lanes",
            what, d["resident_hits"], d["resident_misses"], table_lanes,
        )
        check(
            d["resident_uploads"] == 1,
            "%s: %d table uploads, want 1", what, d["resident_uploads"],
        )
        check(
            _counters()["gathered_h2d_bytes"] == gathered_after_first,
            what + ": gathered-table H2D grew after the first height",
        )
    # Sharding is the default wherever more than one device is visible
    # and the batch reaches the mesh floor: every device gets an equal
    # slab there, and below the floor the batch stays on one device.
    shards = {}
    for e in spans:
        if e["name"] == "collect_device":
            dev_id = e["args"]["device"]
            shards[dev_id] = shards.get(dev_id, 0) + int(e["args"]["lanes"])
    sharded = _sharded(spans, impl)
    if dev["count"] >= 2 and n >= mesh.MIN_MESH_LANES:
        check(
            len(shards) == dev["count"] and len(set(shards.values())) == 1,
            "%s: lanes per device %r over %d devices",
            what, shards, dev["count"],
        )
        check(d["mesh_dispatches"] > 0, what + ": no sharded dispatch")
    else:
        check(
            not shards and d["mesh_dispatches"] == 0,
            "%s: sharded below the mesh floor: %r", what, shards,
        )
    return {
        "validators": n,
        "setup_s": round(setup_s, 2),
        "walls": walls,
        "counters": d,
        "compiles": _compiles(spans, impl),
        "lanes_per_device": shards,
        "sharded": sharded,
        "blocks": blocks,
        "edges": edges,
    }


def _run_early_begin(tail: int, impl: str) -> dict:
    """One full engine job and ``tail`` lanes more, of keys no set
    holds, added lane by lane to a ``crypto.BatchVerifier``: the
    verifier begins the job's lanes when lane job + 1 arrives (the
    early begin, crypto/batch.DeviceBatchVerifier) and the rest at
    ``verify()``. The lanes on both sides of the seam and the last are
    tampered and must be refused, each at its place and nowhere else.
    On one chip 4,096 + 204 -> 256 lanes of the legacy kernel, widths
    the sizes above have compiled."""
    import numpy as np

    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.ops import ed25519_batch

    _fresh_node()
    job = ed25519_batch.job_lanes()
    n = job + tail
    what = "early begin at %d + %d lanes" % (job, tail)
    privs = [
        Ed25519PrivKey.from_seed((77_000_000 + i).to_bytes(32, "big")) for i in range(n)
    ]
    pks = [p.pub_key().bytes() for p in privs]
    # lengths differ inside every chunk: hashed on the host, so that no
    # device hash kernel is compiled for the tail's lane count
    msgs = [b"chip-smoke early begin %d " % i + b"." * (i % 5) for i in range(n)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    picks = [job - 1, job, n - 1]
    for i in picks:
        sig = bytearray(sigs[i])
        sig[32] ^= 0x01
        sigs[i] = bytes(sig)
    before = _counters()
    _drain_spans()
    verdicts = _batch_verify(privs[0].pub_key(), pks, msgs, sigs)
    refused = [i for i, v in enumerate(verdicts) if not v]
    check(refused == picks, "%s: refused lanes %r, tampered lanes %r", what, refused[:16], picks)
    rng = np.random.default_rng(SEED)
    _check_oracle(
        pks, msgs, sigs, verdicts, sorted(set(picks) | set(rng.permutation(n)[:256].tolist())), what
    )
    spans = _drain_spans()
    _check_dispatch(spans, {"legacy": n}, what)
    _check_health(_delta(before), what)
    begun = [
        (int(e["args"]["lanes"]), e["args"].get("early", 0))
        for e in sorted(spans, key=lambda e: e["ts"])
        if e["name"] == "batch_verify" and e["args"].get("phase") == "dispatch"
    ]
    check(
        begun == [(job, 1), (tail, 0)],
        "%s: blocks begun (lanes, early) %r, want the job early and the rest at verify()",
        what, begun,
    )
    return {"job": job, "tail": tail, "refused": refused, "compiles": _compiles(spans, impl)}


def _run_pipelined_windows(n: int, window: int, paths: dict, impl: str) -> dict:
    """Two windows of ``window`` commits over one ``n``-validator set
    through ``verify_commits_pipelined``, called as the block syncer
    calls it. Light semantics: each block sends the votes that pass 2/3
    and no more. One block of the second window is tampered as the
    commits above are: the pipeline must refuse that block at the first
    tampered commit index, accept the others, and never look at the
    tampered signature past the block's early exit."""
    from tendermint_tpu.parallel.pipeline import CommitTask, verify_commits_pipelined

    _fresh_node()
    what = "%d-commit windows at %d validators" % (window, n)
    t0 = time.monotonic()
    helpers, vset, commits = build_set(n, 2 * window)
    setup_s = time.monotonic() - t0
    quorum = n * 2 // 3 + 1  # equal powers, every validator signs
    bad_block = window // 2
    bad = commits[window + 1 + bad_block]
    picks = tamper(bad)
    bad_idx = min(picks)
    check(
        bad_idx < quorum <= max(picks),
        "%s: tampered lanes %r do not lie on both sides of the early exit at %d",
        what, sorted(picks), quorum,
    )
    before = _counters()
    _drain_spans()
    walls = []
    for first in (1, window + 1):
        tasks = [
            CommitTask(helpers.CHAIN_ID, vset, commits[h].block_id, h, commits[h])
            for h in range(first, first + window)
        ]
        t0 = time.monotonic()
        verdicts = verify_commits_pipelined(tasks)
        walls.append(round(time.monotonic() - t0, 2))
        refused = {i: str(v.error) for i, v in enumerate(verdicts) if not v.ok}
        if first == 1:
            check(not refused, "%s: sound window refused %r", what, refused)
        else:
            check(
                list(refused) == [bad_block]
                and "(#%d)" % bad_idx in refused[bad_block],
                "%s: refused %r, want block %d at commit index %d",
                what, {i: e[:60] for i, e in refused.items()}, bad_block, bad_idx,
            )
    pks, msgs, sigs = commit_lanes(helpers, vset, bad)
    _check_oracle(
        pks, msgs, sigs, [i not in picks for i in range(n)], range(n), what
    )
    spans = _drain_spans()
    d = _delta(before)
    lanes = 2 * window * quorum
    _check_dispatch(spans, {"resident" if paths["resident"] else "tables": lanes}, what)
    _check_health(d, what)
    if paths["resident"]:
        check(
            d["resident_hits"] == lanes and d["resident_misses"] == 0
            and d["resident_uploads"] == 1,
            "%s: resident store hit %d / missed %d of %d lanes in %d uploads",
            what, d["resident_hits"], d["resident_misses"], lanes, d["resident_uploads"],
        )
    calls = [e for e in spans if e["name"] == "verify_commits_pipelined"]
    check(
        [c["args"]["lanes"] for c in calls] == [window * quorum] * 2,
        "%s: pipeline spans %r", what, [c["args"] for c in calls],
    )
    return {
        "validators": n, "window": window, "lanes_per_window": window * quorum,
        "setup_s": round(setup_s, 2), "walls_s": walls, "counters": d,
        "compiles": _compiles(spans, impl),
    }


def _sr25519_lanes(n: int):
    """n sr25519 lanes over 40 signatures made once, every other one
    broken, one of four ways in turn (a flipped R bit, a flipped s bit,
    s + L under the marker bit, an odd and so non-canonical A)."""
    from tendermint_tpu.crypto import ristretto
    from tendermint_tpu.crypto.sr25519 import Sr25519PrivKey

    lanes = []
    for i in range(40):
        priv = Sr25519PrivKey.from_secret(b"chip-smoke sr25519 %d" % i)
        msg = b"chip-smoke sr25519 vote %d" % i
        pub, sig = priv.pub_key().bytes(), bytearray(priv.sign(msg))
        if i % 8 == 1:
            sig[3] ^= 0x01
        elif i % 8 == 3:
            sig[32] ^= 0x01
        elif i % 8 == 5:
            s = (int.from_bytes(sig[32:], "little") & ((1 << 255) - 1)) + ristretto.L
            sig[32:] = (s | 1 << 255).to_bytes(32, "little")
        elif i % 8 == 7:
            pub = bytes([pub[0] | 1]) + pub[1:]
        lanes.append((pub, msg, bytes(sig)))
    return [list(col) for col in zip(*(lanes[(i * 7) % 40] for i in range(n)))]


def _check_sharded_kinds(spans: list, impl: str, n_mesh: int, kinds: set, what: str) -> list:
    """The sharded dispatches among ``spans`` (:func:`_sharded`: each on
    the implementation ``auto`` resolved to): every one over all
    ``n_mesh`` devices, a chunk of each of ``kinds`` among them, and no
    chunk of any kind left on one device."""
    sharded = _sharded(spans, impl)
    by_kind = {}
    for kind, devices, _lanes, _ran in sharded:
        by_kind.setdefault(kind, set()).add(devices)
    check(
        kinds <= set(by_kind) and all(d == {n_mesh} for d in by_kind.values()),
        "%s: sharded dispatches %r, want kinds %r over %d devices",
        what, sharded, sorted(kinds), n_mesh,
    )
    went_out = [m["ts"] for m in spans if m["name"] == "mesh_dispatch"]
    local = [
        (e["args"]["kind"], e["args"]["lanes"]) for e in spans
        if e["name"] == "dispatch_chunk"
        and not any(e["ts"] <= ts <= e["ts"] + e["dur"] for ts in went_out)
    ]
    check(not local, "%s: chunks %r stayed on one device", what, local)
    return sharded


def _run_sr25519(n: int, impl: str, n_mesh: int = 1) -> dict:
    """One full bucket of sr25519 lanes through the engine, against the
    host schnorrkel oracle lane for lane. Where the mesh has ``n_mesh``
    = two devices or more and the lanes reach its floor they go out
    sharded, a slab a device, under ``pallas`` on the sr25519 shard
    program."""
    from tendermint_tpu.crypto.sr25519 import verify as verify_sr
    from tendermint_tpu.ops import sr25519_batch
    from tendermint_tpu.parallel import mesh

    pks, msgs, sigs = _sr25519_lanes(n)
    _drain_spans()
    before = _counters()
    verdicts = sr25519_batch.verify_batch_sr(pks, msgs, sigs)
    oracle = {}
    for lane in zip(pks, msgs, sigs):
        if lane not in oracle:
            oracle[lane] = verify_sr(*lane)
    wrong = [i for i, lane in enumerate(zip(pks, msgs, sigs)) if verdicts[i] != oracle[lane]]
    check(not wrong, "sr25519 at %d lanes: device and oracle disagree on lanes %r", n, wrong[:16])
    check(
        set(oracle.values()) == {True, False},
        "sr25519 at %d lanes: the oracle gave one verdict for every lane", n,
    )
    spans = _drain_spans()
    what = "sr25519 at %d lanes" % n
    _check_dispatch(spans, {"sr25519": n}, what)
    d = _delta(before)
    _check_health(d, what)
    compiles = _compiles(spans, impl)
    sharded = []
    if n_mesh >= 2 and n >= mesh.MIN_MESH_LANES:
        sharded = _check_sharded_kinds(spans, impl, n_mesh, {"sr25519"}, what)
        check(d["mesh_dispatches"] > 0, what + ": no sharded dispatch")
        if impl == "pallas":
            # a width met for the first time in this process fetches its
            # shard program; every one of them is the sr25519 kernel's
            check(
                all(len(c) > 4 and c[1] == "verify_sr" for c in compiles),
                "%s: first calls %r, want the sr25519 shard program alone", what, compiles,
            )
    else:
        check(
            d["mesh_dispatches"] == 0 and not _sharded(spans, impl),
            "%s: sharded below the mesh floor or on one device", what,
        )
    return {
        "lanes": n,
        "accepted": int(sum(map(bool, verdicts))),
        "compiles": compiles,
        "sharded": sharded,
    }


def _mixed_committee(n: int):
    """``(helpers, privs in the set's order, ValidatorSet)``: n // 15
    secp256k1 keys, the rest halves of ed25519 and sr25519."""
    from bench.workload import load_helpers

    helpers = load_helpers()
    n_secp = n // 15
    n_ed = (n - n_secp) // 2
    return (helpers, *helpers.make_mixed_validators(n_ed, n - n_secp - n_ed, n_secp))


def _run_mixed(n: int, impl: str, n_mesh: int = 1) -> dict:
    """A committee of the three key types (n // 15 secp256k1 keys, the
    rest halves) through verify_commit: sound, then with one lane of
    each type tampered, the first blamed and every lane's verdict its
    own key's. The secp256k1 lanes are the host's by design
    (``host_lanes``), verified while both device sub-batches are in
    flight; nothing else may be. Where the mesh has ``n_mesh`` = two
    devices or more and each device type has the mesh floor's lanes,
    both sub-batches go out sharded."""
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.ops import ed25519_batch
    from tendermint_tpu.parallel import mesh
    from tendermint_tpu.types import validation

    helpers, privs, vset = _mixed_committee(n)
    block_id = helpers.make_block_id(b"chip-smoke-mixed-%d-%d" % (SEED, n))
    commit = helpers.make_commit(block_id, 1, 0, vset, privs)
    types = [v.pub_key.type for v in vset.validators]
    sent = {kt: types.count(kt) for kt in set(types)}
    n_secp = sent["secp256k1"]
    _fresh_node()
    _drain_spans()
    before = _counters()
    validation.verify_commit(helpers.CHAIN_ID, vset, block_id, 1, commit)
    spans = _drain_spans()
    names = {e["name"] for e in spans}
    check(
        not names & {"single_verify", "host_fallback"},
        "mixed committee: the commit left the batch path: %r", sorted(names),
    )
    host = {e["args"]["key_type"]: e["args"]["lanes"] for e in spans if e["name"] == "host_lanes"}
    check(host == {"secp256k1": n_secp}, "mixed committee: host lanes %r", host)
    inflight = [e["args"].get("device_lanes_inflight") for e in spans if e["name"] == "host_lanes"]
    check(
        inflight == [n - n_secp],
        "mixed committee: host lanes verified with %r device lanes in flight, want %d",
        inflight, n - n_secp,
    )
    answered_by = [e["args"].get("impl") for e in spans if e["name"] == "host_lanes"]
    check(
        answered_by == ["native"],
        "mixed committee: host lanes answered by %r, want the native routine's one call", answered_by,
    )
    sharded = []
    if n_mesh >= 2 and min(sent["ed25519"], sent["sr25519"]) >= mesh.MIN_MESH_LANES:
        # both sub-batches sharded, the ed25519 one first (a chunk a kind), then sr25519's one
        sharded = _check_sharded_kinds(spans, impl, n_mesh, {"sr25519"}, "mixed committee")
        engines = [e["args"]["engine"] for e in spans if e["name"] == "mesh_dispatch"]
        check(
            engines[:1] == ["ed25519"] and engines.count("sr25519") == 1 and engines[-1] == "sr25519",
            "mixed committee: sharded dispatches by engine %r, want ed25519's then sr25519's", engines,
        )
    by_engine = {}
    for e in spans:
        if e["name"] == "dispatch_chunk":
            eng = e["args"]["engine"]
            by_engine[eng] = by_engine.get(eng, 0) + int(e["args"]["lanes"])
    check(
        by_engine == {kt: sent[kt] for kt in ("ed25519", "sr25519")},
        "mixed committee: lanes dispatched by engine %r != lanes sent %r", by_engine, sent,
    )
    # the committee is one block of the loop: a lane refused at either edge is named
    _blame_block_edges(helpers, vset, commit, 1, [0, n - 1], "mixed committee")
    # one lane of each type tampered: the first is blamed, and every
    # lane's verdict is its own key's
    bad = sorted(types.index(kt) + 1 for kt in sent)
    for idx in bad:
        sig = bytearray(commit.signatures[idx].signature)
        sig[40] ^= 0x01
        commit.signatures[idx].signature = bytes(sig)
    try:
        validation.verify_commit(helpers.CHAIN_ID, vset, block_id, 1, commit)
    except validation.InvalidCommitError as exc:
        check("(#%d)" % bad[0] in str(exc), "mixed committee: blame %s, want lane %d", exc, bad[0])
    else:
        check(False, "mixed committee: a commit with three tampered lanes was accepted")
    bv = crypto_batch.MultiBatchVerifier()
    lanes = [
        (v.pub_key, commit.vote_sign_bytes(helpers.CHAIN_ID, i), commit.signatures[i].signature)
        for i, v in enumerate(vset.validators)
    ]
    for lane in lanes:
        bv.add(*lane)
    _, verdicts = bv.verify()
    wrong = [i for i, (pk, m, sg) in enumerate(lanes) if verdicts[i] != pk.verify_signature(m, sg)]
    check(not wrong, "mixed committee: device and oracles disagree on lanes %r", wrong[:16])
    check(
        [i for i, ok in enumerate(verdicts) if not ok] == bad,
        "mixed committee: refused lanes %r, tampered %r",
        [i for i, ok in enumerate(verdicts) if not ok], bad,
    )
    spans += _drain_spans()
    _check_health(_delta(before), "mixed committee")
    blocks = _check_blocks(spans, "mixed committee")
    if max(sent.values()) < ed25519_batch.job_lanes():
        check(blocks == 1, "mixed committee: a commit under one job built in %d blocks", blocks)
    return {
        "validators": n, "sent": sent, "tampered": bad, "compiles": _compiles(spans, impl),
        "sharded": sharded,
    }


def _run_mixed_window(n: int, window: int, impl: str) -> dict:
    """One blocksync window of ``window`` commits over a committee of the
    three key types (``_run_mixed``'s split), 5% of the validators absent
    and 1% voting nil at each height, through ``verify_commits_pipelined``
    as the block syncer calls it, with one included lane of each key
    type tampered, each in a block of its own. Every block's verdict is
    held to the light rule over the keys' own ``verify_signature``, block
    by block; and the window must have been planned by key type: each
    device type's lanes begun once a window, the secp256k1 lanes of every
    block in one ``host_lanes`` call made while both are in flight, no
    block given to ``verify_commit_light`` alone."""
    from tendermint_tpu.parallel.pipeline import CommitTask, verify_commits_pipelined
    from tendermint_tpu.types import BLOCK_ID_FLAG_COMMIT

    what = "%d-commit window at %d validators of three key types" % (window, n)
    helpers, privs, vset = _mixed_committee(n)
    types = [v.pub_key.type for v in vset.validators]
    quorum = n * 2 // 3 + 1  # equal powers
    rng = random.Random(SEED)
    n_absent, n_nil = math.ceil(0.05 * n), math.ceil(0.01 * n)
    tasks, included = [], []
    for h in range(1, window + 1):
        order = rng.sample(range(n), n)
        block_id = helpers.make_block_id(b"chip-smoke-mixed-window-%d-%d" % (SEED, h))
        commit = helpers.make_commit(
            block_id, h, 0, vset, privs,
            absent=set(order[:n_absent]), nil_votes=set(order[n_absent : n_absent + n_nil]),
        )
        tasks.append(CommitTask(helpers.CHAIN_ID, vset, block_id, h, commit))
        included.append([
            i for i, cs in enumerate(commit.signatures) if cs.block_id_flag == BLOCK_ID_FLAG_COMMIT
        ][:quorum])
    tampered = {}
    for block, kt in zip(rng.sample(range(window), 3), ("ed25519", "sr25519", "secp256k1")):
        idx = rng.choice([i for i in included[block] if types[i] == kt])
        sig = bytearray(tasks[block].commit.signatures[idx].signature)
        sig[40] ^= 0x01
        tasks[block].commit.signatures[idx].signature = bytes(sig)
        tampered[block] = idx
    sent = {kt: sum(types[i] == kt for lanes in included for i in lanes) for kt in set(types)}
    _fresh_node()
    _drain_spans()
    before = _counters()
    t0 = time.monotonic()
    verdicts = verify_commits_pipelined(tasks)
    wall_s = round(time.monotonic() - t0, 2)
    spans = _drain_spans()
    for block, (task, lanes, verdict) in enumerate(zip(tasks, included, verdicts)):
        bad = next(
            (i for i in lanes if not vset.validators[i].pub_key.verify_signature(
                task.commit.vote_sign_bytes(helpers.CHAIN_ID, i), task.commit.signatures[i].signature)),
            None,
        )
        check(bad == tampered.get(block), "%s: the oracles refuse lane %r of block %d", what, bad, block)
        check(
            verdict.ok if bad is None else "(#%d)" % bad in str(verdict.error),
            "%s: block %d got %s, the oracles say %s",
            what, block, "ok" if verdict.ok else str(verdict.error)[:60], "ok" if bad is None else "#%d" % bad,
        )
    names = [e["name"] for e in spans]
    check(
        not set(names) & {"verify_commit", "single_verify", "host_fallback"},
        "%s: a block left the window's plan: %r", what, sorted(set(names)),
    )
    begun = [
        (e["args"]["key_type"], int(e["args"]["lanes"]))
        for e in sorted(spans, key=lambda e: e["ts"])
        if e["name"] == "batch_verify" and e["args"].get("phase") == "dispatch"
    ]
    check(
        begun == [(kt, sent[kt]) for kt in ("ed25519", "sr25519")],
        "%s: device sub-batches begun %r, want one a key type a window: %r", what, begun, sent,
    )
    host = [
        (e["args"]["key_type"], e["args"]["lanes"], e["args"].get("device_lanes_inflight"), e["args"].get("impl"))
        for e in spans if e["name"] == "host_lanes"
    ]
    check(
        host == [("secp256k1", sent["secp256k1"], sent["ed25519"] + sent["sr25519"], "native")],
        "%s: host lanes %r, want the window's %d in one native call under both sub-batches",
        what, host, sent["secp256k1"],
    )
    by_engine = {}
    for e in spans:
        if e["name"] == "dispatch_chunk":
            by_engine[e["args"]["engine"]] = by_engine.get(e["args"]["engine"], 0) + int(e["args"]["lanes"])
    check(
        by_engine == {kt: sent[kt] for kt in ("ed25519", "sr25519")},
        "%s: lanes dispatched by engine %r != lanes sent %r", what, by_engine, sent,
    )
    (call,) = [e["args"] for e in spans if e["name"] == "verify_commits_pipelined"]
    check(
        (call["lanes"], call["sub_batches"], call["host_lanes"]) == (window * quorum, 3, sent["secp256k1"]),
        "%s: pipeline span %r", what, call,
    )
    _check_health(_delta(before), what)
    return {
        "validators": n, "window": window, "sent": sent, "tampered": tampered, "wall_s": wall_s,
        "launches": names.count("dispatch_chunk"), "compiles": _compiles(spans, impl),
    }


def library_phase(
    expect_platform: str, sizes=SIZES, heights: int = HEIGHTS,
    sync=(SYNC_VALS, SYNC_WINDOW), sr_buckets=SR_BUCKETS, mixed: int = MIXED_VALS,
    early_tail: int = EARLY_TAIL, mixed_sync=MIXED_SYNC,
) -> dict:
    """The library phase, in this process. Raises SmokeFailure."""
    from tendermint_tpu.ops import backend as ops_backend

    dev = ops_backend.device_identity()
    say("device: platform=%(platform)s kind=%(kind)s count=%(count)d" % dev)
    check(
        dev["platform"] == expect_platform,
        "JAX gave platform %r, want %r (JAX_PLATFORMS=%r)"
        % (dev["platform"], expect_platform, os.environ.get("JAX_PLATFORMS")),
    )

    from tendermint_tpu.crypto import hashing
    from tendermint_tpu.libs import tracing
    from tendermint_tpu.ops import (
        autotune, ed25519_batch, hash512, precompute, resident,
    )
    from tendermint_tpu.ops.device_policy import HEALTHY, shared as health
    from tendermint_tpu.parallel import mesh

    check(
        not precompute.result_cache_enabled(),
        "result cache is on: repeats would not reach the device",
    )
    check(
        not os.environ.get("TENDERMINT_TPU_VERIFY_REMOTE"),
        "a verifyd remote is configured: lanes would leave this process",
    )
    host_hash = hashing.host_hash_impl()
    say("host hashing: %s" % host_hash)
    check(
        host_hash == "native",
        "native/sha512_batch.c did not build: host hashing fell to hashlib",
    )
    host_secp = hashing.host_secp256k1_impl()
    say("host secp256k1: %s" % host_secp)
    check(
        host_secp == "native",
        "native/secp256k1_batch.c built without 128-bit integers: secp256k1 lanes fell to OpenSSL",
    )
    impl = ed25519_batch.active_impl()
    paths = {
        "device_hash": hash512.device_hash_enabled(),
        "resident": resident.enabled(),
        "autotune": autotune.enabled(),
    }
    say("verify implementation: %s; auto paths: %r" % (impl, paths))
    if expect_platform == "tpu":
        check(all(paths.values()), "a path auto turns on for tpu is off: %r", paths)

    prev_mode = tracing.tracer.mode
    tracing.configure("ring")
    try:
        report = {
            "device": dev, "impl": impl, "paths": paths, "host_hash": host_hash,
            "host_secp256k1": host_secp,
        }

        # ZIP-215 edge vectors straight into ops.verify_batch.
        _drain_spans()
        before = _counters()
        pks, msgs, sigs = edge_vectors()
        verdicts = ed25519_batch.verify_batch(pks, msgs, sigs)
        _check_oracle(pks, msgs, sigs, verdicts, range(len(pks)), "edge vectors")
        spans = _drain_spans()
        _check_dispatch(spans, {"legacy": len(pks)}, "edge vectors")
        _check_health(_delta(before), "edge vectors")
        report["edge"] = {
            "lanes": len(pks),
            "accepted": int(sum(map(bool, verdicts))),
            "compiles": _compiles(spans, impl),
        }
        say(
            "edge vectors: %(lanes)d lanes, %(accepted)d accepted, as the "
            "oracle; compiled %(compiles)r" % report["edge"]
        )

        if dev["count"] >= 2:
            report["sharded_edge"] = _run_sharded_edge(dev, impl)
            say(
                "sharded edge vectors: %(lanes)d lanes, %(accepted)d accepted, "
                "as the oracle, %(wall_s)ss; sharded %(sharded)r; compiled "
                "%(compiles)r" % report["sharded_edge"]
            )

        report["sizes"] = []
        for n in sizes:
            rep = _run_size(n, heights, dev, impl, paths)
            report["sizes"].append(rep)
            say("%(validators)d validators: set-up %(setup_s)ss, walls %(walls)r" % rep)
            say("  counters %(counters)r" % rep)
            say("  compiled %(compiles)r" % rep)
            say("  lanes/device %(lanes_per_device)r sharded %(sharded)r" % rep)
            say("  the entry's loop: %(blocks)d blocks a call at most, lanes %(edges)r refused one at a time and named" % rep)

        if early_tail:
            rep = report["early_begin"] = _run_early_begin(early_tail, impl)
            say(
                "early begin: %(job)d lanes begun at the add after them, %(tail)d at "
                "verify(), lanes %(refused)r tampered and refused; compiled %(compiles)r" % rep
            )

        if sync:
            rep = _run_pipelined_windows(sync[0], sync[1], paths, impl)
            report["pipelined"] = rep
            say(
                "%(window)d-commit windows at %(validators)d validators: "
                "%(lanes_per_window)d lanes a window, set-up %(setup_s)ss, "
                "walls %(walls_s)r" % rep
            )
            say("  counters %(counters)r" % rep)
            say("  compiled %(compiles)r" % rep)

        # the devices a batch over the mesh floor is sharded over: all of
        # them, unless the operator capped the mesh ([ops] mesh_devices)
        n_mesh = mesh.manager.device_count()
        if n_mesh >= 2 and sr_buckets:
            # a slab of the widest bucket on every device, and a mixed
            # committee both of whose device sub-batches pass the mesh floor
            sr_buckets = tuple(sr_buckets) + (SR_MESH_LANES,)
        if n_mesh >= 2 and mixed:
            mixed = max(mixed, MIXED_MESH_VALS)
        report["sr25519"] = [_run_sr25519(n, impl, n_mesh) for n in sr_buckets]
        for rep in report["sr25519"]:
            say(
                "sr25519: %(lanes)d lanes, %(accepted)d accepted, as the oracle; "
                "sharded %(sharded)r; compiled %(compiles)r" % rep
            )
        report["mixed_committee"] = _run_mixed(mixed, impl, n_mesh) if mixed else None
        if mixed:
            say(
                "mixed committee: %(validators)d validators %(sent)r, lanes %(tampered)r "
                "tampered and refused; sharded %(sharded)r; compiled %(compiles)r"
                % report["mixed_committee"]
            )

        report["mixed_window"] = _run_mixed_window(*mixed_sync, impl) if mixed_sync else None
        if mixed_sync:
            say(
                "mixed catch-up window: %(window)d commits at %(validators)d validators, lanes %(sent)r "
                "in %(launches)d launches and one host call, %(wall_s)ss; blocks and lanes %(tampered)r "
                "tampered and refused; compiled %(compiles)r" % report["mixed_window"]
            )

        snap = health.snapshot()
        check(snap["state"] == HEALTHY, "device health ended %r", snap["state"])
        tuned = autotune.stats()
        report["autotune"] = {k: tuned[k] for k in ("selections", "timings_ms")}
        say(
            "autotune selections: %(selections)r timings_ms %(timings_ms)r"
            % report["autotune"]
        )
        return report
    finally:
        tracing.configure(prev_mode)


# --- served phase (parent side) -----------------------------------------------


def _start_daemon(workdir: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [HERE] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )
    env["TENDERMINT_TPU_RESULT_CACHE"] = "0"
    env["TENDERMINT_TPU_FLIGHTREC_DIR"] = os.path.join(workdir, "flightrec")
    out = open(os.path.join(workdir, "verifyd.out"), "w+")
    err = open(os.path.join(workdir, "verifyd.err"), "w+")
    proc = subprocess.Popen(
        [sys.executable, *WARNING_FLAGS, "-m", "tendermint_tpu", "verifyd",
         "--listen", "127.0.0.1:0"],
        stdout=out, stderr=err, env=env, cwd=workdir,
    )
    return proc, out, err


def _read(f) -> str:
    f.flush()
    f.seek(0)
    return f.read()


def _await_banner(proc, out, err, timeout: float) -> str:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        m = re.search(r"verifyd serving on (\S+:\d+) \((.*)\)", _read(out))
        if m:
            say("daemon: " + m.group(0))
            return m.group(1)
        check(
            proc.poll() is None,
            "verifyd exited %r at start-up: %s", proc.returncode, _read(err)[-2000:],
        )
        time.sleep(0.2)
    raise SmokeFailure("verifyd printed no banner in %.0fs" % timeout)


def _check_served(snap: dict, pool: list, expect_platform: str, requests: int):
    """The daemon's stats and the clients' counters: the device served
    the requests, and nothing on either side fell back."""
    stats, health = snap["stats"], snap["device_health"]
    dev = stats["device"]
    check(
        dev is not None and dev["platform"] == expect_platform,
        "daemon runs on %r, want platform %r", dev, expect_platform,
    )
    for key in ("host_direct_lanes", "admission_rejections", "deadline_expired"):
        check(stats[key] == 0, "daemon %s = %r", key, stats[key])
    check(
        health["state"] == "healthy"
        and health["fallback_batches"] == 0
        and sum(health["failures"].values()) == 0,
        "daemon device health: %r", health,
    )
    check(
        stats["requests_served"] == requests,
        "daemon served %r of %d requests", stats["requests_served"], requests,
    )
    # Not "== lanes sent": the scheduler may cut a flush under 16 lanes,
    # which crypto.batch verifies on the host by design.
    if _auto_on("TENDERMINT_TPU_DEVICE_HASH", dev["platform"]):
        check(
            snap["hash512"]["device_lanes"] > 0,
            "daemon hashed no lanes on the device: %r", snap["hash512"],
        )
    if _auto_on("TENDERMINT_TPU_RESIDENT", dev["platform"]):
        r = snap["resident"]
        check(
            r["hits"] > 0 and r["misses"] == 0 and r["gathered_h2d_bytes"] == 0,
            "daemon resident store served no lanes, or tables were shipped "
            "per batch: %r", r,
        )
    for c in pool:
        check(
            c.fallback_calls == 0 and not c.rejected,
            "a client fell back or was rejected: %r %r", c.stats(), c.rejected,
        )


def served_phase(
    expect_platform: str,
    n_vals: int = SERVED_VALS,
    clients: int = CLIENTS,
    per_client: int = REQUESTS_PER_CLIENT,
    warmups: int = WARMUP_REQUESTS,
) -> dict:
    """Start the CLI daemon, drive it with verifyd.client, read its
    stats, stop it. Runs in the caller's process, which needs no
    device: the daemon is the one process that holds it."""
    from tendermint_tpu.crypto.ed25519_ref import verify_zip215
    from tendermint_tpu.verifyd.client import VerifydClient
    from tendermint_tpu.verifyd.protocol import CLASS_CONSENSUS

    helpers, vset, commits = build_set(n_vals, 1 + clients * per_client)
    picks = tamper(commits[2])  # client 0's first request
    lanes = {h: commit_lanes(helpers, vset, c) for h, c in commits.items()}
    want = {
        h: [verify_zip215(*lane) for lane in zip(*lanes[h])] for h in lanes
    }
    check(
        [i for i, v in enumerate(want[2]) if not v] == sorted(picks),
        "oracle does not refuse exactly the tampered lanes",
    )

    workdir = tempfile.mkdtemp(prefix="chip_smoke_verifyd_")
    proc, out, err = _start_daemon(workdir)
    pool = []
    try:
        addr = _await_banner(proc, out, err, timeout=180.0)

        def new_client():
            c = VerifydClient(addr, fallback=False, timeout=DEADLINE_S)
            pool.append(c)
            return c

        def call(client, h):
            got = client.verify(
                *lanes[h], klass=CLASS_CONSENSUS, deadline=DEADLINE_S
            )
            check(got == want[h], "height %d: served verdicts differ from the oracle", h)

        t0 = time.monotonic()
        warm = new_client()
        for _ in range(warmups):
            call(warm, 1)
        warm_s = time.monotonic() - t0

        errors = []

        def worker(k):
            try:
                client = new_client()
                for j in range(per_client):
                    call(client, 2 + k * per_client + j)
            except Exception as exc:  # re-raised in the caller's thread
                errors.append(exc)

        t0 = time.monotonic()
        threads = [
            threading.Thread(target=worker, args=(k,)) for k in range(clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=DEADLINE_S * per_client + 30)
            check(not t.is_alive(), "a client thread did not finish")
        if errors:
            raise errors[0]
        burst_s = time.monotonic() - t0

        snap = warm.server_stats(timeout=30.0)
        requests = warmups + clients * per_client
        _check_served(snap, pool, expect_platform, requests)

        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("verifyd did not exit within 60s of SIGTERM")
        check(rc == 0, "verifyd exited %r: %s", rc, _read(err)[-2000:])
        stderr = "\n".join(
            line for line in _read(err).splitlines()
            if _HUGEPAGES not in line and "warnings.warn(" not in line
        )
        check(
            "Traceback" not in stderr and "Warning" not in stderr,
            "verifyd stderr: %s", stderr[-2000:],
        )
        stats, health = snap["stats"], snap["device_health"]
        stat_keys = (
            "requests_served", "host_direct_lanes", "admission_rejections",
            "deadline_expired", "cross_client_flushes", "compile_events",
            "scheduler",
        )
        client_keys = ("transport", "calls", "fallback_calls", "shm_fallbacks")
        report = {
            "device": stats["device"],
            "requests": requests,
            "lanes": requests * n_vals,
            "warmup_s": round(warm_s, 2),
            "burst_s": round(burst_s, 2),
            "stats": {k: stats[k] for k in stat_keys},
            "device_health": {
                k: health[k] for k in ("state", "fallback_batches", "failures")
            },
            "hash512": snap["hash512"],
            "resident": snap["resident"],
            "brownout": snap["brownout"],
            "clients": [
                {k: cs[k] for k in client_keys} for cs in (c.stats() for c in pool)
            ],
        }
        say(
            "served: %d requests / %d lanes as the oracle, tampered lanes %r "
            "attributed" % (requests, report["lanes"], sorted(picks))
        )
        say(
            "  warm-up %(warmup_s)ss, concurrent clients %(burst_s)ss; "
            "stats %(stats)r" % report
        )
        say(
            "  health %(device_health)r hash %(hash512)r resident %(resident)r"
            % report
        )
        say("  brownout %(brownout)r clients %(clients)r" % report)
        return report
    finally:
        for c in pool:
            c.close()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        out.close()
        err.close()
        shutil.rmtree(workdir, ignore_errors=True)


# --- entry points -------------------------------------------------------------


def _child_library(report_path: str) -> int:
    """``--phase library``: the library phase in a process of its own."""
    os.environ["TENDERMINT_TPU_RESULT_CACHE"] = "0"
    report = library_phase("tpu")
    with open(report_path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    return 0


def _cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        HERE, ".jax_cache"
    )


def _cache_entries() -> int:
    total = 0
    for _root, _dirs, files in os.walk(_cache_dir()):
        total += sum(1 for f in files if not f.endswith("-atime"))
    return total


def _run_library_child(run: int, workdir: str, deadline: float) -> dict:
    report_path = os.path.join(workdir, "library_run%d.json" % run)
    env = dict(os.environ)
    # jax keeps only compiles over a second by default; the smoke keeps
    # all of them, so that "the second run adds no entry" is exact.
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    t0 = time.monotonic()
    try:
        rc = subprocess.run(
            [sys.executable, *WARNING_FLAGS, os.path.abspath(__file__),
             "--phase", "library", "--report", report_path],
            env=env, cwd=HERE, timeout=max(1.0, deadline - time.monotonic()),
        ).returncode
    except subprocess.TimeoutExpired:
        raise SmokeFailure(
            "library run %d ran out of the smoke's time budget" % run
        )
    check(rc == 0, "library run %d exited %d", run, rc)
    with open(report_path) as f:
        report = json.load(f)
    report["wall_s"] = round(time.monotonic() - t0, 1)
    report["compile_s"] = round(sum(c[3] for c in _first_calls(report)), 1)
    return report


def _keep(reports: dict) -> None:
    """Leave the reports where the chip tool brings files back from."""
    out = os.path.join(HERE, "chiprun_out", "chip_smoke")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "report.json"), "w") as f:
        json.dump(reports, f, indent=1, sort_keys=True)


def run_smoke() -> dict:
    deadline = time.monotonic() + BUDGET_S
    check(
        os.path.isdir(os.path.join(HERE, "tendermint_tpu"))
        and os.path.isfile(os.path.join(HERE, "tests", "helpers.py")),
        "the repository is not beside chip_smoke.py (%s)" % HERE,
    )
    sys.path.insert(0, HERE)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        say("compile cache: %s (%d entries)" % (_cache_dir(), _cache_entries()))
        run1 = _run_library_child(1, workdir, deadline)
        entries1 = _cache_entries()
        run2 = _run_library_child(2, workdir, deadline)
        entries2 = _cache_entries()
        for run, rep, entries in ((1, run1, entries1), (2, run2, entries2)):
            say(
                "library run %d: %.1fs wall, %.1fs in first calls of kernels "
                "(stored programs: %d hit, %d miss), cache %d entries"
                % (run, rep["wall_s"], rep["compile_s"], *_stored_counts(rep), entries)
            )
        check(
            entries1 > 0,
            "run 1 left no entry in the compile cache at %s", _cache_dir(),
        )
        check(
            entries2 == entries1,
            "run 2 added %d entries to a cache run 1 had filled",
            entries2 - entries1,
        )
        for run, rep in ((1, run1), (2, run2)):
            for ran, kernel, lanes, secs, devices, stored in _sharded_first_calls(rep):
                say(
                    "library run %d: sharded %s kernel %s, %s lanes a device on %s "
                    "devices: first call %.2fs, stored program: %s"
                    % (run, ran, kernel, lanes, devices, secs, stored)
                )
        _check_store_warm(2, run2)
        check(
            time.monotonic() < deadline,
            "out of the smoke's time budget before the served phase",
        )
        served = served_phase("tpu")
        check(
            served["device"] == run1["device"],
            "daemon device %r != library device %r",
            served["device"], run1["device"],
        )
        _keep({"library_run1": run1, "library_run2": run2, "served": served,
               "cache_entries": [entries1, entries2]})
        return run1["device"]
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", choices=("library",), help=argparse.SUPPRESS)
    ap.add_argument("--report", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    warnings.simplefilter("error")
    warnings.filterwarnings("ignore", message=_HUGEPAGES, category=UserWarning)
    try:
        if args.phase == "library":
            sys.path.insert(0, HERE)
            return _child_library(args.report)
        device = run_smoke()
    except SmokeFailure as exc:
        print("chip_smoke: FAILED: %s" % exc, file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
