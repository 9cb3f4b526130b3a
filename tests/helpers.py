"""Shared test fixtures: deterministic validator sets and signed commits.

The analog of the reference's types test helpers (types/test_util.go
makeCommit / deterministicValidatorSet).
"""

from __future__ import annotations

import collections
import contextlib
import fcntl
import functools
import hashlib
import json
import os
import random
import socket
import subprocess
import sys
import tempfile
import time
from typing import List, Optional, Tuple

from chipbench import spec
from tendermint_tpu.crypto.keys import Ed25519PrivKey
from tendermint_tpu.encoding.canonical import Timestamp
from tendermint_tpu.types import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    BlockID,
    Commit,
    CommitSig,
    PartSetHeader,
    Validator,
    ValidatorSet,
)

CHAIN_ID = "test-chain"


def make_block_id(seed: bytes = b"block") -> BlockID:
    h = hashlib.sha256(seed).digest()
    ph = hashlib.sha256(seed + b"-parts").digest()
    return BlockID(h, PartSetHeader(1, ph))


def make_validators(
    n: int, power: int = 10, key_factory=None
) -> Tuple[List[Ed25519PrivKey], ValidatorSet]:
    """Deterministic validator set; ``key_factory(i) -> PrivKey`` swaps
    the key scheme per slot (mixed ed25519/sr25519 sets for BASELINE
    config 5 pass a factory; default is all-ed25519)."""
    if key_factory is None:
        key_factory = lambda i: Ed25519PrivKey.from_seed(i.to_bytes(32, "big"))
    privs = [key_factory(i) for i in range(n)]
    vals = [Validator(p.pub_key(), power) for p in privs]
    vset = ValidatorSet(vals)
    # Sort privkeys to match the canonical validator order (by power desc,
    # address asc — all powers equal here so address order).
    by_addr = {p.pub_key().address(): p for p in privs}
    privs_sorted = [by_addr[v.address] for v in vset.validators]
    return privs_sorted, vset


def make_mixed_validators(n_ed: int, n_sr: int, n_secp: int, power: int = 10):
    """``make_validators`` over the three key types a set may hold
    (BASELINE config 5): ``(privs in the set's order, ValidatorSet)``.
    Sixteen lanes of a type that batches reach the device
    (crypto.batch.DEVICE_THRESHOLD)."""
    from tendermint_tpu.crypto.keys import Secp256k1PrivKey
    from tendermint_tpu.crypto.sr25519 import Sr25519PrivKey

    def factory(i):
        if i < n_ed:
            return Ed25519PrivKey.from_seed(i.to_bytes(32, "big"))
        if i < n_ed + n_sr:
            return Sr25519PrivKey.from_secret(b"mixed-sr %d" % i)
        return Secp256k1PrivKey(hashlib.sha256(b"mixed-secp %d" % i).digest())

    return make_validators(n_ed + n_sr + n_secp, power=power, key_factory=factory)


def make_commit(
    block_id: BlockID,
    height: int,
    round_: int,
    vset: ValidatorSet,
    privs: List[Ed25519PrivKey],
    chain_id: str = CHAIN_ID,
    absent: Optional[set] = None,
    nil_votes: Optional[set] = None,
    time_ns: int = 1_700_000_000_000_000_000,
) -> Commit:
    """Sign a precommit for every validator (indices in ``absent`` produce
    absent CommitSigs; in ``nil_votes``, nil-block precommits)."""
    absent = absent or set()
    nil_votes = nil_votes or set()
    sigs: List[CommitSig] = []
    commit = Commit(height=height, round=round_, block_id=block_id)
    for i, val in enumerate(vset.validators):
        if i in absent:
            sigs.append(CommitSig.absent())
            continue
        flag = BLOCK_ID_FLAG_NIL if i in nil_votes else BLOCK_ID_FLAG_COMMIT
        ts = Timestamp.from_unix_ns(time_ns + i)
        cs = CommitSig(flag, val.address, ts, b"")
        commit.signatures.append(cs)
        sign_bytes = commit.vote_sign_bytes(chain_id, len(commit.signatures) - 1)
        cs.signature = privs[i].sign(sign_bytes)
        commit.signatures.pop()
        sigs.append(cs)
    commit.signatures = sigs
    return commit


@functools.lru_cache(maxsize=None)
def warm_verify(lanes: int = 16) -> None:
    """``lanes`` valid ed25519 lanes through ``verify_batch`` twice, once
    a process: with keys met for the first time (the ``legacy`` kernel)
    and with the keys pinned, as a validator set's are (``tables``). The
    two kernels of their bucket are then compiled, or read from the
    compile cache, before a test starts a clock that a first compile
    must not run on: a node that joins a chain whose blocks are pruned
    behind it, a caller with a timeout of its own."""
    from tendermint_tpu.ops import ed25519_batch, precompute

    privs = [Ed25519PrivKey.from_seed(bytes([i + 1]) * 32) for i in range(lanes)]
    pks = [p.pub_key().bytes() for p in privs]
    try:
        for pinned in (False, True):
            if pinned:
                precompute.pin_pubkeys(set(pks))
            # lanes of each pass's own: the result cache, where it is on, has met none of them
            msgs = [b"warm %d, pinned: %d" % (i, pinned) for i in range(lanes)]
            sigs = [p.sign(m) for p, m in zip(privs, msgs)]
            assert ed25519_batch.verify_batch(pks, msgs, sigs) == [True] * lanes
    finally:
        precompute.reset()


def _below_the_kernels_own_ports(n: int):
    """Bases of ``n``-port blocks under the range the kernel takes
    ``bind(0)`` and a ``connect()``'s own end from, drawn apart for
    every process."""
    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            top = int(f.read().split()[0])
    except (OSError, ValueError):
        top = 32768
    draw = random.Random(os.getpid() * 1_000_003 + time.monotonic_ns())
    while True:
        yield draw.randrange(10_000, top - n)


def free_port_block(n: int, candidates=None) -> int:
    """The first of ``n`` consecutive ports of 127.0.0.1, all of which
    this process held bound at one moment, and released only then; a
    block with a taken port is left for the next of ``candidates``.
    Asking the kernel for one free port and assuming its neighbours
    hands a node the port another worker's socket was given a moment
    before: by default the blocks lie where the kernel gives none away
    (:func:`_below_the_kernels_own_ports`)."""
    for tried, base in enumerate(candidates or _below_the_kernels_own_ports(n)):
        held = []
        try:
            for port in range(base, base + n):
                sock = socket.socket()
                held.append(sock)
                sock.bind(("127.0.0.1", port))
            return base
        except OSError:
            if tried >= 100:
                raise
        finally:
            for sock in held:
                sock.close()
    raise OSError("no block of %d free ports among the candidates" % n)


@contextlib.contextmanager
def trace_turn(root: str, exclusive: bool, bound: float, every: float = 0.2):
    """This process's turn at the one ``.chipbench_trace`` of the
    checkout at ``root``: a lock file held alone (``exclusive``, a
    traced run) or together with other untraced runs. Asked for without
    blocking, again every ``every`` seconds, for ``bound`` seconds at
    most: a test that cannot have its turn fails and says that it
    waited, where a blocking ``flock`` would book another test's
    minutes to it, or hang the run."""
    path = os.path.join(
        tempfile.gettempdir(),
        "chipbench_trace_%s.lock" % hashlib.sha256(root.encode()).hexdigest()[:12],
    )
    mode = (fcntl.LOCK_EX if exclusive else fcntl.LOCK_SH) | fcntl.LOCK_NB
    deadline = time.monotonic() + bound
    with open(path, "w") as turn:
        while True:
            try:
                fcntl.flock(turn, mode)
                break
            except BlockingIOError:
                if time.monotonic() >= deadline:
                    raise AssertionError(
                        "waited %.0f s for the trace lock %s and did not get it: "
                        "another rehearsal holds it" % (bound, path)
                    ) from None
                time.sleep(every)
        yield


def rehearse_cell(
    bench: str, cell: str, seed: int, trace: int, *extra, timeout: int = 300, prelude: str = "",
    seconds: float = 1, env=None,
):
    """(result line, standard output) of one CPU rehearsal of a
    benchmark cell's tiny twin: ``chipbench.run --rehearse`` in a child.

    Every run empties one directory of the checkout (``.chipbench_trace``)
    before and after its window, and a traced run profiles into it. The
    rehearsals, wherever xdist runs them, therefore take turns
    (:func:`trace_turn`): a traced run has the directory alone, untraced
    ones have it together, and none waits longer for it than the
    child's own ``timeout``. ``prelude`` is Python the child
    runs before ``chipbench.run``'s ``main``: a fault planted in the
    program where ``chipbench/breaks.py`` knows none. ``seconds`` is the
    window's length and ``env`` what the child's environment holds
    besides this process's (a cell on virtual devices of its own)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    args = [
        "--workload", cell, "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace),
        "--rehearse", "--bench-file", bench, *extra,
    ]
    cmd = [sys.executable, "-m", "chipbench.run", *args]
    if prelude:
        code = prelude + "\nimport sys, chipbench.run\nsys.exit(chipbench.run.main(sys.argv[1:]))\n"
        cmd = [sys.executable, "-c", code, *args]
    waited = time.monotonic()
    with trace_turn(root, exclusive=bool(trace), bound=timeout):
        began = time.monotonic()
        proc = subprocess.run(
            cmd, cwd=root, capture_output=True, text=True, timeout=timeout,
            env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})),
        )
    # captured with the test's output: what a layout of these cases is judged by
    print("rehearsal %s trace=%d: waited %.1f s for the trace lock, ran %.1f s"
          % (cell, trace, began - waited, time.monotonic() - began))
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


# --- the benchmark's per-layer metrics, named through the cell that reports them ------
# A test says what a cell measures, never what an entry is called: no suffix spelled, no
# entry counted, so that data files alone can merge, rename or share entries.
REAL_BENCH = os.path.join(spec.ROOT, "BENCHMARK.json")
# what ``chipbench/selftest.py::test_files`` compares (``testdata/definitions_at_pr36.json``'s ``keys``)
DEFINITION_KEYS = ("reader", "args", "layer", "unit", "better", "source", "moves")
bench_spec = functools.lru_cache(maxsize=None)(spec.Spec)  # read once a process; nobody writes into either
layer_metric = functools.lru_cache(maxsize=None)(spec.layer_metric)


def traced(fn, catch=Exception):
    """``(what fn raised of ``catch`` or None, the spans it recorded)``
    with the tracer's ring on for the call: complete events by start,
    an enclosing span before what it holds."""
    from tendermint_tpu.libs import tracing

    tracing.tracer.set_metrics_observer(None)
    tracing.configure("ring")
    tracing.tracer.clear()
    try:
        raised = None
        try:
            fn()
        except catch as exc:
            raised = exc
        events = [e for e in tracing.tracer.export(clear=True)["traceEvents"] if e.get("ph") == "X"]
    finally:
        tracing.configure("off")
        tracing.tracer.clear()
    return raised, sorted(events, key=lambda e: (e["ts"], -e["dur"]))


def span(name, ts, dur, tid=1, **args):
    """A span as the tracer exports it: times in microseconds."""
    return {"name": name, "ts": float(ts), "dur": float(dur), "tid": tid, "args": args}


class Evidence:
    """What a reader is handed of a window of ``calls`` calls."""

    def __init__(self, spans, calls=1):
        self.spans, self.calls = spans, [{}] * calls


def stem_of(name: str) -> str:
    return name.split(".", 1)[0]


def metric(bench: str, cell: str, stem: str, **like) -> str:
    """The name of the one per-layer entry of the benchmark file ``bench`` that
    ``cell`` reports under ``stem`` (the name, or the name up to a dot). Where a cell
    reports two of one stem, ``like`` tells them apart by what they measure: a key of
    the definition (``reader``, ``moves``) or an item of its ``args``. None or
    several is an error, never a neighbour's entry."""
    found = []
    for m in bench_spec(bench).metrics_for("per_layer", cell):
        if m["name"] == stem or m["name"].startswith(stem + "."):
            doc = layer_metric(m["name"])
            if all(doc.get(key, doc["args"].get(key)) == value for key, value in like.items()):
                found.append(m["name"])
    if len(found) != 1:
        raise LookupError("%s reports %s under %r %s" % (cell, found or "nothing", stem, like or ""))
    return found[0]


def read(ev, bench: str, cell: str, stem: str, **like):
    """What that entry's reader makes of the evidence ``ev``."""
    doc = layer_metric(metric(bench, cell, stem, **like))
    return spec.reader(doc["reader"]).read(ev, **doc["args"])


def definitions(bench: str, cell: str, stems=None, but=()) -> collections.Counter:
    """The definitions ``cell`` reports under whatever names, each as
    ``selftest.test_files`` writes it: of ``stems`` alone, without ``but``."""
    return collections.Counter(
        json.dumps([layer_metric(m["name"])[key] for key in DEFINITION_KEYS], sort_keys=True)
        for m in bench_spec(bench).metrics_for("per_layer", cell)
        if (stems is None or stem_of(m["name"]) in stems) and stem_of(m["name"]) not in but
    )


def sound(out: dict, said: str, compared, bench=None, cell=None):
    """A rehearsal came out correct with each of ``compared`` at 0; a traced one
    (``bench``, ``cell``) printed every per-layer metric of the cell: ``value(stem, **like)``."""
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    for name in compared:
        assert "compared: %s = 0 (limit 0)" % name in said, name
    for m in bench_spec(bench).metrics_for("per_layer", cell) if bench else ():
        assert isinstance(out["metrics"][m["name"]]["value"], float), m["name"]
    return lambda stem, **like: out["metrics"][metric(bench, cell, stem, **like)]["value"]


def over_limit(said: str) -> list:
    """The comparisons a run's output marks over their limit, in its order."""
    return [ln.split("compared: ", 1)[1].split(" = ")[0] for ln in said.splitlines() if ln.endswith("<-- over")]


# --- the commit entry's loop as it ran lane by lane, and what a verifier was handed ------


def lane_by_lane_commit_batch(
    chain_id, vals, commit, voting_power_needed, ignore_sig, count_sig, count_all_signatures, look_up_by_index
):
    """``types/validation._verify_commit_batch`` as it stood until PR 45:
    one turn of a ``for`` loop a commit signature, ``encoder.lane`` and
    ``bv.add`` a lane. The reference the block-wise loop is held against;
    stand it in with ``monkeypatch.setattr(validation, "_verify_commit_batch", ...)``."""
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.libs import tracing
    from tendermint_tpu.types import validation
    from tendermint_tpu.types.validation import InvalidCommitError, NotEnoughVotingPowerError

    tallied = 0
    seen_vals = {}
    batch_sig_idxs = []
    crypto_batch.note_validator_set_traced(vals)
    bv = crypto_batch.MultiBatchVerifier()
    unbatchable = False
    early_lanes = 0
    encoder = commit.sign_bytes_encoder(chain_id)
    n_sigs = len(commit.signatures)
    entries = enumerate(commit.signatures)
    try:
        building = True
        while building:
            building = False
            with tracing.span("build_lanes") as lsp:
                held = len(batch_sig_idxs)
                for idx, commit_sig in entries:
                    if ignore_sig(commit_sig):
                        continue
                    if look_up_by_index:
                        val = vals.validators[idx]
                    else:
                        val_idx, val = vals.get_by_address(commit_sig.validator_address)
                        if val is None:
                            continue
                        if val_idx in seen_vals:
                            raise InvalidCommitError(
                                f"double vote from validator {val_idx} ({seen_vals[val_idx]} and {idx})"
                            )
                        seen_vals[val_idx] = idx
                    vote_sign_bytes = encoder.lane(idx)
                    try:
                        bv.add(val.pub_key, vote_sign_bytes, commit_sig.signature)
                    except ValueError:
                        unbatchable = True
                        break
                    batch_sig_idxs.append(idx)
                    if count_sig(commit_sig):
                        tallied += val.voting_power
                    if not count_all_signatures and tallied > voting_power_needed:
                        break
                    if bv.ready and idx + 1 < n_sigs:
                        building = True
                        break
                lsp.set(lanes=len(batch_sig_idxs) - held)
            if building:
                early_lanes += bv.begin_ready()
        tracing.tag(early_lanes=early_lanes)
        if not unbatchable:
            if tallied <= voting_power_needed:
                raise NotEnoughVotingPowerError(got=tallied, needed=voting_power_needed)
            ok, valid_sigs = bv.verify()
    finally:
        bv.close()
    if unbatchable:
        return validation._verify_commit_single(
            chain_id, vals, commit, voting_power_needed, ignore_sig, count_sig,
            count_all_signatures, look_up_by_index,
        )
    if ok:
        return
    for i, sig_ok in enumerate(valid_sigs):
        if not sig_ok:
            idx = batch_sig_idxs[i]
            raise InvalidCommitError(
                f"wrong signature (#{idx}): {commit.signatures[idx].signature.hex().upper()}"
            )
    raise InvalidCommitError("BUG: batch verification failed with no invalid signatures")


def record_verifier(monkeypatch) -> list:
    """Stands a ``MultiBatchVerifier`` in that writes down what it is
    handed and told, in order: ``("lane", key bytes, msg, sig)`` a lane,
    whether by ``add`` or by ``add_many`` and whether or not it then
    took it, ``("begun", lanes)`` where ``begin_ready()`` began some,
    ``("verify",)``, ``("close",)``. The list is what comes back; empty
    it between two calls to compare."""
    from tendermint_tpu.crypto import batch as crypto_batch

    said = []

    class Recording(crypto_batch.MultiBatchVerifier):
        def add(self, pub_key, msg, sig):
            said.append(("lane", pub_key.bytes(), msg, sig))
            super().add(pub_key, msg, sig)

        def add_many(self, pub_keys, msgs, sigs):
            said.extend(("lane", key.bytes(), msg, sig) for key, msg, sig in zip(pub_keys, msgs, sigs))
            super().add_many(pub_keys, msgs, sigs)

        def begin_ready(self):
            begun = super().begin_ready()
            if begun:
                said.append(("begun", begun))
            return begun

        def verify(self):
            said.append(("verify",))
            return super().verify()

        def close(self):
            said.append(("close",))
            super().close()

    monkeypatch.setattr(crypto_batch, "MultiBatchVerifier", Recording)
    return said


def outcome(fn):
    """``(type, message)`` of what ``fn`` raised, or None."""
    try:
        fn()
    except Exception as exc:  # noqa: BLE001 - whatever it is, it is compared
        return type(exc).__name__, str(exc)
    return None
