"""The benchmark's own checks, run by hand on the CPU (a few minutes):

    python3 -m chipbench.selftest [name ...]

- ``files``: ``BENCHMARK.json`` and the files its names lead to agree.
- ``opcount``: operations and bytes for one lane, against hand-worked
  values.
- ``reference``: the plain reference on RFC 8032's first test vector
  and on each kind of tampering.
- ``trace``: the reduction from a trace to busy time, kernel time and
  idle gaps, on a hand-made trace and on a small trace recorded on a
  TPU v5 lite (``testdata/trace_small.json``).
- ``cells``: every cell end to end at tiny sizes (``testdata/``), with
  ``JAX_PLATFORMS=cpu``, the four-chip cell on four virtual devices.
  These are rehearsals: they prove paths, not numbers.
- ``broken``: a run whose requests cycle through fewer signatures than
  the verdict cache holds, one whose engine drops the ``s < L`` check,
  and one with a verdict altered where it is produced all come out
  ``correct: false``; one answered by the host oracle counts its calls
  ``failed``. These drive the whole of a run but the look for a chip.

Nothing here is a measurement, and no number from it is ever written
under the name of a device metric.
"""

from __future__ import annotations

import collections
import json
import os
import sys

from chipbench import control, opcount, reference, spec, tracefile, workload

TINY = os.path.join(spec.HERE, "testdata", "selftest_benchmark.json")


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def test_files():
    real = spec.Spec(os.path.join(spec.ROOT, "BENCHMARK.json"))
    tiny = spec.Spec(TINY)
    for s in (real, tiny):
        cells = {w["name"] for w in s.doc["workloads"]}
        e2e = {m["name"] for m in s.doc["end_to_end"]}
        for w in s.doc["workloads"]:
            config = s.config(w["config"])
            check(config["chips"] == w["chips"], "%s: chips differ from its config" % w["name"])
            traffic = s.traffic(w["traffic"])
            check(hasattr(spec.generator(traffic["kind"]), "build"), "no generator %s" % traffic["kind"])
            check(len(s.metrics_for("per_layer", w["name"])) >= 1, "%s reports no per-layer metric" % w["name"])
            check(len(s.metrics_for("end_to_end", w["name"])) >= 2, "%s reports under two end-to-end metrics" % w["name"])
        for m in s.doc["end_to_end"]:
            __import__("chipbench.end_to_end." + m["name"])
        for m in s.doc["per_layer"]:
            doc = spec.layer_metric(m["name"])
            check(hasattr(spec.reader(doc["reader"]), "read"), "no reader %s" % doc["reader"])
            check(m["moves"] in e2e, "%s moves an unknown metric" % m["name"])
            check(set(m.get("workloads", cells)) <= cells, "%s lists an unknown cell" % m["name"])
            if s is real:
                # an entry without ``workloads`` is reported by every cell that reports its ``moves``
                for key in ("layer", "unit", "better", "source", "moves", "workloads"):
                    check(doc.get(key) == m.get(key), "%s: %s differs between BENCHMARK.json and its file" % (m["name"], key))
    # the converse: no file without its entry
    files = {f[: -len(".json")] for f in os.listdir(os.path.join(spec.HERE, "layer_metrics"))}
    orphans = files - {m["name"] for m in real.doc["per_layer"]}
    check(not orphans, "layer_metrics/ holds files no entry names: %s" % sorted(orphans))
    # merging, renaming or sharing entries loses no cell a definition
    # it reported: reader and arguments letter for letter, under any name
    frozen = spec.load_json(os.path.join(spec.HERE, "testdata", "definitions_at_pr36.json"))
    for cell, ids in frozen["cells"].items():
        got = collections.Counter(
            json.dumps([spec.layer_metric(m["name"])[key] for key in frozen["keys"]], sort_keys=True)
            for m in real.metrics_for("per_layer", cell)
        )
        want = collections.Counter(json.dumps(frozen["definitions"][i], sort_keys=True) for i in ids)
        check(not want - got, "%s no longer reports %s" % (cell, sorted((want - got).elements())))
    peaks = spec.load_json(os.path.join(spec.HERE, "peaks.json"))
    for kind, row in peaks.items():
        check(row["source"] and row["bf16_flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0, kind)


def test_opcount():
    # one lane of the table kernels: R decompressed (275), 64 windows of
    # four doublings, a 7-mul and an 8-mul add (64 * 47 = 3008), then
    # subtract R and clear the cofactor (1 + 8 + 24 = 33)
    check(opcount.DECOMPRESS == 275, opcount.DECOMPRESS)
    check(opcount.fe_mul_per_lane("resident") == 275 + 3008 + 33 == 3316, "resident fe_mul")
    check(opcount.fe_mul_per_lane("tables") == 3316, "tables fe_mul")
    # legacy adds A's decompression and the lane table (1 + 56 + 8)
    check(opcount.fe_mul_per_lane("legacy") == 3316 + 275 + 65 == 3656, "legacy fe_mul")
    check(opcount.ops_per_lane("legacy") == 3656 * 2048 == 7487488, "legacy ops")
    check(opcount.bytes_per_lane("legacy") == 129, "legacy bytes")
    check(opcount.bytes_per_lane("resident") == 96 + 4 + 2 + 1024, "resident bytes")
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = opcount.least_seconds({"legacy": 10000}, peak)
    check(least["bound"] == "compute", least)
    check(abs(least["seconds"] - 10000 * 7487488 / 197e12) < 1e-15, least)
    # a kernel that did no arithmetic would be bound by its bytes
    check(opcount.least_seconds({"resident": 1}, {"bf16_flops_per_s": 1e30, "hbm_bytes_per_s": 819e9})["bound"] == "memory", "memory bound")


def test_reference():
    # RFC 8032 7.1, test 1
    pub = bytes.fromhex("d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a")
    sig = bytes.fromhex(
        "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
        "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b"
    )
    check(reference.verify(pub, b"", sig), "RFC 8032 test 1 refused")
    check(not reference.verify(pub, b"x", sig), "wrong message accepted")
    for kind in workload.TAMPER_KINDS:
        check(not reference.verify(pub, b"", workload.tamper_signature(sig, kind)), kind)
    signer = workload.Signer(b"\x07" * 32)
    check(reference.verify(signer.pub, b"m", signer.sign(b"m")), "own signature refused")
    # ZIP-215: a non-canonical y (p + 1 encodes the identity's y = 1) is accepted
    s = 12345
    r = reference.scalar_mult(s, reference.BASE)
    zinv = pow(r[2], reference.P - 2, reference.P)
    x, y = r[0] * zinv % reference.P, r[1] * zinv % reference.P
    r_enc = (y | ((x & 1) << 255)).to_bytes(32, "little")
    ident = (reference.P + 1).to_bytes(32, "little")
    check(reference.verify(ident, b"x", r_enc + s.to_bytes(32, "little")), "non-canonical identity key refused")


def test_trace():
    # hand-made: one device, window 0..140 ns, ops at 5-15 and 35-55
    # (one nested), spans a(0-100){b(10-40){c(20-30)}, d(50-60)}, e(120-130)
    tr = {
        "devices": {"d0": {"ops": [["k", 5, 10], ["w", 35, 20], ["x", 36, 2]],
                           "modules": [["jit_run(1)", 5, 10], ["jit_other(2)", 35, 20]]}},
        "anchors": [[0, 100], [100, 40]],
    }
    b = tracefile.busy(tr)
    check(abs(b["busy_s"] - 30e-9) < 1e-18 and abs(b["window_s"] - 140e-9) < 1e-18, b)
    spans = [["a", 0, 100], ["b", 10, 40], ["c", 20, 30], ["d", 50, 60], ["e", 120, 130]]
    gaps = dict(tracefile.idle_gaps(tr, spans))
    want = {"a": 45e-9, "between_calls": 30e-9, "b": 10e-9, "c": 10e-9, "e": 10e-9, "d": 5e-9}
    check(all(abs(gaps[k] - v) < 1e-18 for k, v in want.items()) and len(gaps) == 6, gaps)
    check(tracefile.device_ops(tr) == [["w", 20e-9], ["k", 10e-9]], tracefile.device_ops(tr))
    check(tracefile.matching_time(tr["devices"]["d0"]["modules"], ["jit_run*"], 0, 140) == (10e-9, 1), "matching_time")
    # recorded on the chip
    path = os.path.join(spec.HERE, "testdata", "trace_small.json")
    rec = spec.load_json(path)
    want = spec.load_json(os.path.join(spec.HERE, "testdata", "trace_small.expected.json"))
    b = tracefile.busy(rec)
    check(abs(b["busy_s"] - want["busy_s"]) < 1e-12, ("busy_s", b["busy_s"], want["busy_s"]))
    check(abs(b["window_s"] - want["window_s"]) < 1e-12, ("window_s", b["window_s"]))
    lo, hi = b["window"]
    secs = sum(
        tracefile.matching_time(dev["modules"], want["patterns"], lo, hi)[0]
        for dev in rec["devices"].values()
    )
    check(abs(secs - want["kernel_s"]) < 1e-12, ("kernel_s", secs, want["kernel_s"]))
    # a program's interval holds its operations and the gaps between them
    check(secs <= 1.001 * b["busy_s"] * len(rec["devices"]), "programs ran longer than the device was busy")


CELL_ENV = {"tiny-big-x4": {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}}
CELL_SECONDS = {"tiny-big-x4": 5.0}  # four virtual devices share the host's cores


def rehearse(cell, seed, trace, brk=None):
    out = control.run_cell(
        cell, seed, CELL_SECONDS.get(cell, 2.0), brk,
        extra=["--rehearse", "--bench-file", TINY] + (["--trace", "1"] if trace else []),
        env=dict(os.environ, JAX_PLATFORMS="cpu", **CELL_ENV.get(cell, {})),
    )
    check(out["rc"] == 0, "%s: exit code %s\n%s" % (cell, out["rc"], out.get("stderr")))
    return out


def test_cells():
    tiny = spec.Spec(TINY)
    for i, w in enumerate(tiny.doc["workloads"]):
        cell = w["name"]
        for trace in (0, 1):
            out = rehearse(cell, 2**31 + 17 + i, trace)
            check(out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0, (cell, trace, out))
            section = "per_layer" if trace else "end_to_end"
            want = {m["name"] for m in tiny.metrics_for(section, cell)}
            want.discard("commit_p95_ms")  # needs 200 calls; a rehearsal makes a few
            missing = want - set(out["metrics"])
            check(not missing, "%s trace %d lacks %s" % (cell, trace, sorted(missing)))
            if trace:
                check(out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0, out["device"])
                check(out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"], "breakdown")
            print("  rehearsed %s trace %d: %d calls" % (cell, trace, out["attempted"]), flush=True)


def test_broken():
    for cell, brk in (
        ("tiny-hub-warm", "cache_answers"),
        ("tiny-hub-warm", "no_canonical_s"),
        ("tiny-hub-warm", "flip_verdict"),
        ("tiny-hub-warm", "host_answers"),
        ("tiny-big-flood", "cache_answers"),
        ("tiny-big-flood", "flip_verdict"),
    ):
        out = rehearse(cell, 99, 0, brk)
        check(control.caught(brk, out), "%s broken by %s came out correct=%s failed=%s"
              % (cell, brk, out["correct"], out["failed"]))
        print("  %s broken by %s: caught (%s)" % (cell, brk, "; ".join(
            c for c in out["compared"] if "over" in c) or "failed=%d" % out["failed"]), flush=True)


TESTS = {
    "files": test_files, "opcount": test_opcount, "reference": test_reference,
    "trace": test_trace, "cells": test_cells, "broken": test_broken,
}


def main(argv=None) -> int:
    names = (argv if argv is not None else sys.argv[1:]) or list(TESTS)
    failed = 0
    for name in names:
        try:
            TESTS[name]()
            print("PASS %s" % name, flush=True)
        except AssertionError as exc:
            failed += 1
            print("FAIL %s: %s" % (name, exc), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
