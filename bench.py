#!/usr/bin/env python
"""Headline benchmark: batched Ed25519 ZIP-215 verification throughput.

Mirrors the reference's BenchmarkVerifyBatch (crypto/ed25519/bench_test.go:31-67)
at large batch — the hot path of VerifyCommit / blocksync / light client
(types/validation.go:154) — plus VerifyCommit latency, light-client /
blocksync / cache / verifyd / multichip sections. Prints ONE JSON line:

    {"metric": ..., "value": N, "unit": "sigs/s", "vs_baseline": N,
     ..., "sections": {...per-section status...}}

vs_baseline divides by the reference's Go batch-verify throughput class
(curve25519-voi batched verify ~33 us/sig on a modern x86 core =>
30,000 sigs/s; no Go toolchain exists in this image — see BASELINE.md).

Robustness contract: one wedged section must cost its own measurement,
never the round. Every section runs in its OWN subprocess under a
heartbeat watchdog (the parent never touches jax, so each child has the
chip to itself); each completed section is persisted to a
partial-result JSON before the next one starts; failed sections retry
down a size-degradation ladder and land with an honest status
(ok|timeout|crashed|skipped) instead of killing the round. A section
that cannot get the device fails — nothing re-runs it on the CPU unless
the caller exported BENCH_FORCE_CPU=1. See
bench/runner.py for the orchestration and README "Benchmarking" for
the knobs, the partial-result format, and ``--resume``.
"""

import os
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from bench import runner  # noqa: E402

if __name__ == "__main__":
    sys.exit(runner.cli(sys.argv[1:]))
