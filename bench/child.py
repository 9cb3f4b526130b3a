"""Section child entry point: run ONE registry section under the
parent's heartbeat watchdog and print its JSON fragment.

Invoked as ``python bench.py --child-section <name>`` with the spool
path in ``BENCH_HEARTBEAT_FILE``. The child owns everything that must
happen before the backend is touched (the caller's explicit
BENCH_FORCE_CPU=1, result-cache default, tracing mode); the parent
owns timeouts and retries and never touches jax. One section per
process is the isolation contract: a wedged backend here takes down
exactly this measurement, and the chip is free again for the next
section's child once this process has exited.
"""

from __future__ import annotations

import json
import os
import sys

from bench import sections
from bench.heartbeat import HeartbeatWriter


def child_main(name: str) -> int:
    section = sections.get(name)
    beat = HeartbeatWriter(name)

    # Throughput rounds must measure verification, not dictionary hits:
    # the digest-keyed result cache would answer rounds 2..N instantly.
    # Explicit operator env still wins; run_cache re-enables it locally
    # to report the cache numbers.
    os.environ.setdefault("TENDERMINT_TPU_RESULT_CACHE", "0")
    # Span tracing in ring mode: trace summaries come from the spans the
    # verify pipeline actually emitted. Explicit operator env wins.
    os.environ.setdefault("TENDERMINT_TPU_TRACE", "ring")

    if section.needs_jax:
        import jax

        # The caller's explicit CPU switch; the config knob (applied
        # before first backend use) holds whatever JAX_PLATFORMS says.
        if os.environ.get("BENCH_FORCE_CPU") == "1":
            jax.config.update("jax_platforms", "cpu")
        backend = jax.default_backend()  # first backend use: may wedge
        # FIRST beat only after the backend answered — until this line
        # the parent holds the child to the probe window, not the
        # (longer) heartbeat window.
        beat("backend ready: %s" % backend)
    else:
        beat("start (no jax)")

    from tendermint_tpu.libs import flightrec, tracing
    from tendermint_tpu.ops import introspect

    tracing.configure()
    # Continuous kernel profiler (ops/introspect.py): on by default,
    # TENDERMINT_TPU_PROFILE=off for the overhead-control runs the CI
    # stage compares against. The digests ride the tracer's profile
    # sink, so reported section numbers never include digesting time —
    # same instrumentation-stripping rule as tpusan.
    introspect.install()
    # Post-mortem ring: a child that dies on an unhandled exception or
    # SIGTERM dumps its last seconds into the run's shared dump dir
    # (DIR_ENV inherited from the parent); the runner references every
    # dump from the partial JSON. SIGKILL leaves the parent's dump only.
    flightrec.install()
    with tracing.tracer.span("bench_section_body", section=name):
        fragment = section.fn(beat)

    # Every fragment records the scheduler config it ran under (ISSUE
    # 17): resolved knobs — mesh-aware batch default, env-resolved
    # continuous/dyn-batch — not the static constants, so A/B artifacts
    # stay attributable. Sections that measured a specific live
    # scheduler (slo_replay) embed richer per-run knobs themselves.
    if isinstance(fragment, dict):
        from tendermint_tpu.crypto.scheduler import resolved_default_knobs

        fragment.setdefault("scheduler_knobs", resolved_default_knobs())
        # Per-section kernel/compile profile digests (ISSUE 18): what
        # the device actually spent per (engine, batch bucket) while
        # this section ran. Off-profiler runs still get the fragment
        # (enabled:false, empty digests) so schema diffs stay aligned.
        fragment.setdefault("profile", introspect.profiler.snapshot())

    beat("done")
    print(json.dumps({"section": name, "fragment": fragment}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(child_main(sys.argv[1]))
