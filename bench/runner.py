"""Parent orchestration: one subprocess per bench section, each under a
heartbeat watchdog, results appended to an on-disk partial JSON.

Why this shape: a single child under a single hard timeout loses every
measurement of the round to one wedged kernel compile. Here each
section:

- runs in its own child (``bench.py --child-section <name>``), so a
  wedge takes down exactly one measurement. A chip belongs to one
  process at a time: the parent never imports jax, and children run
  strictly one after another;
- is watched by heartbeat silence (bench/heartbeat.py), not just
  wall-clock, with TENDERMINT_TPU_PROBE_TIMEOUT as the first-beat
  budget;
- lands in the partial-result file the moment it completes
  (bench/results.py, atomic rename), so later failures cannot destroy
  earlier evidence;
- retries down a size ladder (sizes halved per attempt, recorded as
  ``degraded``) before giving up with an honest ``timeout``/``crashed``
  status. No rung moves a section to another platform: a section that
  cannot get the device fails, it is never re-measured on the CPU
  under the same metric name. ``BENCH_FORCE_CPU=1`` set by the CALLER
  is the one explicit CPU switch (tests, contract checks).

``--resume <partial.json>`` re-runs only sections that are not ``ok``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

from bench import results, sections
from bench.heartbeat import HEARTBEAT_FILE_ENV, Watchdog
from bench.workload import REPO, env_float, env_int

BENCH_PY = os.path.join(REPO, "bench.py")

def _say(msg: str) -> None:
    # stdout is reserved for the single merged-JSON line (the round
    # driver consumes it); narration and the per-section log -> stderr.
    print("bench: %s" % msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# Config
# --------------------------------------------------------------------------


def section_timeout(name: str) -> float:
    """Per-section wall budget: BENCH_SECTION_TIMEOUT_<NAME> >
    BENCH_SECTION_TIMEOUT > legacy BENCH_TIMEOUT (which used to bound
    the whole single-child run, so it safely bounds any one section)."""
    per = os.environ.get("BENCH_SECTION_TIMEOUT_%s" % name.upper().lstrip("_"))
    if per:
        try:
            return float(per)
        except ValueError:
            pass
    if os.environ.get("BENCH_SECTION_TIMEOUT"):
        return env_float("BENCH_SECTION_TIMEOUT", 600.0)
    if os.environ.get("BENCH_TIMEOUT"):
        return env_float("BENCH_TIMEOUT", 600.0)
    return 600.0


def heartbeat_timeout() -> float:
    return env_float("BENCH_HEARTBEAT_TIMEOUT", 180.0)


def probe_timeout() -> float:
    return env_float("TENDERMINT_TPU_PROBE_TIMEOUT", 120.0)


def max_attempts() -> int:
    return max(1, env_int("BENCH_SECTION_ATTEMPTS", 3))


def ladder_env(section: sections.Section, attempt: int) -> Dict[str, str]:
    """Size rung for attempt N (1-based): halve every size knob per
    extra attempt (respecting operator-set bases and floors). Sizes
    only — no rung changes the platform."""
    overrides: Dict[str, str] = {}
    if attempt <= 1:
        return overrides
    factor = 2 ** (attempt - 1)
    for name, default, floor in section.degrade:
        base = env_int(name, default)
        overrides[name] = str(max(floor, base // factor))
    return overrides


# --------------------------------------------------------------------------
# Children
# --------------------------------------------------------------------------


def caller_forced_cpu() -> bool:
    """The explicit switch: the CALLER exported BENCH_FORCE_CPU=1."""
    return os.environ.get("BENCH_FORCE_CPU") == "1"


def build_child_env(
    section: sections.Section,
    overrides: Dict[str, str],
    spool: str,
) -> Dict[str, str]:
    env = dict(os.environ)
    for key, val in section.extra_env:
        if key == "XLA_FLAGS":
            env[key] = ("%s %s" % (env.get(key, ""), val)).strip()
        else:
            env[key] = val
    env.update(overrides)
    # the sanitizer never rides into a bench child: instrumented locks
    # and attribute hooks would poison every number the child reports
    env.pop("TENDERMINT_TPU_SANITIZE", None)
    env[HEARTBEAT_FILE_ENV] = spool
    if caller_forced_cpu():
        import __graft_entry__

        pinned = __graft_entry__.cpu_env()
        env["PYTHONPATH"] = pinned["PYTHONPATH"]
        env["JAX_PLATFORMS"] = pinned["JAX_PLATFORMS"]
    return env


class AttemptOutcome:
    __slots__ = ("ok", "fragment", "reason", "stalled", "stderr_tail")

    def __init__(self, ok, fragment=None, reason=None, stalled=False, stderr_tail=""):
        self.ok = ok
        self.fragment = fragment
        self.reason = reason
        self.stalled = stalled  # watchdog/timeout kill (wedge, not crash)
        self.stderr_tail = stderr_tail


def run_section_child(
    section: sections.Section, env: Dict[str, str], spool: str
) -> AttemptOutcome:
    """One child attempt under the watchdog. Never raises for child
    misbehavior — every failure mode folds into an AttemptOutcome."""
    wall = section_timeout(section.name)
    dog = Watchdog(
        spool,
        beat_timeout=heartbeat_timeout(),
        wall_timeout=wall,
        # jax sections owe their first beat within the probe budget
        # (backend import/init); host-only sections just owe beats.
        startup_timeout=probe_timeout() if section.needs_jax else None,
    )
    out_f = tempfile.TemporaryFile(mode="w+")
    err_f = tempfile.TemporaryFile(mode="w+")
    kill_reason: Optional[str] = None
    try:
        proc = subprocess.Popen(
            [sys.executable, BENCH_PY, "--child-section", section.name],
            stdout=out_f,
            stderr=err_f,
            text=True,
            env=env,
            cwd=REPO,
        )
        while True:
            rc = proc.poll()
            if rc is not None:
                break
            kill_reason = dog.check()
            if kill_reason is not None:
                from tendermint_tpu.libs import tracing

                tracing.instant(
                    "bench_watchdog_kill",
                    section=section.name,
                    reason=kill_reason,
                )
                proc.kill()
                proc.wait()
                rc = proc.returncode
                break
            time.sleep(dog.poll_interval())
        out_f.seek(0)
        err_f.seek(0)
        stdout = out_f.read()
        stderr = err_f.read()
    finally:
        out_f.close()
        err_f.close()
    tail = " | ".join((stderr or "").strip().splitlines()[-3:])
    if kill_reason is not None:
        return AttemptOutcome(False, reason=kill_reason, stalled=True, stderr_tail=tail)
    if rc != 0:
        return AttemptOutcome(
            False,
            reason="child rc=%d%s" % (rc, (": " + tail) if tail else ""),
            stderr_tail=tail,
        )
    for line in reversed(stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                doc = json.loads(line)
            except json.JSONDecodeError:
                continue
            if doc.get("section") == section.name:
                return AttemptOutcome(True, fragment=doc.get("fragment") or {})
    return AttemptOutcome(False, reason="no JSON line in child output", stderr_tail=tail)


# --------------------------------------------------------------------------
# Orchestration
# --------------------------------------------------------------------------


def run_sections(
    plan: Tuple[str, ...],
    doc: dict,
    partial_path: Optional[str],
) -> dict:
    """Run every section in ``plan`` (skipping ones already ``ok`` in a
    resumed ``doc``), recording each outcome into ``doc``/the partial
    file as it lands. Returns the updated doc."""
    from tendermint_tpu.libs import tracing

    os.environ.setdefault("TENDERMINT_TPU_TRACE", "ring")
    tracing.configure()
    force_cpu = caller_forced_cpu()

    for name in plan:
        section = sections.get(name)
        prior = doc["sections"].get(name)
        if prior is not None and prior.get("status") == results.OK:
            _say("section %s: already ok in partial, skipping (resume)" % name)
            continue

        attempts = max_attempts()
        t_section = time.monotonic()
        block = None
        for attempt in range(1, attempts + 1):
            overrides = ladder_env(section, attempt)
            degraded = bool(overrides)

            spool_fd, spool = tempfile.mkstemp(prefix="bench_hb_%s_" % name.lstrip("_"))
            os.close(spool_fd)
            try:
                env = build_child_env(section, overrides, spool)
                _say(
                    "section %s: attempt %d/%d%s%s"
                    % (
                        name,
                        attempt,
                        attempts,
                        " (BENCH_FORCE_CPU=1)" if force_cpu and section.needs_jax else "",
                        " overrides=%s" % overrides if overrides else "",
                    )
                )
                with tracing.tracer.span(
                    "bench_section",
                    section=name,
                    attempt=attempt,
                    force_cpu=force_cpu,
                ):
                    outcome = run_section_child(section, env, spool)
            finally:
                try:
                    os.unlink(spool)
                except OSError:
                    pass

            duration = time.monotonic() - t_section
            if outcome.ok:
                backend = None
                frag = outcome.fragment
                if isinstance(frag, dict):
                    backend = frag.get("backend") or (
                        frag.get("multichip") or {}
                    ).get("backend")
                block = results.section_block(
                    results.OK,
                    attempts=attempt,
                    duration_s=duration,
                    degraded=degraded,
                    note="degraded rung %s" % overrides if degraded else None,
                    backend=backend,
                    result=frag,
                )
                break

            _say(
                "section %s: attempt %d failed (%s)"
                % (name, attempt, outcome.reason)
            )
            status = results.TIMEOUT if outcome.stalled else results.CRASHED
            block = results.section_block(
                status,
                attempts=attempt,
                duration_s=time.monotonic() - t_section,
                degraded=degraded,
                note=outcome.reason,
            )

        assert block is not None
        results.record_section(doc, partial_path, name, block)
        _say(
            "section %s: %s in %.1fs (attempts=%d, backend=%s%s)"
            % (
                name,
                block["status"],
                block["duration_s"],
                block["attempts"],
                block.get("backend") or "?",
                ", degraded" if block.get("degraded") else "",
            )
        )

    return doc


def mark_skipped(doc: dict, partial_path: Optional[str]) -> None:
    """Legacy BENCH_SKIP_* opt-outs land as honest ``skipped`` status
    blocks (the old bench reported them as nulls)."""
    if os.environ.get("BENCH_SECTIONS", "").strip():
        return  # an explicit section list is its own statement of scope
    for name in sections.ORDER:
        section = sections.get(name)
        if name in doc["sections"] or name == "_chaos":
            continue
        hit = [e for e in section.skip_env if os.environ.get(e) == "1"]
        if hit:
            results.record_section(
                doc,
                partial_path,
                name,
                results.section_block(
                    results.SKIPPED, attempts=0, duration_s=0.0,
                    note="%s=1" % hit[0],
                ),
            )


def collect_flightrec(doc: dict, partial_path: Optional[str]) -> None:
    """Reference every flight-recorder dump this round produced (parent
    watchdog dumps AND child dumps — they share the run's dump dir via
    the inherited env) from the partial JSON, so a wedged section ships
    its own post-mortem next to the numbers it failed to produce."""
    from tendermint_tpu.libs import flightrec

    d = flightrec.dump_dir()
    try:
        names = sorted(os.listdir(d))
    except OSError:
        names = []
    dumps = []
    for fname in names:
        if not (fname.startswith("flightrec-") and fname.endswith(".json")):
            continue
        path = os.path.join(d, fname)
        entry: Dict[str, object] = {"path": path}
        try:
            with open(path, "r") as f:
                dumped = json.load(f)
            entry["pid"] = dumped.get("pid")
            entry["reason"] = dumped.get("reason")
            entry["records"] = len(dumped.get("records") or [])
        except (OSError, ValueError):
            entry["error"] = "unreadable"
        dumps.append(entry)
    if dumps:
        doc["flightrec_dumps"] = dumps
        if partial_path:
            results.write_partial(doc, partial_path)


def diff_against_baseline(merged: dict, baseline_path: str) -> Optional[dict]:
    """bench.py --baseline: after the merge, diff this round against a
    prior BENCH JSON with scripts/bench_diff (the regression sentinel),
    print the verdict table and the one-line verdict to stderr, and
    attach the structured result to the merged doc.
    Never changes the bench exit code — a regression verdict is
    evidence, the sentinel's own CLI is the gate."""
    from scripts import bench_diff

    try:
        with open(baseline_path) as f:
            base = bench_diff.normalize(json.load(f), baseline_path)
    except (OSError, ValueError) as exc:
        _say("baseline diff skipped: %s" % exc)
        return None
    tol = bench_diff.default_tolerance()
    rows = bench_diff.diff_sections(base, bench_diff.normalize(merged, "run"), tol)
    print(bench_diff.render_table(rows, tol), file=sys.stderr)
    line = bench_diff.verdict_line(baseline_path, "this-round", rows, tol)
    _say(line)
    return {
        "baseline": baseline_path,
        "tolerance_pct": tol,
        "summary": bench_diff.summarize(rows),
        "regressions": [
            r for r in rows if r["verdict"] == bench_diff.REGRESSION
        ],
    }


def run(
    plan: Optional[Tuple[str, ...]] = None,
    resume_path: Optional[str] = None,
    partial_path: Optional[str] = None,
    baseline_path: Optional[str] = None,
) -> Tuple[dict, int]:
    """Full orchestration; returns (merged_doc, exit_code)."""
    from tendermint_tpu.libs import flightrec, tracing

    platform = os.environ.get("JAX_PLATFORMS", "default")
    if resume_path:
        doc = results.load_partial(resume_path)
        if partial_path is None:
            partial_path = resume_path
    else:
        doc = results.new_partial(platform)
        if partial_path is None:
            partial_path = os.environ.get(
                "BENCH_PARTIAL", os.path.join(REPO, "BENCH_partial.json")
            )
    doc.setdefault("probe", {})["configured_backend"] = platform

    # Flight recorder: the parent's ring absorbs watchdog instants and
    # runner metric deltas; children inherit the same dump dir through
    # build_child_env, so one collection pass sees the whole fleet.
    os.environ.setdefault(flightrec.DIR_ENV, partial_path + ".flightrec")
    flightrec.install()

    if plan is None:
        # On resume, finish the round that was interrupted: prefer the
        # plan recorded in the partial file over today's env/default —
        # otherwise resuming a BENCH_SECTIONS subset run would widen to
        # the whole registry.
        recorded = doc.get("plan")
        if resume_path and recorded:
            plan = tuple(n for n in recorded if n in sections.REGISTRY)
        else:
            plan = sections.default_plan()
    doc["plan"] = list(plan)

    run_sections(plan, doc, partial_path)
    mark_skipped(doc, partial_path)
    collect_flightrec(doc, partial_path)

    merged = results.merge(doc, list(sections.ORDER))
    merged["runner_trace_summary"] = tracing.tracer.summary() or None
    if doc.get("flightrec_dumps"):
        merged["flightrec_dumps"] = doc["flightrec_dumps"]
    code = results.exit_code(doc)

    statuses = [b["status"] for b in doc["sections"].values()]
    summary = ", ".join(
        "%d %s" % (statuses.count(s), s)
        for s in results.STATUSES
        if statuses.count(s)
    )
    _say(
        "bench round on JAX_PLATFORMS=%s: %s — best %.0f sigs/s (backend=%s impl=%s)"
        % (
            platform,
            summary or "nothing ran",
            merged.get("value") or 0.0,
            merged.get("backend"),
            merged.get("impl"),
        )
    )
    if baseline_path:
        diff = diff_against_baseline(merged, baseline_path)
        if diff is not None:
            merged["baseline_diff"] = diff
    _say("done: %s (exit %d); partial at %s" % (summary, code, partial_path))
    return merged, code


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------


USAGE = """\
bench.py — per-section benchmark runner (one child per section)

  python bench.py                      run every registered section
  python bench.py --sections a,b       run an explicit subset
  python bench.py --resume PATH        re-run only failed/missing sections
  python bench.py --baseline PATH      diff this round against a prior
                                       BENCH JSON after merge (sentinel)
  python bench.py --list-sections      show the registry and exit
  python bench.py --impl=mxu|xla|pallas|auto   pin the verifier impl

Knobs (env): BENCH_SECTION_TIMEOUT[_<NAME>], BENCH_HEARTBEAT_TIMEOUT,
TENDERMINT_TPU_PROBE_TIMEOUT, BENCH_SECTION_ATTEMPTS, BENCH_SECTIONS,
BENCH_PARTIAL, BENCH_FORCE_CPU=1 (explicit CPU run), BENCH_CHAOS (test).
"""


def cli(argv: List[str]) -> int:
    resume_path = None
    plan: Optional[Tuple[str, ...]] = None
    partial_path = None
    baseline_path = None
    args = list(argv)
    i = 0
    while i < len(args):
        arg = args[i]
        if arg.startswith("--impl="):
            impl = arg.split("=", 1)[1]
            if impl not in ("mxu", "xla", "pallas", "auto"):
                print(
                    "--impl must be one of mxu|xla|pallas|auto, got %r" % impl,
                    file=sys.stderr,
                )
                return 2
            os.environ["TENDERMINT_TPU_VERIFY_IMPL"] = impl
        elif arg == "--child-section":
            from bench.child import child_main

            return child_main(args[i + 1])
        elif arg == "--resume":
            resume_path = args[i + 1]
            i += 1
        elif arg == "--sections":
            names = tuple(n.strip() for n in args[i + 1].split(",") if n.strip())
            for n in names:
                sections.get(n)  # raises on unknown
            plan = names
            i += 1
        elif arg == "--partial":
            partial_path = args[i + 1]
            i += 1
        elif arg == "--baseline":
            baseline_path = args[i + 1]
            i += 1
        elif arg == "--list-sections":
            for name in sections.ORDER:
                s = sections.get(name)
                print(
                    "%-14s needs_jax=%-5s degrade=%s"
                    % (name, s.needs_jax, [d[0] for d in s.degrade])
                )
            return 0
        elif arg in ("-h", "--help"):
            print(USAGE)
            return 0
        elif arg == "--child":
            # Pre-ISSUE-6 single-child mode is gone; fail loudly so a
            # stale driver script can't silently measure nothing.
            print(
                "bench.py --child was replaced by per-section children "
                "(--child-section <name>); run bench.py with no args",
                file=sys.stderr,
            )
            return 2
        else:
            print("unknown argument %r\n\n%s" % (arg, USAGE), file=sys.stderr)
            return 2
        i += 1

    merged, code = run(
        plan=plan,
        resume_path=resume_path,
        partial_path=partial_path,
        baseline_path=baseline_path,
    )
    print(json.dumps(merged))
    return code
