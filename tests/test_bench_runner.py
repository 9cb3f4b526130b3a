"""Chaos battery for the per-section bench harness (bench/): a dead
or silent section child must cost exactly its own
section — the merged JSON still carries every other section's real
measurements plus an honest per-section status — and ``--resume``
re-runs only what failed.

The subprocess scenarios lean on the two no-jax sections (``host_ref``
measures the pure-python reference verifier; ``_chaos`` misbehaves on
demand via BENCH_CHAOS) so each child costs interpreter startup, not a
kernel compile.
"""

import json
import os
import signal
import time

import pytest

from bench import heartbeat, results, runner, sections
from bench.heartbeat import Watchdog

pytestmark = pytest.mark.chaos


@pytest.fixture()
def bench_env(monkeypatch, tmp_path):
    """Isolated runner environment: partial in tmp, tracing off, single
    attempt, short watchdog windows."""
    partial = tmp_path / "partial.json"
    monkeypatch.setenv("BENCH_PARTIAL", str(partial))
    monkeypatch.setenv("TENDERMINT_TPU_TRACE", "off")
    monkeypatch.setenv("BENCH_SECTION_ATTEMPTS", "1")
    monkeypatch.setenv("BENCH_SECTION_TIMEOUT", "60")
    monkeypatch.setenv("BENCH_HEARTBEAT_TIMEOUT", "15")
    monkeypatch.setenv("BENCH_HOST_REF_SIGS", "4")
    monkeypatch.delenv("BENCH_SECTIONS", raising=False)
    monkeypatch.delenv("BENCH_CHAOS", raising=False)
    yield {"partial": str(partial)}
    # runner.run() installs the process-wide flight recorder; left in,
    # it records every span of whatever test this worker runs next
    # (tpusan then reports its pre-sanitizer lock as a data race)
    from tendermint_tpu.libs import flightrec

    flightrec.recorder.uninstall()
    os.environ.pop(flightrec.DIR_ENV, None)


# --- registry ----------------------------------------------------------------


def test_registry_covers_documented_sections():
    """The sections the ISSUE names, each with the isolation metadata
    the runner keys on."""
    for name in (
        "throughput",
        "stages",
        "cache",
        "light_client",
        "blocksync",
        "verify_commit",
        "verifyd",
        "multichip",
    ):
        assert sections.get(name).needs_jax, name
    assert not sections.get("host_ref").needs_jax
    assert not sections.get("_chaos").needs_jax
    with pytest.raises(KeyError, match="unknown bench section"):
        sections.get("nope")


def test_default_plan_respects_skips_and_chaos_gate(monkeypatch):
    monkeypatch.delenv("BENCH_SECTIONS", raising=False)
    monkeypatch.delenv("BENCH_CHAOS", raising=False)
    plan = sections.default_plan()
    assert "_chaos" not in plan  # only present when BENCH_CHAOS asks
    assert "throughput" in plan and "host_ref" in plan
    monkeypatch.setenv("BENCH_SKIP_COMMIT", "1")
    monkeypatch.setenv("BENCH_SKIP_EXTRAS", "1")
    plan = sections.default_plan()
    assert "verify_commit" not in plan
    assert "light_client" not in plan and "blocksync" not in plan
    monkeypatch.setenv("BENCH_CHAOS", "ok")
    assert "_chaos" in sections.default_plan()
    monkeypatch.setenv("BENCH_SECTIONS", "host_ref,bogus")
    with pytest.raises(KeyError):
        sections.default_plan()


def test_retry_ladder_halves_knobs_and_never_forces_cpu(monkeypatch):
    """The ladder degrades SIZES only. No rung moves a section to the
    CPU: a section that cannot get the device lands crashed/timeout, it
    is never re-measured on another platform under the same name."""
    monkeypatch.setenv("BENCH_SECTION_ATTEMPTS", "3")
    monkeypatch.delenv("BENCH_BATCH", raising=False)
    monkeypatch.delenv("BENCH_FORCE_CPU", raising=False)
    sec = sections.get("throughput")
    assert runner.ladder_env(sec, 1) == {}
    rung2 = runner.ladder_env(sec, 2)
    assert rung2["BENCH_BATCH"] == "4096"
    rung3 = runner.ladder_env(sec, 3)
    assert rung3["BENCH_BATCH"] == "2048"
    for rung in (rung2, rung3):
        assert "BENCH_FORCE_CPU" not in rung and "JAX_PLATFORMS" not in rung
        env = runner.build_child_env(sec, rung, "/tmp/spool")
        assert env.get("BENCH_FORCE_CPU") != "1"
        assert env.get("JAX_PLATFORMS") == os.environ.get("JAX_PLATFORMS")
    # operator-set bases degrade from the operator's number, with floors
    monkeypatch.setenv("BENCH_BATCH", "600")
    assert runner.ladder_env(sec, 2)["BENCH_BATCH"] == "300"
    assert runner.ladder_env(sec, 3)["BENCH_BATCH"] == "256"  # floor


def test_only_the_callers_switch_pins_a_child_to_cpu(monkeypatch):
    """BENCH_FORCE_CPU=1 exported by the CALLER is the one explicit CPU
    switch; the runner itself never sets it."""
    sec = sections.get("throughput")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.delenv("BENCH_FORCE_CPU", raising=False)
    assert runner.build_child_env(sec, {}, "/tmp/spool")["JAX_PLATFORMS"] == "tpu"
    monkeypatch.setenv("BENCH_FORCE_CPU", "1")
    env = runner.build_child_env(sec, {}, "/tmp/spool")
    assert env["JAX_PLATFORMS"] == "cpu" and env["BENCH_FORCE_CPU"] == "1"


def test_parent_imports_no_jax():
    """A chip belongs to one process: the bench parent must stay off
    jax entirely so each section child has the device to itself."""
    import subprocess
    import sys

    code = (
        "import sys; sys.argv=['bench.py']; "
        "from bench import runner, results, sections, heartbeat; "
        "import bench.runner; "
        "assert 'jax' not in sys.modules, 'bench parent imported jax'"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=repo, capture_output=True, text=True
    )
    assert proc.returncode == 0, proc.stderr


def test_child_env_strips_sanitizer(monkeypatch):
    """tpusan must never ride into a bench child: instrumented locks
    would poison every number it reports. The runner strips the env var
    no matter what mode the parent runs under."""
    sec = sections.get("host_ref")
    for mode in ("1", "hb", "explore:42"):
        monkeypatch.setenv("TENDERMINT_TPU_SANITIZE", mode)
        env = runner.build_child_env(sec, {}, "/tmp/spool")
        assert "TENDERMINT_TPU_SANITIZE" not in env
    # and an explicit override cannot smuggle it back pre-strip
    monkeypatch.delenv("TENDERMINT_TPU_SANITIZE", raising=False)
    env = runner.build_child_env(
        sec, {"TENDERMINT_TPU_SANITIZE": "hb"}, "/tmp/spool"
    )
    assert "TENDERMINT_TPU_SANITIZE" not in env


# --- heartbeat / watchdog units ---------------------------------------------


def test_watchdog_kills_on_silence_not_on_progress(tmp_path):
    spool = str(tmp_path / "hb.spool")
    clock = [0.0]
    dog = Watchdog(
        spool, beat_timeout=10.0, wall_timeout=100.0, clock=lambda: clock[0]
    )
    writer = heartbeat.HeartbeatWriter("sec", path=spool)
    writer("first")
    clock[0] = 8.0
    assert dog.check() is None  # beat seen, inside the window
    clock[0] = 17.0
    assert dog.check() is None  # 9s of silence < 10s window
    writer("progress")
    clock[0] = 26.0
    assert dog.check() is None  # the beat reset the silence clock
    clock[0] = 37.0
    reason = dog.check()
    assert reason is not None and "heartbeat silence" in reason
    assert "progress" in reason  # diagnostic carries the last beat line


def test_watchdog_startup_window_is_the_probe_budget(tmp_path):
    """A child that never produces its FIRST beat (wedged backend
    import) is held to the probe window, not the heartbeat window."""
    spool = str(tmp_path / "hb.spool")
    clock = [0.0]
    dog = Watchdog(
        spool,
        beat_timeout=300.0,
        wall_timeout=1000.0,
        startup_timeout=20.0,
        clock=lambda: clock[0],
    )
    clock[0] = 19.0
    assert dog.check() is None
    clock[0] = 21.0
    reason = dog.check()
    assert reason is not None and "probe window" in reason


def test_watchdog_wall_timeout_caps_a_dutiful_beater(tmp_path):
    spool = str(tmp_path / "hb.spool")
    clock = [0.0]
    dog = Watchdog(
        spool, beat_timeout=10.0, wall_timeout=50.0, clock=lambda: clock[0]
    )
    writer = heartbeat.HeartbeatWriter("sec", path=spool)
    for t in range(5, 56, 5):
        clock[0] = float(t)
        writer("tick %d" % t)
        verdict = dog.check()
        if t <= 50:
            assert verdict is None, t
    clock[0] = 51.0
    writer("tick")
    assert "wall timeout" in (dog.check() or "")


def test_heartbeat_writer_degrades_without_spool(monkeypatch):
    monkeypatch.delenv(heartbeat.HEARTBEAT_FILE_ENV, raising=False)
    writer = heartbeat.HeartbeatWriter("sec")
    writer("no spool configured")  # must not raise
    assert writer.beats == 1


# --- partial-result JSON ------------------------------------------------------


def test_partial_roundtrip_merge_and_exit_codes(tmp_path):
    path = str(tmp_path / "p.json")
    doc = results.new_partial("cpu")
    results.record_section(
        doc, path, "host_ref",
        results.section_block(
            results.OK, attempts=1, duration_s=1.0,
            result={"host_ref": {"sigs_per_s": 123.0}},
        ),
    )
    assert results.exit_code(doc) == 0
    results.record_section(
        doc, path, "throughput",
        results.section_block(
            results.TIMEOUT, attempts=2, duration_s=9.0, note="heartbeat silence",
        ),
    )
    loaded = results.load_partial(path)  # survives the round-trip
    assert loaded["sections"]["throughput"]["status"] == results.TIMEOUT
    merged = results.merge(loaded, list(sections.ORDER))
    assert merged["schema"] == results.MERGED_SCHEMA
    assert merged["host_ref"] == {"sigs_per_s": 123.0}
    assert merged["value"] == 0.0  # throughput died: headline honest zero
    assert merged["sections"]["throughput"]["note"] == "heartbeat silence"
    assert "result" not in merged["sections"]["host_ref"]
    assert results.exit_code(loaded) == 3  # partial evidence
    doc2 = results.new_partial("cpu")
    results.record_section(
        doc2, None, "throughput",
        results.section_block(results.CRASHED, attempts=3, duration_s=1.0),
    )
    assert results.exit_code(doc2) == 1  # nothing measured


def test_load_partial_rejects_foreign_json(tmp_path):
    path = tmp_path / "x.json"
    path.write_text(json.dumps({"metric": "x", "value": 1}))
    with pytest.raises(ValueError, match="schema"):
        results.load_partial(str(path))


# --- chaos: subprocess scenarios ---------------------------------------------


def _run(plan, **env):
    for k, v in env.items():
        os.environ[k] = v
    try:
        return runner.run(plan=plan)
    finally:
        for k in env:
            os.environ.pop(k, None)


def test_sigkilled_section_keeps_other_sections_evidence(bench_env):
    """SIGKILL one section child mid-run: the merged JSON still carries
    the completed section's real numbers and an honest ``crashed``
    status (attempt count included) for the dead one."""
    merged, code = _run(("host_ref", "_chaos"), BENCH_CHAOS="sigkill")
    assert merged["host_ref"]["sigs_per_s"] > 0  # real measurement survived
    chaos = merged["sections"]["_chaos"]
    assert chaos["status"] == "crashed"
    assert chaos["attempts"] == 1
    assert "-9" in chaos["note"]  # the SIGKILL is visible, not laundered
    assert merged["sections"]["host_ref"]["status"] == "ok"
    assert code == 3  # partial evidence, not rc=124-style total loss
    # the partial file on disk is schema-valid and carries the evidence
    doc = results.load_partial(bench_env["partial"])
    assert doc["sections"]["host_ref"]["result"]["host_ref"]["sigs_per_s"] > 0


def test_heartbeat_silence_triggers_watchdog_kill(bench_env, monkeypatch):
    """A section that goes silent (sleeping child) dies by heartbeat
    watchdog within the configured window — long before the 60s wall
    budget — and lands as ``timeout``."""
    # well above the child's own start-up: between its first beat and
    # the section's it imports jax, ~2 s of CPU on this host
    monkeypatch.setenv("BENCH_HEARTBEAT_TIMEOUT", "6")
    t0 = time.monotonic()
    merged, code = _run(("host_ref", "_chaos"), BENCH_CHAOS="hang")
    elapsed = time.monotonic() - t0
    chaos = merged["sections"]["_chaos"]
    assert chaos["status"] == "timeout"
    assert "heartbeat silence" in chaos["note"]
    assert "mode=hang" in chaos["note"]  # last beat line = kill diagnostic
    assert elapsed < 30, "watchdog must kill well before the wall budget"
    assert merged["host_ref"]["sigs_per_s"] > 0
    assert code == 3


def test_resume_reruns_only_failed_sections(bench_env):
    """--resume on a partial with one dead section re-runs exactly that
    section; finished sections keep their original evidence untouched."""
    merged1, code1 = _run(("host_ref", "_chaos"), BENCH_CHAOS="sigkill")
    assert code1 == 3
    before = results.load_partial(bench_env["partial"])
    host_ref_block = dict(before["sections"]["host_ref"])

    os.environ["BENCH_CHAOS"] = "ok"
    try:
        merged2, code2 = runner.run(
            plan=("host_ref", "_chaos"), resume_path=bench_env["partial"]
        )
    finally:
        os.environ.pop("BENCH_CHAOS", None)
    assert code2 == 0
    assert merged2["sections"]["_chaos"]["status"] == "ok"
    assert merged2["chaos"] == {"mode": "ok"}
    # host_ref was NOT re-run: its block (timestamp included) is byte-identical
    after = results.load_partial(bench_env["partial"])
    assert after["sections"]["host_ref"] == host_ref_block


def test_resume_without_plan_finishes_the_recorded_round(bench_env):
    """A partial from a BENCH_SECTIONS subset run records its plan;
    resuming with NO explicit plan must finish that round, not widen to
    the full registry (which would probe jax sections never asked for)."""
    merged1, code1 = _run(("host_ref", "_chaos"), BENCH_CHAOS="crash")
    assert code1 == 3
    recorded = results.load_partial(bench_env["partial"])
    assert recorded["plan"] == ["host_ref", "_chaos"]

    os.environ["BENCH_CHAOS"] = "ok"
    try:
        merged2, code2 = runner.run(resume_path=bench_env["partial"])
    finally:
        os.environ.pop("BENCH_CHAOS", None)
    assert code2 == 0
    # only the recorded round's sections appear — no jax section was drafted
    assert set(merged2["sections"]) == {"host_ref", "_chaos"}
    assert merged2["sections"]["_chaos"]["status"] == "ok"


def test_crashing_section_retries_down_the_ladder(bench_env, monkeypatch):
    monkeypatch.setenv("BENCH_SECTION_ATTEMPTS", "2")
    merged, code = _run(("_chaos",), BENCH_CHAOS="crash")
    chaos = merged["sections"]["_chaos"]
    assert chaos["status"] == "crashed"
    assert chaos["attempts"] == 2  # the ladder actually re-attempted
    assert "injected chaos crash" in chaos["note"]
    assert code == 1  # nothing measured at all


def test_stderr_gets_one_structured_line_per_section(bench_env, capfd):
    """The per-section log goes to stderr — a bench run writes to no
    tracked file."""
    merged, _ = _run(("host_ref", "_chaos"), BENCH_CHAOS="sigkill")
    text = capfd.readouterr().err
    lines = [l for l in text.splitlines() if " in " in l and "attempts=" in l]
    assert len(lines) == 2
    ok_line = next(l for l in lines if "section host_ref" in l)
    assert "ok in" in ok_line and "attempts=1" in ok_line
    dead_line = next(l for l in lines if "section _chaos" in l)
    assert "crashed in" in dead_line
    # plus the whole-round summary line the old harness always wrote
    assert any("bench round on JAX_PLATFORMS" in l for l in text.splitlines())


def test_skipped_sections_get_honest_status(bench_env, monkeypatch):
    """Legacy BENCH_SKIP_* opt-outs surface as status=skipped blocks in
    the merged JSON rather than silently vanishing."""
    monkeypatch.setenv("BENCH_SKIP_COMMIT", "1")
    doc = results.new_partial("cpu")
    runner.mark_skipped(doc, None)
    assert doc["sections"]["verify_commit"]["status"] == "skipped"
    assert doc["sections"]["verify_commit"]["note"] == "BENCH_SKIP_COMMIT=1"
    assert "throughput" not in doc["sections"]  # not skipped, just not run yet
