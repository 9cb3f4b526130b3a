"""bench_diff sentinel (scripts/bench_diff.py, ISSUE 18).

Pins the documented contract: regressions detected, noise tolerated,
partial-vs-full handled without false alarms, and the 0/2/4 exit-code
scheme — including an acceptance run against small synthetic fixtures
(tests/fixtures/bench_diff_*.json) in the document shapes the sentinel
accepts: two legacy driver wrappers and one partial.
"""

import json
import os

import pytest

from scripts import bench_diff

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
# wrapper with a headline only / wrapper with nested sections and a
# tenfold lower headline / partial with one ok and one crashed section
BASE = os.path.join(FIXTURES, "bench_diff_base.json")
REGRESSED = os.path.join(FIXTURES, "bench_diff_regressed.json")
PARTIAL = os.path.join(FIXTURES, "bench_diff_partial.json")


def _merged(**metrics):
    doc = {"schema": bench_diff.MERGED_SCHEMA}
    doc.update(metrics)
    return doc


# --- direction heuristic ------------------------------------------------------


class TestDirection:
    def test_time_suffixes_are_lower_better(self):
        for path in (
            "stages_ms.kernel_ms",
            "verify_commit_p50_ms_v2000",
            "latency_attrib.p95_ms",
            "queue.wait_s",
            "dispatch.stall_us",
        ):
            assert bench_diff.lower_is_better(path), path

    def test_throughputs_are_higher_better(self):
        for path in (
            "value",
            "light_client_headers_per_s_v250",
            "blocksync_blocks_per_s_v125",
            "vs_baseline",
        ):
            assert not bench_diff.lower_is_better(path), path


# --- judging ------------------------------------------------------------------


class TestJudge:
    def test_throughput_drop_beyond_tolerance_regresses(self):
        rows = bench_diff.diff_sections(
            {"headline": {"value": 100.0}},
            {"headline": {"value": 80.0}},
            tolerance_pct=5.0,
        )
        assert rows[0]["verdict"] == bench_diff.REGRESSION
        assert rows[0]["delta_pct"] == -20.0

    def test_latency_rise_beyond_tolerance_regresses(self):
        rows = bench_diff.diff_sections(
            {"s": {"kernel_ms": 10.0}},
            {"s": {"kernel_ms": 12.0}},
            tolerance_pct=5.0,
        )
        assert rows[0]["verdict"] == bench_diff.REGRESSION

    def test_latency_drop_is_improvement(self):
        rows = bench_diff.diff_sections(
            {"s": {"kernel_ms": 10.0}},
            {"s": {"kernel_ms": 8.0}},
            tolerance_pct=5.0,
        )
        assert rows[0]["verdict"] == bench_diff.IMPROVED

    def test_noise_within_tolerance_is_a_wash(self):
        rows = bench_diff.diff_sections(
            {"headline": {"value": 100.0}},
            {"headline": {"value": 96.0}},
            tolerance_pct=5.0,
        )
        assert rows[0]["verdict"] == bench_diff.OK
        # ... and the same delta regresses once tolerance tightens
        rows = bench_diff.diff_sections(
            {"headline": {"value": 100.0}},
            {"headline": {"value": 96.0}},
            tolerance_pct=2.0,
        )
        assert rows[0]["verdict"] == bench_diff.REGRESSION

    def test_zero_baseline_judged_by_direction_only(self):
        rows = bench_diff.diff_sections(
            {"s": {"stall_ms": 0.0, "value": 0.0}},
            {"s": {"stall_ms": 3.0, "value": 3.0}},
            tolerance_pct=5.0,
        )
        by = {r["metric"]: r for r in rows}
        assert by["stall_ms"]["verdict"] == bench_diff.REGRESSION
        assert by["stall_ms"]["delta_pct"] is None
        assert by["value"]["verdict"] == bench_diff.IMPROVED


# --- missing / new handling ---------------------------------------------------


class TestMissing:
    def test_missing_and_new_are_not_regressions(self):
        rows = bench_diff.diff_sections(
            {"a": {"value": 1.0}, "gone": {"x_ms": 2.0}},
            {"a": {"value": 1.0}, "fresh": {"y_ms": 3.0}},
            tolerance_pct=5.0,
        )
        verdicts = {r["section"]: r["verdict"] for r in rows}
        assert verdicts["gone"] == bench_diff.MISSING
        assert verdicts["fresh"] == bench_diff.NEW
        assert bench_diff.summarize(rows)["regressions"] == 0

    def test_strict_missing_upgrades_to_regression(self):
        rows = bench_diff.diff_sections(
            {"gone": {"x_ms": 2.0}},
            {},
            tolerance_pct=5.0,
            strict_missing=True,
        )
        assert rows[0]["verdict"] == bench_diff.REGRESSION


# --- shape normalization ------------------------------------------------------


class TestNormalize:
    def test_legacy_wrapper_unwraps_parsed(self):
        with open(BASE) as f:
            sections = bench_diff.normalize(json.load(f), "base")
        assert sections["headline"]["value"] == pytest.approx(1000.0)
        # wrapper bookkeeping (n, rc, cmd, tail) must not leak in
        assert "n" not in sections.get("headline", {})
        assert "rc" not in sections.get("headline", {})

    def test_partial_takes_only_ok_sections(self):
        with open(PARTIAL) as f:
            sections = bench_diff.normalize(json.load(f), "partial")
        assert sections  # at least one ok section contributed metrics
        for metrics in sections.values():
            assert metrics  # no empty sections

    def test_profile_and_probe_subtrees_excluded(self):
        doc = _merged(
            value=1.0,
            probe={"primary_failure_ms": 99.0},
            profile={"kernel": {"ed25519/b64": {"p50_ms": 1.0}}},
            scheduler_knobs={"target_ms": 5.0},
        )
        sections = bench_diff.normalize(doc, "doc")
        assert sections == {"headline": {"value": 1.0}}

    def test_unrecognized_shape_raises(self):
        with pytest.raises(ValueError):
            bench_diff.normalize({"random": "junk"}, "junk")
        with pytest.raises(ValueError):
            bench_diff.normalize(["not", "an", "object"], "list")


# --- CLI exit-code contract (0 / 2 / 4) ---------------------------------------


class TestCLI:
    def test_acceptance_pair_regresses(self, capsys):
        """ISSUE 18 acceptance: a pair whose headline collapses exits 4
        with a verdict table."""
        rc = bench_diff.main([BASE, REGRESSED])
        out = capsys.readouterr().out
        assert rc == bench_diff.EXIT_REGRESSION == 4
        assert "REGRESSION" in out
        assert "verdict" in out  # table header rendered

    def test_identity_diff_is_clean(self, capsys):
        rc = bench_diff.main([REGRESSED, REGRESSED])
        out = capsys.readouterr().out
        assert rc == bench_diff.EXIT_OK == 0
        assert "0 regressed" in out

    def test_partial_vs_full_never_false_alarms(self):
        # disjoint section sets: everything is missing/new, nothing
        # regressed, exit stays 0
        assert bench_diff.main([PARTIAL, REGRESSED]) == bench_diff.EXIT_OK

    def test_unreadable_input_is_usage_error(self, tmp_path, capsys):
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        missing = tmp_path / "nope.json"
        assert bench_diff.main([str(bad), REGRESSED]) == bench_diff.EXIT_USAGE == 2
        assert bench_diff.main([str(missing), REGRESSED]) == bench_diff.EXIT_USAGE
        assert "bench_diff:" in capsys.readouterr().err

    def test_tolerance_env_sets_default(self, monkeypatch):
        monkeypatch.setenv(bench_diff.TOLERANCE_ENV, "25")
        assert bench_diff.default_tolerance() == 25.0
        monkeypatch.setenv(bench_diff.TOLERANCE_ENV, "garbage")
        assert bench_diff.default_tolerance() == (
            bench_diff.DEFAULT_TOLERANCE_PCT
        )

    def test_json_output_mode(self, capsys):
        rc = bench_diff.main(["--json", REGRESSED, REGRESSED])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["summary"]["regressions"] == 0
        assert doc["rows"]


# --- probe-log verdict line ---------------------------------------------------


class TestVerdictLine:
    def test_one_liner_names_files_and_counts(self):
        rows = bench_diff.diff_sections(
            {"headline": {"value": 100.0}},
            {"headline": {"value": 50.0}},
            tolerance_pct=5.0,
        )
        line = bench_diff.verdict_line("/x/old.json", "/y/new.json", rows, 5.0)
        assert "old.json -> new.json" in line
        assert "REGRESSION" in line
        assert "1 regressed" in line
