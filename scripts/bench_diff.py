"""bench_diff: the schema-aware bench regression sentinel (ISSUE 18).

Diffs two bench result JSONs and renders a per-section verdict table::

    python -m scripts.bench_diff old.json new.json
    python -m scripts.bench_diff --tolerance 10 old.json new.json

Accepted input shapes (auto-detected, mixable — a partial can be
diffed against a full merged round):

- merged ``tendermint-tpu-bench/2`` (bench.py's BENCH_rNN.json)
- ``tendermint-tpu-bench-partial/1`` (the resumable evidence file;
  only sections with status ``ok`` contribute metrics)
- the legacy driver wrapper ``{n, cmd, rc, tail, parsed}`` whose
  ``parsed`` payload is a merged-style doc

Each numeric leaf becomes a dotted metric path grouped into a section
(top-level scalars -> ``headline``; nested objects -> their key).
Non-measurement subtrees (probe, sections status map, scheduler_knobs,
profile digests) are excluded — they describe the run, they are not
the run's numbers.

Direction is inferred from the metric name: paths ending in a time
unit (``_ms``/``_s``/``_us``/``_seconds``) or carrying a latency-ish
token (``p50``/``p95``/``p99``/``latency``/``wait``/``stall``) are
lower-is-better; everything else (throughputs, rates, counts) is
higher-is-better.

Noise tolerance: a direction-adjusted delta within ``--tolerance``
percent (default 5.0, env ``BENCH_DIFF_TOLERANCE``) is a wash.
Sections or metrics present on only one side are reported (``missing``
/ ``new``) but are NOT regressions — that is what makes a partial
diffable against a full round. ``--strict-missing`` upgrades a
baseline metric missing from the candidate to a regression.

Exit codes (documented contract, chosen to never collide with
bench.py's own 0/1/3):

    0  no regression (improvements and washes only)
    2  usage error / unreadable or unrecognized input
    4  at least one metric regressed beyond tolerance
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

MERGED_SCHEMA = "tendermint-tpu-bench/2"
PARTIAL_SCHEMA = "tendermint-tpu-bench-partial/1"

EXIT_OK = 0
EXIT_USAGE = 2
EXIT_REGRESSION = 4

DEFAULT_TOLERANCE_PCT = 5.0
TOLERANCE_ENV = "BENCH_DIFF_TOLERANCE"

# Run-description subtrees: never diffed as measurements.
_EXCLUDE_KEYS = {
    "schema",
    "probe",
    "sections",
    "scheduler_knobs",
    "profile",
    "runner_trace_summary",
    "plan",
    "metric",
    "unit",
    "n",
    "rc",
}

_LOWER_BETTER_RE = re.compile(
    r"(_ms|_us|_s|_seconds)$|p50|p95|p99|latency|wait|stall"
)

# verdict labels (ranked: any REGRESSION in the table -> exit 4)
REGRESSION = "REGRESSION"
IMPROVED = "improved"
OK = "ok"
MISSING = "missing"
NEW = "new"


def lower_is_better(path: str) -> bool:
    leaf = path.rsplit(".", 1)[-1]
    return bool(_LOWER_BETTER_RE.search(leaf))


def _flatten(obj, prefix: str = "") -> Dict[str, float]:
    """Dotted numeric leaves of a fragment (bools excluded)."""
    out: Dict[str, float] = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            if prefix == "" and k in _EXCLUDE_KEYS:
                continue
            key = "%s.%s" % (prefix, k) if prefix else str(k)
            out.update(_flatten(v, key))
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        if prefix:
            out[prefix] = float(obj)
    return out


def _sections_from_merged(doc: dict) -> Dict[str, Dict[str, float]]:
    """A merged doc is flat: top-level scalars form the ``headline``
    section, nested measurement objects become their own sections."""
    out: Dict[str, Dict[str, float]] = {}
    headline: Dict[str, float] = {}
    for k, v in doc.items():
        if k in _EXCLUDE_KEYS:
            continue
        if isinstance(v, dict):
            flat = _flatten(v)
            if flat:
                out[k] = flat
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            headline[k] = float(v)
    if headline:
        out["headline"] = headline
    return out


def _sections_from_partial(doc: dict) -> Dict[str, Dict[str, float]]:
    out: Dict[str, Dict[str, float]] = {}
    for name, block in (doc.get("sections") or {}).items():
        if not isinstance(block, dict) or block.get("status") != "ok":
            continue
        result = block.get("result")
        if isinstance(result, dict):
            flat = _flatten(result)
            if flat:
                out[name] = flat
    return out


def normalize(doc: dict, label: str) -> Dict[str, Dict[str, float]]:
    """Any accepted shape -> {section: {metric_path: value}}."""
    if not isinstance(doc, dict):
        raise ValueError("%s: not a JSON object" % label)
    if doc.get("schema") == PARTIAL_SCHEMA:
        return _sections_from_partial(doc)
    if doc.get("schema") == MERGED_SCHEMA:
        return _sections_from_merged(doc)
    if isinstance(doc.get("parsed"), dict):  # legacy driver wrapper
        return _sections_from_merged(doc["parsed"])
    # tolerant fallback: a merged-shaped doc without the schema stamp
    # (hand-edited fixtures); require the headline key to avoid
    # swallowing arbitrary JSON silently
    if "value" in doc and "metric" in doc:
        return _sections_from_merged(doc)
    raise ValueError(
        "%s: unrecognized bench result shape (want schema %r or %r, or a "
        "legacy {parsed: ...} wrapper)" % (label, MERGED_SCHEMA, PARTIAL_SCHEMA)
    )


def diff_sections(
    base: Dict[str, Dict[str, float]],
    cand: Dict[str, Dict[str, float]],
    tolerance_pct: float,
    strict_missing: bool = False,
) -> List[dict]:
    """One row per (section, metric): {section, metric, old, new,
    delta_pct, verdict}. Rows come out grouped by section, baseline
    order first, candidate-only sections last."""
    rows: List[dict] = []
    for section in list(base) + [s for s in cand if s not in base]:
        b = base.get(section)
        c = cand.get(section)
        if b is None:
            for path, val in sorted((c or {}).items()):
                rows.append(_row(section, path, None, val, NEW))
            continue
        if c is None:
            verdict = REGRESSION if strict_missing else MISSING
            for path, val in sorted(b.items()):
                rows.append(_row(section, path, val, None, verdict))
            continue
        for path in sorted(set(b) | set(c)):
            if path not in c:
                verdict = REGRESSION if strict_missing else MISSING
                rows.append(_row(section, path, b[path], None, verdict))
            elif path not in b:
                rows.append(_row(section, path, None, c[path], NEW))
            else:
                rows.append(
                    _judge(section, path, b[path], c[path], tolerance_pct)
                )
    return rows


def _row(section, path, old, new, verdict, delta_pct=None) -> dict:
    return {
        "section": section,
        "metric": path,
        "old": old,
        "new": new,
        "delta_pct": delta_pct,
        "verdict": verdict,
    }


def _judge(section, path, old, new, tolerance_pct) -> dict:
    if old == new:
        return _row(section, path, old, new, OK, 0.0)
    if old == 0.0:
        # no ratio to take; direction still tells us which way it moved
        moved_worse = (new > 0.0) == lower_is_better(path)
        verdict = REGRESSION if moved_worse else IMPROVED
        return _row(section, path, old, new, verdict, None)
    delta_pct = (new - old) / abs(old) * 100.0
    gain = -delta_pct if lower_is_better(path) else delta_pct
    if gain < -tolerance_pct:
        verdict = REGRESSION
    elif gain > tolerance_pct:
        verdict = IMPROVED
    else:
        verdict = OK
    return _row(section, path, old, new, verdict, round(delta_pct, 2))


def summarize(rows: List[dict]) -> dict:
    counts: Dict[str, int] = {}
    for r in rows:
        counts[r["verdict"]] = counts.get(r["verdict"], 0) + 1
    return {
        "rows": len(rows),
        "regressions": counts.get(REGRESSION, 0),
        "improvements": counts.get(IMPROVED, 0),
        "ok": counts.get(OK, 0),
        "missing": counts.get(MISSING, 0),
        "new": counts.get(NEW, 0),
    }


def verdict_line(
    base_path: str, cand_path: str, rows: List[dict], tolerance_pct: float
) -> str:
    """The one-line verdict bench.py --baseline prints to stderr."""
    s = summarize(rows)
    word = "REGRESSION" if s["regressions"] else "ok"
    return (
        "bench_diff %s -> %s: %s (%d regressed / %d improved / %d ok"
        " / %d missing, tol %.1f%%)"
        % (
            os.path.basename(base_path),
            os.path.basename(cand_path),
            word,
            s["regressions"],
            s["improvements"],
            s["ok"],
            s["missing"],
            tolerance_pct,
        )
    )


def _fmt(v: Optional[float]) -> str:
    if v is None:
        return "-"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return "%.4g" % v


def render_table(rows: List[dict], tolerance_pct: float) -> str:
    headers = ("section", "metric", "old", "new", "delta%", "verdict")
    table: List[Tuple[str, ...]] = [headers]
    for r in rows:
        delta = "-" if r["delta_pct"] is None else "%+.2f" % r["delta_pct"]
        table.append(
            (
                r["section"],
                r["metric"],
                _fmt(r["old"]),
                _fmt(r["new"]),
                delta,
                r["verdict"],
            )
        )
    widths = [max(len(row[i]) for row in table) for i in range(len(headers))]
    lines = []
    for n, row in enumerate(table):
        lines.append(
            "  ".join(cell.ljust(widths[i]) for i, cell in enumerate(row)).rstrip()
        )
        if n == 0:
            lines.append("  ".join("-" * w for w in widths))
    s = summarize(rows)
    lines.append("")
    lines.append(
        "%d metrics: %d regressed, %d improved, %d ok, %d missing, %d new"
        " (tolerance %.1f%%)"
        % (
            s["rows"],
            s["regressions"],
            s["improvements"],
            s["ok"],
            s["missing"],
            s["new"],
            tolerance_pct,
        )
    )
    return "\n".join(lines)


def diff_files(
    base_path: str,
    cand_path: str,
    tolerance_pct: float,
    strict_missing: bool = False,
) -> List[dict]:
    with open(base_path) as f:
        base = normalize(json.load(f), base_path)
    with open(cand_path) as f:
        cand = normalize(json.load(f), cand_path)
    return diff_sections(base, cand, tolerance_pct, strict_missing)


def default_tolerance() -> float:
    try:
        return float(os.environ.get(TOLERANCE_ENV, DEFAULT_TOLERANCE_PCT))
    except ValueError:
        return DEFAULT_TOLERANCE_PCT


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="bench_diff",
        description="diff two bench result JSONs (baseline candidate)",
    )
    p.add_argument("baseline")
    p.add_argument("candidate")
    p.add_argument(
        "--tolerance",
        type=float,
        default=default_tolerance(),
        help="noise tolerance in percent (default %g, env %s)"
        % (DEFAULT_TOLERANCE_PCT, TOLERANCE_ENV),
    )
    p.add_argument(
        "--strict-missing",
        action="store_true",
        help="a baseline metric missing from the candidate is a regression",
    )
    p.add_argument(
        "--json", action="store_true", help="emit rows as JSON instead of a table"
    )
    args = p.parse_args(argv)
    try:
        rows = diff_files(
            args.baseline,
            args.candidate,
            args.tolerance,
            strict_missing=args.strict_missing,
        )
    except (OSError, ValueError) as exc:
        print("bench_diff: %s" % exc, file=sys.stderr)
        return EXIT_USAGE
    if args.json:
        print(json.dumps({"rows": rows, "summary": summarize(rows)}, indent=1))
    else:
        print(render_table(rows, args.tolerance))
    return EXIT_REGRESSION if summarize(rows)["regressions"] else EXIT_OK


if __name__ == "__main__":
    sys.exit(main())
