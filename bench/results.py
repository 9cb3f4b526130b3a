"""Partial-result JSON: the on-disk evidence trail of a bench round.

The contract that makes one failed section cost only itself: each section's
result is persisted (atomically: tmp + rename) the moment the section
completes, so a later hang/SIGKILL/reboot cannot destroy earlier
evidence. The final ``BENCH_rNN.json`` is a *merge* of the partial
file — completed sections contribute their real measurement fragments
at the same top-level keys the single-child bench always used, and a
``sections`` block records per-section status / attempts / degradation
so a partially-failed round reads as partial truth, never as zero.

``--resume <partial.json>`` re-runs only sections whose status is not
``ok`` (bench/runner.py), which is why the partial schema is versioned
and validated on load.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

PARTIAL_SCHEMA = "tendermint-tpu-bench-partial/1"
MERGED_SCHEMA = "tendermint-tpu-bench/2"

# Per-section terminal statuses (ISSUE 6 tentpole).
OK = "ok"
TIMEOUT = "timeout"
CRASHED = "crashed"
SKIPPED = "skipped"
STATUSES = (OK, TIMEOUT, CRASHED, SKIPPED)

GO_CPU_BATCH_SIGS_PER_SEC = 30_000.0  # curve25519-voi batch verify, 1 core


def utc_now() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


def new_partial(configured_backend: str) -> dict:
    return {
        "schema": PARTIAL_SCHEMA,
        "started_at": utc_now(),
        "configured_backend": configured_backend,
        "probe": {},
        "sections": {},
    }


def load_partial(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") != PARTIAL_SCHEMA:
        raise ValueError(
            "not a bench partial-result file (schema=%r, want %r): %s"
            % (doc.get("schema"), PARTIAL_SCHEMA, path)
        )
    if not isinstance(doc.get("sections"), dict):
        raise ValueError("bench partial-result file has no sections map: %s" % path)
    return doc


def write_partial(doc: dict, path: str) -> None:
    """Atomic write: a watchdog kill (or operator ^C) between sections
    can never leave a torn JSON behind."""
    tmp = "%s.tmp.%d" % (path, os.getpid())
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def section_block(
    status: str,
    attempts: int,
    duration_s: float,
    note: Optional[str] = None,
    degraded: bool = False,
    backend: Optional[str] = None,
    result: Optional[dict] = None,
) -> dict:
    assert status in STATUSES, status
    block = {
        "status": status,
        "attempts": attempts,
        "duration_s": round(duration_s, 2),
        "completed_at": utc_now(),
        "degraded": degraded,
        "note": note,
        "backend": backend,
    }
    if result is not None:
        block["result"] = result
    return block


def record_section(doc: dict, path: Optional[str], name: str, block: dict) -> None:
    doc["sections"][name] = block
    if path:
        write_partial(doc, path)


def merge(doc: dict, section_order: List[str]) -> dict:
    """Flatten a partial document into the headline BENCH JSON.

    Completed sections' result fragments are merged in registry order
    (so e.g. the stages section's ``impl`` refines the throughput
    section's); failed/skipped sections appear only in the ``sections``
    status map. The headline keys (metric/value/unit/vs_baseline) are
    always present — 0.0 when the throughput section itself died — so
    downstream tooling keyed on them keeps working.
    """
    sections: Dict[str, dict] = doc.get("sections", {})
    merged: dict = {
        "metric": "ed25519_batch_verify_throughput_b%s"
        % os.environ.get("BENCH_BATCH", "8192"),
        "value": 0.0,
        "unit": "sigs/s",
        "vs_baseline": 0.0,
    }
    ordered = [n for n in section_order if n in sections]
    ordered += [n for n in sections if n not in ordered]
    for name in ordered:
        block = sections[name]
        if block.get("status") == OK and isinstance(block.get("result"), dict):
            merged.update(block["result"])
    if merged.get("value"):
        merged["vs_baseline"] = round(
            merged["value"] / GO_CPU_BATCH_SIGS_PER_SEC, 3
        )
    merged["probe"] = doc.get("probe", {})
    merged["sections"] = {
        name: {k: v for k, v in block.items() if k != "result"}
        for name, block in sections.items()
    }
    merged["schema"] = MERGED_SCHEMA
    return merged


def exit_code(doc: dict) -> int:
    """0 = every section ok/skipped; 3 = partial evidence (some ok,
    some failed); 1 = nothing measured. Never the shell's 124 — a
    wedged section is an entry in ``sections``, not a whole-run kill."""
    statuses = [b.get("status") for b in doc.get("sections", {}).values()]
    failed = [s for s in statuses if s in (TIMEOUT, CRASHED)]
    ok = [s for s in statuses if s == OK]
    if not failed:
        return 0
    return 3 if ok else 1
