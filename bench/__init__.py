"""Per-section benchmark harness.

``bench.py`` at the repo root is the CLI entry point; this package is
the implementation:

- ``sections``  — the section registry + measurement bodies
- ``runner``    — per-section subprocess orchestration, watchdog,
                  retry/degradation ladder, resume, merged output
- ``heartbeat`` — child progress spool + parent watchdog
- ``results``   — partial-result JSON, per-section status, merging
- ``child``     — the per-section child entry point
- ``workload``  — shared signature/header fixtures
"""
