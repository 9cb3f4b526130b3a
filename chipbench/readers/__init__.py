"""Readers: each takes one per-layer metric from the evidence of a
traced run (``chipbench.run.Evidence``): the program's spans and
counters, the calls the window made, and the profiler's trace.

``read(ev, **args) -> float | None``; the arguments come from the
metric's file under ``layer_metrics/``. A reader that finds nothing to
read returns None, and the metric is left out of the result line.
"""
