"""chipbench: the benchmark of record for tendermint_tpu on a TPU.

One command runs one cell once (see ``run.py``). Everything that decides
a number lives here, where a later PR that claims a gain cannot change
it: traffic generation, the plain reference, the op and byte counts,
the table of published peaks, and the reduction from spans, counters
and the profiler's trace to metrics. From the program it takes only the
system under test and its spans, counters and kernel names.
"""
