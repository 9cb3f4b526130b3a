"""End-to-end metrics, one small file each, found by the metric's name
in ``BENCHMARK.json``. Each is taken by the benchmark itself, on the
host's clock, from every call of the window: ``read(run) -> float``,
where ``run`` holds the calls' wall times in milliseconds
(``durations_ms``), ``setup_s``, ``lanes_per_call`` and the start of
the first call and the end of the last (``first_start_ns``,
``last_end_ns``)."""
