"""Traffic generators, found by the ``kind`` a traffic file names.

``build(ctx) -> traffic`` runs during set-up and returns an object with

- ``lanes_per_call``: useful signatures one call verifies;
- ``warm()``: the untimed calls that compile or load every shape;
- ``call(i)``: the i-th timed call, entered through the entry point a
  user of the program calls, returning what the program answered;
- ``check(outcomes, results)``: after the window, compares every timed
  call's answer with what generation knows, verifies the tampered
  requests, and holds a seeded sample of lanes against the plain
  reference; adds each number compared to ``results``.

A new mix of an existing kind is a new file under ``traffic/`` and
nothing else.
"""

import math


def cycle_length(traffic: dict, lanes_per_request: int, cache_cap: int) -> int:
    """How many distinct pre-signed requests the loop cycles through,
    so that the program's verdict cache (an LRU of ``cache_cap``
    signatures) has dropped a request before it comes round again:
    between two visits the other ``n - 1`` requests put
    ``(n - 1) * lanes`` distinct signatures in. The traffic file asks
    for ``cycle_over_verdict_cache`` times the capacity. Refuses a
    cycle the cache could answer."""
    want = traffic["cycle_over_verdict_cache"] * cache_cap
    n = math.ceil(want / lanes_per_request) + 1
    if (n - 1) * lanes_per_request < cache_cap and not traffic.get(
        "_allow_cache_answers"
    ):
        raise SystemExit(
            "chipbench: %d requests of %d signatures cycle through fewer than "
            "the verdict cache's %d: the cache would answer the benchmark"
            % (n, lanes_per_request, cache_cap)
        )
    return max(n, 2)
