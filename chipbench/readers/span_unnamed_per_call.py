"""Milliseconds per call that a span spends under no name at all: its
self time (its duration less its named child spans), plus, for each
child that carries phase totals, that child's duration less the sum of
its ``<phase>_us`` arguments. ``phased`` maps such a child to its
phases. A program that records none of the phased children gives
nothing: the number would be the whole span."""

from chipbench.readers import span_self_time_per_call


def read(ev, span, children, phased):
    loops = [s for s in ev.spans if s["name"] in phased]
    if not loops:
        return None
    own = span_self_time_per_call.read(ev, span, children)
    if own is None:
        return None
    unphased_us = sum(
        s["dur"] - sum(s["args"].get(p + "_us", 0.0) for p in phased[s["name"]])
        for s in loops
    )
    return own + unphased_us / 1000.0 / len(ev.calls)
