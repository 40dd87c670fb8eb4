"""Seconds of the `engine.cycle` span that still have no name, per cycle:
its self time, and what is left of `engine.preprocess` after its children
and its accumulated pieces (`wait_s`, `route_s`, `memo_fp_s`,
`triage_s`)."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.per_cycle(ctx, cycle_spans.uncovered_seconds)
