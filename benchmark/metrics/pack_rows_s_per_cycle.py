"""Seconds in the `engine.pack.rows` spans, per cycle: a launch's per-row
host arrays (a band row's history and current joined), under each
`engine.dispatch`."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.span_seconds(ctx, "engine.pack.rows")
