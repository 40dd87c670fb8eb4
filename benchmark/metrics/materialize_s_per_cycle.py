"""Seconds in the `engine.materialize` spans, per cycle: blocking on each
launch's device values and copying them to the host."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.span_seconds(ctx, "engine.materialize")
