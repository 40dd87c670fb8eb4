"""Seconds in the `engine.pack.block` spans, per cycle: allocating and
filling a launch's `(B, T)` blocks and `(B,)` vectors, under each
`engine.dispatch`."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.span_seconds(ctx, "engine.pack.block")
