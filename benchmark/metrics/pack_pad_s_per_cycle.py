"""Seconds in the `engine.pack.pad` spans, per cycle: cutting a launch's
blocks into chunks and edge-padding a chunk up to its batch rung, under
each `engine.dispatch`."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.span_seconds(ctx, "engine.pack.pad")
