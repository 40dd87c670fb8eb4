"""Seconds in the `engine.advance` span (`store.advance` for every job
that fetched, the shed and failed branches for the rest), per cycle."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.span_seconds(ctx, "engine.advance")
