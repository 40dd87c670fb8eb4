"""Seconds in the `engine.claim` span (`store.claim_open_jobs`), per
cycle."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.span_seconds(ctx, "engine.claim")
