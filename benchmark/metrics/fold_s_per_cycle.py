"""Seconds in the `engine.fold` span (results into verdicts, provenance
records, store transitions), per cycle: equals `stage_seconds["fold"]`."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.span_seconds(ctx, "engine.fold")
