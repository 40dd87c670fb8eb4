"""Partitions by detected period that a cycle's band launches split into
(`period_partitions` on the `engine.score` span)."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.attr(ctx, cycle_spans.SCORE, "period_partitions")
