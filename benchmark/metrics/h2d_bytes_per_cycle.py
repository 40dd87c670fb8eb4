"""Bytes of host arrays handed to jitted programs, per cycle, counted where
they are handed (`h2d_bytes` on the `engine.score` span)."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.attr(ctx, cycle_spans.SCORE, "h2d_bytes")
