"""Seconds in the `engine.launch` spans, per cycle: the call of each
chunk's scorer. The band closure blocks in here on its predictions and
its sigma, so the transfers and the device time of two of its three
programs are inside."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.span_seconds(ctx, "engine.launch")
