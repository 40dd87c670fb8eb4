"""Bytes of device values brought back to the host, per cycle, counted
where `np.asarray` takes them (`d2h_bytes` on the `engine.score` span)."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.attr(ctx, cycle_spans.SCORE, "d2h_bytes")
