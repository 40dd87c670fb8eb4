"""Seconds `DeltaWindowSource._cpu_lock` was held, per cycle: the splice and
the grid themselves. One thread holds it at a time, so these are wall
seconds (`pool_lock_held_seconds` on the `engine.preprocess` span)."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.attr(ctx, cycle_spans.PREPROCESS,
                            "pool_lock_held_seconds")
