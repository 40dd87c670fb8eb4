"""Seconds in the `engine.publish` span (from the end of the fold to the
return of `_run_cycle`: gauges, `provenance.finish_cycle`, the pruning
sweep, `store.put_state`, `store.flush`), per cycle."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.span_seconds(ctx, "engine.publish")
