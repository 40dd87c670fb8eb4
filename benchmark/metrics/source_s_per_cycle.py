"""Thread-seconds the fetch pool spent inside the inner source's call
(`DeltaWindowSource._series`: the metric store's side of a fetch, here the
benchmark's own `FleetSource`), per cycle, summed over its threads
(`pool_source_thread_seconds` on the `engine.preprocess` span)."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.attr(ctx, cycle_spans.PREPROCESS,
                            "pool_source_thread_seconds")
