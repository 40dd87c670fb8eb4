"""Thread-seconds of `Analyzer._preprocess` outside its source calls, per
cycle: counting valid samples, the policy lookup, building the family
items, the per-fetch notes and histogram, summed over the pool's threads
(`pool_items_thread_seconds` on the `engine.preprocess` span)."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.attr(ctx, cycle_spans.PREPROCESS,
                            "pool_items_thread_seconds")
