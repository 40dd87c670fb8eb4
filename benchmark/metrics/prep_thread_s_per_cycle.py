"""Thread-seconds the fetch pool spent in `Analyzer._preprocess`, per
cycle: summed over its threads, so 16 threads can book 16 s in one wall
second (`pool_prep_thread_seconds` on the `engine.preprocess` span)."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.attr(ctx, cycle_spans.PREPROCESS,
                            "pool_prep_thread_seconds")
