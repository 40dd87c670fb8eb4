"""Thread-seconds the fetch pool queued for `DeltaWindowSource._cpu_lock`,
per cycle, summed over its threads (`pool_lock_wait_thread_seconds` on the
`engine.preprocess` span)."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.attr(ctx, cycle_spans.PREPROCESS,
                            "pool_lock_wait_thread_seconds")
