"""Thread-seconds of the fetch outside its named parts, per cycle: the
source calls' wall time less their URL, store, splice-lock wait and
splice seconds, which is the delta source's own bookkeeping (the cache
lookup, the unmoved-range rule, the counters and notes), summed over the
pool's threads (`pool_cache_thread_seconds` on the `engine.preprocess`
span)."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.attr(ctx, cycle_spans.PREPROCESS,
                            "pool_cache_thread_seconds")
