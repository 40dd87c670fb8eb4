"""Thread-seconds the fetch pool spent on URLs, per cycle: materializing
a job's placeholders (`Analyzer._fetch_window`), parsing the range and
deriving the cache key (`DeltaWindowSource.fetch_window`) and setting the
delta query's range (`_try_delta`), summed over its threads
(`pool_url_thread_seconds` on the `engine.preprocess` span)."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.attr(ctx, cycle_spans.PREPROCESS,
                            "pool_url_thread_seconds")
