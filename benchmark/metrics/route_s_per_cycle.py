"""Wall seconds the cycle thread spent between taking a result from the
fetch stream and going back to wait, less what has a name of its own
(streamed launches, the triage screen, the memo's fingerprint), per cycle
(`route_s` on the `engine.preprocess` span)."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.attr(ctx, cycle_spans.PREPROCESS, "route_s")
