"""Self time of the `engine.dispatch` spans, per cycle: building and
packing a launch's host arrays, before and after the `engine.launch`
calls under them."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.span_seconds(ctx, "engine.dispatch",
                                    of=cycle_spans.self_seconds)
