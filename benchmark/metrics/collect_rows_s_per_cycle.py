"""Self time of the `engine.collect` spans, per cycle: the per-row Python
of a family's collect, which turns a launch's rows of results into one
record a job, outside the `engine.materialize` wait under it. With
`materialize_s_per_cycle` it accounts for `collect_s_per_cycle`, up to
what `CyclePipeline.finish` does outside the spans."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.span_seconds(ctx, "engine.collect",
                                    of=cycle_spans.self_seconds)
