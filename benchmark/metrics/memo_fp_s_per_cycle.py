"""Wall seconds inside `CyclePipeline._memo_check` (key, blake2b over every
window, lookup), per cycle (`memo_fp_s` on the `engine.preprocess`
span)."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.attr(ctx, cycle_spans.PREPROCESS, "memo_fp_s")
