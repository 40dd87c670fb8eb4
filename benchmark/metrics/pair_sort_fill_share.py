"""Valid samples of the real rows over the elements the pair program
sorts, as a share: 100 x `pair_real_elems` / `pair_sort_elems` (both on
the `engine.score` span; a launched row sorts both windows at its bucket,
2T elements). None where the program has no such counters."""
from lib import cycle_spans


def read(ctx):
    real = cycle_spans.attr(ctx, cycle_spans.SCORE, "pair_real_elems")
    total = cycle_spans.attr(ctx, cycle_spans.SCORE, "pair_sort_elems")
    if real is None or not total:
        return None
    return 100.0 * real / total
