"""Real samples of real rows over the padded (rung, T) blocks of the packed
value arrays, as a share: 100 x `pack_real_elems` / `pack_total_elems`
(both on the `engine.score` span)."""
from lib import cycle_spans


def read(ctx):
    real = cycle_spans.attr(ctx, cycle_spans.SCORE, "pack_real_elems")
    total = cycle_spans.attr(ctx, cycle_spans.SCORE, "pack_total_elems")
    if real is None or not total:
        return None
    return 100.0 * real / total
