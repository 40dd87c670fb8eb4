"""Delta fetches a cycle that `DeltaWindowSource`'s append rule served: a
contiguous on-grid tail joined to the cached window without a splice
(`splice_appends` on the `engine.preprocess` span, summed from the jobs'
`fetch_append` notes)."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.attr(ctx, cycle_spans.PREPROCESS, "splice_appends")
