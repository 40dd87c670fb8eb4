"""Seconds in the `engine.detect_period` spans, per cycle: from the
enqueue of period detection to the periods on the host, the one wait a
seasonal band launch keeps (it holds the chunk's upload). The spans lie
inside `engine.launch`, so `launch_s_per_cycle` less this is the enqueue.
A cycle with no seasonal band launch reads 0; a program that has no such
span (a parent commit from before it existed) reads nothing."""
from lib import cycle_spans

SPAN = "engine.detect_period"


def read(ctx):
    try:
        from foremast_tpu.utils.tracing import SPAN_NAMES
    except ImportError:
        return None
    if SPAN not in SPAN_NAMES:
        return None
    return cycle_spans.per_cycle(ctx, lambda root: sum(
        cycle_spans.seconds(s) for s in cycle_spans.find(root, SPAN)))
