"""Thread-CPU seconds (`time.thread_time`) of the cycle thread over the
fetch stream, less streamed launches and screens: the CPU of routing and
of the memo's fingerprint together (no clock reads the fingerprint's CPU:
the call costs 6 us on the chip's host). `route_s_per_cycle` plus
`memo_fp_s_per_cycle` less this one is the cycle thread's wait for the
interpreter lock (`route_cpu_s` on the `engine.preprocess` span)."""
from lib import cycle_spans


def read(ctx):
    return cycle_spans.attr(ctx, cycle_spans.PREPROCESS, "route_cpu_s")
