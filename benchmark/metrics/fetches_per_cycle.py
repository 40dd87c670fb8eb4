"""Requests the engine made of the metric store, per cycle of the window
(the source's `request_count`)."""


def read(ctx):
    cycles = ctx["cycles"]
    return sum(c["fetches"] for c in cycles) / len(cycles)
