"""Seconds spent packing launches and calling the scorers, per cycle
(`last_cycle_stages["stage_seconds"]["dispatch"]`): the band closure
blocks on its predictions here, so the launch's transfers and device
time land in this stage."""


def read(ctx):
    cycles = ctx["cycles"]
    return sum(c["stage_seconds"]["dispatch"] for c in cycles) / len(cycles)
