"""Seconds spent materializing launches and building per-row results,
per cycle (`last_cycle_stages["stage_seconds"]["collect"]`): the (B, T)
bounds and flags come back to the host here."""


def read(ctx):
    cycles = ctx["cycles"]
    return sum(c["stage_seconds"]["collect"] for c in cycles) / len(cycles)
