"""Seconds the cycle thread waited on fetch, splice and pack, per cycle
(`Analyzer.last_cycle_stages["stage_seconds"]["preprocess"]`)."""


def read(ctx):
    cycles = ctx["cycles"]
    return sum(c["stage_seconds"]["preprocess"] for c in cycles) / len(cycles)
