"""Seconds of a cycle that no stage counter covers, per cycle: the
cycle's wall time less the sum of `last_cycle_stages["stage_seconds"]`.
The cycle thread's own work between fetch results (routing, the memo's
fingerprints) is here, as are claim, advance and flush."""


def read(ctx):
    cycles = ctx["cycles"]
    return sum(c["seconds"] - sum(c["stage_seconds"].values())
               for c in cycles) / len(cycles)
