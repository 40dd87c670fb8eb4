"""Device launches per cycle (`Analyzer.device_launches`)."""


def read(ctx):
    cycles = ctx["cycles"]
    return sum(c["launches"] for c in cycles) / len(cycles)
