"""Device seconds of the Holt-Winters fit, per cycle: the program that
holds the grid pass (the candidates side by side) and the winners' pass
(`costs_hw.FIT_PROGRAMS`), over every partition, from the trace."""
from lib import costs_hw


def read(ctx):
    device_s = costs_hw.device_seconds(ctx["trace"], costs_hw.FIT_PROGRAMS)
    if not device_s:
        return None
    return device_s / len(ctx["cycles"])
