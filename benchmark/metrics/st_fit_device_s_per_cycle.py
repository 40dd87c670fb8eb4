"""Device seconds of the seasonal-trend fit, per cycle: the program that
holds the Gram, the right-hand side, the solves and the predictions
(`costs_st.FIT_PROGRAMS`), over every partition, from the trace."""
from lib import costs_st
from lib.costs_hw import device_seconds


def read(ctx):
    device_s = device_seconds(ctx["trace"], costs_st.FIT_PROGRAMS)
    if not device_s:
        return None
    return device_s / len(ctx["cycles"])
