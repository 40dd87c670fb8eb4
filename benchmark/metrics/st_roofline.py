"""The seasonal-trend band launch's share of its roofline: the least time
the chip could take for the bytes and operations the launch needs
(`lib/costs_st.py`, from each traced cycle's real rows and samples) over
the summed device time of every program of the band launch under this
forecaster (`costs_st.PROGRAMS`): the region and history masks, period
detection, each partition's gather, fit (Gram, right-hand side, solves
and predictions: one program), sigma, bounds and flags, and the scatter
back into claim order."""
from lib import costs, costs_st
from lib.costs_hw import device_seconds


def read(ctx):
    fl = ctx["fleet"]
    device_s = device_seconds(ctx["trace"], costs_st.PROGRAMS)
    if not device_s or ctx["peaks"] is None:
        return None
    eng = fl.config["engine"]
    lags = 2 * sum(1 for p in eng.get(
        "hw_period_candidates", (60, 480, 720, 1440)) if p >= 4)
    columns, solves = costs_st.fit_shape(eng)

    def cost(rows, c, k_now):
        history = fl.held("historical", c, k_now)
        return costs_st.band_st(
            rows, history + fl.held("current", c, k_now), history,
            columns=columns, solves=solves, lags=lags)

    least, bound = costs.least_over_cycles(ctx, "band", cost)
    ctx["notes"]["st_roofline_bound"] = bound
    ctx["notes"]["st_device_s"] = device_s
    return 100.0 * least / device_s
