"""The Holt-Winters band launch's share of its roofline: the least time
the chip could take for the bytes and operations the launch needs
(`lib/costs_hw.py`, from each traced cycle's real rows and samples) over
the summed device time of every program of the band launch under this
forecaster (`costs_hw.PROGRAMS`): the region and history masks, period
detection, each partition's gather, fit mask, grid fit and winners' pass
(one program), sigma, bounds and flags, and the scatter back into claim
order."""
from lib import costs, costs_hw


def read(ctx):
    fl = ctx["fleet"]
    device_s = costs_hw.device_seconds(ctx["trace"], costs_hw.PROGRAMS)
    if not device_s or ctx["peaks"] is None:
        return None
    lags = 2 * sum(1 for p in fl.config["engine"].get(
        "hw_period_candidates", (60, 480, 720, 1440)) if p >= 4)

    def cost(rows, c, k_now):
        history = fl.held("historical", c, k_now)
        return costs_hw.band_hw(rows, history + fl.held("current", c, k_now),
                                history, lags=lags)

    least, bound = costs.least_over_cycles(ctx, "band", cost)
    ctx["notes"]["hw_roofline_bound"] = bound
    ctx["notes"]["hw_device_s"] = device_s
    return 100.0 * least / device_s
