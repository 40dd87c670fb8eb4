"""The bivariate launch's share of its roofline: the least time for the
bytes and operations of the joint fits (`lib/costs.py`, from each traced
cycle's real rows and samples) over the summed device time of the
bivariate program's runs in the trace."""
from lib import costs

PROGRAMS = ("jit_bivariate_normal_anomalies",)


def read(ctx):
    tr, fl = ctx["trace"], ctx["fleet"]
    if not tr or ctx["peaks"] is None:
        return None
    device_s = sum(tr["programs"].get(p, (0.0, 0))[0] for p in PROGRAMS)
    if device_s <= 0:
        return None

    def cost(rows, c, k_now):
        judged = fl.held("current", c, k_now)
        return costs.bivariate(
            rows, fl.held("historical", c, k_now) + judged, judged)

    least, bound = costs.least_over_cycles(ctx, "bivariate", cost)
    ctx["notes"]["bivariate_roofline_bound"] = bound
    ctx["notes"]["bivariate_device_s"] = device_s
    return 100.0 * least / device_s
