"""The band launch's share of its roofline: the least time the chip
could take for the bytes and operations the band scorer needs
(`lib/costs.py`, from each traced cycle's real rows and samples) over
the summed device time of the band programs' runs in the trace: the
region and history masks, the predictions, sigma, and the bounds and
flags. `costs.band` already counts the region mark in its bytes a
sample."""
from lib import costs

PROGRAMS = ("jit_region_masks", "jit__moving_average_1d",
            "jit_residual_sigma", "jit_band_anomalies")


def read(ctx):
    tr, fl = ctx["trace"], ctx["fleet"]
    if not tr or ctx["peaks"] is None:
        return None
    device_s = sum(tr["programs"].get(p, (0.0, 0))[0] for p in PROGRAMS)
    if device_s <= 0:
        return None
    least, bound = costs.least_over_cycles(
        ctx, "band", lambda rows, c, k_now: costs.band(
            rows, fl.held("historical", c, k_now)
            + fl.held("current", c, k_now)))
    ctx["notes"]["band_roofline_bound"] = bound
    ctx["notes"]["band_device_s"] = device_s
    return 100.0 * least / device_s
