"""The pair launch's share of its roofline: the least time for the bytes
and operations of the rank tests (`lib/costs.py`) over the summed device
time of the pair program's runs in the trace."""
from lib import costs

PROGRAMS = ("jit__score_rows",)


def read(ctx):
    tr, fl = ctx["trace"], ctx["fleet"]
    if not tr or ctx["peaks"] is None:
        return None
    device_s = sum(tr["programs"].get(p, (0.0, 0))[0] for p in PROGRAMS)
    if device_s <= 0:
        return None
    least, bound = costs.least_over_cycles(
        ctx, "pair", lambda rows, c, k_now: costs.pair(
            rows, fl.held("baseline", c, k_now),
            fl.held("current", c, k_now)))
    ctx["notes"]["pair_roofline_bound"] = bound
    ctx["notes"]["pair_device_s"] = device_s
    return 100.0 * least / device_s
