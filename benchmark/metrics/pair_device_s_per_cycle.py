"""Device seconds of the pair program, per cycle, from the trace: the
rank tests and the baseline's band of every pair row (`jit__score_rows`).
In `rollover7d_polled` that is a 20-to-31-point window against the
trailing 7 days at T=16384, the device's main work."""
PROGRAMS = ("jit__score_rows",)


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    device_s = sum(tr["programs"].get(p, (0.0, 0))[0] for p in PROGRAMS)
    if device_s <= 0:
        return None
    return device_s / len(ctx["cycles"])
