"""Bytes and operations a Holt-Winters band launch needs, from its shapes.

Counted as `lib/costs.py` counts: what the algorithm needs for the real
rows and samples of a launch, not what today's programs do (padding to a
rung and a bucket, the gathered partitions, the transposes, the season
state going to and from memory every step), so a later kernel cannot push
its share past 100% by doing less than this.
"""
from __future__ import annotations

GRID_OPS = 17   # a candidate a history sample, see `band_hw`
BAND_OPS = 12   # a sample, as `costs.band`
DETECT_OPS = 6  # a history sample, and as many again a lag
# the launch's programs by their `jit_<function>` names: the fit (the grid
# pass and the winners' pass are one program), and every program
FIT_PROGRAMS = ("jit_fit_holt_winters",)
PROGRAMS = ("jit_region_masks", "jit_detect_period", "jit_take_rows",
            "jit_hw_fit_mask", *FIT_PROGRAMS, "jit_residual_sigma",
            "jit_band_anomalies", "jit_scatter_rows")


def device_seconds(trace, programs) -> float | None:
    """Summed device time of `programs` in a reduced trace; None where the
    trace or any of them is missing (a program without this launch)."""
    if not trace or any(p not in trace["programs"] for p in programs):
        return None
    return sum(trace["programs"][p][0] for p in programs)


def band_hw(rows: int, points: int, history: int, candidates: int = 60,
            lags: int = 8) -> dict:
    """One Holt-Winters band launch over `rows` series of `points` samples,
    the first `history` of them the history, fitted over `candidates`
    parameter triples after a period detection over `lags` lags (a
    candidate period and its half lag each).

    bytes: as `costs.band`, 15 a sample (values, validity and region mark
    read; upper and lower bounds and flags written) and 12 a row, plus
    detection's read of the history's values and validity, 5 a history
    sample.
    operations, a history sample: for each candidate of the grid the
    prediction (2 adds), the level (4: a subtract, two multiplies, an
    add), the trend (4), the season slot (4), the residual, its square and
    its sum (3): 17; the same once more for the winner; detection's line
    (the two sums and the detrended value: 6) and, a lag, three products
    and three sums (6). A judged sample: the winner's prediction (2). Every
    sample: the band's 12 (`costs.band`: the residual scale, the two
    bounds, the two comparisons)."""
    judged = points - history
    per_history = GRID_OPS * (candidates + 1) + DETECT_OPS * (1 + lags)
    return {"bytes": rows * (15 * points + 12 + 5 * history),
            "ops": rows * (history * per_history + 2 * judged
                           + BAND_OPS * points)}
