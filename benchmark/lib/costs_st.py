"""Bytes and operations a seasonal-trend band launch needs, from its
shapes.

Counted as `lib/costs.py` and `lib/costs_hw.py` count: what the algorithm
needs for the real rows and samples of a launch, not what today's
programs do (padding to a rung and a bucket, the gathered partitions, a
full Gram where a symmetric one has D(D+1)/2 entries, float32 products as
six bfloat16 passes), so a later kernel cannot push its share past 100% by
doing less than this. A multiply-add is two operations.
"""
from __future__ import annotations

from lib.costs_hw import BAND_OPS, DETECT_OPS

SOLVES = 3  # one ridge solve and two reweighted, where the fit has hinges
# the launch's programs by their `jit_<function>` names: the fit, and
# every program
FIT_PROGRAMS = ("jit_fit_seasonal_trend",)
PROGRAMS = ("jit_region_masks", "jit_detect_period", "jit_take_rows",
            *FIT_PROGRAMS, "jit_residual_sigma", "jit_band_anomalies",
            "jit_scatter_rows")


def fit_shape(engine: dict) -> tuple[int, int]:
    """(columns, solves) of the fit a configuration's engine block states:
    intercept, slope, a hinge a changepoint, a Fourier pair an order; one
    solve where it has no hinge."""
    changepoints = int(engine.get("st_changepoints", 12))
    return (2 + changepoints + 2 * int(engine.get("st_order", 3)),
            SOLVES if changepoints else 1)


def band_st(rows: int, points: int, history: int, columns: int = 20,
            solves: int = SOLVES, lags: int = 8) -> dict:
    """One seasonal-trend band launch over `rows` series of `points`
    samples, the first `history` of them the history, fitted by `solves`
    ridge solves over `columns` columns after a period detection over
    `lags` lags (a candidate period and its half lag each).

    bytes: as `costs_hw.band_hw`: 15 a sample and 12 a row (`costs.band`),
    plus detection's read of the history, 5 a history sample.
    operations, a history sample: the symmetric Gram's D(D+1)/2
    multiply-adds and the right-hand side's D (2 each), detection's line
    (6) and 6 a lag. A sample: the prediction's D multiply-adds and the
    band's 12. A row and solve: the LU of a (D, D) system, 2/3 D^3, and
    two triangular solves, 2 D^2."""
    d = columns
    per_history = d * (d + 1) + 2 * d + DETECT_OPS * (1 + lags)
    per_solve = 2 * d ** 3 // 3 + 2 * d * d
    return {"bytes": rows * (15 * points + 12 + 5 * history),
            "ops": rows * (history * per_history
                           + points * (2 * d + BAND_OPS)
                           + solves * per_solve)}
