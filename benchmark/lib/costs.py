"""Bytes and operations the scoring families need, from their shapes.

They count what the algorithm needs for the real rows and samples of a
launch, not what today's programs do (padding to a rung and a bucket,
predictions that cross the host between programs, row blocks), so a
later kernel change cannot make them stale and cannot push a roofline
share past 100% by doing less than this.
"""
from __future__ import annotations

import math


def band(rows: int, points: int) -> dict:
    """One band launch over `rows` series of `points` samples (history
    and judged window together).

    bytes: read the values (float32), their validity and the judged-region
    mark (one byte each), write the upper and lower bounds (float32) and
    the anomaly flags (one byte) that the collect reads: 15 per sample,
    and 12 per row for the count, the first index and the points checked.
    operations, per sample: the moving sum and count (2 adds, 2
    subtracts), the mean (1 divide), the residual and its square and sum
    (3), the two bounds (2) and the two comparisons (2): 12."""
    return {"bytes": rows * (15 * points + 12), "ops": 12 * rows * points}


def pair(rows: int, base_points: int, cur_points: int) -> dict:
    """One pair launch over `rows` baseline and current windows.

    bytes: read both windows (float32) and their validity (one byte),
    write five scalars per row.
    operations, per row: the comparisons of one sort of the combined
    sample, n log2 n, and 12 per sample for the baseline band."""
    n = base_points + cur_points
    return {"bytes": rows * (5 * n + 20),
            "ops": rows * (n * math.log2(n) + 12 * n)}


def bivariate(rows: int, points: int, judged: int) -> dict:
    """One bivariate launch over `rows` jobs of two series of `points`
    samples each, the last `judged` of them the judged window.

    bytes: read both values (float32), their validity and the judged
    mark (one byte each), write the anomaly flag (one byte): 12 per
    sample. Per row, read the radius, the two floors and the two bound
    masks (20) and write the count, the first index, the points checked
    and the four marginal bounds, which are constant along a row (28).
    The distances and a bound for every sample, which today's program
    also writes, are not needed.
    operations, per history sample: the three sums of the means and the
    count (3), the two deviations (2), their three products and sums (6):
    11. Per judged sample: the two deviations (2), the three products
    (3), their weighting by the inverse covariance and sum (5), the
    comparison with the radius (1), the two signs and their gate (3):
    14."""
    return {"bytes": rows * (12 * points + 48),
            "ops": rows * (11 * (points - judged) + 14 * judged)}


def least_over_cycles(ctx: dict, family: str,
                      cost) -> tuple[float, str | None]:
    """The least seconds of a family's launches over a run's traced
    cycles: `cost(rows, c, k_now)` for the rows class `c` gave the family
    in a cycle whose clock was at slot `k_now`, summed, and the peak that
    bound the last of them (None where no class gave it rows)."""
    least, bound = 0.0, None
    for cycle in ctx["cycles"]:
        for c, rows in cycle["class_rows"].items():
            if rows.get(family):
                secs, bound = least_seconds(
                    cost(rows[family], c, cycle["now_slot"]), ctx["peaks"])
                least += secs
    return least, bound


def least_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    by_bytes = cost["bytes"] / peaks["bytes_per_s"]
    by_ops = cost["ops"] / peaks["flops_per_s"]
    return (by_bytes, "bandwidth") if by_bytes >= by_ops \
        else (by_ops, "compute")
