"""The band family's side of `correct` under upstream's Prophet menu entry,
the seasonal-trend fit: a job with a `historical` window is judged by the
band of a piecewise-linear trend plus Fourier seasonality fitted to its
history by iterated ridge least squares, at the period detected in it
(`lib/reference_st.py`). The reference of `engine.algorithm`
`seasonal_trend*` and `prophet*` (one route in the program) and of no
other forecaster.

Numbers compared (name, how the jobs' readings merge, limit: a key of the
configuration's `check` block, or the limit itself):
  st_band_gap            widest gap between the program's upper or lower
                         bound and the nearest band the reference kept
                         for the job, in that band's sigmas
  st_count_out           jobs whose anomalous-point count is outside what
                         a band within the limit of one the reference
                         kept could count
  st_period_margin_rows  jobs for which the reference kept more than one
                         period (a deciding comparison of the detection
                         within `reference_hw.MARGIN_ABS` of its
                         threshold): the escape hatch's own count, whose
                         limit keeps the hatch from becoming the rule
"""
from foremast_tpu.ops import forecast as _program
from lib import reference, reference_st
from lib.fleet import BenchError

REFERENCE_OF = {"algorithm": ("seasonal_trend", "prophet")}
NUMBERS = (("st_band_gap", "max", "st_band_gap_sigmas"),
           ("st_count_out", "sum", 0),
           ("st_period_margin_rows", "sum", "st_period_margin_rows"))

# A program whose fit names no matmul precision can run this cell, and on
# the TPU forms its normal equations from bfloat16-rounded operands: its
# bands read 0.07 reference sigmas off (PERF.md, PR 35's and PR 38's chip
# runs), where the configuration states float32. It cannot run the
# configuration as stated, and is refused here, in set-up, with no result.
if not hasattr(_program, "st_columns"):
    raise BenchError(
        "this program's seasonal-trend fit states no matmul precision (no "
        "ops.forecast.st_columns): on the TPU it computes the normal "
        "equations in bfloat16, and the configuration states float32")


def reference_rows(fleet, jobs: list, slots: tuple, k_now: int,
                   limits: dict, precision: str = "float64") -> dict:
    (slot,) = slots
    checked = fleet.held("current", int(fleet.class_of[jobs[0]]), k_now)
    return {"rows": reference_st.fleet_rows(
                fleet, jobs, slot, k_now,
                float(limits["st_band_gap_sigmas"]), precision),
            "gate": max(reference.BAND_MIN_POINTS,
                        reference.BAND_VIOLATION_FRACTION * checked)}


def answer(ref: dict, i: int) -> dict:
    """The reference's row in the shape the program records it: the band
    of the elected period."""
    upper, lower, _, count, _, _ = ref["rows"][i]["bands"][0]
    return {"unhealthy": bool(count >= ref["gate"]),
            "anomalous_points": int(count),
            "band": [round(lower, 4), round(upper, 4)]}


def judge(entry: dict, ref: dict, i: int, limits: dict):
    """({number: reading}, the reference says unhealthy whatever the
    rounding, the reference says healthy whatever the rounding)."""
    row = ref["rows"][i]
    lower, upper = entry["band"]
    gap = min(max(abs(upper - u), abs(lower - lo)) / s
              for u, lo, s, _, _, _ in row["bands"])
    count_min = min(b[4] for b in row["bands"])
    count_max = max(b[5] for b in row["bands"])
    inside = count_min <= entry["anomalous_points"] <= count_max
    return ({"st_band_gap": float(gap), "st_count_out": int(not inside),
             "st_period_margin_rows": int(len(row["periods"]) > 1)},
            bool(count_min >= ref["gate"]), bool(count_max < ref["gate"]))
