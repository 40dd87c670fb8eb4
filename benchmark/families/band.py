"""The band family's side of `correct`: a job with a `historical` window
is judged by the moving-average band of its history (`lib/reference.py`).
The reference of `engine.algorithm` `moving_average*` and of no other
forecaster: a configuration that sets another names its own file under
`references` (`lib/check.py`).

Numbers compared (name, how the jobs' readings merge, limit: a key of the
configuration's `check` block, or the limit itself):
  band_gap        widest gap between the program's and the reference's
                  upper or lower bound, in the reference's sigmas
  band_count_out  jobs whose anomalous-point count is outside what a band
                  within the limit of the reference's could count
"""
from lib import reference

REFERENCE_OF = {"algorithm": "moving_average"}
NUMBERS = (("band_gap", "max", "band_gap_sigmas"),
           ("band_count_out", "sum", 0))


def reference_rows(fleet, jobs: list, slots: tuple, k_now: int,
                   limits: dict, precision: str = "float64") -> dict:
    (slot,) = slots
    hist = fleet.role_rows(jobs, slot, "historical", k_now)
    cur = fleet.role_rows(jobs, slot, "current", k_now)
    return reference.band_rows(hist, cur, fleet.metrics_of(jobs[0])[slot],
                               float(limits["band_gap_sigmas"]), precision)


def answer(ref: dict, i: int) -> dict:
    """The reference's row in the shape the program records it."""
    return {"unhealthy": bool(ref["count"][i] >= ref["gate"]),
            "anomalous_points": int(ref["count"][i]),
            "band": [round(float(ref["lower"][i]), 4),
                     round(float(ref["upper"][i]), 4)]}


def judge(entry: dict, ref: dict, i: int, limits: dict):
    """({number: reading}, the reference says unhealthy whatever the
    rounding, the reference says healthy whatever the rounding)."""
    lower, upper = entry["band"]
    gap = max(abs(upper - ref["upper"][i]),
              abs(lower - ref["lower"][i])) / ref["sigma"][i]
    inside = ref["count_min"][i] <= entry["anomalous_points"] \
        <= ref["count_max"][i]
    return ({"band_gap": float(gap), "band_count_out": int(not inside)},
            bool(ref["count_min"][i] >= ref["gate"]),
            bool(ref["count_max"][i] < ref["gate"]))
