"""The band family's side of `correct` under upstream's Holt-Winters
forecaster: a job with a `historical` window is judged by the band of the
Holt-Winters fit of its history, at the period detected in it
(`lib/reference_hw.py`). The reference of `engine.algorithm`
`holt_winters*` and of no other forecaster.

Numbers compared (name, how the jobs' readings merge, limit: a key of the
configuration's `check` block, or the limit itself):
  hw_band_gap            widest gap between the program's upper or lower
                         bound and the nearest band the reference kept
                         for the job, in that band's sigmas
  hw_count_out           jobs whose anomalous-point count is outside what
                         a band within the limit of one the reference
                         kept could count
  hw_tie_rows            jobs for which the reference kept more than one
                         candidate of the grid (float64 errors within
                         `reference_hw.TIE_REL` of the winner's)
  hw_period_margin_rows  jobs for which it kept more than one period (a
                         deciding comparison of the detection within
                         `reference_hw.MARGIN_ABS` of its threshold)
The last two are the escape hatches' own counts: their limits keep the
hatch from becoming the rule.

The reference's cost is the check's largest: 60 candidates over 10,081
steps for every job. The first time a class is asked for, the whole
class is computed, over a process pool where it is large, and the blocks
`lib/check.py` asks for are answered from that; the arithmetic is the
same either way.
"""
from __future__ import annotations

import numpy as np

from foremast_tpu.ops import forecast as _program
from lib import reference, reference_hw
from lib.fleet import BenchError

REFERENCE_OF = {"algorithm": "holt_winters"}
NUMBERS = (("hw_band_gap", "max", "hw_band_gap_sigmas"),
           ("hw_count_out", "sum", 0),
           ("hw_tie_rows", "sum", "hw_tie_rows"),
           ("hw_period_margin_rows", "sum", "hw_period_margin_rows"))

# A program without the side-by-side fit can run this cell, at 281 s a
# cycle and 607 s of set-up (PERF.md, PR 35's chip runs): a run that never
# ends inside its limit. It is refused here, in set-up, with no result.
if not hasattr(_program, "hw_state_bytes"):
    raise BenchError(
        "this program's Holt-Winters fit walks the 60 candidates one after "
        "another with a rolled season buffer (no ops.forecast."
        "hw_state_bytes): a cycle of the cell would take minutes")


def _class_rows(fleet, job: int, slot: int, k_now: int, slack: float,
                precision: str) -> dict:
    """{job: its reference row} for every job of `job`'s class, computed
    once a (slot, clock, precision) and kept on the fleet."""
    cls = int(fleet.class_of[job])
    cache = fleet.__dict__.setdefault("_band_hw_rows", {})
    key = (cls, slot, k_now, precision)
    if key not in cache:
        cache[key] = reference_hw.fleet_rows(
            fleet, np.nonzero(fleet.class_of == cls)[0].tolist(), slot,
            k_now, slack, precision)
    return cache[key]


def reference_rows(fleet, jobs: list, slots: tuple, k_now: int,
                   limits: dict, precision: str = "float64") -> dict:
    (slot,) = slots
    rows = _class_rows(fleet, jobs[0], slot, k_now,
                       float(limits["hw_band_gap_sigmas"]), precision)
    checked = fleet.held("current", int(fleet.class_of[jobs[0]]), k_now)
    return {"rows": [rows[j] for j in jobs],
            "gate": max(reference.BAND_MIN_POINTS,
                        reference.BAND_VIOLATION_FRACTION * checked)}


def answer(ref: dict, i: int) -> dict:
    """The reference's row in the shape the program records it: the
    elected period's winning candidate."""
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
    return ({"hw_band_gap": float(gap), "hw_count_out": int(not inside),
             "hw_tie_rows": int(row["ties"] > 0),
             "hw_period_margin_rows": int(len(row["periods"]) > 1)},
            bool(count_min >= ref["gate"]), bool(count_max < ref["gate"]))
