"""The pair family's side of `correct`: a job with a `baseline` window is
judged by the rank test of its current window against it, beside the
baseline's own band (`lib/reference.py`). The reference of
`engine.pairwise_algorithm` `mann_whitney*` and of no other rank test: a
configuration that sets another names its own file under `references`
(`lib/check.py`).

Number compared:
  pair_p_gap  widest gap between the program's and the reference's
              smallest p-value
"""
from lib import reference

REFERENCE_OF = {"pairwise_algorithm": "mann_whitney"}
NUMBERS = (("pair_p_gap", "max", "pair_p_gap"),)


def reference_rows(fleet, jobs: list, slots: tuple, k_now: int,
                   limits: dict, precision: str = "float64") -> dict:
    (slot,) = slots
    base = fleet.role_rows(jobs, slot, "baseline", k_now)
    cur = fleet.role_rows(jobs, slot, "current", k_now)
    return reference.pair_rows(base, cur, fleet.metrics_of(jobs[0])[slot],
                               float(limits["band_gap_sigmas"]), precision)


def answer(ref: dict, i: int) -> dict:
    return {"unhealthy": bool(ref["min_p"][i] < reference.PAIR_ALPHA
                              or ref["band_min"][i]),
            "min_p": round(float(ref["min_p"][i]), 8)}


def judge(entry: dict, ref: dict, i: int, limits: dict):
    p, p_lim = ref["min_p"][i], float(limits["pair_p_gap"])
    return ({"pair_p_gap": float(abs(entry["min_p"] - p))},
            bool(p < reference.PAIR_ALPHA - p_lim or ref["band_min"][i]),
            bool(p > reference.PAIR_ALPHA + p_lim
                 and not ref["band_max"][i]))
