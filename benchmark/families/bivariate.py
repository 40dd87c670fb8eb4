"""The bivariate family's side of `correct`: a job with two metrics and a
`historical` window of each is judged by the joint normal of the two
histories (`lib/reference.py`), the two metrics together in one result.

Numbers compared:
  bi_bound_gap  widest gap between the program's and the reference's
                marginal bounds (upper and lower of both metrics, as the
                program exports them for the job's app), in the
                reference's sigmas of that metric
  bi_count_out  jobs whose anomalous-point count is outside what an
                ellipse within the limit of the reference's could count:
                bounds within g sigmas put the centre within g sigmas on
                both axes, which moves a point's distance by at most
                g sqrt(2 / (1 - |rho|)), 2.45 g at this trace's
                correlation of 2/3, and its scale by g / radius, about
                g at the radius; the bracket is SLACK = 4 times g
"""
from lib import reference

JOINS = "&"  # one result for the job's metrics together, named "a&b"
SLACK = 4.0
NUMBERS = (("bi_bound_gap", "max", "bi_bound_gap_sigmas"),
           ("bi_count_out", "sum", 0))


def reference_rows(fleet, jobs: list, slots: tuple, k_now: int,
                   limits: dict, precision: str = "float64") -> dict:
    hists = tuple(fleet.role_rows(jobs, s, "historical", k_now)
                  for s in slots)
    curs = tuple(fleet.role_rows(jobs, s, "current", k_now) for s in slots)
    metrics = tuple(fleet.metrics_of(jobs[0])[s] for s in slots)
    ref = reference.bivariate_rows(
        hists, curs, metrics, SLACK * float(limits["bi_bound_gap_sigmas"]),
        precision)
    ref["metrics"] = metrics
    return ref


def answer(ref: dict, i: int) -> dict:
    """The reference's row in the shape the program records it."""
    return {"unhealthy": bool(ref["count"][i] >= ref["gate"]),
            "anomalous_points": int(ref["count"][i]),
            "exported": {m: [float(lo[i]), float(up[i])]
                         for m, (lo, up) in zip(ref["metrics"],
                                                ref["bounds"])}}


def judge(entry: dict, ref: dict, i: int, limits: dict):
    """({number: reading}, the reference says unhealthy whatever the
    rounding, the reference says healthy whatever the rounding)."""
    gap = 0.0
    for m, (lo, up), sigma in zip(ref["metrics"], ref["bounds"],
                                  ref["sigma"]):
        got = entry["exported"].get(m)
        if not got or None in got:
            gap = float("inf")  # no bound exported for the job's app
            continue
        gap = max(gap, abs(got[0] - lo[i]) / sigma[i],
                  abs(got[1] - up[i]) / sigma[i])
    inside = ref["count_min"][i] <= entry["anomalous_points"] \
        <= ref["count_max"][i]
    return ({"bi_bound_gap": float(gap), "bi_count_out": int(not inside)},
            bool(ref["count_min"][i] >= ref["gate"]),
            bool(ref["count_max"][i] < ref["gate"]))
