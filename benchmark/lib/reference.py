"""Plain reference of the scoring families, in numpy and float64.

Imports nothing of the program and takes nothing it has made: the inputs
are the fleet's own series (four decimals, as the store serves them), the
policies are those the configuration's source states. It follows the
published judgment rules:

  band (historical model, foremast-brain `moving_average_all`): a causal
    moving average over the last `ma_window` time slots of history gives
    the one-step prediction; sigma is the RMS one-step residual over the
    history; a current point is anomalous above prediction + k sigma (and,
    where the policy's bound asks, below prediction - k sigma, floored);
    the window is unhealthy when anomalous points reach
    max(band_min_points, band_violation_fraction * checked). Across the
    judged window, where no history slot is left inside the lookback, the
    prediction holds the moving average taken just after the last
    history sample.
  pair (canary against baseline, `mann_whitney_all`): two-sided
    Mann-Whitney U, normal approximation with tie and continuity
    corrections, judged at `pairwise_threshold`, beside a moving-average
    band of the baseline that condemns when over 30% of current points
    leave it.
  bivariate (two metrics a job, foremast design.md:53-88, "Bivariate
    Normal Distribution"): the joint mean and covariance of the two
    histories over the slots both hold; a judged point is anomalous when
    its squared Mahalanobis distance passes the square of the stricter of
    the two metrics' thresholds, and only where it left the mean in a
    direction one of the two policies' bounds watches; unhealthy by the
    band's gate. The published rule is the menu entry alone, so three
    things are this project's and are noted, not published: a ridge of
    RIDGE times the larger variance (at least 1) on both variances, which
    keeps the inverse defined for a constant or perfectly correlated
    history; the covariance over n, not n - 1; and marginal bounds
    (mean -+ radius x sigma, the lower one floored) drawn for both metrics
    at the pair's one radius, not at each metric's own.

`precision` is the control: "bfloat16" rounds the served samples to
bfloat16 before anything is computed, the step a later change would be
tempted by (half the bytes to the device). The arithmetic stays float64,
so the gap it shows is the least such a change could cause.
"""
from __future__ import annotations

import math

import numpy as np

# policies of the configuration's source (foremast-brain.yaml:34-73):
# metric -> (band half-width in sigmas, bound bitmask, lower floor)
POLICIES = {"error5xx": (2.0, 1, 0.0), "error4xx": (3.0, 1, 0.0),
            "latency": (10.0, 3, 0.0)}
MA_WINDOW = 30
BAND_MIN_POINTS = 2
BAND_VIOLATION_FRACTION = 0.1
PAIR_ALPHA = 0.01
PAIR_BAND_FRACTION = 0.3
MIN_MANN_WHITNEY_POINTS = 20
RIDGE = 1e-6


def _quantize(x: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float64":
        return x
    if precision == "bfloat16":
        import ml_dtypes

        return x.astype(ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


def _band(hist: np.ndarray, cur: np.ndarray, policy: tuple,
          window: int) -> dict:
    """The band of `hist` (B, H, every sample present) across the judged
    window `cur` (B, C): one-step predictions, sigma and the bounds."""
    k, bound, floor = policy
    n_h, n_c = hist.shape[1], cur.shape[1]
    if n_h < 2 or n_c < 1:
        raise ValueError("a band needs history and a judged window")
    c0 = np.zeros((hist.shape[0], n_h + 1))
    np.cumsum(hist, axis=1, out=c0[:, 1:])
    # history slot t is predicted by the mean of slots [t - window, t)
    preds = np.empty_like(hist)
    preds[:, 0] = hist[:, 0]  # nothing before it: it stands for itself
    w = min(window, n_h)
    preds[:, 1:w] = c0[:, 1:w] / np.arange(1, w)
    preds[:, w:] = (c0[:, w:n_h] - c0[:, :n_h - w]) / w
    r = hist - preds
    sigma = np.sqrt(np.sum(r * r, axis=1) / n_h)
    # judged slot n_h + i sees history slots [n_h + i - window, n_h); past
    # the lookback it holds the average taken just after the last sample
    lo = np.clip(n_h + np.arange(n_c) - window, 0, n_h)
    cnt = n_h - lo
    pred_c = (c0[:, [n_h]] - c0[:, lo]) / np.maximum(cnt, 1)
    pred_c[:, cnt == 0] = pred_c[:, [0]]
    reach = np.where(cnt == 0, cnt[0], cnt)  # samples behind each prediction
    upper = pred_c + k * sigma[:, None]
    lower = np.maximum(pred_c - k * sigma[:, None], floor)
    return {"sigma": sigma, "upper": upper, "lower": lower, "cur": cur,
            "slack_scale": w / reach,
            "check_upper": bool(bound & 1 or bound == 0),
            "check_lower": bool(bound & 2 or bound == 0)}


def _count(b: dict, slack: float) -> np.ndarray:
    """Anomalous points per row with the band moved out (+) or in (-):
    the two counts that bracket any count whose moving sums are within
    `slack` sigmas a sample of the reference's. A judged slot with only
    n of the window's samples behind it moves window / n times as far:
    a running sum in float32 is off by the same amount whatever it is
    divided by (PERF.md, "How `correct` is decided")."""
    d = slack * b["sigma"][:, None] * b["slack_scale"][None, :]
    out = np.zeros(b["cur"].shape, bool)
    if b["check_upper"]:
        out |= b["cur"] > b["upper"] + d
    if b["check_lower"]:
        out |= b["cur"] < b["lower"] - d
    return out.sum(axis=1)


def band_rows(hist: np.ndarray, cur: np.ndarray, metric: str,
              slack: float, precision: str = "float64") -> dict:
    """Reference results for band jobs: `hist` (B, H+1) and `cur` (B, C)
    served samples. Returns per-row upper and lower (means over the judged
    window, as the engine reports them), sigma, and the count bracket."""
    b = _band(_quantize(hist, precision), _quantize(cur, precision),
              POLICIES[metric], MA_WINDOW)
    checked = cur.shape[1]
    gate = max(BAND_MIN_POINTS, BAND_VIOLATION_FRACTION * checked)
    return {
        "upper": b["upper"].mean(axis=1), "lower": b["lower"].mean(axis=1),
        "sigma": b["sigma"], "count": _count(b, 0.0),
        "count_min": _count(b, slack), "count_max": _count(b, -slack),
        "gate": gate,
    }


def _mann_whitney_p(x: np.ndarray, y: np.ndarray) -> float:
    n1, n2 = x.shape[0], y.shape[0]
    comb = np.concatenate([x, y])
    order = np.argsort(comb, kind="stable")
    sv = comb[order]
    # tie-averaged ranks
    _, inv, cnt = np.unique(sv, return_inverse=True, return_counts=True)
    ends = np.cumsum(cnt)
    avg = (ends - (cnt - 1) / 2.0)[inv]
    ranks = np.empty(n1 + n2)
    ranks[order] = avg
    r1 = ranks[:n1].sum()
    u1 = r1 - n1 * (n1 + 1) / 2.0
    u = max(u1, n1 * n2 - u1)
    n = n1 + n2
    tie = float(np.sum(cnt.astype(np.float64) ** 3 - cnt))
    s2 = n1 * n2 / 12.0 * ((n + 1.0) - tie / (n * (n - 1.0)))
    if s2 <= 0.0:
        return 1.0
    z = (u - n1 * n2 / 2.0 - 0.5) / math.sqrt(s2)
    return min(max(math.erfc(z / math.sqrt(2.0)), 0.0), 1.0)


def pair_rows(base: np.ndarray, cur: np.ndarray, metric: str,
              slack: float, precision: str = "float64") -> dict:
    """Reference results for pair jobs: `base` (B, Nb) and `cur` (B, Nc)."""
    base = _quantize(base, precision)
    cur = _quantize(cur, precision)
    n_min = min(base.shape[1], cur.shape[1])
    if n_min >= MIN_MANN_WHITNEY_POINTS:
        p = np.asarray([_mann_whitney_p(b, c) for b, c in zip(base, cur)])
    else:
        p = np.ones(base.shape[0])
    b = _band(base, cur, POLICIES[metric], MA_WINDOW)
    frac = PAIR_BAND_FRACTION * cur.shape[1]
    return {"min_p": p, "band_min": _count(b, slack) > frac,
            "band_max": _count(b, -slack) > frac}


def _watched(dev: np.ndarray, bound: int) -> np.ndarray:
    """Where a deviation from the mean lies on a side the bound watches
    (bit 1 above, bit 2 below, 0 both)."""
    bound = bound or 3
    return ((dev > 0) & bool(bound & 1)) | ((dev < 0) & bool(bound & 2))


def bivariate_rows(hists: tuple, curs: tuple, metrics: tuple, slack: float,
                   precision: str = "float64") -> dict:
    """Reference results for two-metric jobs: `hists` two (B, H) and
    `curs` two (B, C) blocks of served samples, every sample present.
    Returns per row the four marginal bounds and sigmas, the count of
    anomalous judged points, and the counts with the ellipse's radius
    moved out and in by `slack` (in units of the distance itself): the
    bracket of any count whose ellipse is within that of the
    reference's."""
    (h1, h2), (c1, c2) = ([_quantize(x, precision) for x in pair]
                          for pair in (hists, curs))
    (k1, b1, f1), (k2, b2, f2) = (POLICIES[m] for m in metrics)
    radius = min(k1, k2)
    n_h = h1.shape[1]
    if n_h < 2 or c1.shape[1] < 1:
        raise ValueError("a joint fit needs history and a judged window")
    mu1, mu2 = h1.mean(axis=1), h2.mean(axis=1)
    d1, d2 = h1 - mu1[:, None], h2 - mu2[:, None]
    var1, var2 = (d1 * d1).sum(axis=1) / n_h, (d2 * d2).sum(axis=1) / n_h
    cov = (d1 * d2).sum(axis=1) / n_h
    ridge = RIDGE * np.maximum(np.maximum(var1, var2), 1.0)
    var1, var2 = var1 + ridge, var2 + ridge
    det = var1 * var2 - cov * cov
    a, b = c1 - mu1[:, None], c2 - mu2[:, None]
    dist = np.sqrt((var2[:, None] * a * a - 2.0 * cov[:, None] * a * b
                    + var1[:, None] * b * b) / det[:, None])
    watched = _watched(a, b1) | _watched(b, b2)
    s1, s2 = np.sqrt(var1), np.sqrt(var2)

    def count(r):
        return ((dist > r) & watched).sum(axis=1)

    checked = c1.shape[1]
    return {
        "bounds": ((np.maximum(mu1 - radius * s1, f1), mu1 + radius * s1),
                   (np.maximum(mu2 - radius * s2, f2), mu2 + radius * s2)),
        "sigma": (s1, s2), "count": count(radius),
        "count_min": count(radius + slack), "count_max": count(radius - slack),
        "gate": max(BAND_MIN_POINTS, BAND_VIOLATION_FRACTION * checked),
    }
