"""Plain reference of the seasonal-trend band (upstream's Prophet menu
entry), in numpy and float64.

Imports nothing of the program and takes nothing it has made: the inputs
are the fleet's own served series, the settings are upstream's menu entry
(foremast docs/guides/design.md:53-88, Prophet) with this repository's
documented defaults for what upstream's brain source would state and the
reference tree does not hold (Fourier order 3, 12 changepoints on 80% of
the window, ridge 1e-4, changepoint shrink 3e-3, three solves, and the
period candidates and margins it shares with Holt-Winters: the
configuration lists them under `assumed`). A loop over rows, one
`np.linalg.solve` a row and round.

The published rules:

  period: the detection `lib/reference_hw.py` holds (`detect_periods`),
    the same candidates, margins and fallback.
  fit: per row, packed as history then judged window into a bucket of T
    slots (the least of `BUCKETS` that holds them), period p, Fourier
    order K, C changepoints, D = 2 + C + 2K:
    columns over slots t = 0..T-1, with tn = t / (T - 1): 1; tn; hinges
    max(tn - s_j, 0), s_j = 0.8 j / (C + 1), j = 1..C; sin(2 pi k t / p),
    cos(2 pi k t / p), k = 1..K: X, (T, D).
    sel: the history slots that hold a sample.
    G = X^T diag(sel) X, r = X^T (sel * x);
    beta = solve(G + diag(ridge + cp_shrink * is_cp), r), is_cp 1 on the
    hinge columns; then `l1_iters - 1` rounds of
    pen = ridge + cp_shrink * is_cp / (|beta| + 1e-3),
    beta = solve(G + diag(pen), r).
    Predictions X beta over every slot: the judged window is
    extrapolated, never fitted.
    The hinge grid and tn are laid on the bucket, not on the row's own
    history, and the L1 prior on the slope deltas is the reweighted ridge
    above: this repository's two departures from Prophet (PARITY.md).
  band: sigma is the RMS residual over sel; a judged point is anomalous
    above prediction + k sigma (and, where the policy's bound asks, below
    prediction - k sigma, floored); the window is unhealthy at
    max(band_min_points, band_violation_fraction x checked).

A float32 program cannot tell the two sides of a detection comparison
that lie within `reference_hw.MARGIN_ABS`: as `reference_hw` does, the
reference keeps the band of both periods of such a row and the program is
held to the nearest. The fit has no grid and so no tie rule.
"""
from __future__ import annotations

import numpy as np

from lib import reference
from lib.reference_hw import (BUCKETS, detect_periods, fallback_period,
                              quantize, settings)

# this repository's defaults (docs/configuration.md:107-108, ST_*; the
# ridge, the shrink and the rounds are the fit's own and have no setting)
DEFAULTS = {"st_order": 3, "st_changepoints": 12}
RIDGE = 1e-4
CP_SHRINK = 3e-3
L1_ITERS = 3
CHANGEPOINT_RANGE = 0.8
REWEIGHT_EPS = 1e-3


def st_settings(engine: dict) -> dict:
    """The seasonal-trend settings of a configuration's engine block."""
    return {k: int(engine.get(k, v)) for k, v in DEFAULTS.items()}


def bucket(points: int) -> int:
    """The bucket a window of `points` samples is packed to."""
    return next(b for b in BUCKETS if points <= b)


def design(T: int, period: int, order: int, changepoints: int) -> np.ndarray:
    """X (T, D): intercept, normalized time, hinges, Fourier pairs."""
    t = np.arange(T, dtype=np.float64)
    tn = t / max(T - 1, 1)
    cols = [np.ones(T), tn]
    for j in range(1, changepoints + 1):
        cols.append(np.maximum(
            tn - CHANGEPOINT_RANGE * j / (changepoints + 1), 0.0))
    for k in range(1, order + 1):
        w = 2.0 * np.pi * k * t / period
        cols += [np.sin(w), np.cos(w)]
    return np.stack(cols, axis=1)


def fit_rows(X: np.ndarray, x: np.ndarray, sel: np.ndarray,
             changepoints: int) -> np.ndarray:
    """beta (B, D) of rows `x` (B, n) over the slots `sel` (B, n) bool, the
    first n rows of X their columns. Rows with the same `sel` have the
    same Gram, which is then formed once."""
    n = x.shape[1]
    Xn = X[:n]
    is_cp = np.zeros(X.shape[1])
    is_cp[2:2 + changepoints] = 1.0
    grams: dict = {}
    beta = np.empty((x.shape[0], X.shape[1]))
    for i in range(x.shape[0]):
        key = sel[i].tobytes()
        if key not in grams:
            Xs = Xn[sel[i]]
            grams[key] = (Xs, Xs.T @ Xs)
        Xs, G = grams[key]
        r = Xs.T @ x[i][sel[i]]
        b = np.linalg.solve(G + np.diag(RIDGE + CP_SHRINK * is_cp), r)
        for _ in range(L1_ITERS - 1):
            pen = RIDGE + CP_SHRINK * is_cp / (np.abs(b) + REWEIGHT_EPS)
            b = np.linalg.solve(G + np.diag(pen), r)
        beta[i] = b
    return beta


def fit_block(hist: np.ndarray, n_judged: int, period: int, cfg: dict,
              present: np.ndarray | None = None) -> dict:
    """The fit of rows `hist` (R, H) at one period: "beta" (R, D), "sigma"
    (R,) the RMS residual over the history's samples, "preds" (R,
    n_judged) the predictions of the slots after the history. `present`
    (R, H) bool says which history slots hold a sample; every one where it
    is None."""
    R, H = hist.shape
    sel = np.ones((R, H), bool) if present is None else present
    X = design(bucket(H + n_judged), period, cfg["st_order"],
               cfg["st_changepoints"])
    beta = fit_rows(X, hist, sel, cfg["st_changepoints"])
    resid = np.where(sel, hist - beta @ X[:H].T, 0.0)
    n = sel.sum(axis=1)
    return {"beta": beta, "preds": beta @ X[H:H + n_judged].T,
            "sigma": np.sqrt((resid * resid).sum(axis=1) / np.maximum(n, 1))}


def bands(fit: dict, cur: np.ndarray, policy: tuple, slack: float,
          present: np.ndarray | None = None) -> list:
    """Per row of `fit`, its band across the judged window `cur` (R, C):
    (upper mean, lower mean, sigma, count, count with the band moved out
    by `slack` sigmas, moved in). `present` (R, C) bool says which judged
    slots hold a sample (only those can be anomalous; the means are over
    every slot)."""
    k, bound, floor = policy
    up_w, lo_w = bool(bound & 1 or bound == 0), bool(bound & 2 or bound == 0)
    seen = np.ones(cur.shape, bool) if present is None else present
    out = []
    for i in range(cur.shape[0]):
        sigma = float(fit["sigma"][i])
        upper = fit["preds"][i] + k * sigma
        lower = np.maximum(fit["preds"][i] - k * sigma, floor)

        def count(d):
            hit = np.zeros(cur.shape[1], bool)
            if up_w:
                hit |= cur[i] > upper + d
            if lo_w:
                hit |= cur[i] < lower - d
            return int((hit & seen[i]).sum())

        out.append((float(upper.mean()), float(lower.mean()), sigma,
                    count(0.0), count(slack * sigma), count(-slack * sigma)))
    return out


def band_rows(hist: np.ndarray, cur: np.ndarray, policy: tuple, hw_cfg: dict,
              st_cfg: dict, slack: float) -> list:
    """Reference bands of rows `hist` (B, H) and `cur` (B, C), every sample
    present: per row {"periods": the periods kept (more than one where a
    deciding comparison of the detection lies within `MARGIN_ABS` of its
    threshold), "bands": one band (`bands`) a period kept, the elected
    period's first}."""
    H, C = hist.shape[1], cur.shape[1]
    if H < 2 or C < 1:
        raise ValueError("a band needs history and a judged window")
    periods = detect_periods(hist, hw_cfg, fallback_period(hw_cfg, H + C))
    rows = [{"periods": ps, "bands": []} for ps in periods]
    for rank in range(max(len(ps) for ps in periods)):
        for p in sorted({ps[rank] for ps in periods if len(ps) > rank}):
            idx = [i for i, ps in enumerate(periods)
                   if len(ps) > rank and ps[rank] == p]
            fit = fit_block(hist[idx], C, p, st_cfg)
            for i, band in zip(idx, bands(fit, cur[idx], policy, slack)):
                rows[i]["bands"].append(band)
    return rows


def fleet_rows(fleet, jobs: list, slot: int, k_now: int, slack: float,
               precision: str) -> list:
    """The reference rows (`band_rows`) of `jobs` from the fleet's own
    served series: the `historical` window's slots, and the `current`
    window's up to `k_now` (`Fleet.window_slots`). "bfloat16" rounds the
    samples first (the control)."""
    hist = fleet.role_rows(jobs, slot, "historical", k_now)
    cur = fleet.role_rows(jobs, slot, "current", k_now)
    engine = fleet.config["engine"]
    return band_rows(quantize(hist, precision), quantize(cur, precision),
                     reference.POLICIES[fleet.metrics_of(jobs[0])[slot]],
                     settings(engine), st_settings(engine), slack)
