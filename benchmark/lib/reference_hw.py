"""Plain reference of the Holt-Winters band, in numpy and float64.

Imports nothing of the program and takes nothing it has made: the inputs
are the fleet's own served series (every sample present), the settings are
upstream's menu entry (foremast docs/guides/design.md:53-88, Holt-Winters)
with this repository's documented defaults for what upstream's brain
source would state and the reference tree does not hold (the 60-point
grid, the period candidates, the detection margins: the configuration
lists them under `assumed`). A loop over time, no scan, no batching trick
beyond running the candidates of a block of rows side by side.

The published rules:

  period (`detect_period`): remove the least-squares line from the
    history; the autocorrelation at each candidate lag p over the pairs
    (t, t + p), a candidate counting only with at least p pairs of
    support; a candidate passes the half-lag contrast when its
    autocorrelation plus `contrast_margin` reaches the one at lag p // 2
    (candidates under 4 pass); the best is the largest passing score; the
    first candidate, in the listed order, that passes and scores at least
    max(best - `alias_margin`, `min_acf`) is the period; with none, the
    fallback (the configured period, at most half the window's bucket).
  fit: per row, period p, candidate (alpha, beta, gamma):
    l0 = mean of x[0:p], s0 = x[0:p] - l0, b0 = 0; at step t,
    s_t = season[t mod p], prediction l + b + s_t, then
    l' = alpha (x - s_t) + (1 - alpha)(l + b),
    b' = beta (l' - l) + (1 - beta) b,
    season[t mod p] = gamma (x - l') + (1 - gamma) s_t.
    The error of a candidate is the mean squared one-step residual over
    history slots t >= 2p; the least wins, the first on an exact tie.
    Across the judged window the fit sees no sample: l' = l + b, b' = b
    and the season stands, so judged slot i is predicted
    l_H + (i + 1) b_H + season_H[(H + i) mod p].
  band: sigma is the RMS one-step residual over the whole history; a
    judged point is anomalous above prediction + k sigma (and, where the
    policy's bound asks, below prediction - k sigma, floored); the window
    is unhealthy at max(band_min_points, band_violation_fraction x
    checked).

Two things the comparison is honest about by construction, not by a loose
limit. A float32 program cannot tell two candidates whose float64 errors
lie within `TIE_REL` of each other, nor the two sides of a detection
comparison that lie within `MARGIN_ABS`: the reference keeps the band of
every candidate that close to the winner, and of both periods of a row on
a margin, and the program is held to the nearest. Each row reports how
many bands it kept, so the counts of such rows are numbers of the run
with limits of their own (`families/band_hw.py`).
"""
from __future__ import annotations

import itertools
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from lib import reference

ALPHAS = (0.1, 0.3, 0.5, 0.7, 0.9)
BETAS = (0.0, 0.1, 0.3)
GAMMAS = (0.05, 0.1, 0.3, 0.5)
GRID = np.asarray(list(itertools.product(ALPHAS, BETAS, GAMMAS)))  # (60, 3)
# this repository's defaults (docs/configuration.md:97-108, HW_*)
DEFAULTS = {"hw_period": 1440, "hw_period_candidates": (60, 480, 720, 1440),
            "hw_min_seasonal_acf": 0.2, "hw_alias_margin": 0.05,
            "hw_contrast_margin": 0.01}
BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384)
# two float64 errors this close (relative) are one to a float32 fit whose
# running sum of some 7,000 squared residuals rounds at 3e-6 (PERF.md)
TIE_REL = 3e-5
# two sides of a detection comparison this close are one to float32 sums
# over 10,000 samples
MARGIN_ABS = 2e-5
_ROWS = 256  # rows of one time loop: a (rows, 60) state stays in cache
_CHUNK = 512  # jobs a task of the pool
_POOL_FROM = 2048  # jobs from which a pool is worth its start
_WORKERS = 12
_worker_fleet = None


def settings(engine: dict) -> dict:
    """The Holt-Winters settings of a configuration's engine block."""
    return {k: engine.get(k, v) for k, v in DEFAULTS.items()}


def fallback_period(cfg: dict, points: int) -> int:
    """The configured period, at most half the bucket the window packs to."""
    bucket = next(b for b in BUCKETS if points <= b)
    return min(int(cfg["hw_period"]), max(bucket // 2, 2))


# ------------------------------------------------------------------ period
def _acf(d: np.ndarray, p: int) -> np.ndarray:
    """(B,) autocorrelation of detrended rows at lag p; -inf without p
    pairs of support."""
    n = d.shape[1]
    if not 2 <= p < n or n - p < p:
        return np.full(d.shape[0], -np.inf)
    lead, lag = d[:, p:], d[:, :-p]
    den = np.sqrt((lead * lead).sum(axis=1) * (lag * lag).sum(axis=1))
    r = (lead * lag).sum(axis=1) / np.where(den == 0, 1.0, den)
    return np.where(den > 0, r, -np.inf)


def _elect(scores, halves, cands, cfg, flips=()):
    """(the period of one row, or None; the floor its scores were held to)
    from its scores at the candidate lags and half lags; `flips` names
    comparisons to decide the other way."""
    ok = [p < 4 or ((s + cfg["hw_contrast_margin"] >= h) != (("c", i) in flips))
          for i, (p, s, h) in enumerate(zip(cands, scores, halves))]
    best = max((s for s, o in zip(scores, ok) if o), default=-np.inf)
    floor = max(best - cfg["hw_alias_margin"], cfg["hw_min_seasonal_acf"])
    for i, (p, s, o) in enumerate(zip(cands, scores, ok)):
        if o and ((s >= floor) != (("e", i) in flips)) and s > -np.inf:
            return int(p), floor
    return None, floor


def detect_periods(hist: np.ndarray, cfg: dict, fallback: int):
    """[(period, ...)] of each row of `hist` (B, H): the elected period
    first, then every other period that deciding a comparison within
    `MARGIN_ABS` of its threshold the other way would elect."""
    n = hist.shape[1]
    t = np.arange(n, dtype=np.float64)
    tc = t - t.mean()
    slope = (hist * tc).sum(axis=1) / (tc * tc).sum()
    d = hist - hist.mean(axis=1, keepdims=True) - slope[:, None] * tc
    cands = [int(p) for p in cfg["hw_period_candidates"] if int(p) >= 2]
    S = np.stack([_acf(d, p) for p in cands], axis=1)
    H = np.stack([_acf(d, p // 2) if p >= 4 else np.full(len(d), -np.inf)
                  for p in cands], axis=1)
    out = []
    for s, h in zip(S.tolist(), H.tolist()):
        first, floor = _elect(s, h, cands, cfg)
        # comparisons near their threshold: a contrast, or a score at the
        # floor (the floor moves with the best: taken as elected)
        near = [("c", i) for i, (p, a, b) in enumerate(zip(cands, s, h))
                if p >= 4 and abs(a + cfg["hw_contrast_margin"] - b)
                < MARGIN_ABS]
        near += [("e", i) for i, a in enumerate(s)
                 if abs(a - floor) < MARGIN_ABS]
        periods = [first if first is not None else fallback]
        for k in range(1, len(near) + 1):
            for flips in itertools.combinations(near, k):
                p = _elect(s, h, cands, cfg, flips)[0]
                p = p if p is not None else fallback
                if p not in periods:
                    periods.append(p)
        out.append(tuple(periods))
    return out


# --------------------------------------------------------------------- fit
def fit_block(hist: np.ndarray, n_judged: int, period: int) -> dict:
    """Every candidate of GRID over a block of rows `hist` (R, H), every
    sample present: per candidate the fit error (slots t >= 2 period), the
    squared residual summed over the whole history, and the predictions of
    the `n_judged` slots after the history. One loop over time; the state
    of a step is (R, 60)."""
    R, H = hist.shape
    p = int(period)
    G = GRID.shape[0]
    alpha, beta, gamma = GRID[:, 0], GRID[:, 1], GRID[:, 2]
    a1, b1, g1 = 1.0 - alpha, 1.0 - beta, 1.0 - gamma
    l0 = hist[:, :p].mean(axis=1)
    season = np.repeat((hist[:, :p] - l0[:, None]).T[:, :, None], G, axis=2)
    l = np.repeat(l0[:, None], G, axis=1)
    b = np.zeros((R, G))
    sse_fit, sse_all = np.zeros((R, G)), np.zeros((R, G))
    lb, u, v = np.empty((R, G)), np.empty((R, G)), np.empty((R, G))
    xs = np.ascontiguousarray(hist.T)[:, :, None]  # (H, R, 1)
    for t in range(H):
        s = season[t % p]
        x = xs[t]
        np.add(l, b, out=lb)
        np.add(lb, s, out=u)            # the prediction
        np.subtract(x, u, out=u)        # its residual
        np.multiply(u, u, out=u)
        sse_all += u
        if t >= 2 * p:
            sse_fit += u
        np.subtract(x, s, out=u)        # l' = alpha (x - s) + (1 - alpha)(l + b)
        u *= alpha
        lb *= a1
        u += lb
        np.subtract(u, l, out=v)        # b' = beta (l' - l) + (1 - beta) b
        v *= beta
        b *= b1
        b += v
        l, u = u, l
        np.subtract(x, l, out=v)        # season = gamma (x - l') + (1 - gamma) s
        v *= gamma
        s *= g1
        s += v
    steps = np.arange(1, n_judged + 1)
    slots = (H + np.arange(n_judged)) % p
    preds = l[:, :, None] + b[:, :, None] * steps + season[slots].transpose(1, 2, 0)
    return {"err": sse_fit / max(H - 2 * p, 1), "sse_all": sse_all,
            "preds": preds}  # (R, G), (R, G), (R, G, C)


def _bands(fit: dict, cur: np.ndarray, n_hist: int, policy: tuple,
           slack: float) -> list:
    """Per row, the band of the winning candidate and of every candidate
    whose error is within TIE_REL of it: [(upper mean, lower mean, sigma,
    count, count with the band moved out, moved in)]."""
    k, bound, floor = policy
    up_w, lo_w = bool(bound & 1 or bound == 0), bool(bound & 2 or bound == 0)
    out = []
    for i in range(cur.shape[0]):
        err = fit["err"][i]
        best = int(np.argmin(err))
        keep = [best] + [g for g in np.nonzero(
            err <= err[best] * (1.0 + TIE_REL))[0].tolist() if g != best]
        row = []
        for g in keep:
            sigma = float(np.sqrt(fit["sse_all"][i, g] / n_hist))
            pred = fit["preds"][i, g]
            upper = pred + k * sigma
            lower = np.maximum(pred - k * sigma, floor)

            def count(d):
                hit = np.zeros(cur.shape[1], bool)
                if up_w:
                    hit |= cur[i] > upper + d
                if lo_w:
                    hit |= cur[i] < lower - d
                return int(hit.sum())

            row.append((float(upper.mean()), float(lower.mean()), sigma,
                        count(0.0), count(slack * sigma),
                        count(-slack * sigma)))
        out.append(row)
    return out


def band_rows(hist: np.ndarray, cur: np.ndarray, policy: tuple, cfg: dict,
              slack: float) -> list:
    """Reference bands of rows `hist` (B, H) and `cur` (B, C), every sample
    present: per row {"periods": the periods kept, "bands": the bands kept
    (the first is the elected period's winner), "ties": candidates kept
    beside a winner}."""
    H, C = hist.shape[1], cur.shape[1]
    if H < 2 or C < 1:
        raise ValueError("a band needs history and a judged window")
    periods = detect_periods(hist, cfg, fallback_period(cfg, H + C))
    rows = [{"periods": ps, "bands": [], "ties": 0} for ps in periods]
    for rank in range(max(len(ps) for ps in periods)):
        for p in sorted({ps[rank] for ps in periods if len(ps) > rank}):
            idx = [i for i, ps in enumerate(periods)
                   if len(ps) > rank and ps[rank] == p]
            for lo in range(0, len(idx), _ROWS):
                part = idx[lo:lo + _ROWS]
                bands = _bands(fit_block(hist[part], C, p), cur[part], H,
                               policy, slack)
                for i, row in zip(part, bands):
                    rows[i]["bands"] += row
                    rows[i]["ties"] += len(row) - 1
    return rows


def quantize(x: np.ndarray, precision: str) -> np.ndarray:
    """`precision` is the control: "bfloat16" rounds the served samples
    before anything is computed; the arithmetic stays float64."""
    if precision == "float64":
        return x
    if precision == "bfloat16":
        import ml_dtypes

        return x.astype(ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


# ---------------------------------------------------------- a fleet's class
def _set_worker_fleet(fleet):
    global _worker_fleet
    _worker_fleet = fleet


def _chunk_rows(jobs, slot, k_now, slack, precision, fleet=None):
    fleet = fleet if fleet is not None else _worker_fleet
    hist = fleet.role_rows(jobs, slot, "historical", k_now)
    cur = fleet.role_rows(jobs, slot, "current", k_now)
    return band_rows(quantize(hist, precision), quantize(cur, precision),
                     reference.POLICIES[fleet.metrics_of(jobs[0])[slot]],
                     settings(fleet.config["engine"]), slack)


def fleet_rows(fleet, jobs: list, slot: int, k_now: int, slack: float,
               precision: str) -> dict:
    """{job: its reference row (`band_rows`)} from the fleet's own served
    series: the `historical` window's slots, and the `current` window's
    up to `k_now` (`Fleet.window_slots`). Blocks of jobs are independent,
    so a large class is computed over a process pool (spawned: the
    workers import numpy and this package, never the program or the
    chip); the arithmetic is the same."""
    chunks = [jobs[i:i + _CHUNK] for i in range(0, len(jobs), _CHUNK)]
    args = (slot, k_now, slack, precision)
    if len(jobs) < _POOL_FROM:
        rows = [_chunk_rows(c, *args, fleet=fleet) for c in chunks]
    else:
        workers = max(1, min(_WORKERS, len(os.sched_getaffinity(0)) - 1))
        with ProcessPoolExecutor(
                workers, mp_context=multiprocessing.get_context("spawn"),
                initializer=_set_worker_fleet, initargs=(fleet,)) as pool:
            rows = list(pool.map(_chunk_rows, chunks,
                                 *([a] * len(chunks) for a in args)))
    return {j: r for c, rs in zip(chunks, rows) for j, r in zip(c, rs)}
