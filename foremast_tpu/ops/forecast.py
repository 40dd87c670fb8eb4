"""Batched forecasting models + anomaly-band logic (lax.scan smoothers).

The reference brain's historical-model judgment mode fits a forecaster on the
7-day historical window, derives an upper/lower band, and flags current-window
points outside it (spec: SURVEY.md §2.4; algorithm menu at
docs/guides/design.md:53-88 — moving average, exponential smoothing, double
exponential smoothing, Holt-Winters; default ML_ALGORITHM=moving_average_all
at deploy/foremast/3_brain/foremast-brain.yaml:24-25; per-metric
threshold/bound/min_lower_bound overrides at foremast-brain.yaml:26-73).

TPU design:
  * every model is an online one-step-ahead predictor rolled over the FULL
    (historical ++ current) series by `lax.scan` — no Python loops, no
    data-dependent shapes. Gaps advance the model state by its own forecast
    (standard missing-data handling for exponential smoothers).
  * band sigma is the RMS one-step residual over the *historical* region only
    (region selected by index masks, not slicing, so hist_len is a traced
    per-series value and one compiled program serves every job shape bucket).
  * Holt-Winters parameters are fit by a grid search minimizing historical
    SSE: the candidates run side by side through one pass over time, the
    season a (period, candidates, rows) array indexed by t mod period, then
    one more pass gives the winners' predictions — replacing the per-series
    scipy.optimize loop a CPU brain would run.

All kernels take (B, T) values + masks and are jit-compiled once per (T,
period/window) bucket.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .ranks import _cummax

__all__ = [
    "ALGO_MOVING_AVERAGE",
    "ALGO_SES",
    "ALGO_DES",
    "ALGO_HOLT_WINTERS",
    "BOUND_BOTH",
    "BOUND_UPPER",
    "BOUND_LOWER",
    "masked_mean_std",
    "moving_average_predictions",
    "ses_predictions",
    "des_predictions",
    "holt_winters_predictions",
    "detect_period",
    "fit_holt_winters",
    "hw_state_bytes",
    "hw_fit_mask",
    "HW_CANDIDATES",
    "take_rows",
    "scatter_rows",
    "fit_seasonal_trend",
    "st_columns",
    "st_solves",
    "judged_region",
    "region_masks",
    "residual_sigma",
    "band_anomalies",
]

_F = jnp.float32

ALGO_MOVING_AVERAGE = 0
ALGO_SES = 1
ALGO_DES = 2
ALGO_HOLT_WINTERS = 3

# ML_BOUND codes. The reference deploy config uses small-int codes
# (deploy/foremast/3_brain/foremast-brain.yaml: bound=1 for error5xx/4xx/
# cpu/memory, bound=3 for latency); we read them as a bitmask:
# bit0 = check upper band, bit1 = check lower band. 0 is treated as both.
BOUND_UPPER = 1
BOUND_LOWER = 2
BOUND_BOTH = 3


def _hold_last(vals, flags, reverse: bool = False):
    """At each slot, the most recent `vals` entry whose flag was True
    (looking left, or right when reverse=True); vals[0-ish] propagated as-is
    where no flagged entry precedes. Gather-free: the classic "last
    non-null" associative combiner in O(log T) depth — scatters/gathers
    serialize on TPU, associative scans do not (see ops/ranks.py)."""

    def combine(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, av), af | bf

    held, _ = lax.associative_scan(
        combine, (vals, flags), axis=vals.ndim - 1, reverse=reverse
    )
    return held


def _first_valid(x, mask):
    """Value at the first True of mask (0.0 if none)."""
    held = _hold_last(x.astype(_F), mask, reverse=True)
    return jnp.where(jnp.any(mask), held[..., 0], 0.0)


def masked_mean_std(x, mask, axis=-1):
    m = mask.astype(_F)
    n = jnp.sum(m, axis=axis)
    denom = jnp.where(n == 0, 1.0, n)
    mean = jnp.sum(x * m, axis=axis) / denom
    var = jnp.sum(m * (x - jnp.expand_dims(mean, axis)) ** 2, axis=axis) / denom
    return mean, jnp.sqrt(var)


# ---------------------------------------------------------------------------
# One-step-ahead predictors. All: (T,) x, (T,) mask -> (T,) preds where
# preds[t] is the model's forecast of x[t] before observing it.
# ---------------------------------------------------------------------------
def _moving_average_1d(x, mask, window: int):
    """Causal rolling mean over the last `window` time slots (valid only).

    Time-based, not count-based: a gap shrinks the sample, it does not pull
    older points into the window — a 5-step MA always looks back 5 minutes at
    a 60 s step, matching how the brain's moving-average band tracks recency.
    When the whole window is a gap, the prediction freezes at the most
    recent DEFINED rolling mean, not the last raw sample: band checks
    extrapolate this prediction across the whole judged region, and a
    single noisy final observation anchoring every extrapolated step
    inflates the false-positive rate by an order of magnitude (a last
    sample 2 sigma low condemns ~half of an identical current window).
    Only slots before the first observation see the first valid value.
    """
    T = x.shape[0]
    xf = x.astype(_F)
    xm = jnp.where(mask, xf, 0.0)
    m = mask.astype(_F)
    t = jnp.arange(T)
    # windowed sums as exclusive-cumsum differences. The lookback is a
    # dynamic ROLL (two slices), never a per-element gather: csum[lo] with
    # lo = max(t - window, 0) equals the exclusive cumsum shifted right by
    # `window`, zeroed where the window still touches the series start.
    ex_s = jnp.cumsum(xm) - xm
    ex_c = jnp.cumsum(m) - m
    in_range = t >= window
    s = ex_s - jnp.where(in_range, jnp.roll(ex_s, window), 0.0)
    c = ex_c - jnp.where(in_range, jnp.roll(ex_c, window), 0.0)
    ma = s / jnp.where(c == 0, 1.0, c)
    defined = c > 0
    # freeze-fill at the rolling mean evaluated just AFTER the last
    # observation, where the window still holds up to `window` trailing
    # points. (Freezing at the last slot whose window held ANY data would
    # re-anchor to the final sample alone: that window has slid to a
    # single point.) h[t] carries ma[prev_idx+1] forward without a gather:
    # it resets to ma[t] whenever slot t-1 was observed.
    idx = jnp.where(mask, t, -1)
    last_le = _cummax(idx)  # last valid index <= t
    prev_idx = jnp.concatenate([jnp.full((1,), -1), last_le[:-1]])
    reset = jnp.concatenate([jnp.ones((1,), bool), mask[:-1]])
    h = _hold_last(ma, reset)
    first = _first_valid(x, mask)
    filled = jnp.where(prev_idx >= 0, h, first)
    return jnp.where(defined, ma, filled)


def _ses_1d(x, mask, alpha):
    s0 = _first_valid(x, mask)

    def step(s, inp):
        xt, mt = inp
        pred = s
        s_next = jnp.where(mt, alpha * xt + (1.0 - alpha) * s, s)
        return s_next, pred

    _, preds = lax.scan(step, s0, (x.astype(_F), mask))
    return preds


def _des_1d(x, mask, alpha, beta):
    """Holt's linear (double exponential smoothing)."""
    l0 = _first_valid(x, mask)
    b0 = jnp.asarray(0.0, _F)

    def step(carry, inp):
        l, b = carry
        xt, mt = inp
        pred = l + b
        l_next = jnp.where(mt, alpha * xt + (1.0 - alpha) * (l + b), l + b)
        b_next = jnp.where(mt, beta * (l_next - l) + (1.0 - beta) * b, b)
        return (l_next, b_next), pred

    _, preds = lax.scan(step, (l0, b0), (x.astype(_F), mask))
    return preds


def _hw_init(xT, mT, period: int):
    """Holt-Winters start of time-major rows `(T, ...)`: the level is the
    masked mean of the first period, the season the first period's
    deviations from it (0 where a sample is absent)."""
    x0, m0 = xT[:period].astype(_F), mT[:period]
    n0 = jnp.maximum(jnp.sum(m0.astype(_F), axis=0), 1.0)
    l0 = jnp.sum(jnp.where(m0, x0, 0.0), axis=0) / n0
    return l0, jnp.where(m0, x0 - l0, 0.0)


def _hw_step(l, b, s_t, xt, mt, alpha, beta, gamma):
    """One Holt-Winters step: (prediction, l', b', the season slot's new
    value). An absent sample advances the state by its own forecast."""
    pred = l + b + s_t
    l_next = jnp.where(mt, alpha * (xt - s_t) + (1.0 - alpha) * (l + b), l + b)
    b_next = jnp.where(mt, beta * (l_next - l) + (1.0 - beta) * b, b)
    s_new = jnp.where(mt, gamma * (xt - l_next) + (1.0 - gamma) * s_t, s_t)
    return pred, l_next, b_next, s_new


def _hw_predictions_tm(xT, mT, period: int, alpha, beta, gamma):
    """Additive Holt-Winters over time-major rows: `xT`, `mT` are
    `(T, ...)`, the parameters broadcast against the trailing axes, the
    `(T, ...)` one-step predictions come back.

    Per row, period p: `l0` = masked mean of `x[0:p]`, `s0 = x[0:p] - l0`
    (0 where absent), `b0 = 0`. At step t, `s_t = season[t mod p]` and the
    prediction is `l + b + s_t`; where the sample is present
    `l' = alpha (x - s_t) + (1 - alpha)(l + b)`,
    `b' = beta (l' - l) + (1 - beta) b`,
    `season[t mod p] = gamma (x - l') + (1 - gamma) s_t`; where it is
    absent `l' = l + b`, `b' = b` and the slot keeps its value.

    The season is a `(p, ...)` array read and written at `t mod p`: a
    dynamic slice and an in-place update of one contiguous slab on the
    leading axis a step, never a roll or a copy of the buffer.
    """
    l0, season0 = _hw_init(xT, mT, period)

    def step(carry, inp):
        l, b, season, idx = carry
        xt, mt = inp
        s_t = lax.dynamic_index_in_dim(season, idx, 0, keepdims=False)
        pred, l, b, s_new = _hw_step(l, b, s_t, xt, mt, alpha, beta, gamma)
        season = lax.dynamic_update_index_in_dim(season, s_new, idx, 0)
        return (l, b, season, jnp.where(idx + 1 == period, 0, idx + 1)), pred

    init = (l0, jnp.zeros_like(l0), season0, jnp.int32(0))
    _, preds = lax.scan(step, init, (xT.astype(_F), mT))
    return preds


def _hw_1d(x, mask, period: int, alpha, beta, gamma):
    """Additive Holt-Winters with static seasonal period over one series
    (the time-major recurrence at a single row)."""
    return _hw_predictions_tm(x, mask, period, alpha, beta, gamma)


def _holt_winters_rows(x, mask, period: int, alpha, beta, gamma):
    """`(B, T)` rows through the time-major recurrence, `(B,)` parameters."""
    return _hw_predictions_tm(x.T, mask.T, period, alpha, beta, gamma).T


# Batched, jitted entry points.
moving_average_predictions = jax.jit(
    jax.vmap(_moving_average_1d, in_axes=(0, 0, None)), static_argnames=("window",)
)
ses_predictions = jax.jit(jax.vmap(_ses_1d, in_axes=(0, 0, 0)))
des_predictions = jax.jit(jax.vmap(_des_1d, in_axes=(0, 0, 0, 0)))
holt_winters_predictions = jax.jit(
    _holt_winters_rows, static_argnames=("period",)
)


# ---------------------------------------------------------------------------
# Seasonality detection: which candidate period (if any) does the history
# actually exhibit?
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("candidates",))
def detect_period(x, mask, candidates: tuple, fallback, min_acf,
                  alias_margin=0.05, contrast_margin=0.01):
    """Batched seasonal-period estimation over masked history.

    The reference models TPS "seasonality+trend" for HPA scoring
    (docs/dynamic_autoscaling.md:28-44) and SURVEY §7 lists Holt-Winters
    seasonality detection as a hard part; a static HW_PERIOD silently
    mis-bands any service whose cycle is not the configured default (a
    shift-pattern service on a daily default, an hourly batch job, ...).

    Method, shaped for one jitted program over the whole fleet:
      1. remove a masked linear trend per series (closed form — trend
         inflates autocorrelation at every lag and would drown the
         comparison between candidates);
      2. masked autocorrelation at each CANDIDATE lag only (static tuple,
         so each lag is a static slice — no FFT, no dynamic shapes; the
         fleet's periods are operational ones: hour / shift / day / week);
      3. a candidate only counts when the history holds >= 2 full cycles
         of overlap support (pair count >= lag), else its score is -inf;
      3b. HALF-LAG CONTRAST: a candidate p is genuinely periodic only if
         its ACF at lag p beats the ACF at lag p/2 — a true p-cycle
         anti-aligns at the half lag, while a smooth LONGER cycle scores
         nearly as high at p/2 as at p (lag 60 of a pure daily cycle
         correlates at ~0.97; without this test every slow series would
         elect the shortest candidate);
      4. the FIRST contrast-passing candidate within a small margin of the
         best contrast-passing score wins — every multiple of the true
         period scores just as high (lag 2p realigns a p-cycle exactly),
         so list candidates fundamental-first (ascending) and the margin
         rule resolves the harmonic alias toward the shortest supported
         cycle;
      5. fall back to `fallback` when even the best autocorrelation is
         below `min_acf` (aperiodic series keep the configured default
         rather than chasing noise).

    Args:
      x, mask:    (B, T) values + validity (history region only — pass the
                  historical mask, not the full-window mask).
      candidates: static tuple of candidate periods in steps (each >= 2),
                  in preference order — ascending, so the fundamental
                  beats its harmonics.
      fallback:   (scalar or (B,)) period used when no candidate is
                  supported/confident.
      min_acf:    scalar — minimum autocorrelation to accept a candidate.

    Returns (period (B,) int32, scores (B, C) float32).
    """
    B, T = x.shape
    m = mask.astype(_F)
    t = jnp.arange(T, dtype=_F)
    n = jnp.maximum(jnp.sum(m, -1), 1.0)
    st = jnp.sum(m * t, -1)
    stt = jnp.sum(m * t * t, -1)
    xf = jnp.where(mask, x.astype(_F), 0.0)
    sy = jnp.sum(xf, -1)
    sty = jnp.sum(t * xf, -1)
    det = n * stt - st * st
    slope = jnp.where(det > 0, (n * sty - st * sy) / jnp.where(det == 0, 1.0, det), 0.0)
    icept = (sy - slope * st) / n
    d = jnp.where(mask, xf - icept[:, None] - slope[:, None] * t[None, :], 0.0)

    def acf_at(p):
        w = m[:, p:] * m[:, :-p]
        lead, lag = d[:, p:], d[:, :-p]
        num = jnp.sum(w * lead * lag, -1)
        den = jnp.sqrt(
            jnp.sum(w * lead * lead, -1) * jnp.sum(w * lag * lag, -1)
        )
        r = num / jnp.where(den == 0, 1.0, den)
        supported = jnp.sum(w, -1) >= float(p)  # >= 2 full cycles of span
        return jnp.where(supported & (den > 0), r, -jnp.inf)

    scores, contrasts = [], []
    for p in candidates:
        if not (2 <= p < T):
            scores.append(jnp.full((B,), -jnp.inf, _F))
            contrasts.append(jnp.zeros((B,), bool))
            continue
        r = acf_at(p)
        scores.append(r)
        # half-lag contrast: a TRUE period p anti-aligns at lag p/2
        # (ACF strongly negative there), while a smooth longer cycle
        # scores almost as high at p/2 as at p — plain lag-p ACF alone
        # would let any slow series elect the shortest candidate (lag 60
        # of a pure daily cycle correlates at cos(2*pi*60/1440) ~ 0.97).
        # The comparison carries a small tolerance: a series whose true
        # period divides BOTH p and p/2 (e.g. period 30 under candidate
        # 60) realigns exactly at both lags — r(p) ~ r(p/2) to within
        # noise — and is a harmonically VALID pick that must pass, not a
        # per-series coin flip; only a half-lag ACF that beats lag p by
        # MORE than the tolerance marks p as riding a smoother, longer
        # cycle. Candidates too short for a meaningful half lag skip it.
        contrasts.append(
            r + contrast_margin >= acf_at(p // 2) if p >= 4
            else jnp.full((B,), True))
    S = jnp.stack(scores, axis=-1)  # (B, C)
    ok = jnp.stack(contrasts, axis=-1)  # (B, C)
    # the margin reference is the best GENUINELY-periodic candidate: a
    # contrast-failing harmonic's score must neither win nor crowd out
    # the fundamental via the margin window
    best_score = jnp.max(jnp.where(ok, S, -jnp.inf), axis=-1, keepdims=True)
    # harmonic-alias resolution: candidates are ordered fundamental-first
    # (ascending), and a multiple of the true period scores (nearly) as
    # high as the fundamental itself, so the FIRST candidate within
    # `alias_margin` of the best score wins (argmax over booleans returns
    # the first True). The margin trades alias robustness against
    # fundamental fidelity: larger values let a slightly-noisier short
    # candidate beat a genuinely better long one; tune via
    # HW_ALIAS_MARGIN (engine) when candidate ACFs sit close together.
    eligible = ok & (S >= jnp.maximum(best_score - alias_margin, min_acf))
    pick = jnp.argmax(eligible, axis=-1)
    cand = jnp.asarray(candidates, jnp.int32)
    period = jnp.where(
        jnp.any(eligible, axis=-1),
        cand[pick],
        jnp.broadcast_to(jnp.asarray(fallback, jnp.int32), (B,)),
    )
    return period, S


# ---------------------------------------------------------------------------
# Holt-Winters grid fit: per series, pick (alpha, beta, gamma) minimizing
# masked SSE over the historical region.
# ---------------------------------------------------------------------------
_HW_ALPHAS = (0.1, 0.3, 0.5, 0.7, 0.9)
_HW_BETAS = (0.0, 0.1, 0.3)
_HW_GAMMAS = (0.05, 0.1, 0.3, 0.5)
HW_CANDIDATES = len(_HW_ALPHAS) * len(_HW_BETAS) * len(_HW_GAMMAS)  # 60


def _default_grid():
    a = jnp.asarray(_HW_ALPHAS, _F)
    b = jnp.asarray(_HW_BETAS, _F)
    g = jnp.asarray(_HW_GAMMAS, _F)
    A, B, G = jnp.meshgrid(a, b, g, indexing="ij")
    return jnp.stack([A.ravel(), B.ravel(), G.ravel()], axis=-1)  # (60, 3)


@jax.jit
def hw_fit_mask(hist_mask, period):
    """The slots a Holt-Winters fit is scored on: history from the third
    period on (the first seeds the season, the second settles it)."""
    return hist_mask & (jnp.arange(hist_mask.shape[-1]) >= 2 * period)


# The seasonal state of the side-by-side fit, `(period, candidates, rows)`
# float32, that one pass may hold on the device beside the launch's block,
# its gathered partition, their transposes and the predictions (about 5e9
# bytes at a full (16384, 16384) chunk on a 16 GB chip; the compiler pads
# the candidate axis to a multiple of 8). Over it, the grid runs in the
# fewest equal groups whose state fits, one after another.
_HW_STATE_BYTES_MAX = 6_600_000_000


def _hw_groups(period: int, n_candidates: int, rows: int) -> int:
    """The fewest equal groups of candidates whose seasonal state fits
    `_HW_STATE_BYTES_MAX`, from the shapes alone."""
    return next(
        g for g in range(1, n_candidates + 1)
        if n_candidates % g == 0 and (
            g == n_candidates
            or 4 * period * (n_candidates // g) * rows <= _HW_STATE_BYTES_MAX))


def hw_state_bytes(period: int, n_candidates: int, rows: int) -> int:
    """Bytes of seasonal state `fit_holt_winters` holds at once: the
    `(period, candidates of a group, rows)` float32 season array."""
    per_group = n_candidates // _hw_groups(period, n_candidates, rows)
    return 4 * period * per_group * rows


def _hw_grid_errors(xT, mT, fT, period: int, grid):
    """Mean squared one-step residual of every candidate of `grid` `(G, 3)`
    over the fit slots of time-major rows: `(G, B)`. The candidates run
    side by side through one pass over time: level, trend and the running
    squared error are `(G, B)`, the season `(period, G, B)`; no `(G, B, T)`
    predictions exist. The pass ends at the last slot any row fits on, so
    a bucket's padding and the judged window are not walked."""
    T, B = xT.shape
    G = grid.shape[0]
    alpha, beta, gamma = (grid[:, i][:, None] for i in range(3))
    l0, s0 = _hw_init(xT, mT, period)
    fit = fT & mT
    n_steps = jnp.max(jnp.where(jnp.any(fit, axis=1), jnp.arange(1, T + 1), 0))

    def body(t, carry):
        l, b, sse, season, idx = carry
        xt = lax.dynamic_index_in_dim(xT, t, 0, keepdims=False)
        mt = lax.dynamic_index_in_dim(mT, t, 0, keepdims=False)
        ft = lax.dynamic_index_in_dim(fit, t, 0, keepdims=False)
        s_t = lax.dynamic_index_in_dim(season, idx, 0, keepdims=False)
        pred, l, b, s_new = _hw_step(l, b, s_t, xt, mt, alpha, beta, gamma)
        r = jnp.where(ft, xt - pred, 0.0)
        season = lax.dynamic_update_index_in_dim(season, s_new, idx, 0)
        return (l, b, sse + r * r, season,
                jnp.where(idx + 1 == period, 0, idx + 1))

    init = (jnp.broadcast_to(l0, (G, B)), jnp.zeros((G, B), _F),
            jnp.zeros((G, B), _F),
            jnp.broadcast_to(s0[:, None, :], (period, G, B)), jnp.int32(0))
    sse = lax.fori_loop(0, n_steps, body, init)[2]
    n = jnp.maximum(jnp.sum(fit.astype(_F), axis=0), 1.0)
    return sse / n


@partial(jax.jit, static_argnames=("period",))
def fit_holt_winters(x, mask, fit_mask, period: int, grid=None):
    """Grid-fit HW per series (the recurrence: `_hw_predictions_tm`).

    The fit is the masked mean squared one-step residual over the slots
    of `fit_mask & mask`, for each candidate of the grid; the least wins,
    the first on an exact tie (so a row with no fit slot, whose errors are
    all 0, takes the grid's first candidate); the predictions are the
    winner's. Two sequential passes over time: the candidates side by
    side (`_hw_grid_errors`; in `_hw_groups` equal groups, one after
    another, where their seasonal state would not fit the device), then
    the winners.

    Args:
      x, mask: (B, T).
      fit_mask: (B, T) bool — region whose residuals define the SSE
                (historical region minus warmup).
      period: seasonal period in steps (static).
      grid: (G, 3) candidate (alpha, beta, gamma); default 60-point grid.

    Returns (params (B, 3), preds (B, T)) — predictions under each series'
    best parameters.
    """
    if grid is None:
        grid = _default_grid()
    xT, mT, fT = x.T.astype(_F), mask.T, fit_mask.T
    G = grid.shape[0]
    groups = _hw_groups(period, G, x.shape[0])
    if groups == 1:
        sses = _hw_grid_errors(xT, mT, fT, period, grid)
    else:
        sses = lax.map(
            lambda part: _hw_grid_errors(xT, mT, fT, period, part),
            grid.reshape(groups, G // groups, 3)).reshape(G, x.shape[0])
    best = jnp.argmin(sses, axis=0)  # (B,)
    params = grid[best]
    preds = _hw_predictions_tm(
        xT, mT, period, params[:, 0], params[:, 1], params[:, 2]).T
    return params, preds


# ---------------------------------------------------------------------------
# Prophet-style decomposable model: linear trend + Fourier seasonality.
# ---------------------------------------------------------------------------
# the fit's settings no deployment sets (docs/configuration.md, ST_*)
ST_RIDGE = 1e-4       # Tikhonov weight on every column
ST_CP_SHRINK = 3e-3   # extra weight on the hinge columns' slope deltas
ST_L1_ITERS = 3       # solves a fit where it has hinges: one ridge, two reweighted
# float32 through the MXU. The TPU's default for a float32 contraction
# rounds both operands to bfloat16 (8 mantissa bits): the Gram and the
# right-hand side below sum up to 16,384 products a column pair, and a
# fit from bfloat16 operands drew its predictions 0.07 residual sigma
# off float64 at the (16384, 16384) block (PERF.md, PR 35), 17 times
# what the moving-average band is held to. HIGHEST (six bfloat16 passes)
# is float32 arithmetic; the configuration states float32 and the
# program computes float32 (the rule of ops/pairwise.py's exact null).
_ST_PRECISION = lax.Precision.HIGHEST


def st_columns(order: int, n_changepoints: int) -> int:
    """D, the columns of a seasonal-trend fit: intercept, slope, one hinge
    a changepoint, a sine and a cosine a Fourier order."""
    return 2 + max(n_changepoints, 0) + 2 * order


def st_solves(n_changepoints: int, l1_iters: int = ST_L1_ITERS) -> int:
    """Batched `(B, D, D)` solves one seasonal-trend fit enqueues: the
    ridge solve, and the reweighting rounds where it has hinges."""
    return max(l1_iters, 1) if n_changepoints > 0 else 1


def _solve_spd(A, r):
    """x of A x = r for symmetric positive definite A (B, D, D), r (B, D):
    Gauss-Jordan elimination without pivoting on the symmetrically scaled
    system (unit diagonal), every row of the batch at once, D rank-one
    updates of a (B, D, D + 1) array on the vector unit.

    Why not `jnp.linalg.solve`: compiled for a v5e it is the compiler's
    `LuDecompositionBlock` call, then `InvertDiagBlocks*` on the two
    triangles and their products with the right-hand side. The explicit
    inverses cost accuracy on these nearly dependent columns (the cell's
    bands read 1.4e-3 to 1.8e-3 reference sigmas off float64 where a
    float64 solve of the same float32 Gram reads 4.8e-4, and this
    elimination 4.8e-4), and the call costs time: 0.27 s for a fit's three
    solves at 16,384 rows against 0.0044 s (PERF.md, PR 38's chip runs).
    Elimination without pivoting is stable for a positive definite matrix
    (its pivots are Schur complements, positive, and no entry grows), and
    the fit's matrix is one: a Gram plus a positive diagonal."""
    d = lax.rsqrt(jnp.diagonal(A, axis1=-2, axis2=-1))
    M = jnp.concatenate(
        [A * d[:, :, None] * d[:, None, :], (r * d)[:, :, None]], axis=-1)
    D = A.shape[-1]
    for k in range(D):  # static and small: unrolled
        row = M[:, k, :] / M[:, k, k][:, None]
        M = (M - M[:, :, k][:, :, None] * row[:, None, :]).at[:, k, :].set(row)
    return d * M[:, :, D]


@partial(jax.jit,
         static_argnames=("period", "order", "n_changepoints", "l1_iters"))
def fit_seasonal_trend(x, mask, fit_mask, period: int, order: int = 3,
                       ridge: float = ST_RIDGE, n_changepoints: int = 0,
                       cp_shrink: float = ST_CP_SHRINK,
                       l1_iters: int = ST_L1_ITERS):
    """Fit trend+seasonality per series by masked ridge least squares.

    The reference brain's menu lists Prophet for single-metric forecasting
    (docs/guides/design.md:53-88). Prophet's core is a decomposable model
    y(t) = g(t) + s(t): PIECEWISE-linear trend plus a Fourier-series
    seasonality, fit by regularized regression. This is that core,
    TPU-shaped: closed-form weighted least-squares solves, the normal
    equations formed by matrix products on the MXU and solved as batched
    (B, D, D) systems (`_solve_spd`), replacing Prophet's per-series
    Stan/L-BFGS loop.

    The equations (`benchmark/lib/reference_st.py` holds the same in numpy
    float64). Per row, with T the length of the BUCKET the row is packed
    to, period p, Fourier order K, C changepoints, D = 2 + C + 2K:
      columns over slots t = 0..T-1, with tn = t / (T - 1):
        1; tn; hinges max(tn - s_j, 0), s_j = 0.8 j / (C + 1), j = 1..C
        (a uniform grid over the first 80% of the window, Prophet's
        default changepoint_range; none at 0, where the delta would be
        the base slope); sin(2 pi k t / p), cos(2 pi k t / p), k = 1..K.
        X is (T, D), shared by the rows.
      sel = mask & fit_mask (present, and in the history).
      G = X^T diag(sel) X, r = X^T (sel * x).
      beta_0 = solve(G + diag(ridge + cp_shrink * is_cp), r), is_cp 1 on
        the hinge columns; then, where C > 0, l1_iters - 1 rounds of
        pen = ridge + cp_shrink * is_cp / (|beta| + 1e-3),
        beta = solve(G + diag(pen), r): small deltas are crushed toward 0
        (sparse kinks), real kinks keep their slope.
      predictions X beta over every slot: a slot outside sel (the judged
        window, a gap, the padding) is extrapolated, never fitted.

    Two departures from Prophet as published (PARITY.md): the hinge grid
    and tn are laid on the padded bucket, not on the row's own history, so
    X is one (T, D) matrix for every row of a launch (a per-row grid would
    make it (B, T, D), 21 GB at the full chunk); for a history shorter
    than 0.8 T the last hinges start at or past its end and are held at 0
    by their penalty alone. And the Laplace (L1) prior on the slope deltas
    is `l1_iters` rounds of reweighted ridge, not a posterior mode.

    Precision: the three contractions state `_ST_PRECISION` (HIGHEST),
    and they are the program's only matrix products: the solves are
    float32 elimination on the vector unit (`_solve_spd`, which says why
    `jnp.linalg.solve` is not used).

    Args:
      x, mask:   (B, T) values + validity.
      fit_mask:  (B, T) bool — points whose residuals define the fit
                 (historical region).
      period:    seasonal period in steps (static).
      order:     Fourier order K (static).
      ridge:     Tikhonov weight keeping the solve well-posed when a series
                 has few valid points or the window spans < one period.
      n_changepoints: hinge-grid size C (static).
      cp_shrink: penalty scale on the hinge slope deltas (the analogue of
                 1/changepoint_prior_scale: larger = straighter trend).
      l1_iters:  solves where C > 0 (static; 1 = plain ridge on hinges).

    Returns (beta (B, D), preds (B, T)).
    """
    B, T = x.shape
    tn = jnp.arange(T, dtype=_F) / jnp.maximum(T - 1, 1)
    cols = [jnp.ones(T, _F), tn]
    C = n_changepoints
    if C > 0:
        s = (jnp.arange(1, C + 1, dtype=_F) / (C + 1)) * 0.8
        cols += [jnp.maximum(tn - sj, 0.0) for sj in s]
    w = 2.0 * jnp.pi * jnp.arange(T, dtype=_F) / period
    for k in range(1, order + 1):
        cols += [jnp.sin(k * w), jnp.cos(k * w)]
    X = jnp.stack(cols, axis=-1)  # (T, D)
    D = X.shape[-1]
    sel = (mask & fit_mask).astype(_F)  # (B, T)
    G = jnp.einsum("td,te,bt->bde", X, X, sel,
                   precision=_ST_PRECISION)  # (B, D, D) gram
    rhs = jnp.einsum("td,bt->bd", X, sel * x.astype(_F),
                     precision=_ST_PRECISION)
    # hinge-column indicator for the per-column penalty vector
    is_cp = jnp.zeros(D, _F).at[2:2 + C].set(1.0) if C > 0 else jnp.zeros(D, _F)

    def solve(pen):  # pen: (B, D) per-series per-column ridge weights
        return _solve_spd(G + jax.vmap(jnp.diag)(pen), rhs)  # (B, D)

    beta = solve(jnp.broadcast_to(ridge + cp_shrink * is_cp, (B, D)))
    for _ in range(st_solves(C, l1_iters) - 1):
        beta = solve(ridge + cp_shrink * is_cp / (jnp.abs(beta) + 1e-3))
    preds = jnp.einsum("td,bd->bt", X, beta, precision=_ST_PRECISION)
    return beta, preds


# ---------------------------------------------------------------------------
# Band + anomaly logic
# ---------------------------------------------------------------------------
def judged_region(n_hist, n_total, T: int):
    """(B, T) bool, slots [n_hist, n_total) of each row: a packed row is
    history, then the current window being judged, then padding. Traced
    inside the program that reads it, so two (B,) int vectors cross to
    the device instead of a (B, T) bool block."""
    t = jnp.arange(T)
    return (t >= n_hist[:, None]) & (t < n_total[:, None])


@jax.jit
def region_masks(mask, n_hist, n_total):
    """(judged region (B, T) bool, mask & ~region: the history mask) of
    packed rows, made on the device for the programs of a launch closure
    that take them as arguments."""
    region = judged_region(n_hist, n_total, mask.shape[-1])
    return region, mask & ~region


@jax.jit
def take_rows(rows, *blocks):
    """Rows `rows` (an int32 index whose length is a batch rung) of each
    device block: a period's partition of a band launch."""
    return tuple(b[rows] for b in blocks)


@partial(jax.jit, static_argnames=("n_rows",))
def scatter_rows(acc, part, rows, n_rows: int):
    """`part`'s per-row results written at `rows` of `acc` (a dict of
    `(n_rows, ...)` arrays, made of zeros where `acc` is None): a
    partition's results back in the launch's row order."""
    if acc is None:
        acc = {k: jnp.zeros((n_rows,) + v.shape[1:], v.dtype)
               for k, v in part.items()}
    return {k: acc[k].at[rows].set(part[k]) for k in part}


@jax.jit
def residual_sigma(x, preds, mask, region_mask):
    """RMS one-step residual over region_mask & mask, per series (B,).

    With fewer than 2 residual samples there is no error scale to estimate;
    sigma is +inf there, so downstream bands become infinitely wide and a
    no-history series can never be judged anomalous (fail-open). The engine
    additionally gates jobs on MIN_HISTORICAL_DATA_POINT_TO_MEASURE before
    scoring, mirroring the reference brain's env config. A genuinely
    constant history (n >= 2, zero residuals) keeps sigma = 0 on purpose:
    any deviation from a perfectly flat metric IS anomalous.
    """
    sel = (mask & region_mask).astype(_F)
    n = jnp.sum(sel, axis=-1)
    r = jnp.where(mask & region_mask, x - preds, 0.0)
    sigma = jnp.sqrt(jnp.sum(r * r, axis=-1) / jnp.maximum(n, 1.0))
    return jnp.where(n >= 2.0, sigma, jnp.inf)


@jax.jit
def band_anomalies(
    x,
    mask,
    region_mask,
    preds,
    sigma,
    threshold,
    bound_mode,
    min_lower_bound,
):
    """Flag points outside the model band in the scored region.

    Args:
      x, mask:      (B, T) values + validity.
      region_mask:  (B, T) bool — the current window being judged.
      preds:        (B, T) model one-step predictions.
      sigma:        (B,) residual scale.
      threshold:    (B,) band half-width in sigmas (per-metric ML_THRESHOLD).
      bound_mode:   (B,) int32 — BOUND_BOTH / BOUND_UPPER / BOUND_LOWER
                    (per-metric ML_BOUND).
      min_lower_bound: (B,) floor applied to the lower band (per-metric
                    min_lower_bound{N} override; lets error-rate metrics not
                    alarm on "too healthy").

    Returns dict with anomaly flags (B, T), and per row (B,): upper/lower,
    the band curves' means over the judged region (every region slot, valid
    or not); counts; first anomaly index (-1 if none); checked point
    counts. Nothing but `flags` is (B, T): the band curves stay on the
    device (what crosses to the host is what a verdict needs).
    """
    thr = threshold[:, None] * sigma[:, None]
    upper = preds + thr
    lower = jnp.maximum(preds - thr, min_lower_bound[:, None])

    over = x > upper
    under = x < lower
    mode = bound_mode[:, None]
    mode = jnp.where(mode == 0, BOUND_BOTH, mode)
    viol = (over & ((mode & 1) > 0)) | (under & ((mode & 2) > 0))
    flags = viol & mask & region_mask
    counts = jnp.sum(flags, axis=-1)
    first = jnp.where(
        counts > 0, jnp.argmax(flags, axis=-1), jnp.full((x.shape[0],), -1)
    )
    checked = jnp.sum((mask & region_mask).astype(jnp.int32), axis=-1)
    n_r = jnp.maximum(jnp.sum(region_mask.astype(_F), axis=-1), 1.0)

    def region_mean(curve):
        # where, not a product with the mask: sigma may be inf
        return jnp.sum(jnp.where(region_mask, curve, 0.0), axis=-1) / n_r

    return {
        "upper": region_mean(upper),
        "lower": region_mean(lower),
        "flags": flags,
        "count": counts,
        "first_index": first,
        "checked": checked,
    }
