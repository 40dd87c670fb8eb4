"""Plain float64 Holt-Winters and period detection for the tests: numpy,
a loop over time, one row at a time, independent of `ops/forecast.py`.

The equations (docstring of `ops.forecast._hw_predictions_tm`): per row,
period p, parameters (alpha, beta, gamma): `l0` = masked mean of `x[0:p]`,
`s0 = x[0:p] - l0` (0 where absent), `b0 = 0`; at step t,
`s_t = season[t mod p]`, prediction `l + b + s_t`; where the sample is
present `l' = alpha (x - s_t) + (1 - alpha)(l + b)`,
`b' = beta (l' - l) + (1 - beta) b`,
`season[t mod p] = gamma (x - l') + (1 - gamma) s_t`; where it is absent
`l' = l + b`, `b' = b`, the slot unchanged.
"""
import itertools

import numpy as np

GRID = np.asarray(list(itertools.product(
    (0.1, 0.3, 0.5, 0.7, 0.9), (0.0, 0.1, 0.3), (0.05, 0.1, 0.3, 0.5))))


def _start(x, mask, p):
    m0 = mask[:p]
    l0 = x[:p][m0].sum() / max(int(m0.sum()), 1)
    return l0, np.where(m0, x[:p] - l0, 0.0)


def hw_predictions(x, mask, p, alpha, beta, gamma):
    """(T,) one-step predictions of one row, the season indexed by t mod p."""
    x = np.asarray(x, np.float64)
    l, season = _start(x, mask, p)
    b = 0.0
    preds = np.empty(x.shape[0])
    for t in range(x.shape[0]):
        s = season[t % p]
        preds[t] = l + b + s
        if mask[t]:
            l_new = alpha * (x[t] - s) + (1.0 - alpha) * (l + b)
            b = beta * (l_new - l) + (1.0 - beta) * b
            season[t % p] = gamma * (x[t] - l_new) + (1.0 - gamma) * s
            l = l_new
        else:
            l = l + b
    return preds


def hw_predictions_rolled(x, mask, p, alpha, beta, gamma):
    """The same recurrence with the season as a queue: the slot in use is
    always the first, and every step rolls the buffer by one."""
    x = np.asarray(x, np.float64)
    l, season = _start(x, mask, p)
    b = 0.0
    preds = np.empty(x.shape[0])
    for t in range(x.shape[0]):
        s = season[0]
        preds[t] = l + b + s
        s_new = s
        if mask[t]:
            l_new = alpha * (x[t] - s) + (1.0 - alpha) * (l + b)
            b = beta * (l_new - l) + (1.0 - beta) * b
            s_new = gamma * (x[t] - l_new) + (1.0 - gamma) * s
            l = l_new
        else:
            l = l + b
        season = np.roll(season, -1)
        season[-1] = s_new
    return preds


def grid_errors(x, mask, fit_mask, p):
    """(G,) mean squared one-step residual of each candidate of GRID over
    the slots of `fit_mask & mask` of one row (0 with no such slot)."""
    sel = fit_mask & mask
    n = max(int(sel.sum()), 1)
    out = np.empty(len(GRID))
    for g, (a, b, c) in enumerate(GRID):
        r = (np.asarray(x, np.float64) - hw_predictions(x, mask, p, a, b, c))[sel]
        out[g] = (r * r).sum() / n
    return out


def detect_period(x, mask, candidates, fallback, min_acf, alias_margin=0.05,
                  contrast_margin=0.01):
    """(period, scores (C,), margin) of one row by the published rule:
    masked linear detrend; the autocorrelation at each candidate lag with
    at least p pairs of support; the half-lag contrast; the first
    candidate within `alias_margin` of the best that passes and reaches
    `min_acf`, else the fallback. `margin` is the least distance of a
    deciding comparison from its threshold."""
    x = np.asarray(x, np.float64)
    t = np.arange(x.shape[0], dtype=np.float64)
    m = mask.astype(np.float64)
    n = max(m.sum(), 1.0)
    tc = t - (m * t).sum() / n
    den = (m * tc * tc).sum()
    slope = (m * tc * x).sum() / den if den > 0 else 0.0
    d = np.where(mask, x - (m * x).sum() / n - slope * tc, 0.0)

    def acf(p):
        if not 2 <= p < x.shape[0]:
            return -np.inf
        w = m[p:] * m[:-p]
        lead, lag = d[p:], d[:-p]
        den = np.sqrt((w * lead * lead).sum() * (w * lag * lag).sum())
        if w.sum() < p or den <= 0:
            return -np.inf
        return (w * lead * lag).sum() / den

    scores = [acf(p) for p in candidates]
    halves = [acf(p // 2) if p >= 4 else -np.inf for p in candidates]
    ok = [p < 4 or s + contrast_margin >= h
          for p, s, h in zip(candidates, scores, halves)]
    best = max((s for s, o in zip(scores, ok) if o), default=-np.inf)
    floor = max(best - alias_margin, min_acf)
    margin = min([abs(s + contrast_margin - h)
                  for p, s, h in zip(candidates, scores, halves)
                  if p >= 4 and np.isfinite(s) and np.isfinite(h)]
                 + [abs(s - floor) for s in scores if np.isfinite(s)]
                 + [np.inf])
    for p, s, o in zip(candidates, scores, ok):
        if o and s >= floor:
            return int(p), np.asarray(scores), margin
    return int(fallback), np.asarray(scores), margin
