"""Bivariate-normal joint anomaly scorer (the two-metric judgment mode).

The reference brain's model menu assigns "Bivariate Normal Distribution" to
jobs monitoring exactly two correlated metrics (docs/guides/design.md:53-88
— one metric: univariate forecasters; two: bivariate normal; 3+: LSTM).
No reference source exists (the brain repo is absent); the spec is the menu
entry itself: fit a 2-D Gaussian to the joint historical distribution of the
metric pair and flag current points that fall outside the k-sigma ellipse.

TPU design: everything is closed-form — masked means, a 2x2 covariance with
a ridge floor, an analytic 2x2 inverse, and a Mahalanobis distance per time
step — batched over (B, T) with no iterative fitting at all. One jitted
program scores every two-metric job in the fleet batch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .forecast import judged_region

__all__ = ["bivariate_normal_anomalies"]

_F = jnp.float32


@jax.jit
def bivariate_normal_anomalies(x1, m1, x2, m2, n_hist, n_total, threshold,
                               min_lower_bound1=None, min_lower_bound2=None,
                               bound_mode1=None, bound_mode2=None):
    """Joint k-sigma-ellipse anomaly flags for a metric pair.

    Args:
      x1, x2:    (B, T) the two metrics on a shared time grid.
      m1, m2:    (B, T) bool validity masks.
      n_hist, n_total: (B,) int — slots [n_hist, n_total) of a row are
                 the current window being judged (forecast.judged_region);
                 the joint Gaussian is fit on the rest (history).
      threshold: (B,) Mahalanobis radius in sigmas (per-metric ML_THRESHOLD;
                 the pair uses the min — stricter — of its two policies).
      min_lower_bound1/2: (B,) optional floors for the exported marginal
                 lower bands (mirrors the univariate min_lower_bound{N}).
      bound_mode1/2: (B,) optional int32 ML_BOUND bitmasks per metric
                 (forecast.BOUND_*: bit0 upper, bit1 lower; 0 = both). The
                 ellipse itself is two-sided; a flagged point is kept only
                 when at least one metric's excursion direction is enabled
                 by that metric's bound mask — an upper-only error metric
                 must not alarm the pair on "too healthy" dips.

    Returns dict:
      flags (B, T) joint anomalies, count/first_index/checked (B,), and
      the marginal upper/lower bounds (B,) per metric (mu_i +- threshold *
      sigma_i: they do not vary along T, so a row has one of each) for the
      foremastbrain:*_{upper,lower} export. Nothing but `flags` is
      (B, T): the squared Mahalanobis distances stay on the device.
    """
    B, T = x1.shape
    region = judged_region(n_hist, n_total, T)
    joint = m1 & m2
    hist = joint & ~region
    w = hist.astype(_F)
    n = jnp.sum(w, axis=-1)
    denom = jnp.maximum(n, 1.0)

    mu1 = jnp.sum(x1 * w, axis=-1) / denom
    mu2 = jnp.sum(x2 * w, axis=-1) / denom
    d1 = (x1 - mu1[:, None]) * w
    d2_ = (x2 - mu2[:, None]) * w
    # covariance with a ridge floor: keeps the ellipse defined for (nearly)
    # constant or perfectly-correlated history instead of exploding Sigma^-1
    var1 = jnp.sum(d1 * d1, axis=-1) / denom
    var2 = jnp.sum(d2_ * d2_, axis=-1) / denom
    cov = jnp.sum(d1 * d2_, axis=-1) / denom
    ridge = 1e-6 * jnp.maximum(jnp.maximum(var1, var2), 1.0)
    var1 = var1 + ridge
    var2 = var2 + ridge
    det = jnp.maximum(var1 * var2 - cov * cov, 1e-12)

    # analytic 2x2 inverse; d^2(t) = [a b] Sigma^-1 [a b]^T
    a = x1 - mu1[:, None]
    b = x2 - mu2[:, None]
    d2 = (var2[:, None] * a * a - 2.0 * cov[:, None] * a * b
          + var1[:, None] * b * b) / det[:, None]

    # fail-open like residual_sigma: <2 history points => nothing judgeable
    enough = (n >= 2.0)[:, None]
    flags = (d2 > (threshold[:, None] ** 2)) & joint & region & enough
    if bound_mode1 is not None or bound_mode2 is not None:
        def directional(dev, mode):
            if mode is None:
                return jnp.ones_like(dev, bool)
            md = jnp.where(mode == 0, 3, mode)[:, None]
            return ((dev > 0) & ((md & 1) > 0)) | ((dev < 0) & ((md & 2) > 0))
        flags = flags & (directional(a, bound_mode1) | directional(b, bound_mode2))
    counts = jnp.sum(flags, axis=-1)
    first = jnp.where(counts > 0, jnp.argmax(flags, axis=-1),
                      jnp.full((B,), -1))
    checked = jnp.sum((joint & region).astype(jnp.int32), axis=-1)

    s1 = jnp.sqrt(var1)
    s2 = jnp.sqrt(var2)
    lo1 = mu1 - threshold * s1
    lo2 = mu2 - threshold * s2
    if min_lower_bound1 is not None:
        lo1 = jnp.maximum(lo1, min_lower_bound1)
    if min_lower_bound2 is not None:
        lo2 = jnp.maximum(lo2, min_lower_bound2)
    return {
        "flags": flags,
        "count": counts,
        "first_index": first,
        "checked": checked,
        "upper1": mu1 + threshold * s1,
        "lower1": lo1,
        "upper2": mu2 + threshold * s2,
        "lower2": lo2,
    }
