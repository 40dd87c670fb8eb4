"""Tier-0 triage screen: one fused vetting pass over packed fleet rows.

Per "Think Before You Grid-Search: Floor-First Triage" (PAPERS.md), most
rows in a steady fleet are boring: their windows changed since last cycle
(so the fingerprint memo misses) but nothing about them is remarkable.
This kernel is the cheap floor that clears them BEFORE the per-family
scoring programs launch, in ONE fused batched program shared by every
screened family and fed by the same packed-row layout the band scorer
uses (`ops/windowing.pack_windows` + the analyzer's `_concat_trimmed`).

Two statistics per row, both over the (historical ++ current) concat grid
with the current region selected by a boolean mask:

  * **smoother-residual band** — the band scorer's OWN masked
    moving-average one-step predictions (`fc._moving_average_1d`, the
    EWMA-class smoother the default `moving_average_all` algorithm
    ships) and RMS residual sigma, with the violation count taken under
    the row's real policy band AND under a band SHRUNK by `margin`
    sigmas. This is what makes CLEAR provably one-sided for the
    moving-average band family: the shrunk band is strictly narrower
    (upper lowered, lower raised — the `min_lower_bound` clamp and the
    `bound` bitmask are replicated exactly), so the shrunk count
    dominates the real count — a shrunk count under the family's
    verdict gate implies the full scorer's count is under the gate and
    the verdict is healthy. The margin absorbs cross-program float
    drift: any point the scorer's program could count differently sits
    within ulps of the real boundary, i.e. a macroscopic
    `margin * sigma` outside the shrunk band, far past any XLA
    fusion-order ulp.
  * **robust z-band** — max over the current region of
    |x - median(hist)| / max(1.4826 * MAD(hist), sigma). Escalation-only
    defense in depth: it can only send MORE rows to the full scorers
    (where the verdict is computed exactly), never clear one the
    residual band would not, so it cannot affect verdict identity. The
    residual-sigma floor keeps quantized metrics (MAD = 0 on
    integer-ish series) from escalating forever.

The engine tier (`engine/triage.py`) makes the CLEAR/SUSPECT call
host-side from these outputs — thresholds never enter the compiled
program, so sweeping them (the verdict-safety sweep test) costs zero
recompiles.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import forecast as fc
from .rowblock import vmap_rows

__all__ = ["screen_rows", "triage_arg_spec"]

_F = jnp.float32


def _screen_1d(x, mask, region, threshold, bound, min_lower_bound, margin,
               window):
    """One row's screen statistics. vmapped by `screen_rows`.

    Args (per row):
      x, mask, region: (T,) values / validity / current-region selector —
        exactly the band scorer's packed layout (history head, current
        tail, zero right-padding with mask False).
      threshold, bound, min_lower_bound: the row's MetricPolicy band
        knobs (sigmas, bitmask, lower clamp).
      margin: shrink (sigmas) applied to the threshold for the
        one-sided CLEAR check; <= 0 disables the float-drift guard and a
        value >= threshold makes the row unclearable (always escalates).
      window: moving-average lookback (static; the engine's ma_window).
    """
    xf = x.astype(_F)
    hist_mask = mask & ~region
    checked_mask = mask & region
    n_h = jnp.sum(hist_mask.astype(_F))

    # -- smoother-residual band: the scorer's own math ----------------------
    preds = fc._moving_average_1d(xf, hist_mask, window)
    r = jnp.where(hist_mask, xf - preds, 0.0)
    sigma = jnp.sqrt(jnp.sum(r * r) / jnp.maximum(n_h, 1.0))
    sigma = jnp.where(n_h >= 2.0, sigma, jnp.inf)
    mode = jnp.where(bound == 0, fc.BOUND_BOTH, bound)

    def band_count(width_sigmas):
        w = width_sigmas * sigma
        upper = preds + w
        lower = jnp.maximum(preds - w, min_lower_bound)
        viol = ((xf > upper) & ((mode & 1) > 0)) | (
            (xf < lower) & ((mode & 2) > 0))
        return (jnp.sum((viol & checked_mask).astype(jnp.int32)),
                upper, lower)

    count, upper, lower = band_count(threshold)
    shrunk_count, _, _ = band_count(threshold - margin)

    # region means of the band curves, matching band_anomalies' reduction
    # (the mean over ALL region slots) so a cleared row's exported bounds
    # agree with the full path up to fusion-order float noise
    n_r = jnp.maximum(jnp.sum(region.astype(_F)), 1.0)
    upper_mean = jnp.sum(jnp.where(region, upper, 0.0)) / n_r
    lower_mean = jnp.sum(jnp.where(region, lower, 0.0)) / n_r

    dev = jnp.abs(xf - preds)
    resid_z = jnp.max(jnp.where(checked_mask, dev, 0.0)) \
        / jnp.maximum(sigma, 1e-30)

    # -- robust z-band: median/MAD over history ----------------------------
    T = x.shape[0]
    n_i = jnp.sum(hist_mask.astype(jnp.int32))
    i0 = jnp.clip((n_i - 1) // 2, 0, T - 1)
    i1 = jnp.clip(n_i // 2, 0, T - 1)
    xs = jnp.sort(jnp.where(hist_mask, xf, jnp.inf))
    med = 0.5 * (xs[i0] + xs[i1])
    dev_sorted = jnp.sort(jnp.where(hist_mask, jnp.abs(xf - med), jnp.inf))
    mad = 0.5 * (dev_sorted[i0] + dev_sorted[i1])
    scale = jnp.maximum(1.4826 * mad,
                        jnp.where(jnp.isfinite(sigma), sigma, 0.0))
    robust_z = jnp.max(jnp.where(checked_mask, jnp.abs(xf - med), 0.0)) \
        / jnp.maximum(scale, 1e-30)
    robust_z = jnp.where(n_i > 0, robust_z, 0.0)

    return {
        "count": count,                   # violations of the REAL band
        "shrunk_count": shrunk_count,     # violations of the shrunk band
        "checked": jnp.sum(checked_mask.astype(jnp.int32)),
        "n_hist": n_i,
        "upper_mean": upper_mean,
        "lower_mean": lower_mean,
        "resid_z": resid_z,
        "robust_z": robust_z,
        "sigma": sigma,
    }


# one fused program per (rung, T) bucket: rows from every screened family
# ride the same launch. Async-dispatched like every jitted kernel; the
# engine materializes under its watchdog before routing.
@partial(jax.jit, static_argnames=("window",))
def screen_rows(values, mask, region, threshold, bound, min_lower_bound,
                margin, window):
    """Fused batched screen over (B, T) packed rows; `window` is static
    (one compiled program per ma_window), positional or keyword — the
    explicit signature lets jit resolve the name to its position, which
    `jit(vmap(...), static_argnames=...)` cannot (vmap's *args wrapper
    hides the signature, silently tracing `window` instead)."""
    # row-blocked (ops/rowblock.py): the screen's median/MAD sorts are the
    # same compile-time hazard on the TPU as the pair family's rank view
    return vmap_rows(
        partial(_screen_1d, window=window),
        (values, mask, region, threshold, bound, min_lower_bound, margin),
        values.shape[-1])


def triage_arg_spec(B: int, T: int):
    """Zeroed argument tuple matching the engine's screen packing (minus
    the static `window`), for `engine.pipeline.prewarm` — same contract
    as `parallel.fleet.pair_arg_spec`: drift from the real packing fails
    the prewarm-coverage regression test, it cannot silently de-warm."""
    return (
        np.zeros((B, T), np.float32),   # values
        np.zeros((B, T), bool),         # mask
        np.zeros((B, T), bool),         # current region
        np.zeros(B, np.float32),        # policy threshold (sigmas)
        np.ones(B, np.int32),           # bound bitmask
        np.zeros(B, np.float32),        # min lower bound
        np.zeros(B, np.float32),        # shrink margin (sigmas)
    )
