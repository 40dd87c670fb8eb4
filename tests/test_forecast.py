"""Forecaster kernels vs straightforward numpy reference loops."""
import numpy as np
import pytest

from foremast_tpu.ops import forecast as fc


def _series(seed, T=64, gaps=True):
    rng = np.random.default_rng(seed)
    x = (10 + np.sin(np.arange(T) * 0.3) * 3 + rng.normal(0, 0.5, T)).astype(
        np.float32
    )
    mask = np.ones(T, bool)
    if gaps:
        mask[rng.choice(T, size=T // 8, replace=False)] = False
    return x, mask


def _np_ses(x, mask, alpha):
    preds = np.zeros_like(x)
    s = x[np.argmax(mask)]
    for t in range(len(x)):
        preds[t] = s
        if mask[t]:
            s = alpha * x[t] + (1 - alpha) * s
    return preds


def _np_des(x, mask, alpha, beta):
    preds = np.zeros_like(x)
    lvl = x[np.argmax(mask)]
    b = 0.0
    for t in range(len(x)):
        preds[t] = lvl + b
        if mask[t]:
            lvl_new = alpha * x[t] + (1 - alpha) * (lvl + b)
            b = beta * (lvl_new - lvl) + (1 - beta) * b
            lvl = lvl_new
        else:
            lvl = lvl + b
    return preds


@pytest.mark.parametrize("seed", range(4))
def test_ses_matches_numpy(seed):
    x, mask = _series(seed)
    alpha = 0.4
    got = np.asarray(fc.ses_predictions(x[None], mask[None], np.float32([alpha])))[0]
    np.testing.assert_allclose(got, _np_ses(x, mask, alpha), rtol=1e-5)


@pytest.mark.parametrize("seed", range(4))
def test_des_matches_numpy(seed):
    x, mask = _series(seed)
    got = np.asarray(
        fc.des_predictions(x[None], mask[None], np.float32([0.5]), np.float32([0.2]))
    )[0]
    np.testing.assert_allclose(got, _np_des(x, mask, 0.5, 0.2), rtol=1e-4, atol=1e-4)


def test_moving_average_causal():
    x = np.arange(10, dtype=np.float32)
    mask = np.ones(10, bool)
    got = np.asarray(fc.moving_average_predictions(x[None], mask[None], 3))[0]
    # pred[t] = mean of last 3 points before t
    np.testing.assert_allclose(got[4], np.mean([1, 2, 3]))
    np.testing.assert_allclose(got[1], 0.0)  # only x[0] seen
    np.testing.assert_allclose(got[0], 0.0)  # nothing seen -> first valid value


def test_moving_average_skips_gaps():
    # window covers time slots [t-3, t); the masked slot shrinks the sample
    x = np.array([1, 100, 3, 5, 7], np.float32)
    mask = np.array([True, False, True, True, True])
    got = np.asarray(fc.moving_average_predictions(x[None], mask[None], 3))[0]
    np.testing.assert_allclose(got[4], np.mean([3, 5]))  # 100 never enters


def test_holt_winters_learns_seasonality():
    P = 12
    t = np.arange(240)
    x = (10 + 5 * np.sin(2 * np.pi * t / P)).astype(np.float32)
    mask = np.ones_like(x, bool)
    preds = np.asarray(
        fc.holt_winters_predictions(
            x[None], mask[None], P, np.float32([0.3]), np.float32([0.05]), np.float32([0.3])
        )
    )[0]
    # after two seasons, predictions track the cycle closely
    err = np.abs(preds[3 * P :] - x[3 * P :]).mean()
    assert err < 0.6, err


def test_fit_holt_winters_beats_fixed_bad_params():
    P = 12
    t = np.arange(240)
    rng = np.random.default_rng(0)
    x = (10 + 5 * np.sin(2 * np.pi * t / P) + rng.normal(0, 0.2, t.size)).astype(
        np.float32
    )
    mask = np.ones_like(x, bool)
    fit_region = np.zeros_like(mask)
    fit_region[2 * P :] = True
    params, preds = fc.fit_holt_winters(x[None], mask[None], fit_region[None], P)
    sse_fit = np.mean((np.asarray(preds)[0][fit_region] - x[fit_region]) ** 2)
    bad = np.asarray(
        fc.holt_winters_predictions(
            x[None], mask[None], P, np.float32([0.9]), np.float32([0.3]), np.float32([0.05])
        )
    )[0]
    sse_bad = np.mean((bad[fit_region] - x[fit_region]) ** 2)
    assert sse_fit <= sse_bad + 1e-6


def test_band_anomalies_modes():
    B, T = 3, 20
    x = np.zeros((B, T), np.float32)
    mask = np.ones((B, T), bool)
    region = np.zeros((B, T), bool)
    region[:, 10:] = True
    preds = np.zeros((B, T), np.float32)
    x[0, 15] = 10.0  # spike up
    x[1, 15] = -10.0  # spike down
    x[2, 15] = -10.0  # spike down but upper-only bound
    sigma = np.ones(B, np.float32)
    thr = np.full(B, 3.0, np.float32)
    modes = np.array([fc.BOUND_BOTH, fc.BOUND_BOTH, fc.BOUND_UPPER], np.int32)
    floor = np.full(B, -np.inf, np.float32)
    out = fc.band_anomalies(x, mask, region, preds, sigma, thr, modes, floor)
    assert list(np.asarray(out["count"])) == [1, 1, 0]
    assert list(np.asarray(out["first_index"]))[:2] == [15, 15]
    assert np.asarray(out["checked"]).tolist() == [10, 10, 10]


def test_band_min_lower_bound_floor():
    # min_lower_bound clamps the lower band UP: with pred=1, thr=2 the raw
    # lower band is -1 (x=0 in-band); flooring it at 0.5 makes x=0 anomalous.
    B, T = 1, 12
    x = np.zeros((B, T), np.float32)
    mask = np.ones((B, T), bool)
    region = np.ones((B, T), bool)
    preds = np.full((B, T), 1.0, np.float32)
    sigma = np.ones(B, np.float32)
    thr = np.full(B, 2.0, np.float32)
    modes = np.array([fc.BOUND_BOTH], np.int32)
    out = fc.band_anomalies(
        x, mask, region, preds, sigma, thr, modes, np.float32([-np.inf])
    )
    assert int(out["count"][0]) == 0
    out2 = fc.band_anomalies(
        x, mask, region, preds, sigma, thr, modes, np.float32([0.5])
    )
    assert int(out2["count"][0]) == 12


def test_band_bitmask_upper_only_ignores_dips():
    B, T = 1, 8
    x = np.full((B, T), -10.0, np.float32)
    mask = np.ones((B, T), bool)
    region = np.ones((B, T), bool)
    preds = np.zeros((B, T), np.float32)
    out = fc.band_anomalies(
        x,
        mask,
        region,
        preds,
        np.ones(B, np.float32),
        np.full(B, 2.0, np.float32),
        np.array([fc.BOUND_UPPER], np.int32),
        np.float32([-np.inf]),
    )
    assert int(out["count"][0]) == 0


def test_band_bounds_are_region_means_a_row():
    """`upper` and `lower` are (B,): the mean over every slot of the
    judged region (valid or not) of preds +- threshold * sigma, the floor
    applied to the lower curve: what the host took of the (B, T) curves.
    `region_masks` makes that region from two vectors on the device."""
    rng = np.random.default_rng(8)
    B, T = 4, 300
    x = rng.normal(10.0, 1.0, (B, T)).astype(np.float32)
    preds = rng.normal(10.0, 0.3, (B, T)).astype(np.float32)
    mask = rng.random((B, T)) > 0.15
    n_hist = np.asarray([250, 100, 0, 299], np.int32)
    n_total = np.asarray([290, 101, 300, 300], np.int32)
    region, hist_mask = (np.asarray(a) for a in
                         fc.region_masks(mask, n_hist, n_total))
    expect = np.zeros((B, T), bool)
    for i in range(B):
        expect[i, n_hist[i]:n_total[i]] = True
    np.testing.assert_array_equal(region, expect)
    np.testing.assert_array_equal(hist_mask, mask & ~expect)

    sigma = np.asarray([0.5, 1.0, 2.0, np.inf], np.float32)
    thr = np.asarray([3.0, 2.0, 1.0, 3.0], np.float32)
    floor = np.asarray([-np.inf, 9.0, 9.5, 0.0], np.float32)
    out = {k: np.asarray(v) for k, v in fc.band_anomalies(
        x, mask, region, preds, sigma, thr,
        np.full(B, fc.BOUND_BOTH, np.int32), floor).items()}
    assert [k for k, v in out.items() if v.ndim == 2] == ["flags"]
    upper = preds + (thr * sigma)[:, None]
    lower = np.maximum(preds - (thr * sigma)[:, None], floor[:, None])
    for i in range(B):
        np.testing.assert_allclose(
            out["upper"][i], np.mean(upper[i][region[i]]), rtol=1e-6)
        np.testing.assert_allclose(
            out["lower"][i], np.mean(lower[i][region[i]]), rtol=1e-6)
        flags = ((x[i] > upper[i]) | (x[i] < lower[i])) & mask[i] & region[i]
        np.testing.assert_array_equal(out["flags"][i], flags)
        assert out["count"][i] == flags.sum()
        assert out["checked"][i] == (mask[i] & region[i]).sum()
    assert out["upper"][3] == np.inf and out["lower"][3] == 0.0


def test_moving_average_long_gap_forward_fills_recent():
    # review finding: a gap longer than the window must fall back to the most
    # recent value before the gap, not the start of the series
    T = 50
    x = np.zeros(T, np.float32)
    x[:10] = 1.0
    x[10:20] = 9.0
    mask = np.ones(T, bool)
    mask[20:45] = False  # 25-slot outage, window is 5
    got = np.asarray(fc.moving_average_predictions(x[None], mask[None], 5))[0]
    np.testing.assert_allclose(got[30], 9.0)  # last seen level, not 1.0


def test_moving_average_extrapolation_freezes_mean_not_last_point():
    # band-path finding (round 3): beyond `window` steps past the last
    # observation the prediction must hold the last rolling MEAN;
    # forward-filling the last raw sample anchors the entire extrapolated
    # band to one noisy point (an identical current window then scores
    # ~half its points outside the band whenever the final baseline
    # sample lands low)
    T = 40
    x = np.full(T, 10.0, np.float32)
    x[19] = 4.0  # noisy final observation
    mask = np.ones(T, bool)
    mask[20:] = False
    got = np.asarray(fc.moving_average_predictions(x[None], mask[None], 5))[0]
    np.testing.assert_allclose(got[30], np.mean(x[15:20]))  # 8.8, not 4.0


def test_kolmogorov_sf_small_x_is_one():
    from foremast_tpu.ops.stats import kolmogorov_sf

    # review finding: truncated series diverges for tiny x; must clamp to 1
    for x in (0.0, 0.005, 0.01, 0.05, 0.19):
        assert float(kolmogorov_sf(np.float32(x))) == 1.0
    import scipy.stats.distributions as dist

    for x in (0.3, 0.5, 1.0, 2.0):
        np.testing.assert_allclose(
            float(kolmogorov_sf(np.float32(x))), dist.kstwobign.sf(x), atol=1e-5
        )


def test_residual_sigma_no_history_fails_open():
    # review finding: empty history must widen the band to inf, not collapse
    # it to zero (which flagged everything)
    B, T = 1, 16
    x = np.ones((B, T), np.float32) * 5
    mask = np.ones((B, T), bool)
    region = np.ones((B, T), bool)  # everything is "current": no history
    preds = np.zeros((B, T), np.float32)
    sigma = np.asarray(fc.residual_sigma(x, preds, mask, ~region))
    assert np.isinf(sigma[0])
    out = fc.band_anomalies(
        x, mask, region, preds, sigma, np.float32([2.0]), np.int32([3]),
        np.float32([-np.inf]),
    )
    assert int(out["count"][0]) == 0  # cannot judge -> nothing flagged


def test_seasonal_trend_recovers_signal():
    """Prophet-core fit: trend + sinusoid recovered near-exactly without noise,
    and predictions extrapolate into a masked-out 'current' region."""
    B, T, period = 3, 256, 32
    t = np.arange(T, dtype=np.float32)
    rng = np.random.default_rng(0)
    xs = []
    for b in range(B):
        a0, a1 = rng.normal(5, 1), rng.normal(0.02, 0.01)
        amp = rng.normal(2, 0.2)
        xs.append(a0 + a1 * t + amp * np.sin(2 * np.pi * t / period))
    x = np.stack(xs).astype(np.float32)
    mask = np.ones((B, T), bool)
    fit = mask.copy()
    fit[:, -32:] = False  # last chunk is "current": excluded from the fit
    _, preds = fc.fit_seasonal_trend(x, mask, fit, period, order=3)
    preds = np.asarray(preds)
    np.testing.assert_allclose(preds[:, -32:], x[:, -32:], atol=0.05)


def test_seasonal_trend_matches_numpy_lstsq():
    """Parity with an unregularized numpy least-squares fit on masked data."""
    B, T, period, order = 2, 128, 24, 2
    rng = np.random.default_rng(1)
    x = rng.normal(10, 2, (B, T)).astype(np.float32)
    mask = rng.random((B, T)) > 0.2
    _, preds = fc.fit_seasonal_trend(x, mask, mask, period, order=order,
                                     ridge=1e-8)
    tn = np.arange(T) / (T - 1)
    w = 2 * np.pi * np.arange(T) / period
    cols = [np.ones(T), tn]
    for k in range(1, order + 1):
        cols += [np.sin(k * w), np.cos(k * w)]
    X = np.stack(cols, axis=-1)
    for b in range(B):
        sel = mask[b]
        beta, *_ = np.linalg.lstsq(X[sel], x[b, sel], rcond=None)
        # float32 sums over some 100 samples of values near 10, six well
        # separated columns: a few ulps of 10 (1e-6 each); measured up to
        # 4.8e-6 over five seeds
        np.testing.assert_allclose(np.asarray(preds)[b], X @ beta, atol=5e-5)


def test_seasonal_trend_sparse_series_stays_finite():
    # ridge keeps the solve well-posed with almost no valid points
    x = np.zeros((1, 64), np.float32)
    mask = np.zeros((1, 64), bool)
    mask[0, 5] = True
    _, preds = fc.fit_seasonal_trend(x, mask, mask, 16)
    assert np.all(np.isfinite(np.asarray(preds)))


# ------------------------------------------------------- seasonality detection
def test_detect_period_recovers_true_period_with_trend_and_gaps():
    """Masked, trending, noisy series: detection votes the true cycle from
    the candidate set (SURVEY §7 hard part: HW seasonality detection)."""
    B, T = 6, 512
    rng = np.random.default_rng(0)
    t = np.arange(T)
    periods = [24, 24, 96, 96, 24, 96]
    x = np.stack([
        5.0 + 0.01 * t + 2.0 * np.sin(2 * np.pi * t / p)
        + rng.normal(0, 0.2, T)
        for p in periods
    ]).astype(np.float32)
    mask = rng.random((B, T)) > 0.15  # real fetches have gaps
    chosen, scores = fc.detect_period(
        x, mask, (24, 96, 384), np.int32(1440), np.float32(0.2)
    )
    assert np.asarray(chosen).tolist() == periods
    assert np.all(np.asarray(scores)[np.arange(B), [0, 0, 1, 1, 0, 1]] > 0.8)


def test_detect_period_aperiodic_falls_back():
    B, T = 3, 256
    rng = np.random.default_rng(1)
    x = rng.normal(10, 1, (B, T)).astype(np.float32)
    mask = np.ones((B, T), bool)
    chosen, _ = fc.detect_period(
        x, mask, (24, 96), np.int32(777), np.float32(0.2)
    )
    assert np.all(np.asarray(chosen) == 777)


def test_detect_period_unsupported_candidates_fall_back():
    """A candidate longer than half the (valid) history has no 2-cycle
    support and must not be chosen, however strong the noise ACF."""
    T = 100
    t = np.arange(T)
    x = (np.sin(2 * np.pi * t / 80) + 1.0).astype(np.float32)[None]
    mask = np.ones((1, T), bool)
    chosen, scores = fc.detect_period(
        x, mask, (80, 120), np.int32(55), np.float32(0.2)
    )
    # lag 80 leaves only 20 overlap pairs (< 80): unsupported; 120 >= T
    assert np.asarray(scores).max() == -np.inf
    assert int(np.asarray(chosen)[0]) == 55


# ------------------- VERDICT r04 #5: Prophet changepoints (piecewise trend)
def test_changepoint_fit_recovers_kinked_trend():
    """A 2-kink piecewise-linear trend (flat -> climb -> decline) with
    daily-ish seasonality: the single-trend fit (n_changepoints=0)
    mis-tracks the regime changes; the hinge fit follows them. This is
    Prophet's defining trend flexibility (docs/guides/design.md:53-88
    names Prophet for single-metric forecasting)."""
    import numpy as np

    from foremast_tpu.ops import forecast as fc

    T, period = 420, 60
    t = np.arange(T, dtype=np.float32)
    trend = np.where(t < 140, 10.0,
                     np.where(t < 280, 10.0 + 0.08 * (t - 140),
                              10.0 + 0.08 * 140 - 0.10 * (t - 280)))
    season = 1.5 * np.sin(2 * np.pi * t / period)
    rng = np.random.default_rng(0)
    x = (trend + season + rng.normal(0, 0.25, T)).astype(np.float32)[None]
    mask = np.ones((1, T), bool)

    _, flat = fc.fit_seasonal_trend(x, mask, mask, period, 3,
                                    n_changepoints=0)
    _, kinked = fc.fit_seasonal_trend(x, mask, mask, period, 3,
                                      n_changepoints=12)
    rms = lambda p: float(np.sqrt(np.mean((np.asarray(p)[0] - x[0]) ** 2)))
    assert rms(kinked) < 0.6 * rms(flat), (rms(kinked), rms(flat))
    # the hinge fit tracks the truth to near the noise floor; the single
    # trend is off by whole units around the regime changes
    assert rms(kinked) < 0.6
    assert rms(flat) > 1.0


def test_changepoint_band_catches_anomaly_the_flat_fit_is_blind_to():
    """End-shape of the VERDICT item: on a series whose trend bent
    mid-history, the single-trend fit mis-bands — its own fit residuals
    inflate sigma (measured ~2.5 vs ~0.18 here, a 14x-wider band), so a
    genuine +2-unit anomaly in the current window sails through
    undetected (the +1.2 step below sits far inside the flat fit's
    inflated band). The changepoint trend tracks the kink, keeps sigma
    at the noise floor, and flags the same anomaly."""
    import numpy as np

    from foremast_tpu.ops import forecast as fc

    T, period = 420, 60
    region_len = 30
    t = np.arange(T, dtype=np.float32)
    trend = np.where(t < 200, 20.0, 20.0 + 0.09 * (t - 200))
    x = (trend + 1.0 * np.sin(2 * np.pi * t / period)
         + np.random.default_rng(1).normal(0, 0.2, T)).astype(np.float32)[None]
    x[:, -region_len:] += 1.2  # real anomaly: step jump in the region
    mask = np.ones((1, T), bool)
    region = np.zeros((1, T), bool)
    region[:, -region_len:] = True
    hist = mask & ~region
    thr = np.float32([3.0])
    bound = np.int32([fc.BOUND_BOTH])
    mlb = np.float32([0.0])

    def verdict(n_cp):
        _, preds = fc.fit_seasonal_trend(x, hist, hist, period, 3,
                                         n_changepoints=n_cp)
        sigma = fc.residual_sigma(x, np.asarray(preds), hist, hist)
        out = fc.band_anomalies(x, mask, region, np.asarray(preds),
                                np.asarray(sigma), thr, bound, mlb)
        return int(out["count"][0]), float(sigma[0])

    n_kinked, sig_kinked = verdict(12)
    n_flat, sig_flat = verdict(0)
    assert sig_flat > 5 * sig_kinked  # the mis-band, quantified
    assert n_kinked >= 10  # anomaly caught through the kinked trend
    assert n_flat <= 2  # flat fit's inflated band swallowed it


def test_detect_period_alias_margin_boundary():
    """VERDICT r04 #7: the alias margin is a knob, exercised AT its
    boundary. A period-97 pulse train scored against candidates (96, 97):
    lag 96 misaligns the pulses by one step, giving a controlled score
    gap below the best. A margin wider than the gap admits the earlier
    (shorter) candidate — which then wins by candidate order; a margin
    narrower than the gap leaves only the true best eligible."""
    T = 2048
    t = np.arange(T)
    x = ((t % 97) < 8).astype(np.float32)[None] * 3.0
    mask = np.ones((1, T), bool)
    _, scores = fc.detect_period(x, mask, (96, 97), np.int32(7),
                                 np.float32(0.05))
    s96, s97 = np.asarray(scores)[0]
    gap = float(s97 - s96)
    assert 0.02 < gap < 0.5  # the fixture really is a controlled near-tie
    # margin just ABOVE the gap: the shorter candidate is eligible -> wins
    chosen, _ = fc.detect_period(x, mask, (96, 97), np.int32(7),
                                 np.float32(0.05),
                                 alias_margin=np.float32(gap + 0.01))
    assert int(np.asarray(chosen)[0]) == 96
    # margin just BELOW the gap: only the best scorer is eligible
    chosen, _ = fc.detect_period(x, mask, (96, 97), np.int32(7),
                                 np.float32(0.05),
                                 alias_margin=np.float32(max(gap - 0.01, 0.0)))
    assert int(np.asarray(chosen)[0]) == 97


def test_detect_period_multi_period_fundamental_wins():
    """Hour+day composite traffic (both cycles genuinely present): the
    fundamental-first candidate order resolves the harmonic tie toward
    the SHORTer true cycle, and a day-only series still picks the day."""
    T = 4096
    t = np.arange(T)
    hour, day = 60, 1440
    rng = np.random.default_rng(3)
    both = (1.5 * np.sin(2 * np.pi * t / hour)
            + 1.5 * np.sin(2 * np.pi * t / day)
            + rng.normal(0, 0.1, T)).astype(np.float32)
    day_only = (2.0 * np.sin(2 * np.pi * t / day)
                + rng.normal(0, 0.1, T)).astype(np.float32)
    x = np.stack([both, day_only])
    mask = np.ones((2, T), bool)
    chosen, scores = fc.detect_period(x, mask, (hour, day), np.int32(7),
                                      np.float32(0.2))
    got = np.asarray(chosen).tolist()
    assert got[0] == hour  # composite: fundamental (shorter) wins
    assert got[1] == day  # pure daily: hour scores ~0, day wins outright


def test_detect_period_sub_candidate_period_elects_valid_multiple():
    """Review hardening: a true period BELOW every candidate (30 under
    candidates starting at 60) realigns exactly at both lag 60 and lag
    30, so the half-lag contrast sees a noise-level tie — which must
    PASS (60 is a harmonically valid seasonal period), not coin-flip
    into the fallback."""
    T = 4096
    t = np.arange(T)
    rng = np.random.default_rng(11)
    x = (2.0 * np.sin(2 * np.pi * t / 30)
         + rng.normal(0, 0.3, T)).astype(np.float32)[None]
    mask = np.ones((1, T), bool)
    chosen, _ = fc.detect_period(x, mask, (60, 480, 1440), np.int32(7),
                                 np.float32(0.2))
    assert int(np.asarray(chosen)[0]) == 60
