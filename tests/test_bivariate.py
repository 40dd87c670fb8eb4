"""Bivariate-normal joint scorer: ops-level behavior + engine dispatch.

The two-metric judgment mode from the reference's model menu
(docs/guides/design.md:53-88): joint Gaussian fit on history, k-sigma
Mahalanobis ellipse on the current window.
"""
import numpy as np

from foremast_tpu.engine import Analyzer, Document, EngineConfig, JobStore, MetricQueries
from foremast_tpu.engine import jobs as J
from foremast_tpu.dataplane import FixtureDataSource
from foremast_tpu.ops.bivariate import bivariate_normal_anomalies
from foremast_tpu.utils.timeutils import to_rfc3339

STEP = 60


def _span(n_hist, n_total):
    """One row's judged region [n_hist, n_total) as the kernel takes it."""
    return np.asarray([n_hist], np.int32), np.asarray([n_total], np.int32)


def _corr_pair(rng, n, rho=0.98, mu=(10.0, 5.0), scale=(1.0, 0.5)):
    z1 = rng.normal(size=n)
    z2 = rho * z1 + np.sqrt(1 - rho**2) * rng.normal(size=n)
    return mu[0] + scale[0] * z1, mu[1] + scale[1] * z2


def test_joint_anomaly_invisible_to_marginals():
    """Points that break the correlation structure are flagged even though
    each metric stays within its own k-sigma marginal band."""
    rng = np.random.default_rng(0)
    n_h, n_c = 400, 40
    x1h, x2h = _corr_pair(rng, n_h)
    # current: same marginals, correlation inverted -> jointly anomalous
    z1 = rng.normal(size=n_c)
    z2 = -0.98 * z1 + np.sqrt(1 - 0.98**2) * rng.normal(size=n_c)
    x1c = 10.0 + 2.0 * z1
    x2c = 5.0 + 1.0 * z2  # anti-correlated, amplitudes ~2 marginal sigma
    x1 = np.concatenate([x1h, x1c])[None].astype(np.float32)
    x2 = np.concatenate([x2h, x2c])[None].astype(np.float32)
    m = np.ones_like(x1, bool)
    out = bivariate_normal_anomalies(
        x1, m, x2, m, *_span(n_h, n_h + n_c), np.asarray([3.0], np.float32)
    )
    assert int(out["count"][0]) >= 5
    # marginal check: most current x1 points are inside mean +- 3 sigma
    inside = np.abs(x1c - x1h.mean()) < 3 * x1h.std()
    assert inside.mean() > 0.5


def test_healthy_current_not_flagged():
    rng = np.random.default_rng(1)
    x1h, x2h = _corr_pair(rng, 400)
    x1c, x2c = _corr_pair(rng, 40)
    x1 = np.concatenate([x1h, x1c])[None].astype(np.float32)
    x2 = np.concatenate([x2h, x2c])[None].astype(np.float32)
    m = np.ones_like(x1, bool)
    out = bivariate_normal_anomalies(
        x1, m, x2, m, *_span(400, 440), np.asarray([4.0], np.float32)
    )
    assert int(out["count"][0]) <= 1


def test_fail_open_without_history():
    x = np.ones((1, 10), np.float32)
    m = np.ones((1, 10), bool)
    span = _span(1, 10)  # a single history point: not judgeable
    out = bivariate_normal_anomalies(
        x * 100, m, x, m, *span, np.asarray([2.0], np.float32)
    )
    assert int(out["count"][0]) == 0


def test_min_lower_bound_floors_marginal_band():
    rng = np.random.default_rng(2)
    x1h, x2h = _corr_pair(rng, 200)
    x1 = x1h[None].astype(np.float32)
    x2 = x2h[None].astype(np.float32)
    m = np.ones_like(x1, bool)
    out = bivariate_normal_anomalies(
        x1, m, x2, m, *_span(150, 200), np.asarray([50.0], np.float32),
        np.asarray([9.0], np.float32), np.asarray([4.0], np.float32),
    )
    assert float(np.min(np.asarray(out["lower1"]))) >= 9.0
    assert float(np.min(np.asarray(out["lower2"]))) >= 4.0


def test_marginal_bounds_are_one_value_a_row():
    """The four bounds are (B,): the value every slot of the old
    broadcast (B, T) rows held, mu +- threshold * sigma over the history
    (population variance plus the kernel's ridge), the floor applied.
    Only `flags` is (B, T), and the distances are not an output."""
    rng = np.random.default_rng(3)
    B, T = 3, 256
    x1 = rng.normal(10.0, 1.0, (B, T)).astype(np.float32)
    x2 = rng.normal(5.0, 0.5, (B, T)).astype(np.float32)
    m1, m2 = rng.random((B, T)) > 0.1, rng.random((B, T)) > 0.1
    n_hist = np.asarray([200, 180, 255], np.int32)
    n_total = np.asarray([240, 256, 256], np.int32)
    thr = np.asarray([3.0, 2.0, 4.0], np.float32)
    floor1 = np.asarray([-1e9, 8.5, -1e9], np.float32)
    out = {k: np.asarray(v) for k, v in bivariate_normal_anomalies(
        x1, m1, x2, m2, n_hist, n_total, thr, floor1,
        np.full(B, -1e9, np.float32)).items()}
    assert sorted(out) == ["checked", "count", "first_index", "flags",
                           "lower1", "lower2", "upper1", "upper2"]
    assert [k for k, v in out.items() if v.ndim == 2] == ["flags"]
    for i in range(B):
        hist = m1[i] & m2[i]
        hist[n_hist[i]:n_total[i]] = False
        for x, floor, up, lo in ((x1, floor1[i], "upper1", "lower1"),
                                 (x2, -1e9, "upper2", "lower2")):
            h = x[i][hist].astype(np.float64)
            var = h.var()
            ridge = 1e-6 * max(x1[i][hist].astype(np.float64).var(),
                               x2[i][hist].astype(np.float64).var(), 1.0)
            sd = np.sqrt(var + ridge)
            np.testing.assert_allclose(out[up][i], h.mean() + thr[i] * sd,
                                       rtol=1e-5)
            np.testing.assert_allclose(
                out[lo][i], max(h.mean() - thr[i] * sd, floor), rtol=1e-5)


# ------------------------------------------------------------- engine dispatch
def _two_metric_job(fixtures, rng, *, bad):
    n_h, n_c = 400, 40
    x1h, x2h = _corr_pair(rng, n_h)
    if bad:
        z1 = rng.normal(size=n_c)
        x1c = 10.0 + 2.0 * z1
        x2c = 5.0 + 1.0 * z1 * -1.0  # correlation flipped
    else:
        x1c, x2c = _corr_pair(rng, n_c)
    h_ts = (np.arange(n_h) * STEP).tolist()
    c_ts = ((n_h + np.arange(n_c)) * STEP).tolist()
    fixtures["h1"] = (h_ts, x1h.tolist())
    fixtures["h2"] = (h_ts, x2h.tolist())
    fixtures["c1"] = (c_ts, x1c.tolist())
    fixtures["c2"] = (c_ts, x2c.tolist())
    return Document(
        id="bi", app_name="app", namespace="d", strategy="canary",
        start_time=to_rfc3339(0), end_time=to_rfc3339(0),
        metrics={
            "latency": MetricQueries(current="c1", historical="h1"),
            "cpu": MetricQueries(current="c2", historical="h2"),
        },
    )


def test_engine_bivariate_mode_flags_broken_correlation():
    rng = np.random.default_rng(3)
    fixtures = {}
    store = JobStore()
    store.create(_two_metric_job(fixtures, rng, bad=True))
    cfg = EngineConfig(algorithm="bivariate_normal", threshold=4.0, policies={})
    analyzer = Analyzer(cfg, FixtureDataSource(fixtures), store)
    out = analyzer.run_cycle(now=100_000.0)
    assert out["bi"] == J.COMPLETED_UNHEALTH
    assert "bivariate" in store.get("bi").reason


def test_engine_bivariate_mode_passes_healthy():
    rng = np.random.default_rng(4)
    fixtures = {}
    store = JobStore()
    store.create(_two_metric_job(fixtures, rng, bad=False))
    cfg = EngineConfig(algorithm="bivariate_normal", threshold=4.0, policies={})
    analyzer = Analyzer(cfg, FixtureDataSource(fixtures), store)
    out = analyzer.run_cycle(now=100_000.0)
    assert out["bi"] == J.COMPLETED_HEALTH


def test_bound_bitmask_upper_only_ignores_improvement_dips():
    """An upper-only metric pair (e.g. error rates, bound=1) must not alarm
    when both metrics drop far BELOW their history (an improvement)."""
    rng = np.random.default_rng(5)
    x1h, x2h = _corr_pair(rng, 300)
    n_c = 30
    x1c = np.full(n_c, x1h.mean() - 8 * x1h.std())
    x2c = np.full(n_c, x2h.mean() - 8 * x2h.std())
    x1 = np.concatenate([x1h, x1c])[None].astype(np.float32)
    x2 = np.concatenate([x2h, x2c])[None].astype(np.float32)
    m = np.ones_like(x1, bool)
    span = _span(300, 300 + n_c)
    thr = np.asarray([3.0], np.float32)
    upper_only = np.asarray([1], np.int32)
    both = np.asarray([3], np.int32)
    out = bivariate_normal_anomalies(
        x1, m, x2, m, *span, thr, None, None, upper_only, upper_only
    )
    assert int(out["count"][0]) == 0  # dips ignored
    out2 = bivariate_normal_anomalies(
        x1, m, x2, m, *span, thr, None, None, both, both
    )
    assert int(out2["count"][0]) == n_c  # two-sided policy still fires
