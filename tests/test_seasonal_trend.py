"""The seasonal-trend fit, upstream's Prophet menu entry (ISSUE 38): the
kernel, the band closure and `Analyzer.run_cycle` against the benchmark's
plain float64 reference (`benchmark/lib/reference_st.py`, which imports
nothing of the program), the `prophet` route, and the counters the fit
leaves on `engine.score`."""
import os
import sys

import numpy as np
import pytest

from foremast_tpu.dataplane.fetch import FixtureDataSource
from foremast_tpu.engine.analyzer import Analyzer
from foremast_tpu.engine.config import EngineConfig, MetricPolicy
from foremast_tpu.engine.jobs import Document, JobStore, MetricQueries
from foremast_tpu.ops import forecast as fc
from foremast_tpu.utils import tracing
from foremast_tpu.utils.timeutils import to_rfc3339

# the benchmark's own directory goes last on the path: nothing of the
# suite's is shadowed by it
_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if _BENCH not in sys.path:
    sys.path.append(_BENCH)
from lib import reference_st as ref  # noqa: E402

STEP = 60.0
# Gaps are in the reference's sigmas (the RMS residual of its own fit).
# Without hinges the system has 8 columns and a condition number near
# 1e2: what is left is float32 sums over up to 1,900 samples (measured up
# to 1.3e-4 on these rows).
TOL_FLAT = 1e-3
# With 12 hinges the columns are nearly dependent (condition number 1e5
# to 1e6): a float32 solve keeps about two digits of beta, and the
# reweighting 1 / (|beta| + 1e-3) carries a small beta's error into the
# next round's penalty. Measured up to 2.6e-2 on these rows, on any slot;
# a dropped hinge or a missing round moves the band by 0.4 to 6 sigmas.
TOL_HINGED = 0.08
POLICY = (3.0, 3, 0.0)  # latency: 3 sigmas, both sides, floor 0


def _rows(seed, B, n_hist, n_cur, period, gaps=True):
    """Seasonal rows whose trend bends once, with gaps: values and
    presence (B, n_hist + n_cur), four decimals as a store serves them."""
    rng = np.random.default_rng(seed)
    t = np.arange(n_hist + n_cur)
    bend = rng.integers(n_hist // 4, 3 * n_hist // 4, (B, 1))
    x = (10.0 + 0.004 * t * rng.uniform(-1, 1, (B, 1))
         + 0.01 * np.maximum(t - bend, 0) * rng.uniform(-1, 1, (B, 1))
         + 2.0 * np.sin(2 * np.pi * (t / period + rng.random((B, 1))))
         + 0.3 * rng.standard_normal((B, t.size)))
    m = np.ones(x.shape, bool)
    if gaps:
        m &= rng.random(x.shape) > 0.05
        m[0, 2 * period:3 * period + 3] = False  # longer than a period
        m[1, :2] = False                         # a late start
    return np.round(x, 4), m


def _packed(x, m, n_hist):
    """The rows as a band launch packs them: (values, validity, history
    mask, region) at the bucket's length."""
    B, n = x.shape
    T = ref.bucket(n)
    xp, mp = np.zeros((B, T), np.float32), np.zeros((B, T), bool)
    xp[:, :n], mp[:, :n] = x, m
    region, hist = fc.region_masks(mp, np.full(B, n_hist, np.int32),
                                   np.full(B, n, np.int32))
    return xp, mp, np.asarray(hist), np.asarray(region)


SHAPES = [(8, 400, 30, 12), (8, 400, 30, 48), (16, 900, 60, 48),
          (8, 1900, 100, 120)]


# ------------------------------------------------------- (a) the kernel
@pytest.mark.parametrize("changepoints", [0, 12])
@pytest.mark.parametrize("B,n_hist,n_cur,period", SHAPES)
def test_fit_matches_the_float64_reference(B, n_hist, n_cur, period,
                                           changepoints):
    x, m = _rows(5, B, n_hist, n_cur, period)
    xp, _, hist, _ = _packed(x, m, n_hist)
    beta, preds = fc.fit_seasonal_trend(xp, hist, hist, period, 3,
                                        n_changepoints=changepoints)
    cfg = {"st_order": 3, "st_changepoints": changepoints}
    fit = ref.fit_block(x[:, :n_hist], n_cur, period, cfg,
                        present=m[:, :n_hist])
    assert beta.shape == (B, fc.st_columns(3, changepoints))
    X = ref.design(xp.shape[1], period, 3, changepoints)
    # every slot of the bucket: fitted, judged and padding
    gap = np.abs(np.asarray(preds, np.float64) - fit["beta"] @ X.T).max(
        axis=1) / fit["sigma"]
    assert gap.max() < (TOL_HINGED if changepoints else TOL_FLAT), gap
    np.testing.assert_allclose(
        (fit["beta"] @ X.T)[:, n_hist:n_hist + n_cur], fit["preds"])


def test_hinges_past_the_history_are_held_at_zero():
    """A history shorter than 0.8 of its bucket: the last hinges start at
    or past its end, their columns are 0 over the fit, and the penalty
    alone holds their deltas at 0, in the program as in the reference."""
    x, m = _rows(2, 4, 600, 40, 48, gaps=False)
    xp, _, hist, _ = _packed(x, m, 600)  # bucket 1024: hinges from 630 on
    beta, _ = fc.fit_seasonal_trend(xp, hist, hist, 48, 3, n_changepoints=12)
    starts = 0.8 * np.arange(1, 13) / 13 * 1023
    past = 2 + np.nonzero(starts >= 599)[0]
    assert len(past) == 3
    assert np.all(np.asarray(beta)[:, past] == 0.0)
    fit = ref.fit_block(x[:, :600], 40, 48, {"st_order": 3,
                                            "st_changepoints": 12})
    assert np.all(fit["beta"][:, past] == 0.0)


# -------------------------------------------------- (b) the band closure
@pytest.mark.parametrize("B,n_hist,n_cur,period", SHAPES[:3])
def test_band_closure_matches_the_reference(B, n_hist, n_cur, period):
    x, m = _rows(11, B, n_hist, n_cur, period)
    x[::3, n_hist + n_cur // 2:] += 4.0  # a third of the rows are loud
    xp, mp, hist, region = _packed(x, m, n_hist)
    _, preds = fc.fit_seasonal_trend(xp, hist, hist, period, 3,
                                     n_changepoints=12)
    sigma = fc.residual_sigma(xp, preds, hist, hist)
    k, bound, floor = POLICY
    out = fc.band_anomalies(
        xp, mp, region, preds, sigma, np.full(B, k, np.float32),
        np.full(B, bound, np.int32), np.full(B, floor, np.float32))
    fit = ref.fit_block(x[:, :n_hist], n_cur, period,
                        {"st_order": 3, "st_changepoints": 12},
                        present=m[:, :n_hist])
    bands = ref.bands(fit, x[:, n_hist:], POLICY, TOL_HINGED,
                      present=m[:, n_hist:])
    checked = m[:, n_hist:].sum(axis=1)
    assert np.asarray(out["checked"]).tolist() == checked.tolist()
    loud = quiet = 0
    for i, (upper, lower, s, count, c_min, c_max) in enumerate(bands):
        assert abs(float(sigma[i]) - s) < 1e-3 * s
        assert abs(float(out["upper"][i]) - upper) < TOL_HINGED * s
        assert abs(float(out["lower"][i]) - lower) < TOL_HINGED * s
        assert c_min <= int(out["count"][i]) <= c_max, (i, count)
        # the verdict, where the bracket decides it
        gate = max(2, 0.1 * checked[i])
        if c_min >= gate or c_max < gate:
            assert (int(out["count"][i]) >= gate) == (count >= gate)
            loud += count >= gate
            quiet += count < gate
    assert loud and quiet


# --------------------------------------------- (c), (d) through run_cycle
P_A, P_B = 12, 48
N_H, N_C = 400, 30


def _fleet(n_jobs=12, seed=9):
    """Jobs of two periods interleaved in claim order, every fourth loud:
    (fixtures, the served rows (n_jobs, N_H + N_C), their periods)."""
    rng = np.random.default_rng(seed)
    t = np.arange(N_H + N_C)
    fixtures, rows, periods = {}, [], []
    for j in range(n_jobs):
        p = P_A if j % 3 else P_B
        w = 10.0 + 0.003 * t + 2.0 * np.sin(2 * np.pi * t / p) \
            + 0.1 * rng.standard_normal(t.size)
        if j % 4 == 0:
            w[N_H + N_C // 2:] += 5.0
        w = np.round(w, 4)
        fixtures[f"h{j}"] = ((t[:N_H] * STEP).tolist(), w[:N_H].tolist())
        fixtures[f"c{j}"] = ((t[N_H:] * STEP).tolist(), w[N_H:].tolist())
        rows.append(w)
        periods.append(p)
    return fixtures, np.asarray(rows), periods


def _cycle(algorithm, n_jobs=12, **kw):
    fixtures, rows, periods = _fleet(n_jobs)
    store = JobStore()
    for j in range(n_jobs):
        store.create(Document(
            id=f"j{j}", app_name=f"a{j}", namespace="d", strategy="canary",
            start_time=to_rfc3339(0), end_time=to_rfc3339(0),
            metrics={"latency": MetricQueries(current=f"c{j}",
                                              historical=f"h{j}")}))
    cfg = EngineConfig(
        algorithm=algorithm, hw_period_candidates=(P_A, P_B),
        policies={"latency": MetricPolicy(threshold=3.0, bound=3,
                                          min_lower_bound=0.0)}, **kw)
    eng = Analyzer(cfg, FixtureDataSource(fixtures), store)
    tracing.tracer.reset()
    outcomes = eng.run_cycle(now=1_000_000.0)
    root = next(t for t in tracing.tracer.snapshot(limit=8)
                if t["name"] == tracing.SPAN_ENGINE_CYCLE)
    return eng, root, outcomes, rows, periods


def _recorded(eng, n_jobs=12):
    """{job: its band entry of the cycle's record}, as `explain` serves."""
    out = {}
    for j in range(n_jobs):
        (entry,) = [f for f in eng.provenance.get(f"j{j}")["families"]
                    if f["family"] == "band"]
        out[j] = entry
    return out


def _find(span, name):
    hits = [span] if span["name"] == name else []
    for c in span.get("children", ()):
        hits += _find(c, name)
    return hits


def test_run_cycle_verdicts_and_bands_are_the_references():
    eng, _, outcomes, rows, periods = _cycle("seasonal_trend")
    assert list(outcomes) == [f"j{j}" for j in range(12)]
    hw_cfg = ref.settings({"hw_period_candidates": (P_A, P_B)})
    got = ref.band_rows(rows[:, :N_H], rows[:, N_H:], POLICY, hw_cfg,
                        {"st_order": 3, "st_changepoints": 12}, TOL_HINGED)
    # the reference detects the two periods the fleet was built with
    assert [r["periods"] for r in got] == [(p,) for p in periods]
    recorded = _recorded(eng)
    gate = max(2, 0.1 * N_C)
    for j, r in enumerate(got):
        ((upper, lower, s, count, c_min, c_max),) = r["bands"]
        lo, up = recorded[j]["band"]
        # the record keeps four decimals
        assert abs(up - upper) < TOL_HINGED * s + 1e-4
        assert abs(lo - lower) < TOL_HINGED * s + 1e-4
        assert c_min <= recorded[j]["anomalous_points"] <= c_max
        assert c_min >= gate or c_max < gate  # the fleet is decisive
        assert recorded[j]["unhealthy"] == (count >= gate) == (j % 4 == 0)
        assert (outcomes[f"j{j}"] == "completed_unhealth") == (j % 4 == 0)


@pytest.mark.parametrize("changepoints", [12, 0])
def test_fit_counters_read_what_the_shapes_say(changepoints):
    eng, root, *_ = _cycle("seasonal_trend", st_changepoints=changepoints)
    (sp,) = _find(root, tracing.SPAN_ENGINE_SCORE)
    columns = 2 + changepoints + 2 * 3
    solves = 2 * (3 if changepoints else 1)  # two partitions
    assert sp["attrs"]["period_partitions"] == 2
    assert sp["attrs"]["st_columns"] == columns == fc.st_columns(
        3, changepoints)
    assert sp["attrs"]["st_solves"] == solves
    assert sp["attrs"]["hw_candidates"] == sp["attrs"]["hw_state_bytes"] == 0
    counters = eng.last_cycle_stages["partition"]["counters"]
    assert (counters["st_columns"], counters["st_solves"]) == (columns, solves)
    gauges = {name: value for name, _, value in eng.exporter.samples()}
    assert gauges["foremastbrain:st_columns"] == columns


@pytest.mark.parametrize("algorithm", ["moving_average_all", "holt_winters"])
def test_no_fit_counters_under_another_forecaster(algorithm):
    eng, root, *_ = _cycle(algorithm)
    (sp,) = _find(root, tracing.SPAN_ENGINE_SCORE)
    assert sp["attrs"]["st_columns"] == sp["attrs"]["st_solves"] == 0
    gauges = {name: value for name, _, value in eng.exporter.samples()}
    assert gauges["foremastbrain:st_columns"] == 0


@pytest.mark.parametrize("algorithm", ["prophet", "prophet_all",
                                       "seasonal_trend_all"])
def test_prophet_is_the_seasonal_trend_route(algorithm):
    base, _, base_outcomes, *_ = _cycle("seasonal_trend")
    eng, root, outcomes, *_ = _cycle(algorithm)
    assert outcomes == base_outcomes
    assert _recorded(eng) == _recorded(base)
    (sp,) = _find(root, tracing.SPAN_ENGINE_SCORE)
    assert (sp["attrs"]["st_columns"], sp["attrs"]["st_solves"]) == (20, 6)
    assert sp["attrs"]["period_partitions"] == 2
