"""Holt-Winters at fleet size (ISSUE 36): the kernel against a plain
float64 reference (`tests/hw_reference.py`), the season indexed by
`t mod period` against the rolled buffer, the candidates side by side
against one at a time, and the band launch that partitions a chunk by
detected period on the device."""
import numpy as np
import pytest

import hw_reference as ref
from foremast_tpu.engine import families
from foremast_tpu.engine.analyzer import Analyzer, _BandItem
from foremast_tpu.engine.config import EngineConfig, MetricPolicy
from foremast_tpu.engine.jobs import Document, JobStore, MetricQueries
from foremast_tpu.engine.pipeline import CompileCounter
from foremast_tpu.dataplane.fetch import FixtureDataSource
from foremast_tpu.ops import forecast as fc
from foremast_tpu.ops.windowing import Window
from foremast_tpu.utils import tracing
from foremast_tpu.utils.timeutils import to_rfc3339

STEP = 60.0
# float32 against float64 over a recurrence of up to 1,024 steps on values
# near 10: the state contracts, so the error stays at a few ulps of 10
PRED_ATOL = 2e-4
TIE_REL = 1e-4  # float64 errors this close are one to the float32 fit


def _rows(seed, B, T, period, gaps=True):
    """Seasonal rows with a trend and gaps: values (B, T), validity."""
    rng = np.random.default_rng(seed)
    t = np.arange(T)
    x = (10.0 + 0.002 * t * rng.uniform(-1, 1, (B, 1))
         + 2.0 * np.sin(2 * np.pi * (t / period + rng.random((B, 1))))
         + 0.3 * rng.standard_normal((B, T)))
    mask = np.ones((B, T), bool)
    if gaps:
        mask &= rng.random((B, T)) > 0.05
        mask[0, 3 * period:5 * period + 3] = False  # longer than a period
        mask[1, :2] = False                         # a late start
    return np.round(x, 4).astype(np.float32), mask


SHAPES = [(8, 256, 12), (16, 512, 12), (8, 1024, 48), (32, 1024, 48),
          (8, 500 + 12, 48)]


# ------------------------------------------- (a) the kernel, per candidate
@pytest.mark.parametrize("B,T,period", SHAPES[:4])
def test_predictions_of_every_candidate_match_the_reference(B, T, period):
    x, mask = _rows(1, B, T, period)
    for alpha, beta, gamma in ref.GRID[::7]:
        got = np.asarray(fc.holt_winters_predictions(
            x, mask, period, np.full(B, alpha, np.float32),
            np.full(B, beta, np.float32), np.full(B, gamma, np.float32)))
        for i in range(B):
            want = ref.hw_predictions(x[i], mask[i], period, alpha, beta,
                                      gamma)
            np.testing.assert_allclose(got[i], want, rtol=0, atol=PRED_ATOL)


@pytest.mark.parametrize("B,T,period", SHAPES[:4])
def test_the_winner_is_the_reference_winner_or_a_near_tie(B, T, period):
    x, mask = _rows(2, B, T, period)
    fit = mask & (np.arange(T) >= 2 * period)
    params, preds = fc.fit_holt_winters(x, mask, fit, period)
    params, preds = np.asarray(params), np.asarray(preds)
    for i in range(B):
        errs = ref.grid_errors(x[i], mask[i], fit[i], period)
        g = int(np.argmin(np.abs(ref.GRID - params[i]).sum(axis=1)))
        np.testing.assert_allclose(params[i], ref.GRID[g], rtol=1e-6)
        assert errs[g] <= errs.min() * (1.0 + TIE_REL)
        np.testing.assert_allclose(
            preds[i], ref.hw_predictions(x[i], mask[i], period, *ref.GRID[g]),
            rtol=0, atol=PRED_ATOL)


def test_a_row_with_under_two_periods_of_history_takes_the_first_candidate():
    """No fit slot (t >= 2 period inside the history): every error is 0,
    the tie goes to the grid's first candidate, and the predictions are
    that candidate's: the documented behaviour."""
    period, T = 48, 256
    x, mask = _rows(3, 8, T, period, gaps=False)
    mask[:, 90:] = False  # 90 points: under two periods
    fit = mask & (np.arange(T) >= 2 * period)
    assert not fit.any()
    params, preds = fc.fit_holt_winters(x, mask, fit, period)
    np.testing.assert_allclose(np.asarray(params),
                               np.tile(ref.GRID[0], (8, 1)), rtol=1e-6)
    want = ref.hw_predictions(x[0], mask[0], period, *ref.GRID[0])
    np.testing.assert_allclose(np.asarray(preds)[0], want, atol=PRED_ATOL)


@pytest.mark.parametrize("B,T,period", [(16, 512, 12), (16, 1024, 48)])
def test_detect_period_matches_the_published_rule(B, T, period):
    x, mask = _rows(4, B, T, period)
    cands = (6, 12, 48, 96)
    got, scores = fc.detect_period(x, mask, cands, 24, 0.2)
    got, scores = np.asarray(got), np.asarray(scores)
    decided = 0
    for i in range(B):
        want, want_scores, margin = ref.detect_period(
            x[i], mask[i], cands, 24, 0.2)
        fin = np.isfinite(want_scores)
        np.testing.assert_allclose(scores[i][fin], want_scores[fin],
                                   atol=2e-5)
        if margin > 1e-4:  # a float32 sum cannot cross it
            assert got[i] == want
            decided += 1
    assert decided >= B - 2


# --------------------------------- (b) the indexed season against the roll
@pytest.mark.parametrize("T,period", [(250, 12), (500, 48), (131, 7)])
def test_indexed_season_equals_the_rolled_buffer(T, period):
    """A period that does not divide T, and a masked stretch longer than a
    period: `season[t mod p]` and the queue rolled every step give the
    same predictions, in numpy and in the kernel."""
    assert T % period
    x, mask = _rows(5, 4, T, period)
    mask[2, period + 5:3 * period + 1] = False
    for alpha, beta, gamma in ref.GRID[[0, 17, 38, 59]]:
        for i in range(4):
            indexed = ref.hw_predictions(x[i], mask[i], period, alpha, beta,
                                         gamma)
            rolled = ref.hw_predictions_rolled(x[i], mask[i], period, alpha,
                                               beta, gamma)
            np.testing.assert_allclose(indexed, rolled, rtol=0, atol=1e-12)
            got = np.asarray(fc._hw_1d(x[i], mask[i], period, alpha, beta,
                                       gamma))
            np.testing.assert_allclose(got, rolled, rtol=0, atol=PRED_ATOL)


# ------------------------ (c) side by side against one candidate at a time
@pytest.mark.parametrize("B,T,period", SHAPES)
def test_side_by_side_errors_equal_one_candidate_at_a_time(B, T, period):
    x, mask = _rows(6, B, T, period)
    fit = mask & (np.arange(T) >= 2 * period)
    grid = fc._default_grid()
    side = np.asarray(fc._hw_grid_errors(
        x.T, mask.T, fit.T, period, grid))  # (G, B)
    n = np.maximum(fit.sum(axis=1), 1)
    for g in range(0, len(ref.GRID), 5):
        a, b, c = (np.full(B, v, np.float32) for v in ref.GRID[g])
        preds = np.asarray(fc.holt_winters_predictions(x, mask, period,
                                                       a, b, c))
        r = np.where(fit, x - preds, 0.0).astype(np.float64)
        np.testing.assert_allclose(side[g], (r * r).sum(axis=1) / n,
                                   rtol=2e-5)


@pytest.mark.parametrize("B,T,period", [(24, 256, 12), (40, 512, 48)])
def test_grouped_run_gives_the_ungrouped_winners(B, T, period, monkeypatch):
    """The shape rule, not a knob, decides the groups: with room for half
    the candidates' seasonal state it runs two groups, one after another,
    and elects the same winners with the same predictions."""
    x, mask = _rows(7, B, T, period)
    fit = mask & (np.arange(T) >= 2 * period)
    assert fc._hw_groups(period, fc.HW_CANDIDATES, B) == 1
    whole = fc.fit_holt_winters(x, mask, fit, period)
    monkeypatch.setattr(fc, "_HW_STATE_BYTES_MAX",
                        4 * period * (fc.HW_CANDIDATES // 2) * B)
    assert fc._hw_groups(period, fc.HW_CANDIDATES, B) == 2
    assert fc.hw_state_bytes(period, fc.HW_CANDIDATES, B) \
        == 4 * period * 30 * B
    fc.fit_holt_winters.clear_cache()
    try:
        grouped = fc.fit_holt_winters(x, mask, fit, period)
    finally:
        fc.fit_holt_winters.clear_cache()
    np.testing.assert_array_equal(np.asarray(whole[0]),
                                  np.asarray(grouped[0]))
    np.testing.assert_array_equal(np.asarray(whole[1]),
                                  np.asarray(grouped[1]))


def test_groups_from_the_shapes_at_fleet_size():
    """The full chunk's 5.66e9 bytes of seasonal state fit as one group; a
    chunk four times as tall runs four."""
    assert fc.hw_state_bytes(1440, 60, 16384) == 5_662_310_400
    assert fc._hw_groups(1440, 60, 16384) == 1
    assert fc._hw_groups(1440, 60, 65536) == 4
    assert fc._hw_groups(60, 60, 4096) == 1


# ------------------------------------------------------- (d) the launch
P_A, P_B = 12, 48
N_H, N_C = 400, 30


def _season_window(rng, period, n, start, loud=False):
    t = np.arange(start, start + n)
    v = 10.0 + 2.0 * np.sin(2 * np.pi * t / period) \
        + 0.1 * rng.standard_normal(n)
    if loud:
        v[n // 2:] += 5.0
    return Window(v.astype(np.float32), np.ones(n, bool), start * STEP, STEP)


def _band_items(cfg, n=20, seed=8):
    """Jobs of two periods, interleaved in claim order; some loud."""
    rng = np.random.default_rng(seed)
    policy = cfg.policy_for("latency")
    items = []
    for i in range(n):
        p = P_A if i % 3 else P_B
        items.append(_BandItem(
            f"j{i}", "latency", _season_window(rng, p, N_H, 0),
            _season_window(rng, p, N_C, N_H, loud=i % 4 == 0), policy))
    return items


def _hw_engine(**kw):
    cfg = EngineConfig(
        algorithm="holt_winters", hw_period_candidates=(P_A, P_B),
        policies={"latency": MetricPolicy(threshold=3.0, bound=3,
                                          min_lower_bound=0.0)}, **kw)
    return Analyzer(cfg, None, JobStore())


@pytest.mark.parametrize("which", ["verdicts", "bands", "anomaly_pairs"])
def test_two_periods_in_one_launch_equal_each_period_alone(which):
    eng = _hw_engine()
    items = _band_items(eng.config)
    got = families.family("band").score(eng, items)
    assert list(got) == [(it.job_id, "latency", "band") for it in items]
    assert eng.period_partitions_total == 2
    alone = {}
    for keep in (lambda i: i % 3, lambda i: not i % 3):
        part = [it for i, it in enumerate(items) if keep(i)]
        alone.update(families.family("band").score(_hw_engine(), part))
    assert any(r["unhealthy"] for r in got.values())
    assert not all(r["unhealthy"] for r in got.values())
    for key, r in got.items():
        if which == "verdicts":
            assert (r["unhealthy"], r["count"], r["first_ts"]) == (
                alone[key]["unhealthy"], alone[key]["count"],
                alone[key]["first_ts"])
        elif which == "bands":
            assert (r["upper"], r["lower"]) == (alone[key]["upper"],
                                                alone[key]["lower"])
        else:
            assert r["anomaly_pairs"] == alone[key]["anomaly_pairs"]


def test_the_block_goes_up_once_a_chunk():
    """`h2d_bytes_total` rises by the chunk's values and validity, once,
    and by (rows,) vectors: nothing of the block is uploaded again for a
    partition."""
    eng = _hw_engine()
    items = _band_items(eng.config)
    families.family("band").score(eng, items)
    rows, T = 64, 512  # 20 items pad to the 64 rung, 430 points to 512
    block = rows * T * (4 + 1)
    assert block <= eng.h2d_bytes_total < block + 64 * rows
    # and nothing (B, T) comes down but the flags
    assert eng.d2h_bytes_total < rows * T + 64 * rows


def test_a_partition_that_changes_size_inside_a_rung_compiles_nothing():
    eng = _hw_engine()
    items = _band_items(eng.config, n=40)
    families.family("band").score(eng, items)          # 27 and 13 rows
    counter = CompileCounter().start()
    try:
        # two jobs of each period leave: 25 and 11 rows, the same rungs
        families.family("band").score(
            eng, [it for i, it in enumerate(items) if i not in (0, 1, 2, 3)])
    finally:
        counter.stop()
    assert counter.compiles == 0


@pytest.mark.parametrize("algorithm", ["moving_average_all",
                                       "double_exponential_smoothing"])
def test_no_detection_outside_the_seasonal_forecasters(algorithm,
                                                       monkeypatch):
    cfg = EngineConfig(algorithm=algorithm)
    eng = Analyzer(cfg, None, JobStore())
    monkeypatch.setattr(
        fc, "detect_period",
        lambda *a, **k: pytest.fail("detection under " + algorithm))
    monkeypatch.setattr(
        fc, "take_rows",
        lambda *a, **k: pytest.fail("a partition under " + algorithm))
    got = families.family("band").score(eng, _band_items(cfg, n=6))
    assert len(got) == 6 and eng.period_partitions_total == 0


def test_static_period_runs_one_partitionless_launch():
    eng = _hw_engine(hw_period_auto=False, hw_period=P_A)
    got = families.family("band").score(eng, _band_items(eng.config, n=6))
    assert len(got) == 6 and eng.period_partitions_total == 0


# ------------------------------------------------ (e) the span and attrs
def _cycle(n_jobs=12):
    rng = np.random.default_rng(9)
    fixtures, store = {}, JobStore()
    for j in range(n_jobs):
        p = P_A if j % 3 else P_B
        t = np.arange(N_H + N_C)
        w = 10.0 + 2.0 * np.sin(2 * np.pi * t / p) \
            + 0.1 * rng.standard_normal(N_H + N_C)
        fixtures[f"h{j}"] = ((t[:N_H] * STEP).tolist(), w[:N_H].tolist())
        fixtures[f"c{j}"] = ((t[N_H:] * STEP).tolist(), w[N_H:].tolist())
        store.create(Document(
            id=f"j{j}", app_name="a", namespace="d", strategy="canary",
            start_time=to_rfc3339(0), end_time=to_rfc3339(0),
            metrics={"latency": MetricQueries(current=f"c{j}",
                                              historical=f"h{j}")}))
    cfg = EngineConfig(
        algorithm="holt_winters", hw_period_candidates=(P_A, P_B),
        policies={"latency": MetricPolicy(threshold=3.0, bound=3,
                                          min_lower_bound=0.0)})
    eng = Analyzer(cfg, FixtureDataSource(fixtures), store)
    tracing.tracer.reset()
    eng.run_cycle(now=1_000_000.0)
    root = next(t for t in tracing.tracer.snapshot(limit=8)
                if t["name"] == tracing.SPAN_ENGINE_CYCLE)
    return eng, root


def _find(span, name, path=()):
    hits = [(span, path)] if span["name"] == name else []
    for c in span.get("children", ()):
        hits += _find(c, name, path + (span["name"],))
    return hits


@pytest.mark.parametrize("what", ["span", "score_attrs", "partition",
                                  "exporter"])
def test_detect_period_span_and_counters_once_a_cycle(what):
    eng, root = _cycle()
    if what == "span":
        ((sp, path),) = _find(root, tracing.SPAN_ENGINE_DETECT_PERIOD)
        assert path[-2:] == (tracing.SPAN_ENGINE_DISPATCH,
                             tracing.SPAN_ENGINE_LAUNCH)
        assert sp["attrs"]["rows"] == 12
        assert sp["attrs"]["partitions"] == 2
        assert sp["attrs"]["period_rows"] == {str(P_A): 8, str(P_B): 4}
        assert sp["attrs"]["d2h_bytes"] == 16 * 4  # the rung's int32 periods
        assert tracing.SPAN_ENGINE_DETECT_PERIOD in tracing.SPAN_NAMES
    elif what == "score_attrs":
        ((sp, _),) = _find(root, tracing.SPAN_ENGINE_SCORE)
        assert sp["attrs"]["period_partitions"] == 2
        assert sp["attrs"]["hw_candidates"] == fc.HW_CANDIDATES == 60
        # the largest partition's season: (period, 60, rung of 8 rows)
        assert sp["attrs"]["hw_state_bytes"] == max(
            fc.hw_state_bytes(P_A, 60, 16), fc.hw_state_bytes(P_B, 60, 16))
    elif what == "partition":
        part = eng.last_cycle_stages["partition"]
        assert part["counters"]["period_partitions"] == 2
        assert part["counters"]["hw_state_bytes"] > 0
        # the cycle's partition still sums to the root span
        assert sum(part["seconds"].values()) == pytest.approx(
            root["duration_ms"] * 1e-3, abs=5e-3)
        ((launch, _),) = _find(root, tracing.SPAN_ENGINE_LAUNCH)
        ((detect, _),) = _find(root, tracing.SPAN_ENGINE_DETECT_PERIOD)
        assert detect["duration_ms"] <= launch["duration_ms"]
    else:
        gauges = {name: value for name, _, value in eng.exporter.samples()}
        assert gauges["foremastbrain:period_partitions"] == 2
        assert gauges["foremastbrain:hw_state_bytes"] > 0
