"""The third cell, `rollout7d_hw_polled`: its `--tiny` line, its control,
the faults `correct` has to catch under the Holt-Winters reference, its
cost function and trace readers, and what the family file refuses."""
import os
import types

import numpy as np
import pytest

import run as harness
from lib import check, costs, costs_hw, fleet as fleet_mod, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "rollout7d_hw_polled"
NUMBERS = ["pair_p_gap", "hw_band_gap", "hw_count_out", "hw_tie_rows",
           "hw_period_margin_rows"]


def _args(seed=3, trace=0):
    return types.SimpleNamespace(workload=CELL, seed=seed, seconds=0.2,
                                 trace=trace, tiny=True)


def _config(name="rollout7d_hw"):
    return fleet_mod.load_json(os.path.join(BENCH, "configs",
                                            name + ".json"))


# ---------------------------------------------------------------- a tiny run
@pytest.mark.parametrize("seed", [1, 4000000007])
def test_tiny_run_is_correct_and_detects_two_periods(seed):
    out = harness.run(_args(seed, trace=1))
    assert out["correct"] is True and out["failed"] == 0, out["compared"]
    assert list(out["compared"]) == NUMBERS + [
        "verdict_miss", "stale_jobs", "compiles_in_window"]
    assert 0 < out["compared"]["hw_band_gap"]["value"] < 0.003 / 5
    live = out["cycles"][-1]["offered"]
    assert out["cycles"][-1]["rows"] == {"pair": live, "band": live}
    assert out["cycles"][-1]["launches"] == 2
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["period_partitions_per_cycle"] == 2
    assert 0 < m["detect_s_per_cycle"] <= m["launch_s_per_cycle"]
    assert "hw_roofline" not in m and "hw_fit_device_s_per_cycle" not in m


def test_the_configuration_is_rollout7d_under_holt_winters():
    hw, base = _config(), _config("rollout7d")
    for key in ("classes", "step_s", "history_points", "current_points",
                "max_cycles", "trace", "guarantees"):
        assert hw[key] == base[key]
    assert hw["engine"] == dict(base["engine"], algorithm="holt_winters")
    assert not [k for k in hw["engine"] if k.startswith("hw_")]
    assert hw["references"] == {"band": "band_hw"} and hw["reduced"] == []
    assert hw["assumed"][:len(base["assumed"])] == base["assumed"]
    fl = fleet_mod.Fleet(hw, 1, tiny=True)
    # the tiny history holds its period five times over
    assert fl.lead == 120
    assert fl.held("historical", 0, fl.now_slot()) == 600 >= 2 * fl.lead
    assert fl.config["engine"]["hw_period_candidates"] == [5, 120]
    assert check.family(fl, "band").__name__ == "bench_family_band_hw"


# -------------------------------------------------------------- the control
@pytest.mark.parametrize("seed", [1, 2, 4000000007])
def test_control_in_bfloat16_is_not_correct(seed):
    fl = fleet_mod.Fleet(_config(), seed, tiny=True)
    jobs = [j for j in range(fl.jobs) if j not in fl.anomalous]
    k_now = fl.now_slot() + 3
    sound = check.reference_answers(fl, jobs, k_now, 5.0, "float64")
    numbers = check.compare(fl, sound)
    assert [n for n, _, _ in numbers] == NUMBERS + ["verdict_miss",
                                                    "stale_jobs"]
    assert all(v <= lim for _, v, lim in numbers), numbers
    control = check.reference_answers(fl, jobs, k_now, 5.0, "bfloat16")
    numbers = {n: (v, lim) for n, v, lim in check.compare(fl, control)}
    for name in ("hw_band_gap", "pair_p_gap"):
        assert numbers[name][0] > numbers[name][1], numbers


# --------------------------------------------------------------- the faults
def _wrong_period(monkeypatch):
    """A wrong period forced: every row is fitted at the short candidate."""
    from foremast_tpu.engine.analyzer import Analyzer

    real = Analyzer._detect_periods

    def forced(self, xv_d, hist_mask, rows):
        chosen = real(self, xv_d, hist_mask, rows)
        return np.full_like(chosen, chosen.min())

    monkeypatch.setattr(Analyzer, "_detect_periods", forced)


def _first_candidate(monkeypatch):
    """The winner replaced by the grid's first candidate."""
    from foremast_tpu.ops import forecast as fc

    def first(x, mask, fit_mask, period, grid=None):
        a, b, g = (np.full(x.shape[0], v, np.float32)
                   for v in np.asarray(fc._default_grid())[0])
        return None, fc.holt_winters_predictions(x, mask, period, a, b, g)

    monkeypatch.setattr(fc, "fit_holt_winters", first)


def _sigma_over_the_fit_region(monkeypatch):
    """Sigma over the slots the fit is scored on, not the whole history."""
    from foremast_tpu.ops import forecast as fc

    real = fc.residual_sigma

    def narrowed(x, preds, mask, region_mask):
        return real(x, preds, mask, fc.hw_fit_mask(region_mask, 120))

    monkeypatch.setattr(fc, "residual_sigma", narrowed)


@pytest.mark.parametrize("fault", [_wrong_period, _first_candidate,
                                   _sigma_over_the_fit_region])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = harness.run(_args())
    compared = out["compared"]
    assert out["correct"] is False
    assert compared["hw_band_gap"]["value"] > compared["hw_band_gap"]["limit"]


# ------------------------------------------------------- costs and readers
def test_cost_against_its_hand_count():
    # 2 rows of 110 samples, 100 of them history, 60 candidates, 8 lags:
    # bytes 15 a sample and 12 a row, and detection's 5 a history sample;
    # a history sample 17 x 61 for the grid and the winner and 6 x 9 for
    # detection, a judged sample 2, every sample the band's 12
    assert costs_hw.band_hw(2, 110, 100) == {
        "bytes": 2 * (1650 + 12 + 500),
        "ops": 2 * (100 * (1037 + 54) + 20 + 1320)}
    assert costs_hw.band_hw(1, 10, 10, candidates=1, lags=2) == {
        "bytes": 150 + 12 + 50, "ops": 10 * (34 + 18) + 120}
    # never under the moving-average band's count for the same samples
    assert costs_hw.band_hw(3, 50, 40)["ops"] > costs.band(3, 50)["ops"]


def _ctx(programs):
    fl = fleet_mod.Fleet(_config(), 1, tiny=True)
    return {"trace": {"programs": programs}, "fleet": fl, "notes": {},
            "peaks": peaks.for_kind("TPU v5 lite"),
            "cycles": [{"class_rows": {0: {"band": 40}},
                        "now_slot": fl.now_slot()},
                       {"class_rows": {0: {"band": 40}},
                        "now_slot": fl.now_slot() + 1}]}


def test_hw_readers_on_a_built_trace():
    names = ("jit_region_masks", "jit_detect_period", "jit_take_rows",
             "jit_hw_fit_mask", "jit_fit_holt_winters", "jit_residual_sigma",
             "jit_band_anomalies", "jit_scatter_rows")
    programs = {p: [(i + 1) * 1e-6, 2] for i, p in enumerate(names)}
    programs["jit__score_rows"] = [5e-6, 2]
    ctx = _ctx(programs)
    share = harness.load_reader("hw_roofline")(ctx)
    assert ctx["notes"]["hw_device_s"] == pytest.approx(36e-6)
    least = 0.0
    for i, _ in enumerate(ctx["cycles"]):
        # the tiny history, and the current window at warm-up and after
        history, points = 600, 600 + 80 + i
        least += costs.least_seconds(
            costs_hw.band_hw(40, points, history, lags=2), ctx["peaks"])[0]
    assert share == pytest.approx(100.0 * least / 36e-6)
    assert harness.load_reader("hw_fit_device_s_per_cycle")(ctx) \
        == pytest.approx(5e-6 / 2)


@pytest.mark.parametrize("missing", ["trace", "jit_fit_holt_winters",
                                     "jit_scatter_rows", "peaks"])
def test_hw_readers_read_nothing_where_something_is_missing(missing):
    names = ("jit_region_masks", "jit_detect_period", "jit_take_rows",
             "jit_hw_fit_mask", "jit_fit_holt_winters", "jit_residual_sigma",
             "jit_band_anomalies", "jit_scatter_rows")
    ctx = _ctx({p: [1e-6, 2] for p in names if p != missing})
    if missing == "trace":
        ctx["trace"] = None
    if missing == "peaks":
        ctx["peaks"] = None
    assert harness.load_reader("hw_roofline")(ctx) is None
    fit = harness.load_reader("hw_fit_device_s_per_cycle")(ctx)
    assert (fit is None) == (missing in ("trace", "jit_fit_holt_winters"))


def test_detect_reader_reads_nothing_of_a_program_without_the_span(
        monkeypatch):
    from foremast_tpu.utils import tracing

    monkeypatch.setattr(tracing, "SPAN_NAMES",
                        tracing.SPAN_NAMES - {"engine.detect_period"})
    assert harness.load_reader("detect_s_per_cycle")({"cycles": []}) is None


# ---------------------------------------------- which file judges a family
@pytest.mark.parametrize("config,families", [
    ("rollout7d", ["pair", "band"]),
    ("rollout7d_2m", ["pair", "bivariate"]),
])
def test_the_accepted_configurations_still_resolve_as_before(config,
                                                             families):
    fl = fleet_mod.Fleet(_config(config), 1, tiny=True)
    assert fl.references == {f: f for f in families}
    for f in families:
        assert check.family(fl, f).__file__ == os.path.join(
            BENCH, "families", f + ".py")


def test_band_hw_refuses_a_program_without_the_side_by_side_fit(
        monkeypatch, capsys):
    from foremast_tpu.ops import forecast as fc

    monkeypatch.delattr(fc, "hw_state_bytes")
    monkeypatch.setattr(check, "_FAMILIES", {})
    warmed = []
    monkeypatch.setattr(harness.Engine, "warm_up",
                        lambda self: warmed.append(1))
    with pytest.raises(harness.BenchError, match="one after another"):
        check.family(fleet_mod.Fleet(_config(), 1, tiny=True), "band")
    assert harness.main(["--workload", CELL, "--seed", "3", "--seconds",
                         "0", "--tiny"]) == 3
    said = capsys.readouterr()
    assert said.out == "" and not warmed
    assert "hw_state_bytes" in said.err
