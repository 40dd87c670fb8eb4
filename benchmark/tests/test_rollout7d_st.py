"""The fourth cell, `rollout7d_st_polled`: its `--tiny` line, its control,
the faults `correct` has to catch under the seasonal-trend reference, its
cost function and trace readers, and what the family file refuses.

The tiny fleet's limit of `st_band_gap` is its own, 0.035 where the cell's
is 0.003: with 600 samples of history in a bucket of 1,024 the float32
normal equations (condition number 2e5) read 0.005 to 0.018 reference
sigmas on the CPU, the bfloat16 control 0.064 and more, and the two
planted faults 0.36 and more."""
import ast
import os
import types

import pytest

import run as harness
from lib import check, costs, costs_st, fleet as fleet_mod, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "rollout7d_st_polled"
NUMBERS = ["pair_p_gap", "st_band_gap", "st_count_out",
           "st_period_margin_rows"]


def _args(seed=3, trace=0):
    return types.SimpleNamespace(workload=CELL, seed=seed, seconds=0.2,
                                 trace=trace, tiny=True)


def _config(name="rollout7d_st"):
    return fleet_mod.load_json(os.path.join(BENCH, "configs",
                                            name + ".json"))


# ---------------------------------------------------------------- a tiny run
@pytest.mark.parametrize("seed", [1, 4000000007])
def test_tiny_run_is_correct_and_fits_two_partitions(seed):
    out = harness.run(_args(seed, trace=1))
    assert out["correct"] is True and out["failed"] == 0, out["compared"]
    assert list(out["compared"]) == NUMBERS + [
        "verdict_miss", "stale_jobs", "compiles_in_window"]
    assert 0 < out["compared"]["st_band_gap"]["value"] < 0.035
    live = out["cycles"][-1]["offered"]
    assert out["cycles"][-1]["rows"] == {"pair": live, "band": live}
    assert out["cycles"][-1]["launches"] == 2
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["period_partitions_per_cycle"] == 2
    assert 0 < m["detect_s_per_cycle"] <= m["launch_s_per_cycle"]
    # no device trace on the CPU: the two device metrics stay out
    assert "st_roofline" not in m and "st_fit_device_s_per_cycle" not in m


def test_the_configuration_is_rollout7d_hw_under_the_seasonal_trend_fit():
    st, hw = _config(), _config("rollout7d_hw")
    for key in ("classes", "step_s", "history_points", "current_points",
                "max_cycles", "trace", "guarantees"):
        assert st[key] == hw[key]
    assert st["engine"] == dict(hw["engine"], algorithm="seasonal_trend")
    assert not [k for k in st["engine"] if k.startswith(("hw_", "st_"))]
    assert st["references"] == {"band": "band_st"} and st["reduced"] == []
    assert st["assumed"][1:8] == hw["assumed"][1:8]
    assert dict(st["tiny"], engine=None, check=None) == dict(
        hw["tiny"], engine=None, check=None)
    assert st["tiny"]["engine"] == dict(hw["tiny"]["engine"],
                                        algorithm="seasonal_trend")
    fl = fleet_mod.Fleet(st, 1, tiny=True)
    assert fl.lead == 120 and fl.config["engine"]["hw_period_candidates"] \
        == [5, 120]
    assert check.family(fl, "band").__name__ == "bench_family_band_st"


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference_st.py", "reference_hw.py", "reference.py"):
        with open(os.path.join(BENCH, "lib", name)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            mods = [a.name for a in node.names] if isinstance(
                node, ast.Import) else [node.module or ""] if isinstance(
                node, ast.ImportFrom) else []
            assert not [m for m in mods if m.startswith(("foremast_tpu",
                                                         "jax"))], name


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
    for name in ("st_band_gap", "pair_p_gap"):
        assert numbers[name][0] > numbers[name][1], numbers


# --------------------------------------------------------------- the faults
def _fit_with(monkeypatch, change):
    """The launch's fit called with `change(n_changepoints)` as keywords."""
    from foremast_tpu.ops import forecast as fc

    real = fc.fit_seasonal_trend
    monkeypatch.setattr(
        fc, "fit_seasonal_trend",
        lambda x, mask, fit_mask, period, order, n_changepoints: real(
            x, mask, fit_mask, period, order, **change(n_changepoints)))


def _a_hinge_dropped(monkeypatch):
    """The trend has 11 hinges where the configuration states 12."""
    _fit_with(monkeypatch, lambda c: {"n_changepoints": c - 1})


def _a_round_fewer(monkeypatch):
    """Two solves where the fit has three: the last reweighting is lost."""
    _fit_with(monkeypatch, lambda c: {"n_changepoints": c, "l1_iters": 2})


@pytest.mark.parametrize("fault", [_a_hinge_dropped, _a_round_fewer])
def test_a_broken_fit_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = harness.run(_args())
    compared = out["compared"]
    assert out["correct"] is False
    assert compared["st_band_gap"]["value"] > compared["st_band_gap"]["limit"]


# ------------------------------------------------------- costs and readers
def test_cost_against_its_hand_count():
    # 2 rows of 110 samples, 100 of them history, 20 columns, 3 solves, 8
    # lags: bytes 15 a sample, 12 a row, detection's 5 a history sample;
    # a history sample 20 x 21 for the Gram, 40 for the right-hand side
    # and 6 x 9 for detection; a sample 40 for the prediction and the
    # band's 12; a row 3 x (2/3 x 8000 + 2 x 400)
    assert costs_st.band_st(2, 110, 100) == {
        "bytes": 2 * (1650 + 12 + 500),
        "ops": 2 * (100 * (420 + 40 + 54) + 110 * 52 + 3 * (5333 + 800))}
    assert costs_st.band_st(1, 10, 10, columns=8, solves=1, lags=2) == {
        "bytes": 150 + 12 + 50,
        "ops": 10 * (72 + 16 + 18) + 10 * 28 + (341 + 128)}
    # never under the moving-average band's count for the same samples
    assert costs_st.band_st(3, 50, 40)["ops"] > costs.band(3, 50)["ops"]
    assert costs_st.fit_shape({}) == (20, 3)
    assert costs_st.fit_shape({"st_order": 2, "st_changepoints": 0}) == (6, 1)


def _ctx(programs):
    fl = fleet_mod.Fleet(_config(), 1, tiny=True)
    return {"trace": {"programs": programs}, "fleet": fl, "notes": {},
            "peaks": peaks.for_kind("TPU v5 lite"),
            "cycles": [{"class_rows": {0: {"band": 40}},
                        "now_slot": fl.now_slot()},
                       {"class_rows": {0: {"band": 40}},
                        "now_slot": fl.now_slot() + 1}]}


def test_st_readers_on_a_built_trace():
    programs = {p: [(i + 1) * 1e-6, 2]
                for i, p in enumerate(costs_st.PROGRAMS)}
    programs["jit__score_rows"] = [5e-6, 2]
    programs["jit_fit_holt_winters"] = [7e-6, 2]  # not of this launch
    ctx = _ctx(programs)
    share = harness.load_reader("st_roofline")(ctx)
    assert ctx["notes"]["st_device_s"] == pytest.approx(28e-6)
    assert ctx["notes"]["st_roofline_bound"] in ("bandwidth", "compute")
    least = 0.0
    for i, _ in enumerate(ctx["cycles"]):
        # the tiny history, and the current window at warm-up and after
        history, points = 600, 600 + 80 + i
        least += costs.least_seconds(
            costs_st.band_st(40, points, history, lags=2), ctx["peaks"])[0]
    assert share == pytest.approx(100.0 * least / 28e-6)
    assert harness.load_reader("st_fit_device_s_per_cycle")(ctx) \
        == pytest.approx(4e-6 / 2)


@pytest.mark.parametrize("missing", ["trace", "jit_fit_seasonal_trend",
                                     "jit_scatter_rows", "peaks"])
def test_st_readers_read_nothing_where_something_is_missing(missing):
    ctx = _ctx({p: [1e-6, 2] for p in costs_st.PROGRAMS if p != missing})
    if missing == "trace":
        ctx["trace"] = None
    if missing == "peaks":
        ctx["peaks"] = None
    assert harness.load_reader("st_roofline")(ctx) is None
    fit = harness.load_reader("st_fit_device_s_per_cycle")(ctx)
    assert (fit is None) == (missing in ("trace", "jit_fit_seasonal_trend"))


# ---------------------------------------------- which file judges a family
def test_band_st_under_another_forecaster_ends_in_set_up(monkeypatch,
                                                         capsys):
    cfg = _config()
    cfg["engine"] = dict(cfg["engine"], algorithm="moving_average_all")
    cfg["tiny"]["engine"] = dict(cfg["tiny"]["engine"],
                                 algorithm="moving_average_all")
    with pytest.raises(harness.BenchError, match="seasonal_trend"):
        check.family(fleet_mod.Fleet(cfg, 1, tiny=True), "band")
    real = fleet_mod.load_json
    monkeypatch.setattr(
        fleet_mod, "load_json",
        lambda path: cfg if path.endswith("rollout7d_st.json")
        else real(path))
    warmed = []
    monkeypatch.setattr(harness.Engine, "warm_up",
                        lambda self: warmed.append(1))
    assert harness.main(["--workload", CELL, "--seed", "3", "--seconds",
                         "0", "--tiny"]) == 3
    said = capsys.readouterr()
    assert said.out == "" and not warmed
    assert "band_st.py is the reference of engine.algorithm" in said.err


@pytest.mark.parametrize("algorithm", ["seasonal_trend", "prophet",
                                       "prophet_all"])
def test_band_st_judges_both_names_of_the_route(algorithm):
    cfg = _config()
    cfg["tiny"]["engine"] = dict(cfg["tiny"]["engine"], algorithm=algorithm)
    fl = fleet_mod.Fleet(cfg, 1, tiny=True)
    assert check.family(fl, "band").__name__ == "bench_family_band_st"


def test_band_st_refuses_a_program_that_states_no_precision(monkeypatch,
                                                            capsys):
    from foremast_tpu.ops import forecast as fc

    monkeypatch.delattr(fc, "st_columns")
    monkeypatch.setattr(check, "_FAMILIES", {})
    warmed = []
    monkeypatch.setattr(harness.Engine, "warm_up",
                        lambda self: warmed.append(1))
    with pytest.raises(harness.BenchError, match="no matmul precision"):
        check.family(fleet_mod.Fleet(_config(), 1, tiny=True), "band")
    assert harness.main(["--workload", CELL, "--seed", "3", "--seconds",
                         "0", "--tiny"]) == 3
    said = capsys.readouterr()
    assert said.out == "" and not warmed
    assert "st_columns" in said.err
