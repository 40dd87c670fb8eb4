"""Where a class lays its windows: placement as data (`lib/fleet.py`).

The four accepted configurations state no placement and must read what
they read before it was data; foremast-trigger's rollover request
(baseline = historical = the trailing history, a current window with a
fixed end) comes as a configuration alone, and is judged by where it
lays its roles, not by the default layout."""
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

import run as harness
from lib import costs_hw, costs_st, fleet as fleet_mod, peaks
from lib.source import FleetSource

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
ROLLOVER = os.path.join(HERE, "data", "rollover_tiny.json")


def _config(name):
    return fleet_mod.load_json(os.path.join(BENCH, "configs",
                                            name + ".json"))


def _rollover():
    return fleet_mod.load_json(ROLLOVER)


# ----------------------------------------- a class that states nothing
@pytest.mark.parametrize("tiny", [False, True])
@pytest.mark.parametrize("name", ["rollout7d", "rollout7d_2m",
                                  "rollout7d_hw", "rollout7d_st"])
def test_a_class_that_states_nothing_is_laid_as_before(name, tiny):
    cfg = _config(name)
    fl = fleet_mod.Fleet(cfg, 4000000007, tiny=tiny)
    c = fl.config
    H, W = c["history_points"] - 1, c["current_points"] - 1
    lead = int(round(c["trace"]["diurnal_period_s"] / c["step_s"]))
    # the layout before placement was data, from the configuration's
    # numbers alone
    h_lo, h_hi = lead, lead + H
    b_lo = h_hi - lead
    horizon = lead + H + W + c["max_cycles"] + 16
    assert fl.horizon == horizon and fl.max_cycles == c["max_cycles"]
    assert fl.window_slots("historical", 0) == ("hist", h_lo, h_hi)
    assert fl.window_slots("baseline", 0) == ("base", b_lo, b_lo + W)
    assert fl.window_slots("current", 0) == ("cur", h_hi, horizon - 1)
    assert fl.active_from == float(fl.t0 + (lead + H + W // 2) * 60)
    assert fl.warm_now == float(fl.t0 + (h_hi + W) * 60) + 5.0
    rng = np.random.default_rng(4000000007)
    np.testing.assert_array_equal(
        fl.base, 10.0 + rng.standard_normal((128, horizon)))
    q = fl.queries(3)[fl.metrics_of(3)[0]]
    assert [re.search(r"&w=(\w+)&", q[r]).group(1) for r in
            ("current", "baseline", "historical")] == ["cur", "base", "hist"]


# the `--tiny --trace 1 --seconds 0` lines (two warm-up cycles and two
# traced ones, a process each) read before placement was data:
# the counts, the counters and every number compared
_COUNTERS = ("fetches_per_cycle", "launches_per_cycle", "h2d_bytes_per_cycle",
             "d2h_bytes_per_cycle", "pack_fill_share",
             "period_partitions_per_cycle", "splice_appends_per_cycle",
             "compiles_in_window")


def _pin(live, fetches, rows, h2d, d2h, fill, partitions, compared):
    return {"cycles": 2 * [{"offered": live, "launches": 2,
                            "fetches": fetches, "rows": rows}],
            "counters": dict(zip(_COUNTERS, (
                float(fetches), 2.0, h2d, d2h, fill, partitions,
                float(fetches), 0))),
            "compared": dict(compared, verdict_miss=0, stale_jobs=0,
                             compiles_in_window=0)}


def _one(live, h2d, d2h, fill, partitions, compared):
    return _pin(live, live, {"pair": live, "band": live}, h2d, d2h, fill,
                partitions, compared)


def _two(live, h2d, d2h, fill, compared):
    return _pin(live, 2 * live, {"pair": 2 * live, "bivariate": live},
                h2d, d2h, fill, 0.0, compared)


_BEFORE = {
    ("rollout7d_polled", 3): _one(
        44, 413696.0, 69056.0, 45.3857421875, 0.0,
        {"pair_p_gap": 1.1170633013035669e-07,
         "band_gap": 5.611738713581807e-05, "band_count_out": 0}),
    ("rollout7d_polled", 4000000007): _one(
        46, 413696.0, 69056.0, 47.44873046875, 0.0,
        {"pair_p_gap": 1.1820182332922258e-07,
         "band_gap": 6.159403098935036e-05, "band_count_out": 0}),
    ("rollout7d_2m_polled", 3): _two(
        31, 741888.0, 69568.0, 35.61333550347222,
        {"pair_p_gap": 1.0474772110802277e-07,
         "bi_bound_gap": 1.6877733488922368e-06, "bi_count_out": 0}),
    ("rollout7d_2m_polled", 4000000007): _two(
        33, 996096.0, 76288.0, 28.3660888671875,
        {"pair_p_gap": 1.1820182332922258e-07,
         "bi_bound_gap": 1.353480040331939e-06, "bi_count_out": 0}),
    ("rollout7d_hw_polled", 3): _one(
        41, 414528.0, 69312.0, 42.291259765625, 2.0,
        {"pair_p_gap": 1.0146395720833645e-07,
         "hw_band_gap": 4.4699239022185853e-05, "hw_count_out": 0,
         "hw_tie_rows": 0, "hw_period_margin_rows": 0}),
    ("rollout7d_hw_polled", 4000000007): _one(
        41, 414528.0, 69312.0, 42.291259765625, 2.0,
        {"pair_p_gap": 8.922091421226241e-08,
         "hw_band_gap": 5.9288003757695725e-05, "hw_count_out": 0,
         "hw_tie_rows": 0, "hw_period_margin_rows": 0}),
    ("rollout7d_st_polled", 3): _one(
        37, 414528.0, 69312.0, 38.165283203125, 2.0,
        {"pair_p_gap": 1.0146395720833645e-07,
         "st_band_gap": 0.013910993052830098, "st_count_out": 0,
         "st_period_margin_rows": 0}),
    ("rollout7d_st_polled", 4000000007): _one(
        41, 414528.0, 69312.0, 42.291259765625, 2.0,
        {"pair_p_gap": 8.922091421226241e-08,
         "st_band_gap": 0.009489616457496582, "st_count_out": 0,
         "st_period_margin_rows": 0}),
}


@pytest.mark.parametrize("workload,seed", sorted(_BEFORE))
def test_the_four_cells_read_what_they_read(workload, seed):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", "0", "--trace", "1",
         "--tiny"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    pin = _BEFORE[(workload, seed)]
    assert (out["correct"], out["failed"]) == (True, 0)
    assert out["attempted"] == 2 * pin["cycles"][0]["offered"]
    assert [{k: c[k] for k in ("offered", "launches", "fetches", "rows")}
            for c in out["cycles"]] == pin["cycles"]
    assert {k: out["metrics"][k]["value"] for k in _COUNTERS} \
        == pin["counters"]
    assert {k: v["value"] for k, v in out["compared"].items()} \
        == pin["compared"]


# what the readers read before placement was data, on one built context
# a cell: two cycles of 15,565 and 15,267 rows at two and three slots past
# warm-up, program seconds 0.01 s apart in the order the reader lists them
_ROOFLINES = [
    ("pair_roofline", "rollout7d", "pair", ("jit__score_rows",),
     0.3133931013431014),
    ("band_roofline", "rollout7d", "band",
     ("jit_region_masks", "jit__moving_average_1d", "jit_residual_sigma",
      "jit_band_anomalies"), 5.739662062271061),
    ("bivariate_roofline", "rollout7d_2m", "bivariate",
     ("jit_bivariate_normal_anomalies",), 45.931752527472526),
    ("hw_roofline", "rollout7d_hw", "band", costs_hw.PROGRAMS,
     2.121445593881427),
    ("st_roofline", "rollout7d_st", "band", costs_st.PROGRAMS,
     2.7275729064189775),
]


def _roofline_ctx(fl, family, programs, rows=(15565, 15267)):
    return {"trace": {"programs": {p: [0.01 * (i + 1), 2]
                                   for i, p in enumerate(programs)}},
            "fleet": fl, "notes": {}, "peaks": peaks.for_kind("TPU v5 lite"),
            "cycles": [{"class_rows": {0: {family: r}},
                        "now_slot": fl.now_slot() + 2 + i}
                       for i, r in enumerate(rows)]}


@pytest.mark.parametrize("reader,config,family,programs,before",
                         _ROOFLINES)
def test_a_roofline_reads_what_it_read(reader, config, family, programs,
                                       before):
    fl = fleet_mod.Fleet(_config(config), 1)
    ctx = _roofline_ctx(fl, family, programs)
    assert harness.load_reader(reader)(ctx) == before


def test_the_pair_roofline_counts_the_baseline_where_it_lies():
    from lib import costs

    fl = fleet_mod.Fleet(_rollover(), 1)
    ctx = _roofline_ctx(fl, "pair", ("jit__score_rows",), rows=(40,))
    share = harness.load_reader("pair_roofline")(ctx)
    # a 600-point baseline and the current window two slots past warm-up
    least, _ = costs.least_seconds(costs.pair(40, 600, 22), ctx["peaks"])
    assert share == 100.0 * least / 0.01


# ------------------------------------------- the rollover as a request
def test_the_rollover_lays_its_baseline_on_its_history():
    fl = fleet_mod.Fleet(_rollover(), 1)
    lead = 1440
    assert fl.window_slots("historical", 0) == ("hist", lead, lead + 599)
    assert fl.window_slots("baseline", 0) == ("hist", lead, lead + 599)
    assert fl.window_slots("current", 0) == ("cur", lead + 599,
                                             lead + 599 + 30)
    # the current window fills in 12 cycles: 20 points at warm-up, 31 last
    assert fl.max_cycles == 12
    assert fl.held("current", 0, fl.now_slot()) == 20
    assert fl.held("current", 0, fl.now_slot() + 40) == 31
    for job in (0, 7, 47):
        q = fl.queries(job)["error4xx"]
        assert q["baseline"] == q["historical"]
        assert q["current"].endswith(
            f"&start={fl.t0 + (lead + 599) * 60}"
            f"&end={fl.t0 + (lead + 629) * 60}&step=60")
    # the history's range is answered from the arrays, whichever role
    # asks for it
    rendered = []
    real = fleet_mod.Fleet.body

    def body(self, *a):
        rendered.append(a)
        return real(self, *a)

    fl.body = types.MethodType(body, fl)
    src = FleetSource(fl)
    ts, vals, _ = src.fetch_series(q["baseline"])
    assert ts.shape == (600,) and not rendered
    src.fetch_series(q["current"])
    assert len(rendered) == 1


def _rollover_cell(monkeypatch, cfg):
    real = harness.load_cell

    def load(name):
        cell = real("rollout7d_polled")
        cell["config"] = cfg
        return cell

    monkeypatch.setattr(harness, "load_cell", load)


def _run(seed):
    return harness.run(types.SimpleNamespace(
        workload="rollover_tiny", seed=seed, seconds=0, trace=0, tiny=True))


@pytest.mark.parametrize("seed", [1, 4000000007])
def test_the_rollover_is_correct_through_the_harness(monkeypatch, seed):
    _rollover_cell(monkeypatch, _rollover())
    fetched = []
    real = FleetSource.fetch_series

    def spy(self, url):
        fetched.append(re.search(r"&w=(\w+)&", url).group(1))
        return real(self, url)

    monkeypatch.setattr(FleetSource, "fetch_series", spy)
    out = _run(seed)
    assert out["correct"] is True and out["failed"] == 0, out["compared"]
    # no query names the baseline's own range: it is the history's
    assert set(fetched) == {"cur", "hist"}
    live = out["cycles"][-1]["offered"]
    assert live > 0 and out["cycles"][-1]["rows"] == {"pair": live,
                                                      "band": live}
    # the cache sees one range for the two roles: a cycle fetches only
    # the moving current window
    assert out["cycles"][-1]["fetches"] == live


def test_the_rollover_under_the_default_placement_is_not_correct(
        monkeypatch):
    cfg = _rollover()
    seed = 4000000007
    plain = dict(cfg, classes=[{k: v for k, v in cfg["classes"][0].items()
                                if k != "placement"}])
    default = fleet_mod.Fleet(plain, seed, tiny=True)
    stated = fleet_mod.Fleet(cfg, seed, tiny=True)
    np.testing.assert_array_equal(default.base, stated.base)
    # the program is handed today's layout; the reference reads the
    # stated one
    real = fleet_mod.Fleet.queries
    monkeypatch.setattr(fleet_mod.Fleet, "queries",
                        lambda self, job: real(default, job))
    _rollover_cell(monkeypatch, cfg)
    out = _run(seed)
    compared = out["compared"]
    assert out["correct"] is False
    for name in ("pair_p_gap", "stale_jobs"):
        assert compared[name]["value"] > compared[name]["limit"], compared


def _no_engine(monkeypatch):
    from foremast_tpu.engine.analyzer import Analyzer

    def built(self, *a, **kw):
        raise AssertionError("an engine was built")

    monkeypatch.setattr(Analyzer, "__init__", built)


@pytest.mark.parametrize("placement,naming", [
    ({"current": {"points": 19}}, "cannot hold the 20"),
    ({"baseline": {"start": 0, "points": 21}}, "does not fit the horizon"),
    ({"historical": {"start": -10, "points": 31}},
     "does not fit the horizon"),
    ({"baseline": {"start": -599}}, "'start', 'points'"),
    ({"baseline": {"start": -599, "points": 0}}, "at least one point"),
    ({"current": {"points": 31.0}}, "whole numbers"),
    ({"canary": {"start": -599, "points": 600}}, "'canary'"),
    ([], "an object by role"),
])
def test_an_impossible_placement_ends_the_run_before_an_engine(
        monkeypatch, capsys, placement, naming):
    cfg = _rollover()
    cfg["classes"] = [dict(cfg["classes"][0], placement=placement)]
    _no_engine(monkeypatch)
    _rollover_cell(monkeypatch, cfg)
    with pytest.raises(harness.BenchError, match=re.escape(naming)):
        fleet_mod.Fleet(cfg, 1, tiny=True)
    capsys.readouterr()
    assert harness.main(["--workload", "rollover_tiny", "--seed", "3",
                         "--seconds", "0", "--tiny"]) == 3
    said = capsys.readouterr()
    assert said.out == "" and naming in said.err


def test_a_placement_of_a_role_the_class_does_not_carry_is_refused():
    cfg = _config("rollout7d")
    cls = dict(cfg["classes"][0], windows=["baseline"],
               placement={"historical": {"start": -10080, "points": 10081}})
    with pytest.raises(harness.BenchError, match="carry"):
        fleet_mod.Fleet(dict(cfg, classes=[cls]), 1)


# ----------------------------------------- one reader of a role's slots
_PLACEMENT_ATTRS = re.compile(
    r"\b(hist_hi|hist_lo|base_lo|window_steps|hist_steps|_slots)\b")


def test_only_the_fleet_reads_a_roles_slots():
    """Every reader of where a role lies goes through `Fleet.window_slots`:
    no file of the benchmark but `lib/fleet.py` (and this test) names the
    layout's own arithmetic."""
    found = []
    for top, _, files in os.walk(BENCH):
        for name in files:
            path = os.path.join(top, name)
            if not name.endswith(".py") or path in (
                    os.path.join(BENCH, "lib", "fleet.py"),
                    os.path.abspath(__file__)):
                continue
            with open(path) as f:
                for n, line in enumerate(f, 1):
                    if _PLACEMENT_ATTRS.search(line):
                        found.append(f"{os.path.relpath(path, BENCH)}:{n}")
    assert found == []
