"""The benchmark's own checks: the trace reduction on a small trace, the
array source against the byte path, the cost functions against hand
counts, the last line of a tiny run, which file judges a family, the
control, and the faults that `correct` has to catch."""
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

import run as harness
from lib import check, costs, fleet as fleet_mod, peaks, trace_reduce
from lib.source import FleetSource

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def _args(workload, seed=3, trace=0):
    return types.SimpleNamespace(workload=workload, seed=seed, seconds=0.2,
                                 trace=trace, tiny=True)


def _config(name):
    return fleet_mod.load_json(os.path.join(BENCH, "configs",
                                            name + ".json"))


# ------------------------------------------------------------ trace reduction
def test_trace_reduction_on_small_trace(tmp_path):
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "data", "small_trace.textproto")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path / "small.xplane.pb"
    path.write_bytes(raw)
    out = trace_reduce.reduce(trace_reduce.load(str(path)))
    assert out["window_s"] == pytest.approx(8000e-9)
    assert out["busy_s"] == pytest.approx(2200e-9)
    ops = dict(out["device_ops"])
    assert ops["fusion.1"] == pytest.approx(1500e-9)
    assert ops["sort.2"] == pytest.approx(1000e-9)
    assert ops["copy.3"] == pytest.approx(200e-9)
    assert out["device_ops"][0][0] == "fusion.1"
    assert out["programs"]["jit__moving_average_1d"] == [
        pytest.approx(1500e-9), 1]
    assert out["programs"]["jit_band_anomalies"] == [pytest.approx(500e-9), 1]
    gaps = out["idle_gaps"]
    assert [g[0] for g in gaps] == ["engine.score", "bench.cycle",
                                    "engine.claim"]
    assert [g[1] for g in gaps] == [pytest.approx(3000e-9),
                                    pytest.approx(2500e-9),
                                    pytest.approx(300e-9)]


def test_trace_without_device_plane_reads_nothing(tmp_path):
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 2 name: "/host:CPU" lines { id: 1 name: "t" '
        'events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 } } '
        'event_metadata { key: 1 value { id: 1 name: "bench.cycle" } } }')
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(raw)
    out = trace_reduce.reduce(trace_reduce.load(str(path)))
    assert out["busy_s"] == 0.0 and out["programs"] == {}
    reader = harness.load_reader("device_idle_share")
    assert reader({"trace": out}) is None


# ------------------------------------------------------------------- source
def test_array_path_equals_byte_path_for_one_history():
    cfg = fleet_mod.load_json(os.path.join(BENCH, "configs", "rollout7d.json"))
    fl = fleet_mod.Fleet(cfg, seed=4000000007)
    job = sorted(fl.anomalous)[0]
    url = fl.queries(job)["error4xx"]["historical"]
    arrays, text = FleetSource(fl), FleetSource(fl, arrays_for=())
    ts_a, v_a, _ = arrays.fetch_series(url)
    ts_b, v_b, n_b = text.fetch_series(url)
    assert ts_a.shape == (7 * 1440 + 1,) and n_b > 200_000
    np.testing.assert_array_equal(ts_a, ts_b)
    np.testing.assert_allclose(v_a, v_b, rtol=0, atol=1e-12)
    wa, wb = arrays.fetch_window(url), text.fetch_window(url)
    np.testing.assert_array_equal(wa.values, wb.values)
    np.testing.assert_array_equal(wa.mask, wb.mask)
    assert (wa.start, wa.step) == (wb.start, wb.step)
    # the tails always take the byte path, clipped at the simulated clock
    cur = fl.queries(job)["error4xx"]["current"]
    ts_c, v_c, _ = arrays.fetch_series(cur)
    assert ts_c.shape == (80,) and ts_c[-1] <= fl.now
    np.testing.assert_allclose(
        v_c, fl.role_rows([job], 0, "current", fl.now_slot())[0],
        atol=1e-12)


# -------------------------------------------------------------------- costs
def test_costs_against_hand_counts():
    # 10 rows of 100 samples: 15 bytes a sample and 12 a row; 12 ops a sample
    assert costs.band(10, 100) == {"bytes": 10 * (1500 + 12),
                                   "ops": 12000}
    # 4 rows, 64 + 64 samples: 5 bytes a sample and 20 a row;
    # 128 * log2(128) = 896 comparisons and 12 * 128 band ops a row
    assert costs.pair(4, 64, 64) == {"bytes": 4 * (640 + 20),
                                     "ops": 4 * (896 + 1536)}
    # 10 jobs of 100 samples a metric, 20 of them judged: 12 bytes a
    # sample and 48 a row; 11 ops a history sample and 14 a judged one
    assert costs.bivariate(10, 100, 20) == {
        "bytes": 10 * (1200 + 48), "ops": 10 * (11 * 80 + 14 * 20)}
    pk = peaks.for_kind("TPU v5 lite")
    secs, bound = costs.least_seconds({"bytes": 819e9, "ops": 1.0}, pk)
    assert (secs, bound) == (pytest.approx(1.0), "bandwidth")
    secs, bound = costs.least_seconds({"bytes": 1.0, "ops": 394e12}, pk)
    assert (secs, bound) == (pytest.approx(2.0), "compute")
    with pytest.raises(KeyError):
        peaks.for_kind("cpu")


def test_band_roofline_sums_the_launchs_four_programs():
    fl = fleet_mod.Fleet(_config("rollout7d"), 1, tiny=True)
    pk = peaks.for_kind("TPU v5 lite")
    seconds = {"jit_region_masks": 1e-6, "jit__moving_average_1d": 4e-6,
               "jit_residual_sigma": 2e-6, "jit_band_anomalies": 3e-6}
    ctx = {"trace": {"programs": {p: [s, 2] for p, s in seconds.items()}
                     | {"jit_all_pairwise_tests": [5e-6, 2]}},
           "fleet": fl, "peaks": pk, "notes": {},
           "cycles": [{"class_rows": {0: {"band": 40}},
                       "now_slot": fl.now_slot()}]}
    share = harness.load_reader("band_roofline")(ctx)
    assert ctx["notes"]["band_device_s"] == pytest.approx(10e-6)
    points = 600 + 80  # the tiny history and the warm-up current window
    least, _ = costs.least_seconds(costs.band(40, points), pk)
    assert share == pytest.approx(100.0 * least / 10e-6)
    # a launch none of whose programs is in the trace has no share
    ctx["trace"] = {"programs": {"jit_all_pairwise_tests": [5e-6, 2]}}
    assert harness.load_reader("band_roofline")(ctx) is None


# ---------------------------------------------------------------- a tiny run
def _cli(workload, seed, seconds, trace=0):
    """One `--tiny` run as the driver starts it: a process of its own."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), "--tiny"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return p


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", ["rollout7d_polled",
                                      "rollout7d_2m_polled"])
def test_tiny_run_prints_the_contract_line(workload, trace):
    p = _cli(workload, 4000000007, 0.2, trace)
    line = json.loads(p.stdout.strip().splitlines()[-1])
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2 * 24
    assert line["device"]["platform"] == "cpu"
    manifest = fleet_mod.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if trace == 0:
        assert set(line["metrics"]) == {m["name"]
                                        for m in manifest["end_to_end"]}
    else:
        # every reader that is not the device's reports in every cell;
        # no device metric is ever reported from a CPU run
        assert set(line["metrics"]) == {
            m["name"] for m in manifest["per_layer"]
            if m["source"] != "device_trace"}
        assert "busy_s" not in line["device"]
    assert p.stderr.strip().splitlines()[-1].startswith("compared ")


# what `rollout7d_polled` read at --tiny before a job's metrics and its
# results' keys came from the configuration (PR 27's tree, a process of
# its own, --seconds 0: two warm-up cycles and two in the window): the
# seeded draw and every count are what they were
_PINNED = {
    3: {"attempted": 88, "live": 44, "pair_p_gap": 1.1170633013035669e-07,
        "band_gap": 5.611738713581807e-05},
    4000000007: {"attempted": 92, "live": 46,
                 "pair_p_gap": 1.1820182332922258e-07,
                 "band_gap": 6.159403098935036e-05},
}


@pytest.mark.parametrize("seed", sorted(_PINNED))
def test_one_metric_cell_reads_what_it_read(seed):
    out = json.loads(_cli("rollout7d_polled", seed, 0)
                     .stdout.strip().splitlines()[-1])
    pin = _PINNED[seed]
    live = pin["live"]
    assert (out["correct"], out["attempted"], out["failed"]) == (
        True, pin["attempted"], 0)
    assert [{k: c[k] for k in ("offered", "launches", "fetches", "rows")}
            for c in out["cycles"]] == 2 * [{
                "offered": live, "launches": 2, "fetches": live,
                "rows": {"pair": live, "band": live}}]
    assert out["compared"] == {
        "pair_p_gap": {"value": pin["pair_p_gap"], "limit": 0.0003},
        "band_gap": {"value": pin["band_gap"], "limit": 0.004},
        "band_count_out": {"value": 0, "limit": 0},
        "verdict_miss": {"value": 0, "limit": 0},
        "stale_jobs": {"value": 0, "limit": 0},
        "compiles_in_window": {"value": 0, "limit": 0}}


def test_a_jobs_results_follow_its_metrics_and_families():
    one, two = (fleet_mod.Fleet(fleet_mod.load_json(os.path.join(
        BENCH, "configs", name + ".json")), 1, tiny=True)
        for name in ("rollout7d", "rollout7d_2m"))
    assert check.expected(one, 0) == [("pair", "error4xx", (0,)),
                                      ("band", "error4xx", (0,))]
    assert check.expected(two, 0) == [
        ("pair", "error4xx", (0,)), ("pair", "latency", (1,)),
        ("bivariate", "error4xx&latency", (0, 1))]
    q = two.queries(5)
    assert list(q) == ["error4xx", "latency"]
    assert "&m=0&w=cur&" in q["error4xx"]["current"]
    assert "&m=1&w=hist&" in q["latency"]["historical"]
    assert two.points_fetched(5, two.now_slot()) \
        == 2 * one.points_fetched(5, one.now_slot())
    # the seeded draw does not know how many metrics a job watches
    np.testing.assert_array_equal(one.base, two.base)
    assert one.anomalous == two.anomalous
    full = fleet_mod.Fleet(one.config | {"classes": [
        dict(one.classes[0], jobs=300)]}, 1)
    assert (full.app_name(299), two.app_name(47)) == ("app-43", "app-47")


# -------------------------------------------------- which file judges a family
@pytest.mark.parametrize("config,families", [
    ("rollout7d", ["pair", "band"]),
    ("rollout7d_2m", ["pair", "bivariate"]),
])
def test_an_accepted_configuration_is_judged_by_each_familys_own_file(
        config, families):
    cfg = _config(config)
    assert "references" not in cfg
    fl = fleet_mod.Fleet(cfg, 1, tiny=True)
    assert fl.references == {f: f for f in families}
    for f in families:
        mod = check.family(fl, f)
        assert mod.__name__ == "bench_family_" + f
        assert mod.__file__ == os.path.join(BENCH, "families", f + ".py")
        assert check.family(fl, f) is mod  # loaded once


@pytest.fixture
def families_dir(tmp_path, monkeypatch):
    """A families directory of the test's own: the real files, beside
    `band_fixture.py`, which is `band.py` under other numbers' names and
    keeps every entry its `judge` was handed."""
    for name in os.listdir(fleet_mod.FAMILIES_DIR):
        if name.endswith(".py"):
            shutil.copy(os.path.join(fleet_mod.FAMILIES_DIR, name), tmp_path)
    src = (tmp_path / "band.py").read_text()
    for old, new in (("band_gap", "fx_gap"),
                     ("band_count_out", "fx_count_out")):
        assert f'"{old}"' in src
        src = src.replace(f'"{old}"', f'"{new}"')
    (tmp_path / "band_fixture.py").write_text(src + """

SEEN = []
_judge = judge


def judge(entry, ref, i, limits):
    SEEN.append(entry)
    return _judge(entry, ref, i, limits)
""")
    monkeypatch.setattr(fleet_mod, "FAMILIES_DIR", str(tmp_path))
    monkeypatch.setattr(check, "_FAMILIES", {})
    return tmp_path


def _cell_with(monkeypatch, **keys):
    """`rollout7d_polled` with keys of its configuration replaced."""
    real = harness.load_cell

    def load(name):
        cell = real(name)
        cell["config"] = dict(cell["config"], **keys)
        return cell

    monkeypatch.setattr(harness, "load_cell", load)


def test_a_named_reference_judges_the_programs_band_entries(
        monkeypatch, families_dir):
    _cell_with(monkeypatch, references={"band": "band_fixture"})
    out = harness.run(_args("rollout7d_polled"))
    assert out["correct"] is True and out["failed"] == 0
    assert list(out["compared"]) == [
        "pair_p_gap", "fx_gap", "fx_count_out", "verdict_miss",
        "stale_jobs", "compiles_in_window"]
    assert 0 < out["compared"]["fx_gap"]["value"] < 0.004
    # the result's key stays the family the program records
    assert out["cycles"][-1]["rows"] == {
        "pair": out["cycles"][-1]["offered"],
        "band": out["cycles"][-1]["offered"]}
    fixture = check._FAMILIES["band_fixture"]
    assert fixture.__file__ == str(families_dir / "band_fixture.py")
    assert len(fixture.SEEN) == out["cycles"][-1]["offered"]
    assert {(e["family"], e["metric"]) for e in fixture.SEEN} == {
        ("band", "error4xx")}
    # a configuration that names no file is judged by the family's own,
    # in the same process
    plain = fleet_mod.Fleet(_config("rollout7d"), 1, tiny=True)
    named = fleet_mod.Fleet(_config("rollout7d") | {
        "references": {"band": "band_fixture"}}, 1, tiny=True)
    assert check.family(named, "band") is fixture
    assert check.family(plain, "band").__name__ == "bench_family_band"
    assert check.family(named, "pair") is check.family(plain, "pair")
    assert [n for n, _, _ in check.compare(
        plain, {"jobs": {}, "now_slot": 0, "lag_s": 0.0})][:3] == [
            "pair_p_gap", "band_gap", "band_count_out"]


def _no_engine(monkeypatch):
    from foremast_tpu.engine.analyzer import Analyzer

    def built(self, *a, **kw):
        raise AssertionError("an engine was built")

    monkeypatch.setattr(Analyzer, "__init__", built)


@pytest.mark.parametrize("references,naming", [
    ({"band": "../families/band"}, "'../families/band'"),
    ({"band": "sub/band"}, "'sub/band'"),
    ({"band": ".."}, "'..'"),
    ({"band": ""}, "''"),
    ({"band": 7}, "7"),
    ({"band": "band_holt_winters"}, "families/band_holt_winters.py"),
    ({"bands": "band"}, "bands"),
])
def test_a_stem_that_names_no_file_ends_the_run_before_an_engine(
        monkeypatch, capsys, references, naming):
    _no_engine(monkeypatch)
    _cell_with(monkeypatch, references=references)
    with pytest.raises(harness.BenchError, match=naming):
        harness.run(_args("rollout7d_polled"))
    capsys.readouterr()
    assert harness.main(["--workload", "rollout7d_polled", "--seed", "3",
                         "--seconds", "0", "--tiny"]) == 3
    said = capsys.readouterr()
    assert said.out == "" and naming in said.err


@pytest.mark.parametrize("key,value,stem", [
    ("algorithm", "holt_winters", "band"),
    ("algorithm", "prophet", "band"),
    ("algorithm", None, "band"),
    ("pairwise_algorithm", "wilcoxon_all", "pair"),
    ("pairwise_algorithm", "all", "pair"),
])
def test_a_family_file_refuses_an_algorithm_it_is_not_the_reference_of(
        monkeypatch, capsys, key, value, stem):
    engine = dict(_config("rollout7d")["engine"], **{key: value})
    if value is None:
        del engine[key]
    _cell_with(monkeypatch, engine=engine)
    warmed = []
    monkeypatch.setattr(harness.Engine, "warm_up",
                        lambda self: warmed.append(1))
    assert harness.main(["--workload", "rollout7d_polled", "--seed", "3",
                         "--seconds", "0", "--tiny"]) == 3
    said = capsys.readouterr()
    assert said.out == "" and not warmed
    assert f"benchmark/families/{stem}.py" in said.err
    assert f"engine.{key}" in said.err and repr(value) in said.err


def test_no_accelerator_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "rollout7d_polled", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


# -------------------------------------------------------------- the control
@pytest.mark.parametrize("seed", [1, 2, 4000000007])
@pytest.mark.parametrize("config,names,failing", [
    ("rollout7d", ["pair_p_gap", "band_gap", "band_count_out"],
     ["band_gap", "pair_p_gap"]),
    ("rollout7d_2m", ["pair_p_gap", "bi_bound_gap", "bi_count_out"],
     ["bi_bound_gap", "pair_p_gap"]),
])
def test_control_in_bfloat16_is_not_correct(config, names, failing, seed):
    fl = fleet_mod.Fleet(_config(config), seed, tiny=True)
    jobs = [j for j in range(fl.jobs) if j not in fl.anomalous]
    k_now = fl.now_slot() + 3
    sound = check.reference_answers(fl, jobs, k_now, 5.0, "float64")
    numbers = check.compare(fl, sound)
    assert [n for n, _, _ in numbers] == names + ["verdict_miss",
                                                  "stale_jobs"]
    assert all(v <= lim for _, v, lim in numbers), numbers
    control = check.reference_answers(fl, jobs, k_now, 5.0, "bfloat16")
    numbers = {n: (v, lim) for n, v, lim in check.compare(fl, control)}
    for name in failing:
        assert numbers[name][0] > numbers[name][1], numbers


# --------------------------------------------------------------- the faults
def _broken_answer(monkeypatch):
    """An answer altered where it is produced: every upper bound the
    band kernel returns is moved up by 0.05."""
    from foremast_tpu.ops import forecast as fc

    real = fc.band_anomalies

    def altered(*a, **kw):
        out = dict(real(*a, **kw))
        out["upper"] = out["upper"] + 0.05
        return out

    monkeypatch.setattr(fc, "band_anomalies", altered)


def _half_the_batch(monkeypatch):
    """Half of the batch left out: every second row of a band launch
    comes back with no result (the job still has its pair verdict, so
    only `correct` sees it)."""
    from foremast_tpu.engine.analyzer import Analyzer

    real = Analyzer._collect_bands

    def halved(self, state):
        res = real(self, state)
        return {k: v for i, (k, v) in enumerate(res.items()) if i % 2 == 0}

    monkeypatch.setattr(Analyzer, "_collect_bands", halved)


def _pair_answer(monkeypatch):
    """A pair answer altered where it is produced: p-values doubled."""
    from foremast_tpu.engine.analyzer import Analyzer

    real = Analyzer._collect_pairs

    def altered(self, state):
        res = real(self, state)
        for r in res.values():
            r["min_p"] = min(2.0 * r["min_p"] + 0.001, 1.0)
        return res

    monkeypatch.setattr(Analyzer, "_collect_pairs", altered)


def _stale_newest(monkeypatch):
    """The newest sample dropped where the current window is spliced:
    every tail the source serves ends one scrape early."""
    real = FleetSource.fetch_series

    def short(self, url):
        ts, vals, n = real(self, url)
        return (ts[:-1], vals[:-1], n) if "&w=cur&" in url else (ts, vals, n)

    monkeypatch.setattr(FleetSource, "fetch_series", short)


def _one_pair_result_lost(monkeypatch):
    """One metric's pair result dropped from the record: the rank test of
    `latency` comes back with nothing (the job keeps its other two
    results, so only `correct` sees it)."""
    from foremast_tpu.engine.analyzer import Analyzer

    real = Analyzer._collect_pairs

    def lossy(self, state):
        return {k: v for k, v in real(self, state).items()
                if k[1] != "latency"}

    monkeypatch.setattr(Analyzer, "_collect_pairs", lossy)


def _metrics_swapped(monkeypatch):
    """The two metrics swapped where the joint grid is built: each is
    fitted, bounded and gated as the other."""
    from foremast_tpu.engine import analyzer as an

    real = an._joint_grid

    def swapped(hists, curs):
        x, m, n_h, n_c = real(hists, curs)
        return x[::-1], m[::-1], n_h, n_c

    monkeypatch.setattr(an, "_joint_grid", swapped)


def _joint_counts(monkeypatch):
    """The bivariate counts altered where they are produced: every job
    has one anomalous point more."""
    from foremast_tpu.engine.analyzer import Analyzer

    real = Analyzer._collect_bivariate

    def altered(self, state):
        res = real(self, state)
        for r in res.values():
            r["count"] += 1
        return res

    monkeypatch.setattr(Analyzer, "_collect_bivariate", altered)


def _stale_latency(monkeypatch):
    """The newest `latency` sample dropped: the second metric's tails end
    one scrape early, the first's do not."""
    real = FleetSource.fetch_series

    def short(self, url):
        ts, vals, n = real(self, url)
        return (ts[:-1], vals[:-1], n) if "&m=1&w=cur&" in url \
            else (ts, vals, n)

    monkeypatch.setattr(FleetSource, "fetch_series", short)


@pytest.mark.parametrize("workload,fault,failing", [
    ("rollout7d_polled", None, None),
    ("rollout7d_polled", _broken_answer, "band_gap"),
    ("rollout7d_polled", _half_the_batch, "verdict_miss"),
    ("rollout7d_polled", _pair_answer, "pair_p_gap"),
    ("rollout7d_polled", _stale_newest, "stale_jobs"),
    ("rollout7d_2m_polled", None, None),
    ("rollout7d_2m_polled", _pair_answer, "pair_p_gap"),
    ("rollout7d_2m_polled", _one_pair_result_lost, "verdict_miss"),
    ("rollout7d_2m_polled", _metrics_swapped, "bi_bound_gap"),
    ("rollout7d_2m_polled", _joint_counts, "bi_count_out"),
    ("rollout7d_2m_polled", _stale_latency, "stale_jobs"),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, workload, fault,
                                            failing):
    if fault is not None:
        fault(monkeypatch)
    out = harness.run(_args(workload))
    compared = out["compared"]
    if fault is None:
        assert out["correct"] is True and out["failed"] == 0
        return
    assert out["correct"] is False
    assert compared[failing]["value"] > compared[failing]["limit"]


@pytest.mark.parametrize("seed", [1, 2, 4000000007])
def test_two_metric_cell_is_correct_at_tiny(seed):
    out = harness.run(_args("rollout7d_2m_polled", seed))
    assert out["correct"] is True and out["failed"] == 0, out["compared"]
    live = out["cycles"][-1]["offered"]
    assert out["cycles"][-1]["rows"] == {"pair": 2 * live, "bivariate": live}
    assert out["cycles"][-1]["fetches"] == 2 * live
