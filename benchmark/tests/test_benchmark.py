"""The benchmark's own checks: the trace reduction on a small trace, the
array source against the byte path, the cost functions against hand
counts, the last line of a tiny run, the control, and the faults that
`correct` has to catch."""
import json
import os
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
        v_c, fl.served(job, 0, fl.hist_hi, fl.now_slot()), atol=1e-12)


# -------------------------------------------------------------------- costs
def test_costs_against_hand_counts():
    # 10 rows of 100 samples: 15 bytes a sample and 12 a row; 12 ops a sample
    assert costs.band(10, 100) == {"bytes": 10 * (1500 + 12),
                                   "ops": 12000}
    # 4 rows, 64 + 64 samples: 5 bytes a sample and 20 a row;
    # 128 * log2(128) = 896 comparisons and 12 * 128 band ops a row
    assert costs.pair(4, 64, 64) == {"bytes": 4 * (640 + 20),
                                     "ops": 4 * (896 + 1536)}
    pk = peaks.for_kind("TPU v5 lite")
    secs, bound = costs.least_seconds({"bytes": 819e9, "ops": 1.0}, pk)
    assert (secs, bound) == (pytest.approx(1.0), "bandwidth")
    secs, bound = costs.least_seconds({"bytes": 1.0, "ops": 394e12}, pk)
    assert (secs, bound) == (pytest.approx(2.0), "compute")
    with pytest.raises(KeyError):
        peaks.for_kind("cpu")


# ---------------------------------------------------------------- a tiny run
@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_run_prints_the_contract_line(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "rollout7d_polled", "--seed", "4000000007", "--seconds", "0.2",
         "--trace", str(trace), "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2 * 48 - 6
    assert line["device"]["platform"] == "cpu"
    manifest = fleet_mod.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if trace == 0:
        assert set(line["metrics"]) == {m["name"]
                                        for m in manifest["end_to_end"]}
    else:
        # no device metric is ever reported from a CPU run
        by_source = {m["name"]: m["source"] for m in manifest["per_layer"]}
        assert line["metrics"] and all(
            by_source[n] != "device_trace" for n in line["metrics"])
        assert "busy_s" not in line["device"]
    assert p.stderr.strip().splitlines()[-1].startswith("compared ")


def test_no_accelerator_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "rollout7d_polled", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


# -------------------------------------------------------------- the control
@pytest.mark.parametrize("seed", [1, 2, 4000000007])
def test_control_in_bfloat16_is_not_correct(seed):
    cfg = fleet_mod.load_json(os.path.join(BENCH, "configs", "rollout7d.json"))
    fl = fleet_mod.Fleet(cfg, seed, tiny=True)
    jobs = [j for j in range(fl.jobs) if j not in fl.anomalous]
    k_now = fl.now_slot() + 3
    sound = check.reference_answers(fl, jobs, k_now, 5.0, "float64")
    numbers = check.compare(fl, sound)
    assert [n for n, _, _ in numbers] == [
        "pair_p_gap", "band_gap", "band_count_out", "verdict_miss",
        "stale_jobs"]
    assert all(v <= lim for _, v, lim in numbers), numbers
    control = check.reference_answers(fl, jobs, k_now, 5.0, "bfloat16")
    numbers = {n: (v, lim) for n, v, lim in check.compare(fl, control)}
    assert numbers["band_gap"][0] > numbers["band_gap"][1]
    assert numbers["pair_p_gap"][0] > numbers["pair_p_gap"][1]


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


@pytest.mark.parametrize("fault,failing", [
    (None, None),
    (_broken_answer, "band_gap"),
    (_half_the_batch, "verdict_miss"),
    (_pair_answer, "pair_p_gap"),
    (_stale_newest, "stale_jobs"),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault, failing):
    if fault is not None:
        fault(monkeypatch)
    out = harness.run(_args("rollout7d_polled"))
    compared = out["compared"]
    if fault is None:
        assert out["correct"] is True and out["failed"] == 0
        return
    assert out["correct"] is False
    assert compared[failing]["value"] > compared[failing]["limit"]
