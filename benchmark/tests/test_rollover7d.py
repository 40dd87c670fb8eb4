"""The fifth cell, `rollover7d_polled`: foremast-trigger's rollover request,
a 20-to-31-point current window judged against the trailing 7 days. Its
`--tiny` line, its control, the fault its own `pair_p_gap` limit is there
to catch, and its three readers.

The tiny fleet cuts the jobs alone (48) and keeps the 7-day history: the
fault is the float32 rank sum of the 10,081-point side, which is exact at
600 points (a tiny history of 600 reads the same `pair_p_gap` under both
routes, 1e-7), so only the whole history can show it. The tiny limit is
1e-5, as the cell's: the program reads 9.7e-8 to 1.2e-7 on the CPU over
12 seeds, the long side's route 2.5e-4 to 3.1e-4."""
import json
import os
import subprocess
import sys
import types

import pytest

import run as harness
from lib import check, costs, cycle_spans, fleet as fleet_mod, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "rollover7d_polled"
TINY_P_GAP = 1e-5


def _config(name="rollover7d"):
    return fleet_mod.load_json(os.path.join(BENCH, "configs",
                                            name + ".json"))


def _run(seed):
    return harness.run(types.SimpleNamespace(
        workload=CELL, seed=seed, seconds=0, trace=0, tiny=True))


# --------------------------------------------------------------- the request
def test_the_configuration_is_rollout7d_fleet_under_the_rollover_request():
    r, base = _config(), _config("rollout7d")
    assert r["engine"] == base["engine"]
    assert r["trace"] == dict(base["trace"], diurnal_amp=0.0)
    (cls,), (base_cls,) = r["classes"], base["classes"]
    assert dict(cls, name=None, strategy=None, placement=None) == dict(
        base_cls, name=None, strategy=None, placement=None)
    assert cls["strategy"] == "rollover"
    week = {"start": -10080, "points": 10081}
    assert cls["placement"] == {"historical": week, "baseline": week,
                                "current": {"points": 31}}
    assert (r["history_points"], r["current_points"]) == (10081, 20)
    assert r["references"] == {"pair": "pair", "band": "band"}
    assert r["reduced"] == []
    assert r["check"]["band_gap_sigmas"] == base["check"]["band_gap_sigmas"]
    assert r["check"]["pair_p_gap"] == 1e-5 <= 3e-5
    assert r["tiny"]["check"] == {"band_gap_sigmas": 0.004,
                                  "pair_p_gap": TINY_P_GAP}
    fl = fleet_mod.Fleet(r, 1)
    assert fl.jobs == 16384 and fl.max_cycles == 12 and fl.lead == 0
    assert fl.window_slots("baseline", 0) == fl.window_slots("historical", 0)
    assert fl.held("baseline", 0, fl.now_slot()) == 10081
    tiny = fleet_mod.Fleet(r, 1, tiny=True)
    assert tiny.jobs == 48 and tiny.max_cycles == 12
    assert tiny.held("baseline", 0, tiny.now_slot()) == 10081
    assert tiny.held("current", 0, tiny.now_slot() + 40) == 31


# ---------------------------------------------------------------- a tiny run
_COUNTERS = ("fetches_per_cycle", "launches_per_cycle", "h2d_bytes_per_cycle",
             "d2h_bytes_per_cycle", "pack_fill_share", "pair_sort_fill_share",
             "period_partitions_per_cycle", "splice_appends_per_cycle",
             "compiles_in_window")
_LIVE = 46
_PINNED_COUNTERS = (46.0, 2.0, 15732736.0, 1052096.0, 29.54870859781901,
                    22.161531448364258, 0.0, 46.0, 0)
_PINNED = {
    1: {"pair_p_gap": 1.0614300705036328e-07,
        "band_gap": 0.0003857005956011315},
    4000000007: {"pair_p_gap": 9.939186595975258e-08,
                 "band_gap": 0.0003301962345880233},
}


@pytest.mark.parametrize("seed", sorted(_PINNED))
def test_the_tiny_line_reads_what_it_read(seed):
    # a process of its own: the span readers find a cycle by its id in the
    # tracer's ring, which an earlier run in the same process also filled
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(seed), "--seconds", "0", "--trace", "1", "--tiny"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert (out["correct"], out["failed"]) == (True, 0)
    assert out["attempted"] == 2 * _LIVE
    assert [{k: c[k] for k in ("offered", "launches", "fetches", "rows")}
            for c in out["cycles"]] == 2 * [
        {"offered": _LIVE, "launches": 2, "fetches": _LIVE,
         "rows": {"pair": _LIVE, "band": _LIVE}}]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {k: m[k] for k in _COUNTERS} == dict(zip(_COUNTERS,
                                                    _PINNED_COUNTERS))
    assert {k: v["value"] for k, v in out["compared"].items()} == dict(
        _PINNED[seed], band_count_out=0, verdict_miss=0, stale_jobs=0,
        compiles_in_window=0)
    # no device trace on the CPU: the two device metrics stay out
    assert "pair_device_s_per_cycle" not in m and "pair7d_roofline" not in m


# -------------------------------------------------------------- the control
@pytest.mark.parametrize("seed", [1, 2, 4000000007])
def test_control_in_bfloat16_is_not_correct(seed):
    fl = fleet_mod.Fleet(_config(), seed, tiny=True)
    jobs = [j for j in range(fl.jobs) if j not in fl.anomalous]
    k_now = fl.now_slot() + 3
    sound = check.reference_answers(fl, jobs, k_now, 5.0, "float64")
    numbers = check.compare(fl, sound)
    assert all(v <= lim for _, v, lim in numbers), numbers
    control = check.reference_answers(fl, jobs, k_now, 5.0, "bfloat16")
    numbers = {n: (v, lim) for n, v, lim in check.compare(fl, control)}
    assert numbers["pair_p_gap"][0] > numbers["pair_p_gap"][1], numbers


# ---------------------------------------------------------------- the fault
def _long_side_mann_whitney(x, x_mask, y, y_mask):
    """U from x's own rank sum, the baseline's at 10,081 samples: a float32
    sum of about 5e7, where a step is 4."""
    import jax.numpy as jnp

    from foremast_tpu.ops.ranks import rank_sum_stats
    from foremast_tpu.ops.stats import norm_sf

    f32 = jnp.float32
    from_x = jnp.concatenate([jnp.ones(x.shape, f32), jnp.zeros(y.shape, f32)])
    R1, tie, _ = rank_sum_stats(jnp.concatenate([x, y]).astype(f32),
                                jnp.concatenate([x_mask, y_mask]), from_x)
    n1, n2 = jnp.sum(x_mask.astype(f32)), jnp.sum(y_mask.astype(f32))
    N = n1 + n2
    U1 = R1 - n1 * (n1 + 1.0) / 2.0
    U = jnp.maximum(U1, n1 * n2 - U1)
    s = jnp.sqrt(n1 * n2 / 12.0 * ((N + 1.0) - tie / (N * (N - 1.0))))
    p = jnp.clip(2.0 * norm_sf((U - n1 * n2 / 2.0 - 0.5) / s), 0.0, 1.0)
    return U1, jnp.where(s > 0.0, p, 1.0)


def test_the_long_sides_rank_sum_is_not_correct(monkeypatch):
    import jax

    from foremast_tpu.ops import pairwise
    from foremast_tpu.parallel import fleet as fl

    def tests(x, x_mask, y, y_mask):
        out = dict(pairwise.two_sample_tests(x, x_mask, y, y_mask))
        out["mann_whitney"] = _long_side_mann_whitney(x, x_mask, y, y_mask)
        return out

    monkeypatch.setattr(fl, "two_sample_tests", tests)
    # a function of its own, so that jit traces it afresh and does not
    # serve an earlier trace of `_score_rows`
    monkeypatch.setattr(fl, "score_pairs",
                        jax.jit(lambda *a: fl._score_rows(*a)))
    out = _run(4000000007)
    compared = {k: (v["value"], v["limit"])
                for k, v in out["compared"].items()}
    assert out["correct"] is False
    assert [k for k, (v, lim) in compared.items() if v > lim] \
        == ["pair_p_gap"], compared
    # 25 times the limit and more: the route, not a rounding of this run
    assert compared["pair_p_gap"][0] > 25 * TINY_P_GAP


# -------------------------------------------------------------- the readers
def _trace_ctx(fl, rows=(15300, 15296)):
    return {"trace": {"programs": {"jit__score_rows": [11.5, 2]}},
            "fleet": fl, "notes": {}, "peaks": peaks.for_kind("TPU v5 lite"),
            "cycles": [{"class_rows": {0: {"pair": r}},
                        "now_slot": fl.now_slot() + 2 + i}
                       for i, r in enumerate(rows)]}


def test_pair_device_seconds_and_the_7d_roofline():
    fl = fleet_mod.Fleet(_config(), 1)
    ctx = _trace_ctx(fl)
    assert harness.load_reader("pair_device_s_per_cycle")(ctx) == 5.75
    share = harness.load_reader("pair7d_roofline")(ctx)
    assert share == harness.load_reader("pair_roofline")(_trace_ctx(fl))
    # the real samples of both windows, at 22 and 23 current points
    least = sum(costs.least_seconds(costs.pair(r, 10081, 22 + i),
                                    ctx["peaks"])[0]
                for i, r in enumerate((15300, 15296)))
    assert share == pytest.approx(100.0 * least / 11.5, rel=1e-12)
    for reader in ("pair_device_s_per_cycle", "pair7d_roofline"):
        assert harness.load_reader(reader)(dict(ctx, trace=None)) is None


def test_the_fill_share_is_left_out_without_the_counters(monkeypatch):
    """A program from before the counters (the parent of this cell) has an
    `engine.score` span without them: the metric is left out."""
    score = {"name": cycle_spans.SCORE, "duration_ms": 1.0,
             "attrs": {"pack_real_elems": 3, "pack_total_elems": 4}}
    root = {"name": cycle_spans.ROOT, "duration_ms": 2.0, "attrs": {},
            "children": [score]}
    monkeypatch.setattr(cycle_spans, "roots", lambda ctx: [root, root])
    read = harness.load_reader("pair_sort_fill_share")
    assert read({"cycles": [{}, {}]}) is None
    score["attrs"].update(pair_real_elems=29, pair_sort_elems=100)
    assert read({"cycles": [{}, {}]}) == 29.0
