"""Tier-0 triage screen (ISSUE 7): ops/triage.py + engine/triage.py.

The two load-bearing contracts:

  * the fused screen's statistics match a plain-numpy reference
    (randomized property test over NaN runs, gaps, short windows,
    constant/quantized series);
  * triage never flips a verdict the full path would give — the
    escalation-threshold sweep runs the SAME fixture stream through
    TRIAGE=0 and a grid of (TRIAGE_Z, TRIAGE_MARGIN) arms and pins the
    verdict state byte-identical every time; only the launch count may
    differ. `make perf` additionally gates the launch cut (≤ 20% of the
    screen-free path on a no-anomaly steady fleet).
"""
from __future__ import annotations

import json

import numpy as np
import pytest

from foremast_tpu.dataplane import FixtureDataSource, VerdictExporter
from foremast_tpu.engine import (
    Analyzer,
    Document,
    EngineConfig,
    JobStore,
    MetricQueries,
)
from foremast_tpu.engine import families
from foremast_tpu.engine import provenance as prov
from foremast_tpu.engine.triage import TriageGate, screen_cap
from foremast_tpu.ops import triage as triage_ops
from foremast_tpu.service.api import ForemastService
from foremast_tpu.utils.timeutils import to_rfc3339

STEP = 60
SEED = 20260807


# ---------------------------------------------------------------------------
# plain-numpy reference of the screen statistics (independent loop
# implementation — NOT the kernel's cumsum algebra)
# ---------------------------------------------------------------------------

def _ref_ma_preds(x, mask, window):
    """Causal rolling mean over the valid points of the last `window` time
    slots; undefined slots freeze at the rolling mean evaluated just after
    the most recent observation (slots before the first observation see
    the first valid value). Mirrors the documented semantics of
    `ops.forecast._moving_average_1d`, by loop."""
    T = x.shape[0]
    x = x.astype(np.float32)
    ma = np.full(T, np.nan, np.float32)
    for t in range(T):
        lo = max(t - window, 0)
        sel = mask[lo:t]
        if sel.any():
            ma[t] = np.float32(x[lo:t][sel].mean())
    first = np.float32(x[mask][0]) if mask.any() else x[0]
    preds = np.empty(T, np.float32)
    hold = np.nan
    prev = -1  # last valid index <= t-1
    for t in range(T):
        if t == 0 or mask[t - 1]:
            hold = ma[t]
        if not np.isnan(ma[t]):
            preds[t] = ma[t]
        else:
            preds[t] = hold if prev >= 0 else first
        if mask[t]:
            prev = t
    return preds


def _ref_screen(x, mask, region, thr, bound, mlb, margin, window):
    """Reference screen statistics for one row (float64 reductions)."""
    x = x.astype(np.float32)
    hist = mask & ~region
    checked = mask & region
    n_h = int(hist.sum())
    # predictions come from the HISTORY mask only — the judged region is
    # extrapolated from the frozen rolling mean, exactly like the band
    # scorer's hist_mask = xm & ~region
    preds = _ref_ma_preds(x, hist, window)
    r = np.where(hist, x - preds, 0.0).astype(np.float64)
    sigma = float(np.sqrt((r ** 2).sum() / max(n_h, 1)))
    if n_h < 2:
        sigma = float("inf")
    mode = bound if bound != 0 else 3

    def band(width_sigmas, eps=0.0):
        # errstate: rows with an empty history make preds NaN / sigma inf
        # (evaluated here, skipped by the caller's min-points floor)
        with np.errstate(invalid="ignore"):
            w = width_sigmas * sigma
            upper = preds + w + eps
            lower = np.maximum(preds - w, mlb) - eps
            viol = ((x > upper) & bool(mode & 1)) | (
                (x < lower) & bool(mode & 2))
        return int((viol & checked).sum()), upper, lower

    count, upper, lower = band(thr)
    dev = np.abs(x - preds)
    resid_z = float(np.where(checked, dev, 0.0).max()
                    / max(sigma, 1e-30)) if np.isfinite(sigma) else 0.0
    hv = np.sort(x[hist].astype(np.float64))
    if n_h:
        med = 0.5 * (hv[(n_h - 1) // 2] + hv[n_h // 2])
        ad = np.sort(np.abs(x[hist].astype(np.float64) - med))
        mad = 0.5 * (ad[(n_h - 1) // 2] + ad[n_h // 2])
        scale = max(1.4826 * mad, sigma if np.isfinite(sigma) else 0.0)
        robust_z = float(np.where(checked, np.abs(x - med), 0.0).max()
                         / max(scale, 1e-30))
    else:
        robust_z = 0.0
    n_r = max(int(region.sum()), 1)
    return {
        "count": count,
        "checked": int(checked.sum()),
        "n_hist": n_h,
        "sigma": sigma,
        "resid_z": resid_z,
        "robust_z": robust_z,
        "upper_mean": float(np.where(region, upper, 0.0).sum() / n_r),
        "lower_mean": float(np.where(region, lower, 0.0).sum() / n_r),
        "band": band,  # closure for eps-bracketing count checks
        "thr": thr,
    }


def _rand_row(rng, T):
    """One randomized packed row: varied level/noise, gaps, NaN runs at
    masked slots, occasional quantized (integer) or constant series, and
    occasionally a too-short history."""
    kind = rng.integers(0, 5)
    level = float(rng.uniform(0.5, 100.0))
    noise = float(rng.uniform(0.01, 0.3)) * level
    x = rng.normal(level, noise, T).astype(np.float32)
    if kind == 1:      # quantized: MAD can be 0 while sigma isn't
        x = np.round(x).astype(np.float32)
    elif kind == 2:    # constant series
        x = np.full(T, np.float32(level))
    mask = rng.random(T) > 0.12
    if kind == 3:      # NaN run at masked-out slots (parse gaps)
        run = slice(T // 4, T // 4 + max(T // 8, 1))
        x[run] = np.nan
        mask[run] = False
    L = T if kind != 4 else int(rng.integers(3, max(T // 8, 4)))
    mask[L:] = False   # right padding (short window when kind == 4)
    x[~mask] = np.where(rng.random((~mask).sum()) < 0.3, np.nan,
                        0.0).astype(np.float32)
    n_h = int(L * rng.uniform(0.5, 0.9))
    region = np.zeros(T, bool)
    region[n_h:L] = True
    thr = float(rng.choice([2.0, 3.0, 5.0, 10.0]))
    bound = int(rng.choice([0, 1, 2, 3]))
    mlb = float(rng.choice([0.0, 0.0, level * 0.5]))
    return x, mask, region, thr, bound, mlb


def test_screen_stats_property_vs_numpy_reference():
    rng = np.random.default_rng(SEED)
    window = 30
    margin = 0.25
    for round_i in range(8):
        T = int(rng.choice([32, 64, 128]))
        B = 16
        rows = [_rand_row(rng, T) for _ in range(B)]
        xv = np.stack([r[0] for r in rows])
        xm = np.stack([r[1] for r in rows])
        reg = np.stack([r[2] for r in rows])
        thr = np.asarray([r[3] for r in rows], np.float32)
        bnd = np.asarray([r[4] for r in rows], np.int32)
        mlb = np.asarray([r[5] for r in rows], np.float32)
        mg = np.full(B, margin, np.float32)
        out = {k: np.asarray(v) for k, v in triage_ops.screen_rows(
            xv, xm, reg, thr, bnd, mlb, mg, window).items()}
        for i in range(B):
            ref = _ref_screen(xv[i], xm[i], reg[i], float(thr[i]),
                              int(bnd[i]), float(mlb[i]), margin, window)
            ctx = f"round {round_i} row {i}"
            assert int(out["checked"][i]) == ref["checked"], ctx
            assert int(out["n_hist"][i]) == ref["n_hist"], ctx
            # no NaN may ever escape the kernel: a NaN statistic would
            # make the host-side CLEAR comparison silently False (an
            # escalate, so verdict-safe, but the stats must stay honest)
            for k in ("count", "shrunk_count", "robust_z", "resid_z"):
                assert not np.isnan(float(out[k][i])), f"{ctx}: {k} NaN"
            if ref["n_hist"] == 0:
                continue  # unscreenable either way (min-points floor)
            sg = float(out["sigma"][i])
            if np.isfinite(ref["sigma"]):
                # the kernel's rolling mean is a difference of two f32
                # prefix sums, and a prefix sum is only resolved to the
                # f32 spacing at ITS magnitude (up to n_hist * level),
                # not at the level of one sample. On an exactly constant
                # series the true residual is 0, so the kernel's sigma is
                # that rounding residue: bounded by one f32 spacing at
                # the row's largest prefix sum, which for level 100 and
                # T 128 is ~1e-3 — far above a fixed 1e-5, and still
                # <= 0.15% of the smallest real sigma _rand_row draws
                # (noise >= 1% of level), i.e. inside the rtol.
                hist_abs = np.abs(xv[i][xm[i] & ~reg[i]]).astype(np.float64)
                resolution = float(np.spacing(np.float32(hist_abs.sum())))
                np.testing.assert_allclose(
                    sg, ref["sigma"], rtol=2e-3,
                    atol=max(1e-5, resolution), err_msg=ctx)
            else:
                assert not np.isfinite(sg), ctx
            # counts: float32-vs-float64 drift may flip only points within
            # eps of the band boundary — bracket instead of exact-match
            eps = 1e-3 * max(abs(ref["upper_mean"]), abs(ref["lower_mean"]),
                             1e-3)
            lo, _, _ = ref["band"](ref["thr"], eps)
            hi, _, _ = ref["band"](ref["thr"], -eps)
            assert lo <= int(out["count"][i]) <= hi, ctx
            s_lo, _, _ = ref["band"](ref["thr"] - margin, eps)
            s_hi, _, _ = ref["band"](ref["thr"] - margin, -eps)
            assert s_lo <= int(out["shrunk_count"][i]) <= s_hi, ctx
            # the shrunk band is strictly narrower: dominance, always
            assert int(out["shrunk_count"][i]) >= int(out["count"][i]), ctx
            # degenerate floor: on a (near-)constant series sigma is pure
            # float-rounding noise, so resid_z and the counts are
            # noise/noise ratios — escalation-direction-safe (robust_z is
            # exactly 0 there) but not comparable to a float64 reference
            scale = max(abs(ref["upper_mean"]), abs(ref["lower_mean"]), 1.0)
            if np.isfinite(ref["sigma"]) and ref["sigma"] > 1e-5 * scale:
                np.testing.assert_allclose(
                    float(out["resid_z"][i]), ref["resid_z"], rtol=2e-3,
                    atol=1e-4, err_msg=ctx)
                # the bounds are preds ± thr*sigma: sigma's float32 drift
                # amplifies by thr and the subtraction cancels, so the
                # honest tolerance scales with the BAND WIDTH, not the
                # bound's own magnitude
                btol = 5e-3 * (ref["thr"] * ref["sigma"]
                               + abs(ref["upper_mean"])) + 1e-4
                assert abs(float(out["upper_mean"][i])
                           - ref["upper_mean"]) <= btol, ctx
                assert abs(float(out["lower_mean"][i])
                           - ref["lower_mean"]) <= btol, ctx
            if ref["robust_z"] < 1e6:  # scale ~0 blowups: sign-only check
                np.testing.assert_allclose(
                    float(out["robust_z"][i]), ref["robust_z"], rtol=2e-3,
                    atol=1e-4, err_msg=ctx)


def test_screen_constant_series_clears_and_spike_escalates():
    """A constant series is the boring-row archetype: zero violations,
    robust_z 0 (MAD 0 must not divide-by-zero into always-escalate).
    The same series with one current-region spike must escalate."""
    T, window = 128, 30
    x = np.full(T, np.float32(42.0))
    mask = np.ones(T, bool)
    region = np.zeros(T, bool)
    region[96:] = True
    args = (np.stack([x, x.copy()]), np.stack([mask, mask]),
            np.stack([region, region]),
            np.full(2, 2.0, np.float32), np.ones(2, np.int32),
            np.zeros(2, np.float32), np.full(2, 0.25, np.float32))
    spiked = args[0].copy()
    spiked[1, 100] = 1000.0
    args = (spiked, *args[1:])
    out = {k: np.asarray(v) for k, v in
           triage_ops.screen_rows(*args, window).items()}
    assert int(out["shrunk_count"][0]) == 0
    assert float(out["robust_z"][0]) == 0.0
    assert int(out["shrunk_count"][1]) >= 1
    assert float(out["robust_z"][1]) > 8.0


def test_triage_z_zero_escalates_constant_series():
    """TRIAGE_Z=0 must screen nothing — the documented off-semantics —
    including rows whose robust_z is exactly 0.0 (constant series), which
    a strict > guard would still clear."""
    g = TriageGate.__new__(TriageGate)
    g.z, g.margin, g.min_points = 0.0, 0.25, 1

    class _An:
        @staticmethod
        def _gate(checked):
            return 2.0

    g.an = _An()
    o = {"n_hist": 100, "shrunk_count": 0, "checked": 32, "robust_z": 0.0}
    assert g._row_clear(families.family("band"), o) is False
    g.z = 8.0
    assert g._row_clear(families.family("band"), o) is True


def test_screen_cap_memory_scaling():
    assert screen_cap(16384, 128) == 16384
    assert screen_cap(16384, 1024) == 16384
    assert screen_cap(16384, 4096) == 4096   # budget / T
    assert screen_cap(16384, 16384) == 1024  # floor
    assert screen_cap(4, 128) == 16          # fire_rows floor


def test_arg_spec_matches_kernel_signature():
    out = triage_ops.screen_rows(*triage_ops.triage_arg_spec(16, 64), 30)
    assert np.asarray(out["count"]).shape == (16,)


# ---------------------------------------------------------------------------
# e2e fixtures: a continuous monitor fleet of band jobs
# ---------------------------------------------------------------------------

def _series(rng, level, n, spread=None):
    spread = level * 0.1 + 0.01 if spread is None else spread
    ts = np.arange(n) * STEP
    return ts.tolist(), np.clip(rng.normal(level, spread, n), 0,
                                None).tolist()


def _fleet(n_watch=6, seed=SEED):
    """(store, fixtures, advance): continuous single-metric band monitors
    plus the escalation shapes — a VERDICT-anomalous job (crosses the band
    gate), a borderline sub-verdict job (fails the screen, stays healthy),
    a canary-class band job, and a short-history job. `advance(cycle)`
    appends one fresh sample per series so every fingerprint moves every
    cycle (the memo-miss regime triage exists for)."""
    rng = np.random.default_rng(seed)
    fixtures: dict = {}
    store = JobStore()
    levels: dict = {}

    def mk(job_id, strategy="continuous", level=10.0, n_cur=32,
           n_hist=200, metric="latency"):
        cur, hist = f"u/{job_id}/c", f"u/{job_id}/h"
        fixtures[cur] = _series(rng, level, n_cur)
        fixtures[hist] = _series(rng, level, n_hist)
        levels[job_id] = level
        store.create(Document(
            id=job_id, app_name=f"app-{job_id}", namespace="triage",
            strategy=strategy, start_time=to_rfc3339(0.0),
            end_time="" if strategy == "continuous" else
            to_rfc3339(5_000_000.0),
            metrics={metric: MetricQueries(current=cur, historical=hist)},
        ))

    for i in range(n_watch):
        mk(f"watch-{i}", level=float(5 + 3 * i))
    mk("anomalous", level=10.0)
    cur = fixtures["u/anomalous/c"]
    # every current point far outside the band: crosses the verdict gate
    fixtures["u/anomalous/c"] = (cur[0], [v + 200.0 for v in cur[1]])
    mk("borderline", level=10.0)
    cur = fixtures["u/borderline/c"]
    # sustained sub-verdict anomaly: a few big spikes — enough to fail
    # the screen forever, too few to cross max(2, 0.1 * checked)
    vals = list(cur[1])
    vals[5] += 200.0
    fixtures["u/borderline/c"] = (cur[0], vals)
    mk("canary-band", strategy="canary", level=10.0)
    mk("thin", level=10.0, n_hist=12)  # below TRIAGE_MIN_POINTS

    def advance(cycle):
        for url, (ts, vals) in list(fixtures.items()):
            job_id = url.split("/")[1]
            if not url.endswith("/c"):
                continue
            nrng = np.random.default_rng(hash((url, cycle)) % 2 ** 32)
            lvl = levels[job_id]
            nxt = float(np.clip(nrng.normal(lvl, lvl * 0.1 + 0.01), 0,
                                None))
            if job_id == "anomalous":
                nxt += 200.0
            fixtures[url] = (ts + [ts[-1] + STEP], vals + [nxt])

    return store, fixtures, advance


def _snapshot(store: JobStore) -> str:
    docs = {}
    for doc in store._jobs.values():
        docs[doc.id] = {"status": doc.status, "reason": doc.reason,
                        "anomaly": doc.anomaly}
    return json.dumps(docs, sort_keys=True)


def _run_arm(cycles=3, seed=SEED, **cfg):
    cfg.setdefault("max_stuck_seconds", 1e9)
    cfg.setdefault("multimetric_auto", False)
    store, fixtures, advance = _fleet(seed=seed)
    an = Analyzer(EngineConfig(**cfg), FixtureDataSource(fixtures), store,
                  VerdictExporter())
    snaps = []
    for c in range(cycles):
        an.run_cycle(worker="w", now=1000.0 + 10 * c)
        snaps.append(_snapshot(store))
        advance(c)
    return an, store, snaps


# ------------------------------------------------- verdict-safety sweep

def test_threshold_sweep_verdicts_byte_identical_to_triage_off():
    """The acceptance pin: for EVERY swept (TRIAGE_Z, TRIAGE_MARGIN) the
    per-cycle verdict state equals the TRIAGE=0 arm byte-for-byte on the
    same advancing fixture stream — anomalous, borderline, canary, thin
    and boring jobs alike. Only the launch count may differ."""
    _, _, off_snaps = _run_arm(triage=False)
    swept = [(0.0, 0.25), (2.0, 0.25), (8.0, 0.0), (8.0, 0.25),
             (8.0, 1.0), (1e9, 0.25), (8.0, 100.0)]
    for z, margin in swept:
        an, _, snaps = _run_arm(triage=True, triage_z=z,
                                triage_margin=margin)
        assert snaps == off_snaps, f"TRIAGE_Z={z} TRIAGE_MARGIN={margin}"
        # the arms must actually exercise both classifications: at the
        # default thresholds the boring rows clear; at the paranoid ends
        # (z=0, or margin >= threshold) everything escalates
        cleared = sum(an.triage_cleared_total.values())
        screened = sum(an.triage_screened_total.values())
        assert screened > 0
        if (z, margin) == (8.0, 0.25):
            assert cleared > 0
        if z == 0.0 or margin >= 100.0:
            assert cleared == 0


def test_triage_off_restores_screen_free_path_exactly():
    an, _, _ = _run_arm(triage=False)
    assert an.triage_screened_total == {}
    assert an.last_cycle_stages.get("triage") is None


def test_escalation_classes_always_take_full_path():
    """Canary-class jobs, thin histories, and the verdict-anomalous job
    must never be cleared; the boring watchers clear."""
    an, store, _ = _run_arm(triage=True)
    gate_hits = an.provenance.get("canary-band")
    assert gate_hits["path"] != prov.PATH_TRIAGED
    assert an.provenance.get("thin")["path"] != prov.PATH_TRIAGED
    assert an.provenance.get("anomalous")["path"] == prov.PATH_SCORED
    assert store.get("anomalous").status in ("anomaly",) or \
        store.get("anomalous").anomaly
    assert an.provenance.get("watch-0")["path"] == prov.PATH_TRIAGED
    # the borderline job fails the screen every cycle yet stays healthy:
    # the suspect-that-never-convicts re-escalates forever, by design
    assert an.provenance.get("borderline")["path"] == prov.PATH_SCORED


def test_non_ma_algorithm_disables_band_screening():
    """The one-sided dominance argument only covers moving_average*; any
    other forecaster must deactivate the band screen entirely."""
    an, _, snaps = _run_arm(triage=True, algorithm="exponential_smoothing")
    off_an, _, off_snaps = _run_arm(triage=False,
                                    algorithm="exponential_smoothing")
    assert snaps == off_snaps
    assert an.triage_screened_total == {}


# --------------------------------------------------- provenance + surfaces

def test_explain_names_triaged_path_over_the_wire():
    an, store, _ = _run_arm(triage=True)
    svc = ForemastService(store, exporter=an.exporter, analyzer=an)
    status, payload = svc.explain("watch-0")
    assert status == 200
    rec = payload["provenance"]
    assert rec["path"] == prov.PATH_TRIAGED
    assert "screened clear" in rec["detail"]
    fam = next(f for f in rec["families"] if f.get("triaged"))
    # the screen's statistics vs its thresholds: the "why" is auditable
    assert fam["robust_z"] <= fam["z_threshold"] == 8.0
    assert fam["margin"] == 0.25
    assert fam["checked"] > 0
    assert fam["unhealthy"] is False


def test_status_and_metrics_surface_triage_counters():
    an, store, _ = _run_arm(triage=True)
    svc = ForemastService(store, exporter=an.exporter, analyzer=an)
    status, payload = svc.status_summary()
    assert status == 200
    tri = payload["triage"]
    assert tri["screened"]["band"] > 0
    assert tri["cleared"]["band"] > 0
    assert 0.0 <= tri["escalation_ratio"] < 1.0
    assert tri["screen_launches"] >= 1
    cyc = payload["cycle"]["triage"]
    assert cyc["screened"] == cyc["cleared"] + cyc["escalated"]
    assert cyc["seconds"] >= 0.0
    text = an.exporter.render()
    assert 'foremastbrain:triage_screened_total{family="band"}' in text
    assert 'foremastbrain:triage_cleared_total{family="band"}' in text
    assert "foremastbrain:triage_escalation_ratio" in text
    assert "foremastbrain:triage_seconds" in text


def test_screen_failure_escalates_whole_bucket(monkeypatch):
    """A wedged/poisoned screen must cost only launches, never a cycle:
    every unit escalates to the full path and verdicts match TRIAGE=0."""
    def boom(*a, **k):
        raise RuntimeError("screen wedged")

    monkeypatch.setattr(TriageGate, "_screen", boom)
    an, _, snaps = _run_arm(triage=True)
    _, _, off_snaps = _run_arm(triage=False)
    assert snaps == off_snaps
    assert sum(an.triage_cleared_total.values()) == 0
    assert sum(an.triage_escalated_total.values()) > 0


def test_bench_triage_ab_identity_small():
    """The bench A/B's identity claim in miniature (the 1500-job figure
    is `BENCH_CYCLE_TRIAGE=1 python -m foremast_tpu.bench_cycle`)."""
    from foremast_tpu.bench_cycle import run_triage

    on = run_triage(n_jobs=24, cycles=2, anomaly_rate=0.1, triage=True,
                    metrics_per_job=3)
    off = run_triage(n_jobs=24, cycles=2, anomaly_rate=0.1, triage=False,
                     metrics_per_job=3)
    assert on["verdict_digest"] == off["verdict_digest"]
    assert on["cleared_per_cycle"] > 0


# ------------------------------------------------------------- perf gate

@pytest.mark.perf
def test_triage_launch_cut_gate():
    """`make perf` gate: on a no-anomaly steady fleet whose every row
    changes every cycle, TRIAGE=1 launches ≤ 20% of the TRIAGE=0
    programs, at byte-identical verdicts. pipeline_fire_rows is shrunk so
    the screen-free arm streams multiple rung launches per cycle — the
    shape a real fleet has at PIPELINE_FIRE_ROWS=1024 with 10k+ rows."""
    def arm(triage):
        rng = np.random.default_rng(7)
        fixtures: dict = {}
        store = JobStore()
        for i in range(96):
            cur, hist = f"u/w{i}/c", f"u/w{i}/h"
            fixtures[cur] = _series(rng, 10.0, 32)
            fixtures[hist] = _series(rng, 10.0, 200)
            store.create(Document(
                id=f"w{i}", app_name=f"app-{i}", namespace="perf",
                strategy="continuous", start_time=to_rfc3339(0.0),
                end_time="",
                metrics={"latency": MetricQueries(current=cur,
                                                  historical=hist)},
            ))
        an = Analyzer(
            EngineConfig(max_stuck_seconds=1e9, multimetric_auto=False,
                         triage=triage, pipeline_fire_rows=16),
            FixtureDataSource(fixtures), store, VerdictExporter())
        an.run_cycle(worker="w", now=1000.0)  # warm: compiles + memo fill
        for url, (ts, vals) in list(fixtures.items()):
            if url.endswith("/c"):
                nrng = np.random.default_rng(hash(url) % 2 ** 32)
                fixtures[url] = (ts + [ts[-1] + STEP],
                                 vals + [float(nrng.normal(10.0, 1.0))])
        before = an.device_launches
        an.run_cycle(worker="w", now=1010.0)
        return an.device_launches - before, _snapshot(store)

    on_launches, on_snap = arm(True)
    off_launches, off_snap = arm(False)
    assert on_snap == off_snap
    assert off_launches >= 5  # the gate must compare real streamed launches
    assert on_launches <= 0.2 * off_launches, (
        f"triage launch cut gate: {on_launches} vs {off_launches}")
