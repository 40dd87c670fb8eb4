"""Engine: state machine, lease takeover, and the end-to-end scoring slice.

The e2e test is SURVEY.md §7's "minimum end-to-end slice": a synthetic
ErrorGenerator scenario (reference demo app self-inflicts 5xx) through a
fixture data source -> job -> batched TPU-kernel scoring -> verdict.
"""
import os
import time

import numpy as np
import pytest

from foremast_tpu.dataplane import FixtureDataSource, VerdictExporter
from foremast_tpu.engine import Analyzer, Document, EngineConfig, JobStore, MetricQueries
from foremast_tpu.engine import jobs as J
from foremast_tpu.utils.timeutils import to_rfc3339


# ---------------------------------------------------------------- state machine
def test_status_machine_happy_path():
    store = JobStore()
    doc, created = store.create(Document(id="j1", app_name="a", strategy="canary",
                                         start_time="", end_time=""))
    assert created and doc.status == J.INITIAL
    store.transition("j1", J.PREPROCESS_INPROGRESS)
    store.transition("j1", J.PREPROCESS_COMPLETED)
    store.transition("j1", J.POSTPROCESS_INPROGRESS)
    store.transition("j1", J.COMPLETED_UNHEALTH, reason="bad")
    assert store.get("j1").status == J.COMPLETED_UNHEALTH
    assert J.to_external(J.COMPLETED_UNHEALTH) == "anomaly"
    assert J.to_external(J.INITIAL) == "new"
    assert J.to_external(J.PREPROCESS_FAILED) == "abort"


def test_invalid_transition_rejected():
    store = JobStore()
    store.create(Document(id="j1", app_name="a", strategy="canary",
                          start_time="", end_time=""))
    with pytest.raises(J.InvalidTransition):
        store.transition("j1", J.COMPLETED_HEALTH)


def test_create_dedupes_open_jobs():
    store = JobStore()
    d1, c1 = store.create(Document(id="x", app_name="a", strategy="canary",
                                   start_time="", end_time=""))
    d2, c2 = store.create(Document(id="x", app_name="a", strategy="canary",
                                   start_time="", end_time=""))
    assert c1 and not c2 and d1 is d2
    # terminal jobs may be recreated
    store.transition("x", J.ABORT)
    _, c3 = store.create(Document(id="x", app_name="a", strategy="canary",
                                  start_time="", end_time=""))
    assert c3


def test_stuck_job_takeover():
    store = JobStore()
    store.create(Document(id="j", app_name="a", strategy="canary",
                          start_time="", end_time=""))
    got = store.claim_open_jobs("w1", max_stuck_seconds=90)
    assert [d.id for d in got] == ["j"]
    # w2 cannot steal a fresh lease
    assert store.claim_open_jobs("w2", max_stuck_seconds=90) == []
    # ...but can steal an expired one
    store.get("j").lease_at -= 120
    got2 = store.claim_open_jobs("w2", max_stuck_seconds=90)
    assert [d.id for d in got2] == ["j"]
    assert store.get("j").lease_holder == "w2"


def test_snapshot_resume(tmp_path):
    p = str(tmp_path / "snap.json")
    store = JobStore(snapshot_path=p)
    store.create(Document(id="j", app_name="a", strategy="canary",
                          start_time="", end_time="",
                          metrics={"error5xx": MetricQueries(current="u1")}))
    store.flush()  # write-behind store: boundaries flush explicitly
    store2 = JobStore(snapshot_path=p)
    doc = store2.get("j")
    assert doc is not None and doc.metrics["error5xx"].current == "u1"


def test_snapshot_background_flusher_writes_without_explicit_flush(tmp_path):
    """Mutations persist via the background flusher alone (write-behind
    durability: snapshot at most ~1 s stale with no flush() call)."""
    p = str(tmp_path / "snap.json")
    store = JobStore(snapshot_path=p)
    store.create(Document(id="j", app_name="a", strategy="canary",
                          start_time="", end_time=""))
    deadline = time.time() + 5.0
    while time.time() < deadline:
        if os.path.exists(p) and JobStore(snapshot_path=str(p)).get("j"):
            break
        time.sleep(0.05)
    else:
        raise AssertionError("background flusher never wrote the snapshot")
    store.close()


def test_store_close_flushes_and_is_idempotent(tmp_path):
    p = str(tmp_path / "snap.json")
    store = JobStore(snapshot_path=p)
    store.create(Document(id="j", app_name="a", strategy="canary",
                          start_time="", end_time=""))
    store.close()
    store.close()  # second close is a no-op, not an error
    assert JobStore(snapshot_path=p).get("j") is not None


# ---------------------------------------------------------------- e2e slice
STEP = 60


def _series(rng, level, n, spread=None):
    spread = level * 0.1 + 0.01 if spread is None else spread
    ts = np.arange(n) * STEP
    return ts.tolist(), np.clip(rng.normal(level, spread, n), 0, None).tolist()


def _mk_job(store, fixtures, job_id, *, bad=False, end_time=0.0, rng=None):
    """Canary job: healthy baseline ~0.5 err/s; canary 5 err/s if bad."""
    rng = rng or np.random.default_rng(0)
    cur_url = f"http://prom/{job_id}/cur"
    base_url = f"http://prom/{job_id}/base"
    hist_url = f"http://prom/{job_id}/hist"
    fixtures[cur_url] = _series(rng, 5.0 if bad else 0.5, 30)
    fixtures[base_url] = _series(rng, 0.5, 30)
    fixtures[hist_url] = _series(rng, 0.5, 600)
    doc = Document(
        id=job_id, app_name=f"app-{job_id}", namespace="demo", strategy="canary",
        start_time=to_rfc3339(0.0), end_time=to_rfc3339(end_time),
        metrics={"error5xx": MetricQueries(current=cur_url, baseline=base_url,
                                           historical=hist_url)},
    )
    store.create(doc)
    return doc


def test_e2e_slice_bad_canary_flagged_good_passes():
    rng = np.random.default_rng(7)
    fixtures = {}
    store = JobStore()
    exporter = VerdictExporter()
    _mk_job(store, fixtures, "bad", bad=True, rng=rng)
    _mk_job(store, fixtures, "good", bad=False, rng=rng)
    analyzer = Analyzer(EngineConfig(pairwise_threshold=1e-4), FixtureDataSource(fixtures),
                        store, exporter)
    outcomes = analyzer.run_cycle(now=10_000.0)  # past endTime
    assert outcomes["bad"] == J.COMPLETED_UNHEALTH
    assert outcomes["good"] == J.COMPLETED_HEALTH
    bad = store.get("bad")
    assert "error5xx" in bad.reason
    assert bad.anomaly  # flat [ts, v, ...] payload present
    pairs = next(iter(bad.anomaly.values()))
    assert len(pairs) >= 2 and len(pairs) % 2 == 0
    # exporter published foremastbrain series
    text = exporter.render()
    assert "foremastbrain:error5xx_upper" in text
    assert 'app="app-bad"' in text


def test_e2e_healthy_before_endtime_requeues():
    rng = np.random.default_rng(3)
    fixtures = {}
    store = JobStore()
    _mk_job(store, fixtures, "j", bad=False, end_time=5_000_000.0, rng=rng)
    analyzer = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store)
    outcomes = analyzer.run_cycle(now=100.0)  # before endTime
    assert outcomes["j"] == J.INITIAL  # fail-fast: keep watching
    # bad data arriving on a later cycle flips it
    fixtures[f"http://prom/j/cur"] = _series(rng, 8.0, 30)
    outcomes = analyzer.run_cycle(now=200.0)
    assert outcomes["j"] == J.COMPLETED_UNHEALTH


def test_e2e_fetch_failure_marks_preprocess_failed():
    store = JobStore()
    doc = Document(id="j", app_name="a", namespace="d", strategy="canary",
                   start_time=to_rfc3339(0), end_time=to_rfc3339(0),
                   metrics={"error5xx": MetricQueries(current="http://nope")})
    store.create(doc)
    analyzer = Analyzer(EngineConfig(), FixtureDataSource({}), store)
    out = analyzer.run_cycle()
    # failed in preprocess, never judged — and the outcome is REPORTED
    # (degraded-mode bookkeeping prunes warm state off these outcomes)
    assert out == {"j": J.PREPROCESS_FAILED}
    assert store.get("j").status == J.PREPROCESS_FAILED
    assert J.to_external(store.get("j").status) == "abort"


def test_e2e_no_data_is_unknown():
    store = JobStore()
    fixtures = {"u": ([], [])}
    doc = Document(id="j", app_name="a", namespace="d", strategy="canary",
                   start_time=to_rfc3339(0), end_time=to_rfc3339(0),
                   metrics={"error5xx": MetricQueries(current="u")})
    store.create(doc)
    analyzer = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store)
    out = analyzer.run_cycle(now=100.0)
    assert out["j"] == J.COMPLETED_UNKNOWN


def test_hpa_job_emits_logs_and_requeues():
    rng = np.random.default_rng(5)
    fixtures = {}
    store = JobStore()
    exporter = VerdictExporter()
    tps_url, sla_url = "http://prom/tps", "http://prom/sla"
    hist_ts, hist_v = _series(rng, 100.0, 90, spread=3.0)
    cur_ts = [t + hist_ts[-1] + STEP for t in np.arange(30) * STEP]
    fixtures[tps_url] = (hist_ts + list(cur_ts),
                         hist_v + np.random.default_rng(1).normal(240, 5, 30).tolist())
    fixtures[sla_url] = _series(rng, 5.0, 120, spread=0.3)
    doc = Document(
        id="app:demo:hpa", app_name="app", namespace="demo", strategy="hpa",
        start_time="START_TIME", end_time="END_TIME",
        metrics={
            "tps": MetricQueries(historical=tps_url, current=tps_url, priority=0),
            "latency": MetricQueries(historical=sla_url, current=sla_url, priority=1),
        },
    )
    store.create(doc)
    analyzer = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store, exporter)
    out = analyzer.run_cycle(now=0.0)
    assert out["app:demo:hpa"] == J.INITIAL  # hpa jobs never terminate
    logs = store.hpalogs_for("app:demo:hpa")
    assert logs and logs[0].details[0]["metricType"] == "tps"
    assert "foremastbrain:namespace_app_per_pod:hpa_score" in exporter.render()
    # first cycle is breath-gated to 50
    assert logs[0].hpascore == 50.0


# -------------------------------------------------- review-finding regressions
def test_continuous_job_never_completes_while_healthy():
    rng = np.random.default_rng(2)
    fixtures = {}
    store = JobStore()
    ts = (np.arange(60) * STEP).tolist()
    fixtures["cu"] = (ts, rng.normal(0.5, 0.05, 60).clip(0).tolist())
    fixtures["hu"] = ((np.arange(600) * STEP).tolist(),
                      rng.normal(0.5, 0.05, 600).clip(0).tolist())
    doc = Document(id="c", app_name="a", namespace="d", strategy="continuous",
                   start_time="START_TIME", end_time="END_TIME",
                   metrics={"error5xx": MetricQueries(current="cu", historical="hu")})
    store.create(doc)
    analyzer = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store)
    for cycle in range(3):
        out = analyzer.run_cycle(now=1000.0 + cycle)
        assert out["c"] == J.INITIAL  # healthy continuous jobs loop forever


def test_continuous_job_survives_transient_fetch_error():
    store = JobStore()
    fixtures = {}
    doc = Document(id="c", app_name="a", namespace="d", strategy="continuous",
                   start_time="START_TIME", end_time="END_TIME",
                   metrics={"m": MetricQueries(current="missing")})
    store.create(doc)
    analyzer = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store)
    analyzer.run_cycle(now=100.0)
    assert store.get("c").status == J.INITIAL  # requeued, not dead
    # one-shot canary jobs DO fail terminally on fetch errors
    doc2 = Document(id="k", app_name="a", namespace="d", strategy="canary",
                    start_time=to_rfc3339(0), end_time=to_rfc3339(0),
                    metrics={"m": MetricQueries(current="missing")})
    store.create(doc2)
    analyzer.run_cycle(now=100.0)
    assert store.get("k").status == J.PREPROCESS_FAILED


def test_empty_current_is_unknown_not_healthy():
    rng = np.random.default_rng(4)
    store = JobStore()
    ts = (np.arange(30) * STEP).tolist()
    fixtures = {
        "cu": ([], []),  # deployment produced NO metrics
        "bu": (ts, rng.normal(0.5, 0.05, 30).tolist()),
        "hu": ((np.arange(600) * STEP).tolist(),
               rng.normal(0.5, 0.05, 600).tolist()),
    }
    doc = Document(id="j", app_name="a", namespace="d", strategy="canary",
                   start_time=to_rfc3339(0), end_time=to_rfc3339(0),
                   metrics={"error5xx": MetricQueries(current="cu", baseline="bu",
                                                      historical="hu")})
    store.create(doc)
    analyzer = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store)
    out = analyzer.run_cycle(now=100.0)
    assert out["j"] == J.COMPLETED_UNKNOWN  # silence is not health


def test_band_anomaly_timestamps_on_current_grid():
    rng = np.random.default_rng(6)
    store = JobStore()
    hist_n = 600
    hist_ts = (np.arange(hist_n) * STEP).tolist()
    cur_start = 900_000.0  # current window far from historical grid's end
    cur_ts = (cur_start + np.arange(30) * STEP).tolist()
    fixtures = {
        "cu": (cur_ts, rng.normal(8.0, 0.3, 30).tolist()),
        "hu": (hist_ts, rng.normal(0.5, 0.05, hist_n).tolist()),
    }
    doc = Document(id="j", app_name="a", namespace="d", strategy="canary",
                   start_time=to_rfc3339(0), end_time=to_rfc3339(0),
                   metrics={"error5xx": MetricQueries(current="cu", historical="hu")})
    store.create(doc)
    analyzer = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store)
    out = analyzer.run_cycle(now=1_000_000.0)
    assert out["j"] == J.COMPLETED_UNHEALTH
    pairs = next(iter(store.get("j").anomaly.values()))
    stamps = pairs[0::2]
    assert all(cur_start <= t < cur_start + 30 * STEP for t in stamps), stamps


def test_exporter_sanitizes_metric_names():
    from foremast_tpu.dataplane import VerdictExporter

    ex = VerdictExporter()
    ex.record_bounds("a", "ns", 'x{y} 1\nfake_series 99', 1.0, 0.0, 0.0)
    text = ex.render()
    assert "fake_series 99" not in text.replace("x_y__1_fake_series_99", "")
    for line in text.strip().splitlines():
        assert line.startswith("foremastbrain:"), line


# -------------------------------------------------- multivariate (LSTM) mode
def _multi_job(fixtures, *, bad, n_h=256, n_c=16):
    t_h = np.arange(n_h)
    t_c = n_h + np.arange(n_c)
    rng = np.random.default_rng(11)
    for i, name in enumerate(("latency", "cpu", "tps")):
        wave_h = np.sin(2 * np.pi * t_h / 32 + i) + rng.normal(0, 0.05, n_h)
        wave_c = np.sin(2 * np.pi * t_c / 32 + i) + rng.normal(0, 0.05, n_c)
        if bad and name == "tps":
            wave_c = wave_c + 6.0  # decorrelated level shift
        fixtures[f"h{i}"] = ((t_h * STEP).tolist(), wave_h.tolist())
        fixtures[f"c{i}"] = ((t_c * STEP).tolist(), wave_c.tolist())
    return Document(
        id="multi", app_name="app", namespace="d", strategy="canary",
        start_time=to_rfc3339(0), end_time=to_rfc3339(0),
        metrics={
            name: MetricQueries(current=f"c{i}", historical=f"h{i}")
            for i, name in enumerate(("latency", "cpu", "tps"))
        },
    )


def _lstm_cfg():
    return EngineConfig(algorithm="lstm_autoencoder", lstm_window=16,
                        lstm_epochs=60, lstm_hidden=8, lstm_latent=4,
                        policies={})


def test_engine_lstm_mode_flags_multivariate_anomaly():
    fixtures = {}
    store = JobStore()
    store.create(_multi_job(fixtures, bad=True))
    analyzer = Analyzer(_lstm_cfg(), FixtureDataSource(fixtures), store)
    out = analyzer.run_cycle(now=1_000_000.0)
    assert out["multi"] == J.COMPLETED_UNHEALTH
    assert "LSTM-AE" in store.get("multi").reason


def test_engine_lstm_mode_passes_healthy_and_caches_model():
    fixtures = {}
    store = JobStore()
    store.create(_multi_job(fixtures, bad=False))
    analyzer = Analyzer(_lstm_cfg(), FixtureDataSource(fixtures), store)
    out = analyzer.run_cycle(now=1_000_000.0)
    assert out["multi"] == J.COMPLETED_HEALTH
    assert len(analyzer._lstm_cache) == 1
    # second job for the same app reuses the cached model (no retrain)
    store.create(_multi_job(fixtures, bad=False))
    analyzer.run_cycle(now=1_000_001.0)
    assert len(analyzer._lstm_cache) == 1


# ------------------------------------------------- fetch_window fallback path
# These live here (NOT in test_native.py, which skips wholesale without a
# toolchain) because they are exactly the coverage for the no-native case.
def _prom_raw(series):
    import json as _json

    return _json.dumps({
        "status": "success",
        "data": {"resultType": "matrix",
                 "result": [{"metric": {}, "values": [[t, str(v)] for t, v in s]}
                            for s in series]},
    }).encode()


def test_fetch_window_matches_fetch_plus_grid():
    """RawFixtureDataSource.fetch_window == grid_from_series(fetch(url)) —
    the two engine paths stay equivalent whether or not native is built."""
    from foremast_tpu.dataplane.fetch import RawFixtureDataSource, grid_from_series

    t0 = 1_700_000_000 // 60 * 60
    raw = _prom_raw([[(t0 + 60 * i, float(i) * 1.5) for i in range(100)]])
    src = RawFixtureDataSource({"http://q": raw})
    win = src.fetch_window("http://q")
    ts, vals = src.fetch("http://q")
    want = grid_from_series(ts, vals)
    assert win.start == want.start and win.step == want.step
    np.testing.assert_array_equal(win.values, want.values)
    np.testing.assert_array_equal(win.mask, want.mask)
    assert src.requests == ["http://q", "http://q"]


def test_fetch_window_empty_body_parity_any_step():
    """Empty responses produce the same 1-slot empty Window (including
    step) on both the native and pure-Python paths."""
    from foremast_tpu.dataplane.fetch import window_from_prometheus_body

    raw = _prom_raw([])
    for step in (60, 300):
        w = window_from_prometheus_body(raw, step=step)
        assert len(w.values) == 1 and not w.mask.any()
        assert w.start == 0 and w.step == step


def test_caching_source_caches_windows_separately():
    from foremast_tpu.dataplane.fetch import (
        CachingDataSource,
        FixtureDataSource,
        RawFixtureDataSource,
    )

    t0 = 1_700_000_000 // 60 * 60
    raw = _prom_raw([[(t0 + 60 * i, 2.0) for i in range(10)]])
    inner = RawFixtureDataSource({"http://q": raw})
    src = CachingDataSource(inner, ttl_seconds=60.0)
    w1 = src.fetch_window("http://q")
    w2 = src.fetch_window("http://q")
    assert w2 is w1 and src.hits == 1  # second hit served from cache
    src.fetch("http://q")  # parsed-series entry is a SEPARATE key
    assert src.misses == 2
    # non-byte inner -> fetch_window signals "use fetch()"
    plain = CachingDataSource(FixtureDataSource({"u": ([1], [1.0])}))
    assert plain.fetch_window("u") is None


def test_document_to_json_covers_every_dataclass_field():
    """to_json is hand-rolled for flush speed; this pins it against the
    dataclass so adding a field without serializing it fails here."""
    import dataclasses

    doc = Document(id="j", app_name="a", strategy="canary",
                   start_time="s", end_time="e",
                   metrics={"m": MetricQueries(current="u", priority=2)},
                   anomaly={"m": [1, 2.0]})
    d = doc.to_json()
    assert set(d) == {f.name for f in dataclasses.fields(Document)}
    assert set(d["metrics"]["m"]) == {
        f.name for f in dataclasses.fields(MetricQueries)
    }
    # the payload is detached: mutating it cannot corrupt the doc
    d["anomaly"]["m"].append(99)
    d["metrics"]["m"]["current"] = "x"
    assert doc.anomaly["m"] == [1, 2.0]
    assert doc.metrics["m"].current == "u"
    # and it round-trips
    assert Document.from_json(doc.to_json()) == doc


def test_advance_validates_each_hop_and_rejects_terminal():
    store = JobStore()
    store.create(Document(id="j", app_name="a", strategy="canary",
                          start_time="", end_time=""))
    store.claim_open_jobs("w")
    store.advance("j", J.PREPROCESS_COMPLETED, J.POSTPROCESS_INPROGRESS,
                  worker="w")
    assert store.get("j").status == J.POSTPROCESS_INPROGRESS
    with pytest.raises(J.InvalidTransition):
        store.advance("j", J.COMPLETED_HEALTH)  # terminal -> transition()
    with pytest.raises(J.InvalidTransition):
        store.advance("j", J.PREPROCESS_COMPLETED)  # invalid hop


def test_wavefront_fetch_window_matches_fetch_plus_grid(monkeypatch):
    import json as _json

    from foremast_tpu.dataplane import fetch as F

    t0 = 1_700_000_000 // 60 * 60
    raw = _json.dumps({"timeseries": [
        {"data": [[t0 + 60 * i, float(i)] for i in range(50)]}
    ]}).encode()
    src = F.WavefrontDataSource()
    monkeypatch.setattr(src, "_raw", lambda url: raw)
    win = src.fetch_window("http://wf")
    ts, vals = src.fetch("http://wf")
    want = F.grid_from_series(ts, vals)
    assert win.start == want.start
    np.testing.assert_array_equal(win.values, want.values)
    np.testing.assert_array_equal(win.mask, want.mask)


def test_advance_failed_chain_leaves_doc_untouched():
    """advance() validates the whole chain before mutating: a bad chain
    must not leave the doc half-advanced (snapshot/live divergence)."""
    store = JobStore()
    store.create(Document(id="j", app_name="a", strategy="canary",
                          start_time="", end_time=""))
    store.claim_open_jobs("w")
    before = store.get("j").modified_at
    with pytest.raises(J.InvalidTransition):
        store.advance("j", J.PREPROCESS_COMPLETED, J.COMPLETED_HEALTH)
    doc = store.get("j")
    assert doc.status == J.PREPROCESS_INPROGRESS  # unchanged
    assert doc.modified_at == before


def test_crash_resume_e2e_snapshot_plus_lease_takeover(tmp_path):
    """Checkpoint/resume, whole story: worker-1 claims a job and dies
    mid-flight (nothing scored, lease held); a replacement process
    restores the fleet from the SNAPSHOT, takes over the expired lease,
    and completes the verdict — the reference's MAX_STUCK_IN_SECONDS
    recovery (design.md:37-43) riding our snapshot instead of ES."""
    rng = np.random.default_rng(11)
    fixtures = {}
    snap = str(tmp_path / "snap.json")
    store1 = JobStore(snapshot_path=snap)
    _mk_job(store1, fixtures, "takeover", bad=True, rng=rng)
    # worker-1 claims (job -> preprocess_inprogress, lease held) then dies
    claimed = store1.claim_open_jobs("worker-1")
    assert [d.id for d in claimed] == ["takeover"]
    store1.flush()  # cycle-boundary flush happened before the crash

    # replacement process: fresh store from the snapshot
    store2 = JobStore(snapshot_path=snap)
    doc = store2.get("takeover")
    assert doc.status == J.PREPROCESS_INPROGRESS
    assert doc.lease_holder == "worker-1"
    analyzer = Analyzer(EngineConfig(pairwise_threshold=1e-4),
                        FixtureDataSource(fixtures), store2)
    # fresh lease: not stealable yet -> cycle is a no-op for this job
    out = analyzer.run_cycle(worker="worker-2", now=10_000.0)
    assert "takeover" not in out
    # age the lease past MAX_STUCK_IN_SECONDS -> takeover + full verdict
    store2.get("takeover").lease_at -= 120
    out = analyzer.run_cycle(worker="worker-2", now=10_000.0)
    assert out["takeover"] == J.COMPLETED_UNHEALTH
    assert store2.get("takeover").lease_holder == "worker-2"
    store2.close()
    # and the verdict itself survives another restart
    assert JobStore(snapshot_path=snap).get("takeover").status == \
        J.COMPLETED_UNHEALTH


@pytest.mark.parametrize("megabatch", [False, True], ids=["rungs", "mega"])
@pytest.mark.parametrize("B", [70, 5, 32])
@pytest.mark.parametrize("presized", [False, True],
                         ids=["short", "presized"])
def test_score_chunks_fixed_buckets_and_edge_padding(presized, B, megabatch):
    """_launch_chunks + _collect_chunks: chunked results equal a single whole-batch call, and
    batch sizes map to FIXED buckets so fleet-size changes cannot force
    recompiles (B<=bucket pads up; B>chunk splits). A block its caller
    allocated at `_launch_rows` rows, edge rows written, reaches fn as a
    view of itself; a (B,) vector beside it is edge-padded as before."""
    from foremast_tpu.dataplane import FixtureDataSource

    eng = Analyzer(EngineConfig(score_batch=32, megabatch=megabatch),
                   FixtureDataSource({}), JobStore())
    calls, chunks = [], []

    def fn(vals, mask, w):
        calls.append(vals.shape[0])
        chunks.append((vals, mask, w))
        return {"s": vals.sum(axis=1) + w, "m": mask.any(axis=1)}

    rng = np.random.default_rng(0)
    vals = rng.normal(0, 1, (B, 8)).astype(np.float32)
    mask = rng.random((B, 8)) > 0.5
    w = rng.normal(0, 1, B).astype(np.float32)
    R = eng._launch_rows(B, 8)
    if presized:
        bv = np.concatenate([vals, np.repeat(vals[-1:], R - B, axis=0)])
        bm = np.concatenate([mask, np.repeat(mask[-1:], R - B, axis=0)])
        launches = eng._launch_chunks(fn, [bv, bm, w], rows=B)
    else:
        launches = eng._launch_chunks(fn, [vals, mask, w])
    out = eng._collect_chunks(launches)
    # rungs: full chunks launch at 32 and the 6-row tail re-buckets DOWN
    # the ladder; a rung B fills is not padded; small batches pad UP to a
    # fixed bucket, not down to raw B. Mega: one launch, at the mega class
    want = ({70: [32, 32, 16], 5: [16], 32: [32]} if not megabatch
            else {70: [256], 5: [16], 32: [64]})[B]
    assert calls == want
    assert sum(want) == R
    np.testing.assert_allclose(out["s"], vals.sum(axis=1) + w, rtol=1e-6)
    np.testing.assert_array_equal(out["m"], mask.any(axis=1))
    # the rows past B repeat row B-1, in every array of the last chunk
    i = R - want[-1]
    for got, real in zip(chunks[-1], (vals, mask, w)):
        assert (got[B - i:] == real[B - 1]).all()
    if presized:
        for k, (cv, cm, _) in enumerate(chunks):
            assert np.shares_memory(cv, bv) and np.shares_memory(cm, bm)
            np.testing.assert_array_equal(cv, bv[sum(want[:k]):][:want[k]])


def test_e2e_fleet_crosses_chunk_rungs():
    """Chunk boundaries must not perturb results: a 70-job fleet scored
    with score_batch=32 (three launches: 32+32+16-padded) produces
    byte-identical outcomes to a single whole-fleet launch, and every
    truly-bad job is flagged either way."""
    def run(score_batch):
        rng = np.random.default_rng(5)
        fixtures = {}
        store = JobStore()
        for i in range(70):
            _mk_job(store, fixtures, f"j{i:02d}", bad=(i % 7 == 3), rng=rng)
        a = Analyzer(
            EngineConfig(pairwise_threshold=1e-4, score_batch=score_batch),
            FixtureDataSource(fixtures), store)
        return a.run_cycle(now=10_000.0)

    chunked = run(32)
    single = run(8192)  # 70 <= first rung: one launch
    assert chunked == single  # row<->job mapping survives chunking exactly
    bad_ids = {f"j{i:02d}" for i in range(70) if i % 7 == 3}
    flagged = {j for j, s in chunked.items() if s == J.COMPLETED_UNHEALTH}
    assert bad_ids <= flagged  # no false negatives (FPs are fixture noise)


def test_flusher_cadence_adapts_to_snapshot_cost(tmp_path):
    """The background flusher's interval stretches with the measured
    serialize+write cost (5x, capped 30 s) so huge stores don't pin a
    core re-serializing at 1 Hz, while small stores keep ~1 s cadence."""
    store = JobStore(snapshot_path=str(tmp_path / "s.json"))
    assert store._flush_cost == 0.0  # 1 Hz until measured
    store.create(Document(id="j", app_name="a", strategy="canary",
                          start_time="", end_time=""))
    store.flush()
    assert 0.0 < store._flush_cost < 1.0  # tiny store: stays at 1 Hz floor
    # the PRODUCTION formula (floor 1 s, 5x cost, 30 s cap)
    for cost, want in ((0.01, 1.0), (1.5, 7.5), (60.0, 30.0)):
        store._flush_cost = cost
        assert store._flush_interval() == want
    store.close()


# ------------------------------------------- Holt-Winters period auto-detection
def _seasonal_band_job(period_steps=60, n_h=220, n_c=30, amp=2.0):
    """Healthy hourly-seasonal service: the current window CONTINUES the
    historical pattern."""
    rng = np.random.default_rng(9)
    t_all = np.arange(n_h + n_c)
    wave = 5.0 + amp * np.sin(2 * np.pi * t_all / period_steps) \
        + rng.normal(0, 0.05, n_h + n_c)
    fixtures = {
        "hu": ((t_all[:n_h] * STEP).tolist(), wave[:n_h].tolist()),
        "cu": ((t_all[n_h:] * STEP).tolist(), wave[n_h:].tolist()),
    }
    doc = Document(id="hwj", app_name="a", namespace="d", strategy="canary",
                   start_time=to_rfc3339(0), end_time=to_rfc3339(0),
                   metrics={"latency": MetricQueries(current="cu",
                                                     historical="hu")})
    return fixtures, doc


def test_hw_wrong_static_period_condemns_healthy_seasonal_service():
    """The round-3 verdict's missing capability, shown end-to-end: with the
    static daily default (clamped to the window), the HW band free-runs a
    wrong-phase season across the judged region and condemns a HEALTHY
    hourly-seasonal service; auto-detection picks the true cycle and the
    same service scores healthy. (SURVEY §7 hard part;
    reference spec docs/dynamic_autoscaling.md:28-44.)"""
    from foremast_tpu.engine.config import MetricPolicy

    for auto, expected in ((False, J.COMPLETED_UNHEALTH),
                           (True, J.COMPLETED_HEALTH)):
        fixtures, doc = _seasonal_band_job()
        store = JobStore()
        store.create(doc)
        cfg = EngineConfig(
            algorithm="holt_winters", hw_period_auto=auto,
            policies={"latency": MetricPolicy(threshold=3.0, bound=3,
                                              min_lower_bound=0.0)},
        )
        analyzer = Analyzer(cfg, FixtureDataSource(fixtures), store)
        out = analyzer.run_cycle(now=1_000_000.0)
        assert out["hwj"] == expected, (auto, out)


def test_lstm_train_budget_amortizes_across_cycles():
    """A cold multi-metric fleet warms up under LSTM_MAX_TRAIN_PER_CYCLE
    instead of training every model in one cycle; capped-out jobs stay
    in progress (requeued) and train later."""
    fixtures = {}
    docs = []
    for j in range(3):
        rng = np.random.default_rng(20 + j)
        n_h, n_c = 128, 16
        for i, name in enumerate(("latency", "cpu", "tps")):
            w_h = rng.normal(10, 1, n_h)
            w_c = rng.normal(10, 1, n_c)
            fixtures[f"h{j}{i}"] = ((np.arange(n_h) * STEP).tolist(),
                                    w_h.tolist())
            fixtures[f"c{j}{i}"] = (((n_h + np.arange(n_c)) * STEP).tolist(),
                                    w_c.tolist())
        docs.append(Document(
            id=f"m{j}", app_name=f"app{j}", namespace="d", strategy="canary",
            start_time=to_rfc3339(0), end_time=to_rfc3339(1e9),
            metrics={name: MetricQueries(current=f"c{j}{i}",
                                         historical=f"h{j}{i}")
                     for i, name in enumerate(("latency", "cpu", "tps"))},
        ))
    store = JobStore()
    for d in docs:
        store.create(d)
    cfg = EngineConfig(algorithm="lstm_autoencoder", lstm_window=16,
                       lstm_epochs=3, lstm_hidden=8, lstm_latent=4,
                       lstm_max_train_per_cycle=1, policies={},
                       lstm_threshold=1e9)  # budget is under test, not detection
    analyzer = Analyzer(cfg, FixtureDataSource(fixtures), store)
    for cycle, expected_models in ((1, 1), (2, 2), (3, 3)):
        out = analyzer.run_cycle(now=100.0)
        assert len(analyzer._lstm_cache) == expected_models, (cycle, out)
        # nothing terminal: capped-out jobs requeue, trained ones are
        # healthy within the window and requeue too
        assert all(s == J.INITIAL for s in out.values()), out


def test_loss_window_is_measured_per_flush(tmp_path):
    """VERDICT r3 #8: the RAM-only exposure of accepted jobs is a
    measured gauge, not an assumption. Each flush records how long its
    oldest mutation lived unflushed; the open gauge tracks live dirt."""
    store = JobStore(snapshot_path=str(tmp_path / "s.json"))
    assert store.loss_window_open_seconds == 0.0
    # hold the background flusher off so the open-window gauge is
    # observable deterministically (production: it flushes ~1 Hz)
    store._closed = True
    store.create(Document(id="j", app_name="a", strategy="canary",
                          start_time="", end_time=""))
    time.sleep(0.05)
    open_w = store.loss_window_open_seconds
    assert open_w >= 0.05
    store._closed = False
    store.flush()
    assert store.loss_window_last_seconds >= 0.05
    assert store.loss_window_max_seconds >= store.loss_window_last_seconds
    assert store.loss_window_open_seconds == 0.0  # everything durable
    # a second, faster flush keeps max at the worst case
    store.transition("j", J.PREPROCESS_INPROGRESS)
    store.flush()
    assert store.loss_window_max_seconds >= 0.05
    store.close()


def test_lstm_fleet_scoring_path_engages(monkeypatch):
    """>=4 same-shape multi jobs score through ONE vmapped launch
    (anomaly_scores_fleet) instead of per-job dispatches, with verdicts
    unchanged."""
    from foremast_tpu.models import lstm_ae as L

    calls = {"fleet": 0, "single": 0}
    real_fleet, real_single = L.anomaly_scores_fleet, L.anomaly_scores

    def spy_fleet(*a, **k):
        calls["fleet"] += 1
        return real_fleet(*a, **k)

    def spy_single(*a, **k):
        calls["single"] += 1
        return real_single(*a, **k)

    monkeypatch.setattr(L, "anomaly_scores_fleet", spy_fleet)
    monkeypatch.setattr(L, "anomaly_scores", spy_single)

    fixtures = {}
    docs = []
    n_h, n_c = 128, 16
    for j in range(5):
        rng = np.random.default_rng(40 + j)
        for i, name in enumerate(("latency", "cpu", "tps")):
            fixtures[f"h{j}{i}"] = ((np.arange(n_h) * STEP).tolist(),
                                    rng.normal(10, 1, n_h).tolist())
            fixtures[f"c{j}{i}"] = (((n_h + np.arange(n_c)) * STEP).tolist(),
                                    rng.normal(10, 1, n_c).tolist())
        docs.append(Document(
            id=f"m{j}", app_name=f"app{j}", namespace="d", strategy="canary",
            start_time=to_rfc3339(0), end_time=to_rfc3339(1e9),
            metrics={name: MetricQueries(current=f"c{j}{i}",
                                         historical=f"h{j}{i}")
                     for i, name in enumerate(("latency", "cpu", "tps"))},
        ))
    store = JobStore()
    for d in docs:
        store.create(d)
    cfg = EngineConfig(algorithm="lstm_autoencoder", lstm_window=16,
                       lstm_epochs=3, lstm_hidden=8, lstm_latent=4,
                       policies={}, lstm_threshold=1e9)
    analyzer = Analyzer(cfg, FixtureDataSource(fixtures), store)
    out = analyzer.run_cycle(now=100.0)
    assert all(s == J.INITIAL for s in out.values()), out
    assert calls["fleet"] >= 1, calls
    # anomaly_scores_fleet's jitted body resolves anomaly_scores from the
    # module namespace at trace time, so the spy fires once during the
    # trace — what must NOT happen is one dispatch per job (5 calls)
    assert calls["single"] <= 1, calls


def test_lstm_same_app_jobs_share_one_training_slot():
    """N jobs of one app share a cache key: a cold cycle must train ONE
    model for them (one budget slot), and all N score from it — not N
    redundant trainings draining the warm-up budget."""
    fixtures = {}
    docs = []
    n_h, n_c = 128, 16
    rng = np.random.default_rng(50)
    for i, name in enumerate(("latency", "cpu", "tps")):
        fixtures[f"h{i}"] = ((np.arange(n_h) * STEP).tolist(),
                             rng.normal(10, 1, n_h).tolist())
        fixtures[f"c{i}"] = (((n_h + np.arange(n_c)) * STEP).tolist(),
                             rng.normal(10, 1, n_c).tolist())
    for j in range(3):  # three jobs, same app, same metrics
        docs.append(Document(
            id=f"dup{j}", app_name="one-app", namespace="d",
            strategy="canary",
            start_time=to_rfc3339(0), end_time=to_rfc3339(1e9),
            metrics={name: MetricQueries(current=f"c{i}",
                                         historical=f"h{i}")
                     for i, name in enumerate(("latency", "cpu", "tps"))},
        ))
    store = JobStore()
    for d in docs:
        store.create(d)
    cfg = EngineConfig(algorithm="lstm_autoencoder", lstm_window=16,
                       lstm_epochs=3, lstm_hidden=8, lstm_latent=4,
                       policies={}, lstm_threshold=1e9,
                       lstm_max_train_per_cycle=1)  # ONE slot suffices
    analyzer = Analyzer(cfg, FixtureDataSource(fixtures), store)
    out = analyzer.run_cycle(now=100.0)
    assert len(analyzer._lstm_cache) == 1
    assert analyzer._lstm_trained_this_cycle == 1
    # all three jobs were judged (healthy requeue), none starved
    assert all(s == J.INITIAL for s in out.values()), out


# ------------------------- VERDICT r04 #2: HPA SLA modes + per-pod scoring
def _mk_hpa_job(store, fixtures, job_id, *, tps_current=240.0,
                sla_current=5.0, pods=None, rng=None, sla_absolute=True):
    """HPA job: history ~100 tps / ~5 latency; current window overridable;
    optional pod-count series (hist_pods -> now_pods)."""
    rng = rng or np.random.default_rng(5)
    # production-shaped windows: the current URL covers ONLY the trailing
    # scoring window, the historical URL the 90-step history before it —
    # the per-pod recent/older split keys off current.start, so a
    # current window spanning the whole series would wash it out
    hist_ts, hist_v = _series(rng, 100.0, 90, spread=3.0)
    cur_ts = [hist_ts[-1] + STEP + t for t in np.arange(30) * STEP]
    cur_url = f"http://prom/{job_id}/tps_cur"
    hist_url = f"http://prom/{job_id}/tps_hist"
    fixtures[hist_url] = (hist_ts, hist_v)
    fixtures[cur_url] = (cur_ts, rng.normal(tps_current, 5, 30).tolist())
    s_ts, s_v = _series(rng, 5.0, 90, spread=0.3)
    sla_cur_url = f"http://prom/{job_id}/sla_cur"
    sla_hist_url = f"http://prom/{job_id}/sla_hist"
    fixtures[sla_hist_url] = (s_ts, s_v)
    fixtures[sla_cur_url] = (cur_ts,
                             rng.normal(sla_current, 0.3, 30).tolist())
    pod_url = ""
    if pods is not None:
        hist_pods, now_pods = pods
        pod_url = f"http://prom/{job_id}/pods"
        fixtures[pod_url] = (hist_ts + cur_ts,
                            [hist_pods] * 90 + [now_pods] * 30)
    doc = Document(
        id=job_id, app_name=job_id, namespace="demo", strategy="hpa",
        start_time="START_TIME", end_time="END_TIME",
        metrics={
            "tps": MetricQueries(historical=hist_url, current=cur_url,
                                 priority=0),
            "latency": MetricQueries(historical=sla_hist_url,
                                     current=sla_cur_url,
                                     priority=1, is_absolute=sla_absolute),
        },
        pod_count_url=pod_url,
    )
    store.create(doc)
    return float(cur_ts[-1]) + STEP  # a "now" placing the last 30min window


def _raw_score(store, job_id):
    import re

    logs = store.hpalogs_for(job_id)
    m = re.search(r"raw ([0-9.]+)", logs[0].reason)
    return float(m.group(1))


def test_hpa_per_pod_score_absorbs_taken_scaleups():
    """podCountURL consumed (VERDICT r04 missing #3): traffic 2.4x with
    replicas already scaled 4->9.6 reads per-pod-neutral (~50); the same
    traffic with no pod data reads as a surge (>65)."""
    fixtures, store = {}, JobStore()
    now = _mk_hpa_job(store, fixtures, "nopods:demo:hpa")
    _mk_hpa_job(store, fixtures, "pods:demo:hpa", pods=(4.0, 9.6))
    analyzer = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store)
    analyzer.run_cycle(now=now)
    assert _raw_score(store, "nopods:demo:hpa") > 65
    assert 35 <= _raw_score(store, "pods:demo:hpa") <= 65
    # the reason records the replica count + per-pod demand it used; the
    # details list stays strictly band-shaped {current, upper, lower} so
    # letter templating and wire consumers never render a replicas-vs-
    # demand tuple as a metric band (models.go:194-209)
    podded = store.hpalogs_for("pods:demo:hpa")[0]
    assert "[per-pod: 9.6 pods" in podded.reason
    assert {d["metricType"] for d in podded.details} == {"tps", "latency"}
    # and the no-pod job logs no per-pod context (nothing fabricated)
    assert "per-pod" not in store.hpalogs_for("nopods:demo:hpa")[0].reason


def test_hpa_sla_mode_static_env_plumbed():
    """ML_SLA_MODE=static + ML_SLA_LIMIT below the healthy latency level
    forces the SLA-violation scale-up path; the same data under the
    default dynamic mode stays trend-driven (limit ~ mean+3sigma)."""
    from foremast_tpu.engine.config import from_env

    fixtures, store = {}, JobStore()
    now = _mk_hpa_job(store, fixtures, "app:demo:hpa", tps_current=100.0)
    cfg = from_env({"ML_SLA_MODE": "static", "ML_SLA_LIMIT": "3.0"})
    assert cfg.sla_mode == "static" and cfg.sla_limit == 3.0
    analyzer = Analyzer(cfg, FixtureDataSource(fixtures), store)
    analyzer.run_cycle(now=now)
    assert "SLA violation" in store.hpalogs_for("app:demo:hpa")[0].reason

    store2 = JobStore()
    fixtures2 = {}
    now2 = _mk_hpa_job(store2, fixtures2, "app:demo:hpa", tps_current=100.0)
    analyzer = Analyzer(EngineConfig(), FixtureDataSource(fixtures2), store2)
    analyzer.run_cycle(now=now2)
    assert "SLA violation" not in store2.hpalogs_for("app:demo:hpa")[0].reason


def test_hpa_static_mode_without_limit_degrades_to_dynamic():
    """A static/min mode with no limit configured anywhere must not
    invent one: the job scores under the dynamic criteria instead."""
    fixtures, store = {}, JobStore()
    now = _mk_hpa_job(store, fixtures, "app:demo:hpa", tps_current=100.0)
    analyzer = Analyzer(EngineConfig(sla_mode="static"),
                        FixtureDataSource(fixtures), store)
    analyzer.run_cycle(now=now)
    logs = store.hpalogs_for("app:demo:hpa")
    assert logs and "SLA violation" not in logs[0].reason
    # dynamic limit ~ mean+3sigma of healthy history (~5 +- 0.3) -> single
    # digits, not a 1e9 sentinel leaking into the log details
    sla_detail = [d for d in logs[0].details if d["metricType"] == "latency"]
    assert sla_detail and sla_detail[0]["upper"] < 100


def test_per_metric_sla_limit_env_override():
    from foremast_tpu.engine.config import from_env

    cfg = from_env({
        "metric_type_threshold_count": "1",
        "metric_type0": "latency",
        "sla_limit0": "250",
        "ML_SLA_MODE": "min",
    })
    assert cfg.policy_for("namespace_app_pod_latency").sla_limit == 250.0
    assert cfg.policy_for("error5xx").sla_limit == 0.0


def test_relative_sla_limit_requires_explicit_opt_in():
    """ML_SLA_LIMIT=250 quoted in ms must stay absolute under the wire
    isAbsolute flag's bare default (false); ML_SLA_LIMIT_RELATIVE=1 opts
    the fleet into the multiple-of-mean reading (limit 3x mean ~5 -> ~15,
    healthy ~5 passes; absolute 3.0 would violate — asserted above)."""
    from foremast_tpu.engine.config import from_env

    fixtures, store = {}, JobStore()
    now = _mk_hpa_job(store, fixtures, "app:demo:hpa", tps_current=100.0)
    cfg = from_env({"ML_SLA_MODE": "static", "ML_SLA_LIMIT": "250"})
    analyzer = Analyzer(cfg, FixtureDataSource(fixtures), store)
    analyzer.run_cycle(now=now)
    logs = store.hpalogs_for("app:demo:hpa")
    sla_detail = [d for d in logs[0].details if d["metricType"] == "latency"]
    assert abs(sla_detail[0]["upper"] - 250.0) < 1e-3  # absolute, not 250*mean

    fixtures2, store2 = {}, JobStore()
    now2 = _mk_hpa_job(store2, fixtures2, "app:demo:hpa", tps_current=100.0,
                       sla_absolute=False)  # un-flagged on the wire
    cfg = from_env({"ML_SLA_MODE": "static", "ML_SLA_LIMIT": "3.0",
                    "ML_SLA_LIMIT_RELATIVE": "1"})
    analyzer = Analyzer(cfg, FixtureDataSource(fixtures2), store2)
    analyzer.run_cycle(now=now2)
    logs = store2.hpalogs_for("app:demo:hpa")
    assert "SLA violation" not in logs[0].reason  # 3x mean ~15 > current ~5
    sla_detail = [d for d in logs[0].details if d["metricType"] == "latency"]
    assert 10 < sla_detail[0]["upper"] < 20


def test_garbage_pod_count_body_never_fails_the_job():
    """podCountURL is an OPTIONAL signal: a proxy flattening errors to a
    200 with an unparseable body must degrade to the aggregate score,
    not crash preprocess for the job (or the cycle)."""
    fixtures, store = {}, JobStore()
    now = _mk_hpa_job(store, fixtures, "app:demo:hpa", pods=(4.0, 9.6))
    fixtures["http://prom/app:demo:hpa/pods"] = (["<html>"], ["oops"])
    analyzer = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store)
    out = analyzer.run_cycle(now=now)
    assert out["app:demo:hpa"] == J.INITIAL  # scored + requeued
    logs = store.hpalogs_for("app:demo:hpa")
    assert logs and "per-pod" not in logs[0].reason  # aggregate fallback


def test_hpa_fleet_with_heterogeneous_history_lengths():
    """HPA rows bucket by their own pack length: a lone long-history job
    must not inflate every short job's launch (and both must score)."""
    fixtures, store = {}, JobStore()
    now = _mk_hpa_job(store, fixtures, "short:demo:hpa")
    # a second job with a 7x longer history rides its own bucket
    rng = np.random.default_rng(9)
    hist_ts, hist_v = _series(rng, 100.0, 700, spread=3.0)
    cur_ts = [hist_ts[-1] + STEP + t for t in np.arange(30) * STEP]
    fixtures["http://prom/long/tps_hist"] = (hist_ts, hist_v)
    fixtures["http://prom/long/tps_cur"] = (cur_ts,
                                            rng.normal(240, 5, 30).tolist())
    s_ts, s_v = _series(rng, 5.0, 700, spread=0.3)
    fixtures["http://prom/long/sla_hist"] = (s_ts, s_v)
    fixtures["http://prom/long/sla_cur"] = (cur_ts,
                                            rng.normal(5, 0.3, 30).tolist())
    store.create(Document(
        id="long:demo:hpa", app_name="long", namespace="demo",
        strategy="hpa", start_time="START_TIME", end_time="END_TIME",
        metrics={
            "tps": MetricQueries(historical="http://prom/long/tps_hist",
                                 current="http://prom/long/tps_cur",
                                 priority=0),
            "latency": MetricQueries(historical="http://prom/long/sla_hist",
                                     current="http://prom/long/sla_cur",
                                     priority=1),
        },
    ))
    analyzer = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store)
    outcomes = analyzer.run_cycle(now=now)
    assert outcomes == {"short:demo:hpa": J.INITIAL,
                        "long:demo:hpa": J.INITIAL}
    for job in ("short:demo:hpa", "long:demo:hpa"):
        logs = store.hpalogs_for(job)
        assert logs and 0.0 <= logs[0].hpascore <= 100.0


# ------------------------------------------- LSTM model-cache persistence
def test_lstm_cache_roundtrip_warm_starts_fresh_analyzer(tmp_path):
    """Train on one analyzer, save; a FRESH analyzer must, after load,
    judge the same app WITHOUT training (asserted via the param version,
    which every training bumps) — the restart warm-start the reference
    brain cannot do (its model cache was RAM-only)."""
    fixtures = {}
    store = JobStore()
    store.create(_multi_job(fixtures, bad=False))
    a1 = Analyzer(_lstm_cfg(), FixtureDataSource(fixtures), store)
    assert a1.run_cycle(now=1_000_000.0)["multi"] == J.COMPLETED_HEALTH
    path = str(tmp_path / "lstm_cache.msgpack")
    assert a1.save_lstm_cache(path) == 1

    # warm-start: load -> judged WITHOUT any training (training bumps
    # _lstm_param_version; it must not move past the loaded entries).
    # One warm analyzer per scenario: _multi_job writes fixed fixture
    # keys, so a healthy and a bad job cannot share one fixture dict.
    for bad, expected in ((False, J.COMPLETED_HEALTH),
                          (True, J.COMPLETED_UNHEALTH)):
        fixtures3 = {}
        store3 = JobStore()
        store3.create(_multi_job(fixtures3, bad=bad))
        warm = Analyzer(_lstm_cfg(), FixtureDataSource(fixtures3), store3)
        assert warm.load_lstm_cache(path) == 1
        v_loaded = warm._lstm_param_version
        out = warm.run_cycle(now=1_000_000.0)
        assert out["multi"] == expected
        assert warm._lstm_param_version == v_loaded  # no retrain happened


def test_lstm_cache_load_rejects_corrupt_and_mismatched(tmp_path):
    import dataclasses

    fixtures = {}
    store = JobStore()
    store.create(_multi_job(fixtures, bad=False))
    a1 = Analyzer(_lstm_cfg(), FixtureDataSource(fixtures), store)
    a1.run_cycle(now=1_000_000.0)
    path = str(tmp_path / "cache.msgpack")
    a1.save_lstm_cache(path)

    # corrupt bytes: load 0, no raise
    bad = tmp_path / "corrupt.msgpack"
    bad.write_bytes(b"\x93\x01\x02 not msgpack really \xff\xfe")
    fresh = Analyzer(_lstm_cfg(), FixtureDataSource({}), JobStore())
    assert fresh.load_lstm_cache(str(bad)) == 0
    assert fresh.load_lstm_cache(str(tmp_path / "absent")) == 0

    # architecture mismatch: a different hidden size must refuse the blob
    other = Analyzer(
        dataclasses.replace(_lstm_cfg(), lstm_hidden=16),
        FixtureDataSource({}), JobStore())
    assert other.load_lstm_cache(path) == 0
    # while the matching geometry accepts it
    match = Analyzer(_lstm_cfg(), FixtureDataSource({}), JobStore())
    assert match.load_lstm_cache(path) == 1
