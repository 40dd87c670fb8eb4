"""Pipelined scoring cycle (engine/pipeline.py, ISSUE 2) over the table
of families (engine/families.py, ISSUE 33).

Covers the three tentpole contracts — byte-identical verdicts wherever a
launch is cut (streamed against flushed at once), streamed rung-granular
dispatch, `_isolate` blast radius through the launch/collect split — the
table's seam (a family the engine has never heard of, and every family
answering every question), plus the compile-count regression gates (zero
steady-state recompiles; persistent-cache restarts) and the batch-rung
edge cases.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from foremast_tpu.dataplane import FixtureDataSource, VerdictExporter
from foremast_tpu.dataplane.fetch import FetchError
from foremast_tpu.engine import (
    Analyzer,
    Document,
    EngineConfig,
    JobStore,
    MetricQueries,
)
from foremast_tpu.engine import analyzer as analyzer_mod
from foremast_tpu.engine import families
from foremast_tpu.engine import jobs as J
from foremast_tpu.engine.pipeline import CompileCounter, CyclePipeline, prewarm
from foremast_tpu.ops.windowing import Window
from foremast_tpu.utils import tracing
from foremast_tpu.utils.timeutils import to_rfc3339

STEP = 60


def _series(rng, level, n, spread=None, step=STEP):
    spread = level * 0.1 + 0.01 if spread is None else spread
    ts = np.arange(n) * step
    return ts.tolist(), np.clip(rng.normal(level, spread, n), 0, None).tolist()


def _mixed_fleet(n_pair=12, n_band=6, n_bi=4, n_lstm=2, n_hpa=3, seed=11):
    """A deterministic mixed-family fixture fleet: (store, fixtures).

    Some pair canaries are bad so the fold exercises the unhealthy path;
    band/bi/lstm/hpa jobs are healthy continuous-ish jobs with history.
    """
    rng = np.random.default_rng(seed)
    fixtures = {}
    store = JobStore()

    def mk(job_id, metrics, strategy="canary"):
        doc = Document(
            id=job_id, app_name=f"app-{job_id}", namespace="px",
            strategy=strategy, start_time=to_rfc3339(0.0),
            end_time=to_rfc3339(5_000_000.0), metrics=metrics,
        )
        store.create(doc)

    for i in range(n_pair):
        bad = i % 5 == 3
        cur, base = f"u/p{i}/c", f"u/p{i}/b"
        fixtures[cur] = _series(rng, 5.0 if bad else 0.5, 30)
        fixtures[base] = _series(rng, 0.5, 30)
        mk(f"pair{i}", {"error5xx": MetricQueries(current=cur, baseline=base)})
    for i in range(n_band):
        cur, hist = f"u/bd{i}/c", f"u/bd{i}/h"
        fixtures[cur] = _series(rng, 10.0, 25)
        fixtures[hist] = _series(rng, 10.0, 300)
        mk(f"band{i}", {"latency": MetricQueries(current=cur, historical=hist)})
    for i in range(n_bi):
        ms = {}
        for m in ("latency", "cpu"):
            cur, hist = f"u/bi{i}/{m}/c", f"u/bi{i}/{m}/h"
            fixtures[cur] = _series(rng, 10.0, 25)
            fixtures[hist] = _series(rng, 10.0, 300)
            ms[m] = MetricQueries(current=cur, historical=hist)
        mk(f"bi{i}", ms)
    for i in range(n_lstm):
        ms = {}
        for m in ("latency", "cpu", "tps"):
            cur, hist = f"u/ml{i}/{m}/c", f"u/ml{i}/{m}/h"
            fixtures[cur] = _series(rng, 10.0, 25)
            fixtures[hist] = _series(rng, 10.0, 300)
            ms[m] = MetricQueries(current=cur, historical=hist)
        mk(f"lstm{i}", ms)
    for i in range(n_hpa):
        tps_c, tps_h = f"u/h{i}/tps/c", f"u/h{i}/tps/h"
        lat_c, lat_h = f"u/h{i}/lat/c", f"u/h{i}/lat/h"
        fixtures[tps_c] = _series(rng, 100.0, 25)
        fixtures[tps_h] = _series(rng, 100.0, 300)
        fixtures[lat_c] = _series(rng, 5.0, 25)
        fixtures[lat_h] = _series(rng, 5.0, 300)
        tps = MetricQueries(current=tps_c, historical=tps_h)
        lat = MetricQueries(current=lat_c, historical=lat_h)
        lat.priority, lat.is_increase = 1, True
        mk(f"hpa{i}", {"tps": tps, "latency": lat}, strategy="hpa")
    return store, fixtures


def _snapshot(store: JobStore) -> str:
    """Canonical byte view of every job's verdict-bearing state."""
    docs = {}
    for doc in store._jobs.values():
        docs[doc.id] = {
            "status": doc.status,
            "reason": doc.reason,
            "anomaly": doc.anomaly,
        }
    logs = [
        {"job": h.job_id, "score": h.hpascore, "reason": h.reason,
         "details": h.details}
        for h in store._hpalogs
    ]
    return json.dumps({"docs": docs, "hpalogs": logs}, sort_keys=True)


# a fire threshold (and a chunk) at or above every fleet of this file: no
# accumulator fills, nothing launches before `finish`, which is then the
# barrier the pipeline replaced, through the same `_launch_chunks`
FLUSH_ONLY = dict(pipeline_fire_rows=8192, score_batch=8192)


def _run_fleet(monkeypatch, cycles: int = 2, fleet_kw=None, **cfg_kw):
    """(outcomes, snapshot, launches fired before `finish`) of `cycles`
    cycles over the mixed fleet."""
    store, fixtures = _mixed_fleet(**(fleet_kw or {}))
    cfg = EngineConfig(pairwise_threshold=1e-4, lstm_epochs=2, **cfg_kw)
    eng = Analyzer(cfg, FixtureDataSource(fixtures), store, VerdictExporter())
    streamed = []
    finish = CyclePipeline.finish

    def counting(pipe):
        streamed.append(pipe.launches)
        return finish(pipe)

    with monkeypatch.context() as mp:
        mp.setattr(CyclePipeline, "finish", counting)
        outs = [eng.run_cycle(now=1000.0 + 10 * c) for c in range(cycles)]
    return outs, _snapshot(store), sum(streamed)


# ------------------------------------------------------------ determinism
# The reference of all three is the same fleet flushed at once (FLUSH_ONLY).
def test_streamed_verdicts_byte_identical_to_flushed_at_once(monkeypatch):
    """The acceptance gate: a streamed run (full rungs launch while the
    rest is still being fetched) and the same fleet flushed at once give
    byte-identical verdict state (statuses, reasons, anomaly payloads,
    hpalogs) and identical outcome dicts over two cycles and all five
    families — fold order is claim order regardless of where a launch
    was cut and of device completion order."""
    fleet = dict(n_pair=20, n_band=18, n_bi=4, n_lstm=2, n_hpa=3)
    outs_p, snap_p, early = _run_fleet(monkeypatch, fleet_kw=fleet,
                                       pipeline_fire_rows=16)
    outs_s, snap_s, none = _run_fleet(monkeypatch, fleet_kw=fleet,
                                      **FLUSH_ONLY)
    assert early >= 2 and none == 0
    assert outs_p == outs_s
    assert snap_p == snap_s


def test_pipeline_chunk_boundaries_match_flushed_rungs(monkeypatch):
    """A tiny score_batch (the chunk is the smallest rung, 16) cuts a
    bucket into many launches, some mid-stream; results must still match
    the one padded launch a bucket that the flush makes."""
    fleet = dict(n_pair=40, n_band=20)
    outs_p, snap_p, early = _run_fleet(monkeypatch, cycles=1, fleet_kw=fleet,
                                       score_batch=4)
    outs_s, snap_s, none = _run_fleet(monkeypatch, cycles=1, fleet_kw=fleet,
                                      **FLUSH_ONLY)
    assert early >= 3 and none == 0
    assert outs_p == outs_s
    assert snap_p == snap_s


def test_pipeline_early_fire_rung_keeps_verdicts_identical(monkeypatch):
    """PIPELINE_FIRE_ROWS below the chunk cap launches mid-stream at
    DIFFERENT boundaries than the flush's chunker — scorers are
    row-wise, so verdicts must still be byte-identical."""
    fleet = dict(n_pair=40, n_band=20, n_bi=6, n_lstm=0, n_hpa=18)
    outs_p, snap_p, early = _run_fleet(monkeypatch, cycles=1, fleet_kw=fleet,
                                       pipeline_fire_rows=16)
    outs_s, snap_s, none = _run_fleet(monkeypatch, cycles=1, fleet_kw=fleet,
                                      **FLUSH_ONLY)
    assert early >= 4 and none == 0
    assert outs_p == outs_s
    assert snap_p == snap_s


# ------------------------------------------------------------- streaming
def test_streaming_accumulator_fires_full_rungs_early():
    """Buckets launch the moment they fill the chunk cap; partials flush
    at finish. 40 one-bucket pair items with cap 16 -> 2 early launches
    + 1 flush, every result present."""
    from foremast_tpu.engine.analyzer import _PairItem

    rng = np.random.default_rng(0)
    cfg = EngineConfig(score_batch=16)
    eng = Analyzer(cfg, FixtureDataSource({}), JobStore())

    def item(i):
        vals = rng.normal(5.0, 0.5, 30).astype(np.float32)
        w = Window(vals, np.ones(30, bool), 0)
        w2 = Window(vals.copy(), np.ones(30, bool), 0)
        return _PairItem(f"j{i}", "m", w, w2, cfg.policy_for("m"))

    pipe = CyclePipeline(eng)
    for i in range(40):
        pipe.feed({"pair": [item(i)]})
        # two full rungs fire during the stream, not at the end
        assert pipe.launches == (i + 1) // 16
    results, bad = pipe.finish()
    assert pipe.launches == 3 and not bad
    assert len(results["pair"]) == 40
    sync = families.family("pair").score(eng, [item(i) for i in range(40)])
    assert results["pair"].keys() == sync.keys()


def test_pipeline_collect_failure_retries_per_job():
    """A collect-time failure (deferred device error) falls back to the
    per-job synchronous path: results complete, nothing reported bad."""
    store, fixtures = _mixed_fleet(n_pair=6, n_band=0, n_bi=0, n_lstm=0,
                                   n_hpa=0)
    cfg = EngineConfig(pairwise_threshold=1e-4)
    eng = Analyzer(cfg, FixtureDataSource(fixtures), store)
    orig = eng._collect_pairs
    calls = {"n": 0}

    def flaky(state):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("deferred device error")
        return orig(state)

    eng._collect_pairs = flaky
    out = eng.run_cycle(now=1000.0)
    assert calls["n"] > 1  # the retry actually re-collected
    assert set(out) == {f"pair{i}" for i in range(6)}
    # blast radius: no job ended ABORT/INITIAL-on-error
    assert all(s in (J.INITIAL, J.COMPLETED_UNHEALTH) for s in out.values())


def test_pipeline_poisoned_family_reports_only_bad_jobs():
    """A launch that fails even per job reports {job_id: error} for the
    offenders only; other families' jobs still fold normally."""
    store, fixtures = _mixed_fleet(n_pair=4, n_band=2, n_bi=0, n_lstm=0,
                                   n_hpa=0)
    eng = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store)

    def boom(*a, **kw):
        raise RuntimeError("poisoned launch")

    eng._launch_pairs = boom  # sync fallback hits it too -> per-job errors
    out = eng.run_cycle(now=1000.0)
    # canary pair jobs die terminally on scoring failure...
    assert all(out[f"pair{i}"] == J.ABORT for i in range(4))
    assert all("poisoned launch" in store.get(f"pair{i}").reason
               for i in range(4))
    # ...band jobs are untouched by the pair family's blast
    assert all(out[f"band{i}"] == J.INITIAL for i in range(2))


# ------------------------------------- the table's seam (ISSUE 33)
def _win(rng, level, n, start=0.0):
    return Window(rng.normal(level, 0.03 * level, n).astype(np.float32),
                  np.ones(n, bool), start)


def test_a_sixth_family_needs_no_engine_code(monkeypatch):
    """A family defined here and put in the table is routed, memo-checked,
    launched, collected, retried per job after a planted launch failure
    and folded into verdicts, reasons and records: the pipeline, the memo,
    the retry and the fold read the table and nothing else. What it costs
    outside `engine/families.py` is what this test supplies: a routing
    rule (here a wrapper of `_preprocess`), the launch and collect, and
    the span name."""
    import dataclasses

    from foremast_tpu.ops.windowing import bucket_length, pack_windows

    @dataclasses.dataclass
    class SpreadItem:
        job_id: str
        metric: str
        current: Window
        limit: float

    launched = []  # rows of every launch, in order
    planted = {"n": 1}  # launches still to fail

    def key(it):
        return (it.job_id, it.metric, "spread")

    def launch(an, entries, T):
        launched.append(len(entries))
        if planted["n"]:
            planted["n"] -= 1
            raise RuntimeError("planted launch failure")
        vals, mask = pack_windows([it.current for it in entries], pad_to=T)
        return entries, an._launch_chunks(
            lambda v, m: {"spread": np.where(m, v, -np.inf).max(axis=1)
                          - np.where(m, v, np.inf).min(axis=1)},
            [vals, mask])

    def collect(an, state):
        entries, launches = state
        spreads = an._collect_chunks(launches)["spread"].tolist()
        return {key(it): {"spread": sp, "unhealthy": sp > it.limit}
                for it, sp in zip(entries, spreads)}

    # unhealthy when the current window's max - min is over a limit
    spread = families.Family(
        name="spread", count_attr="spreads", key=key,
        route=lambda an, it: (
            it, bucket_length(it.current.values.shape[0])),
        fp_parts=lambda it, T: (b"spread", T, it.metric, it.current,
                                it.limit),
        launch=launch, collect=collect,
        provenance=lambda an, it, r: {
            "family": "spread", "metric": it.metric,
            "spread": round(r["spread"], 4), "limit": it.limit,
            "unhealthy": bool(r["unhealthy"])},
        unhealthy=lambda an, it, r: (
            it.metric, f"spread {r['spread']:.2f} over {it.limit:g}", []))

    monkeypatch.setattr(families, "FAMILIES", families.FAMILIES + (spread,))
    monkeypatch.setitem(tracing.SCORE_SPANS, "spread", "engine.score.spread")
    store, fixtures = _mixed_fleet(n_pair=6, n_band=0, n_bi=0, n_lstm=0,
                                   n_hpa=0)
    eng = Analyzer(EngineConfig(pairwise_threshold=1e-4),
                   FixtureDataSource(fixtures), store)
    preprocess = eng._preprocess

    def routed_too(doc, now):
        routed = preprocess(doc, now)
        # pair1 is judged by the new family alone, and held to a limit
        # its healthy window cannot keep
        alone = doc.id == "pair1"
        if alone:
            routed["pair"] = []
        routed["spread"] = [
            SpreadItem(doc.id, name, eng._fetch_window(mq.current, now),
                       0.0 if alone else 1.0)
            for name, mq in doc.metrics.items()]
        return routed

    eng._preprocess = routed_too
    out = eng.run_cycle(now=1000.0)
    # one bucket of six failed at launch; each job was retried alone
    assert launched == [6, 1, 1, 1, 1, 1, 1]
    assert eng.last_cycle_stages["family_launches"]["spread"] == 0
    score = next(sp for sp in _spans(_cycle_root(eng))
                 if sp["name"] == tracing.SPAN_ENGINE_SCORE)["attrs"]
    assert score["spreads"] == 6 and score["pairs"] == 5
    # folded: a verdict from the new family alone, and one it shares with
    # the rank test, whose cause comes first (the table's order)
    assert out == {f"pair{i}": J.COMPLETED_UNHEALTH if i in (1, 3)
                   else J.INITIAL for i in range(6)}
    alone = store.get("pair1").reason
    assert alone.startswith("anomaly detected on error5xx :: error5xx: "
                            "spread 0.") and alone.endswith(" over 0")
    shared = store.get("pair3").reason
    assert shared.index("pairwise rejection") < shared.index("; error5xx: "
                                                             "spread ")
    assert shared.endswith(" over 1")
    rec = eng.provenance.get("pair3")
    assert [f["family"] for f in rec["families"]] == ["pair", "spread"]
    assert rec["families"][1]["unhealthy"] is True
    healthy = eng.provenance.get("pair0")["families"][1]
    assert 0.0 < healthy.pop("spread") < 1.0
    assert healthy == {"family": "spread", "metric": "error5xx",
                       "limit": 1.0, "unhealthy": False}
    # memo-checked: the same windows again launch nothing
    eng.run_cycle(now=1000.0)
    assert launched == [6, 1, 1, 1, 1, 1, 1]
    assert eng.last_cycle_stages["score_memo_hits"] == {"pair": 4,
                                                        "spread": 4}
    assert eng.provenance.get("pair0")["path"] == "memo-hit"


@pytest.mark.parametrize("name", ["pair", "band", "bivariate", "hpa", "lstm"])
def test_every_family_answers_every_question_of_the_table(name):
    """Each family of the table, asked everything the pipeline, the memo,
    the retry, the fold, triage and prewarm ask, over a rung of the items
    `prewarm` itself builds (lstm has none there: three-metric items
    stand in)."""
    from foremast_tpu.engine.analyzer import _MultiItem
    from foremast_tpu.ops.windowing import bucket_length

    rng = np.random.default_rng(8)
    cfg = EngineConfig(lstm_epochs=1)
    eng = Analyzer(cfg, None, JobStore())
    fam = families.family(name)
    assert fam.name == name and fam.count_attr
    assert name in tracing.SCORE_SPANS
    T, rung = 128, 16
    n_c, n_h = T // 4, T - T // 4
    items = fam.prewarm_items and fam.prewarm_items(
        rung, n_h, n_c, lambda n: _win(rng, 10.0, n),
        cfg.policy_for("latency"))
    if name == "lstm":
        assert not fam.streams
        items = [_MultiItem(f"w{i}", "app/ns", ["a", "b", "c"],
                            [_win(rng, 10.0, n_h) for _ in range(3)],
                            [_win(rng, 10.0, n_c) for _ in range(3)])
                 for i in range(rung)]
    jobs = {it.job_id for it in items}
    assert len(jobs) == rung
    assert all(isinstance(w, Window) for w in fam.currents(items))
    assert families.newest_sample_ts({name: items}) == (n_c - 1) * 60.0
    results = fam.score(eng, items)
    assert len(results) == rung and eng.device_launches >= 1
    if fam.streams:
        rows = fam.rows(eng, items)
        assert len(rows) == rung
        entry, bucket = fam.route(eng, rows[0])
        if name == "pair":
            # a pair's key is (Tb, Tc); no row of a T=128 bucket splits,
            # and from T=512 prewarm gives the short-current key too
            assert bucket == (T, T) and T == bucket_length(T)
            assert fam.prewarm_currents(eng, 512, 128) == (128, 32)
        else:
            assert bucket == T == bucket_length(bucket)
        assert fam.prewarm_currents(eng, T, n_c) == (n_c,)
        assert {it.job_id for it in fam.row_items(rows[0])} == {"w0"}
        assert {it.job_id for it in fam.items_of([entry])} == {"w0"}
        key, fp, nbytes, hashed, reused = eng._memo_key_fp(fam, entry,
                                                           bucket)
        assert key == fam.entry_key(entry) and key in results
        assert len(fp) == 16 and nbytes > 0 and hashed > 0 and reused == 0
        # the entry's windows keep their digests: a second fingerprint
        # hashes no window bytes again
        _k, fp2, nbytes2, hashed2, reused2 = eng._memo_key_fp(
            fam, entry, 2 * bucket)
        assert fp != fp2 and nbytes2 == hashed2 == 0 and reused2 == hashed
        state = fam.launch(eng, [entry], bucket)
        assert fam.collect(eng, state).keys() == {key}
    if fam.provenance is not None:
        it = items[0]
        r = results[fam.key(it)]
        entry_of = fam.provenance(eng, it, r)
        assert entry_of["family"] == name
        assert entry_of["unhealthy"] is bool(r["unhealthy"])
        metric, cause, pairs = fam.unhealthy(eng, it, r)
        assert metric == entry_of["metric"]
        assert isinstance(cause, str) and isinstance(pairs, list)
        if fam.bounds is not None:
            bounds = list(fam.bounds(it, r))
            assert bounds and all(
                len(b) == 3 and b[1] >= b[2] for b in bounds)
    else:
        # hpa: keyed by the job, folded by Analyzer._finish_hpa
        assert results.keys() == jobs
    screened = fam.screens(cfg)
    assert screened is (name in ("pair", "band", "bivariate"))
    if screened:
        rows = fam.screen_rows(entry)
        assert len(rows) == (2 if name == "bivariate" else 1)
        for vals, mask, start, policy in rows:
            assert vals.shape == mask.shape and 0 < start < vals.shape[0]
            assert policy is cfg.policy_for("latency")
        assert fam.screen_clears(eng, 0, 32) is True
        assert fam.screen_clears(eng, 32, 32) is False
        outs = [{"count": 0, "upper_mean": 2.0, "lower_mean": 1.0}] * len(rows)
        cleared = fam.cleared_result(entry, outs)
        assert cleared["unhealthy"] is False
        # what the fold reads of a scored result, it finds in a cleared one
        assert fam.provenance(eng, items[0], {**r, **cleared})[
            "unhealthy"] is False
    assert not families.family("band").screens(
        EngineConfig(algorithm="holt_winters"))


def test_a_window_fingerprinted_twice_is_digested_once(monkeypatch):
    """The memo hashes a Window's bytes once; the object keeps its digest,
    and a later fingerprint that holds the same object (an unmoved range
    the fetch layer hands back) chains it. `memo_fp_bytes` counts the
    bytes hashed, `memo_fp_reused` the digests served from the object."""
    import hashlib
    import types

    from foremast_tpu.engine.analyzer import _PairItem
    from foremast_tpu.ops import windowing

    made = []

    def blake2b(**kw):
        made.append(kw)
        return hashlib.blake2b(**kw)

    monkeypatch.setattr(windowing, "hashlib",
                        types.SimpleNamespace(blake2b=blake2b))
    rng = np.random.default_rng(5)
    eng = Analyzer(EngineConfig(), None, JobStore())
    pipe = CyclePipeline(eng)
    fam = families.family("pair")
    policy = eng.config.policy_for("latency")
    base, cur = _win(rng, 1.0, 80), _win(rng, 1.0, 32)
    assert not base.digested
    assert not pipe._memo_check(
        fam, _PairItem("j", "latency", base, cur, policy), 128)
    assert len(made) == 2 and base.digested and cur.digested
    assert (pipe.memo_fp_bytes, pipe.memo_fp_hashed,
            pipe.memo_fp_reused) == (5 * (80 + 32), 2, 0)
    # the next cycle: the same baseline object, a current one sample longer
    cur2 = _win(rng, 1.0, 33)
    assert not pipe._memo_check(
        fam, _PairItem("j", "latency", base, cur2, policy), 128)
    assert len(made) == 3
    assert (pipe.memo_fp_bytes, pipe.memo_fp_hashed,
            pipe.memo_fp_reused) == (5 * (80 + 32 + 33), 3, 1)
    assert pipe.memo_lookups == 2


def _one_changed(change, v, m, start, step):
    """(values, mask, start, step) with one part of a window changed."""
    if change == "sample":
        v[3] += np.float32(0.5)
    elif change == "start":
        start += step
    elif change == "step":
        step = 30
    elif change == "length":
        v, m = v[:-1], m[:-1]
    elif change == "mask":
        m[5] = not m[5]
    return v, m, start, step


@pytest.mark.parametrize("change", ["equal", "sample", "start", "step",
                                    "length", "mask"])
def test_window_fingerprint_equal_exactly_when_the_windows_are(change):
    """Two distinct Window objects fingerprint alike exactly when start,
    step, length, values and mask are alike: the digest is of the window's
    identity, not of the object."""
    rng = np.random.default_rng(6)
    v = rng.normal(1.0, 0.1, 40).astype(np.float32)
    m = rng.random(40) < 0.9
    a = Window(v.copy(), m.copy(), 6000, STEP)
    b = Window(*_one_changed(change, v.copy(), m.copy(), 6000, STEP))
    assert a is not b
    fa = analyzer_mod._fp(b"pair", 128, "latency", a, None)
    fb = analyzer_mod._fp(b"pair", 128, "latency", b, None)
    assert (fa == fb) is (change == "equal")
    assert (a.digest() == b.digest()) is (change == "equal")
    # a part's place still counts
    assert analyzer_mod._fp(a, None) != analyzer_mod._fp(None, a)


@pytest.mark.parametrize("field", ["values", "mask"])
def test_a_digested_window_refuses_writes(field):
    """Taking the digest makes both arrays read-only: a write into a
    window the memo has fingerprinted raises instead of serving a stale
    memo hit next cycle."""
    w = _win(np.random.default_rng(7), 1.0, 16)
    arr = getattr(w, field)
    arr[0] = arr[1]  # writable until digested
    w.digest()
    with pytest.raises(ValueError, match="read-only"):
        arr[0] = arr[2]
    with pytest.raises(ValueError, match="read-only"):
        getattr(w, field)[:] = arr[::-1]


def test_fold_order_of_a_four_family_job_shows_in_its_reason():
    """`_preprocess` never routes one job to band, bivariate and lstm at
    once; routed by hand, its results fold in the table's order (pair,
    band, bivariate, lstm), which is the order of its reason and of its
    record's families."""
    from foremast_tpu.engine.analyzer import (
        _BandItem,
        _BiItem,
        _MultiItem,
        _PairItem,
    )

    rng = np.random.default_rng(2)
    # every family convicts: the current windows sit at three times their
    # history, and any reconstruction error is over the lstm's gate
    cfg = EngineConfig(pairwise_threshold=1e-4, lstm_epochs=1,
                       lstm_threshold=-1e9)
    store = JobStore()
    store.create(Document(
        id="four", app_name="app", namespace="px", strategy="canary",
        start_time=to_rfc3339(0.0), end_time=to_rfc3339(5_000_000.0),
        metrics={"m": MetricQueries(current="u/c")}))
    eng = Analyzer(cfg, FixtureDataSource({}), store)
    policy = cfg.policy_for("latency")

    def hist():
        return _win(rng, 10.0, 300)

    def cur():
        return _win(rng, 30.0, 25, start=300 * 60.0)

    routed = {
        # given out of order: the table's order decides, not the mapping's
        "lstm": [_MultiItem("four", "app/px", ["x", "y", "z"],
                            [hist() for _ in range(3)],
                            [cur() for _ in range(3)])],
        "bivariate": [_BiItem("four", ("a", "b"), (hist(), hist()),
                              (cur(), cur()), (policy, policy))],
        "band": [_BandItem("four", "m_band", hist(), cur(), policy)],
        "pair": [_PairItem("four", "m_pair", _win(rng, 10.0, 30),
                           _win(rng, 30.0, 30), policy)],
    }
    eng._preprocess = lambda doc, now: routed
    assert eng.run_cycle(now=1000.0) == {"four": J.COMPLETED_UNHEALTH}
    reason = store.get("four").reason
    assert reason.startswith(
        "anomaly detected on m_pair, m_band, a&b, x+y+z :: m_pair: ")
    at = [reason.index(f"; {m}: ") for m in ("m_band", "a&b", "x+y+z")]
    assert at == sorted(at)
    assert [f["family"] for f in eng.provenance.get("four")["families"]] == [
        "pair", "band", "bivariate", "lstm"]


# ------------------------------------------------------- batch-rung edges
def test_bucket_rows_exact_rung_boundary_and_tiny_cap():
    eng = Analyzer(EngineConfig(score_batch=8192), FixtureDataSource({}),
                   JobStore())
    assert eng._bucket_rows(64) == 64      # exactly on a rung: no pad
    assert eng._bucket_rows(65) == 256     # next rung up
    # score_batch below the smallest rung clamps to 16, not below
    tiny = Analyzer(EngineConfig(score_batch=8), FixtureDataSource({}),
                    JobStore())
    assert tiny._bucket_rows(1) == 16
    assert tiny._bucket_rows(100) == 16    # cap wins over the ladder


def test_score_chunks_rung_boundary_no_padding():
    """n exactly on a rung boundary launches unpadded."""
    eng = Analyzer(EngineConfig(score_batch=8192), FixtureDataSource({}),
                   JobStore())
    calls = []

    def fn(vals):
        calls.append(vals.shape[0])
        return {"s": vals.sum(axis=1)}

    vals = np.ones((64, 4), np.float32)
    out = eng._collect_chunks(eng._launch_chunks(fn, [vals]))
    assert calls == [64]
    assert out["s"].shape == (64,)


def test_score_chunks_big_fleet_tail_pads_to_own_rung():
    """The tail of a big fleet re-buckets DOWN the ladder (6 -> 16), it
    must not pad to the full chunk."""
    eng = Analyzer(EngineConfig(score_batch=64), FixtureDataSource({}),
                   JobStore())
    calls = []

    def fn(vals):
        calls.append(vals.shape[0])
        return {"s": vals.sum(axis=1)}

    vals = np.arange(70, dtype=np.float32)[:, None] * np.ones(4, np.float32)
    out = eng._collect_chunks(eng._launch_chunks(fn, [vals]))
    assert calls == [64, 16]
    np.testing.assert_allclose(out["s"], vals.sum(axis=1))


# --------------------------------------------------- hpa step regression
def test_hpa_bucket_preserves_series_step(monkeypatch):
    """A 30 s-step HPA job must keep its step through the pack path —
    the old build() dropped it back to the 60 s DEFAULT_STEP."""
    from foremast_tpu.engine import analyzer as A

    captured = []
    orig = A.pack_windows

    def spy(windows, pad_to=None):
        captured.append(list(windows))
        return orig(windows, pad_to=pad_to)

    monkeypatch.setattr(A, "pack_windows", spy)
    rng = np.random.default_rng(0)

    def win(n, start, step):
        return Window(rng.normal(100.0, 3.0, n).astype(np.float32),
                      np.ones(n, bool), start, step)

    eng = Analyzer(EngineConfig(), FixtureDataSource({}), JobStore())
    items = [
        A._HpaItem("j30", "tps", win(90, 0, 30), win(30, 90 * 30, 30),
                   True, 0),
        A._HpaItem("j30", "latency", win(90, 0, 30), win(30, 90 * 30, 30),
                   True, 1),
    ]
    out = families.family("hpa").score(eng, items)
    assert "j30" in out and out["j30"]["raw_score"] >= 0.0
    steps = {w.step for group in captured for w in group}
    assert steps == {30}


class _WindowSource:
    """Byte-level-style source: serves prebuilt grid Windows directly
    (the fetch_window fast path), so non-default steps survive fetch."""

    def __init__(self, windows):
        self.windows = windows

    def fetch_window(self, url):
        return self.windows[url]

    def fetch(self, url):  # pragma: no cover - fetch_window always hits
        raise AssertionError("fetch_window path expected")


def test_hpa_e2e_30s_step_job_scores():
    """Full cycle over a 30 s-grid HPA job (fetch_window source): scores,
    emits an hpalog, requeues — no snap back to the 60 s default."""
    rng = np.random.default_rng(4)

    def win(level, n, start):
        return Window(rng.normal(level, level * 0.03, n).astype(np.float32),
                      np.ones(n, bool), start, 30)

    windows = {
        "u/t/c": win(100.0, 30, 9000), "u/t/h": win(100.0, 300, 0),
        "u/l/c": win(5.0, 30, 9000), "u/l/h": win(5.0, 300, 0),
    }
    store = JobStore()
    tps = MetricQueries(current="u/t/c", historical="u/t/h")
    lat = MetricQueries(current="u/l/c", historical="u/l/h")
    lat.priority, lat.is_increase = 1, True
    store.create(Document(
        id="h30", app_name="a", namespace="n", strategy="hpa",
        start_time=to_rfc3339(0.0), end_time=to_rfc3339(5_000_000.0),
        metrics={"tps": tps, "latency": lat},
    ))
    eng = Analyzer(EngineConfig(), _WindowSource(windows), store)
    out = eng.run_cycle(now=10_000.0)
    assert out["h30"] == J.INITIAL  # scored + requeued (continuous)
    assert store._hpalogs and store._hpalogs[-1].job_id == "h30"


# --------------------------------------------------- stage observability
def test_cycle_stage_gauges_and_status_surface():
    exporter = VerdictExporter()
    store, fixtures = _mixed_fleet(n_pair=4, n_band=2, n_bi=0, n_lstm=0,
                                   n_hpa=1)
    eng = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store,
                   exporter)
    eng.run_cycle(now=1000.0)
    text = exporter.render()
    for stage in ("preprocess", "dispatch", "collect", "fold"):
        assert f'foremastbrain:cycle_stage_seconds{{stage="{stage}"}}' in text
    assert 'foremastbrain:cycle_family_score_seconds{family="pair"}' in text
    # /status mirrors the same decomposition
    from foremast_tpu.service.api import ForemastService

    svc = ForemastService(store, exporter=exporter, analyzer=eng)
    status, payload = svc.status_summary()
    assert status == 200
    cyc = payload["cycle"]
    assert "pipelined" not in cyc  # there is one scoring path
    assert set(cyc["stage_seconds"]) == {"preprocess", "dispatch",
                                         "collect", "fold"}
    assert cyc["family_score_seconds"]["pair"] > 0


# ------------------------------------------- the cycle's partition (ISSUE 26)
def _cycle_root(eng):
    """The finished engine.cycle span of the analyzer's last cycle."""
    cid = eng.last_cycle_stages["cycle_id"]
    return next(t for t in reversed(tracing.tracer.snapshot(limit=256))
                if t["name"] == tracing.SPAN_ENGINE_CYCLE
                and t["attrs"]["cycle_id"] == cid)


def _spans(span):
    yield span
    for c in span.get("children", ()):
        yield from _spans(c)


@pytest.mark.parametrize("cfg_kw", [dict(pipeline_fire_rows=16), FLUSH_ONLY],
                         ids=["streamed", "flush_only"])
def test_cycle_partition_sums_to_the_cycle_span(cfg_kw):
    store, fixtures = _mixed_fleet(n_pair=20, n_band=4, n_bi=2, n_lstm=0,
                                   n_hpa=2)
    eng = Analyzer(EngineConfig(**cfg_kw), FixtureDataSource(fixtures),
                   store)
    outcomes = eng.run_cycle(now=1000.0)
    st = eng.last_cycle_stages
    part = st["partition"]
    root = _cycle_root(eng)
    total = root["duration_ms"] * 1e-3
    assert sum(part["seconds"].values()) == pytest.approx(
        total, abs=max(0.02 * total, 0.005))
    assert part["seconds"]["uncovered"] >= -1e-4
    # the four stage counters keep their keys and are the partition's
    assert set(st["stage_seconds"]) == {"preprocess", "dispatch", "collect",
                                        "fold"}
    for stage, piece in (("preprocess", "wait"), ("dispatch", "dispatch"),
                         ("collect", "collect"), ("fold", "fold")):
        assert part["seconds"][piece] == pytest.approx(
            st["stage_seconds"][stage], abs=2e-6)
    by_name = {}
    for sp in _spans(root):
        by_name.setdefault(sp["name"], []).append(sp)
    assert set(by_name) <= tracing.SPAN_NAMES
    for name in (tracing.SPAN_ENGINE_CLAIM, tracing.SPAN_ENGINE_PREPROCESS,
                 tracing.SPAN_ENGINE_ADVANCE, tracing.SPAN_ENGINE_SCORE,
                 tracing.SPAN_ENGINE_LAUNCH, tracing.SPAN_ENGINE_MATERIALIZE,
                 tracing.SPAN_ENGINE_FOLD, tracing.SPAN_ENGINE_PUBLISH):
        assert name in by_name, name
    assert by_name[tracing.SPAN_ENGINE_FOLD][0]["duration_ms"] * 1e-3 == \
        pytest.approx(part["seconds"]["fold"], abs=5e-3)
    dispatch = by_name[tracing.SPAN_ENGINE_DISPATCH]
    assert sum(d["duration_ms"] for d in dispatch) * 1e-3 == \
        pytest.approx(part["seconds"]["dispatch"], abs=5e-3)
    assert {d["attrs"]["family"] for d in dispatch} == {
        "pair", "band", "bivariate", "hpa"}
    # the pair bucket fills its 16-row rung while the rest is fetched
    assert len(dispatch) == (5 if cfg_kw is not FLUSH_ONLY else 4)
    assert len(by_name[tracing.SPAN_ENGINE_COLLECT]) == len(dispatch)
    assert part["counters"]["memo_lookups"] > 0
    # the counters ride the spans: per launch, and summed on engine.score
    score = by_name[tracing.SPAN_ENGINE_SCORE][0]["attrs"]
    assert score["h2d_bytes"] == part["counters"]["h2d_bytes"] == sum(
        sp["attrs"]["h2d_bytes"]
        for sp in by_name[tracing.SPAN_ENGINE_LAUNCH])
    assert score["d2h_bytes"] == part["counters"]["d2h_bytes"] >= sum(
        sp["attrs"]["d2h_bytes"]
        for sp in by_name[tracing.SPAN_ENGINE_MATERIALIZE]) > 0
    assert 0 < score["pack_real_elems"] < score["pack_total_elems"]
    # the pool's notes: thread-seconds summed per chunk, and per job on
    # the fetch record that explain serves
    prep = by_name[tracing.SPAN_ENGINE_PREPROCESS][0]["attrs"]
    per_job = sum(eng.provenance.get(j)["fetch"]["prep_thread_seconds"]
                  for j in outcomes)
    assert part["pool"]["prep_thread_seconds"] == pytest.approx(
        per_job, abs=1e-4)
    assert prep["pool_prep_thread_seconds"] == pytest.approx(
        part["pool"]["prep_thread_seconds"], abs=2e-6)
    assert prep["route_s"] == pytest.approx(part["seconds"]["route"],
                                            abs=2e-6)
    # served where the rest is served: /status and foremast_trace_*
    from foremast_tpu.service.api import ForemastService

    _, payload = ForemastService(store, analyzer=eng).status_summary()
    assert payload["cycle"]["partition"] == part
    metrics = tracing.tracer.render_metrics()
    for name in (tracing.SPAN_ENGINE_ROUTE, tracing.SPAN_ENGINE_ROUTE_CPU,
                 tracing.SPAN_ENGINE_MEMO_FP, tracing.SPAN_ENGINE_LAUNCH,
                 *tracing.POOL_SPANS.values()):
        assert f'foremast_trace_seconds_total{{span="{name}"}}' in metrics


def _two_windows(rng, n_a, n_b, start=0):
    def win(n):
        return Window(rng.normal(10.0, 1.0, n).astype(np.float32),
                      np.ones(n, bool), start)
    return win(n_a), win(n_b)


@pytest.mark.parametrize("family", ["pair", "band", "bivariate"])
def test_transfer_and_pack_counters_equal_hand_counts(family):
    """A two-row launch pads to the 16-row rung: every byte handed to a
    jitted program and brought back, and the pack's fill, by hand. A
    (B, T) array crosses once, upward; what comes back is the programs'
    outputs, of which only `flags` is (B, T)."""
    import jax

    from foremast_tpu.engine.analyzer import _BandItem, _BiItem, _PairItem
    from foremast_tpu.ops import bivariate as bv
    from foremast_tpu.ops import forecast as fc
    from foremast_tpu.parallel import fleet as fl

    rng = np.random.default_rng(5)
    eng = Analyzer(EngineConfig(), None, JobStore())
    policy = eng.config.policy_for("latency")
    R = 16

    def out_bytes(fn, *args):
        return sum(int(np.prod(o.shape)) * o.dtype.itemsize
                   for o in jax.tree_util.tree_leaves(
                       jax.eval_shape(fn, *args)))

    if family == "pair":
        T, lens = 32, [(30, 20), (25, 28)]
        items = [_PairItem(f"j{i}", "latency", *_two_windows(rng, b, c),
                           policy) for i, (b, c) in enumerate(lens)]
        spec = fl.pair_arg_spec(R, T)
        h2d = sum(a.nbytes for a in spec)
        d2h = out_bytes(fl.score_pairs, *spec)
        real, total = sum(b + c for b, c in lens), 2 * R * T
    else:
        T, lens = 512, [(300, 25), (290, 20)]
        f32, b1, row = R * T * 4, R * T, R * 4
        z, m = np.zeros((R, T), np.float32), np.zeros((R, T), bool)
        r4, i4 = np.zeros(R, np.float32), np.ones(R, np.int32)
        if family == "band":
            items = [_BandItem(f"j{i}", "latency",
                               *_two_windows(rng, h, c), policy)
                     for i, (h, c) in enumerate(lens)]
            # one upload of values and validity; the region's two
            # vectors; the three per-row policies. The predictions and
            # sigma are device values from one program to the next
            h2d = (f32 + b1) + 2 * row + 3 * row
            d2h = out_bytes(fc.band_anomalies, z, m, m, z, r4, r4, i4, r4)
            real, total = sum(h + c for h, c in lens), R * T
        else:
            items = []
            for i, (h, c) in enumerate(lens):
                h1, c1 = _two_windows(rng, h, c)
                h2, c2 = _two_windows(rng, h, c)
                c1.start = c2.start = h * 60
                items.append(_BiItem(f"j{i}", ("latency", "cpu"), (h1, h2),
                                     (c1, c2), (policy, policy)))
            # two metrics' values and validity; the region's two vectors
            # and five per-row policies
            h2d = 2 * (f32 + b1) + 7 * row
            d2h = out_bytes(bv.bivariate_normal_anomalies,
                            z, m, z, m, i4, i4, r4, r4, r4, i4, i4)
            real, total = 2 * sum(h + c for h, c in lens), 2 * R * T
    results = families.family(family).score(eng, items)
    assert len(results) == 2
    assert eng.device_launches == 1
    assert eng.h2d_bytes_total == h2d
    assert eng.d2h_bytes_total == d2h
    assert eng.pack_real_elems_total == real
    assert eng.pack_total_elems_total == total


@pytest.mark.parametrize("family", ["pair", "band"])
def test_pair_sort_counters_equal_hand_counts(family):
    """The pair program sorts both windows of every launched row at the
    bucket, 2T a row; the counters hold that against the valid samples
    of the real rows, and the widest bucket. A band launch sorts none."""
    from foremast_tpu.engine.analyzer import _BandItem, _PairItem

    rng = np.random.default_rng(7)
    eng = Analyzer(EngineConfig(), None, JobStore())
    policy = eng.config.policy_for("latency")
    T, lens = 32, [(30, 20), (25, 28), (31, 22)]
    cls = _PairItem if family == "pair" else _BandItem
    items = [cls(f"j{i}", "latency", *_two_windows(rng, b, c), policy)
             for i, (b, c) in enumerate(lens)]
    if family == "pair":
        # a masked slot on each side of the first row is not a sample
        items[0].baseline.mask[5] = False
        items[0].current.mask[3] = False
    families.family(family).score(eng, items)
    if family == "pair":
        # three rows pad to the 16-row rung
        assert eng.pair_sort_elems_total == 16 * 2 * T
        assert eng.pair_real_elems_total == sum(b + c for b, c in lens) - 2
        assert eng._cycle_pair_T_max == T
    else:
        assert eng.pair_sort_elems_total == eng.pair_real_elems_total == 0
        assert eng._cycle_pair_T_max == 0


def test_pair_sort_counters_ride_the_score_span_and_a_gauge():
    exporter = VerdictExporter()
    store, fixtures = _mixed_fleet(n_pair=6, n_band=2, n_bi=0, n_lstm=0,
                                   n_hpa=0)
    eng = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store,
                   exporter)
    eng.run_cycle(now=1000.0)
    score = next(sp for sp in _spans(_cycle_root(eng))
                 if sp["name"] == tracing.SPAN_ENGINE_SCORE)["attrs"]
    counters = eng.last_cycle_stages["partition"]["counters"]
    for key in ("pair_sort_elems", "pair_real_elems", "pair_T_max"):
        assert score[key] == counters[key]
    assert counters["pair_sort_elems"] == eng.pair_sort_elems_total
    assert 0 < counters["pair_real_elems"] < counters["pair_sort_elems"]
    assert counters["pair_sort_elems"] % (2 * counters["pair_T_max"]) == 0
    gauges = {name: value for name, _, value in exporter.samples()}
    assert gauges["foremastbrain:pair_sort_fill_share"] == round(
        counters["pair_real_elems"] / counters["pair_sort_elems"], 6)
    # a cycle with no pair launch reads 0, and nothing is carried over
    store2, fixtures2 = _mixed_fleet(n_pair=0, n_band=2, n_bi=0, n_lstm=0,
                                     n_hpa=0)
    eng2 = Analyzer(EngineConfig(), FixtureDataSource(fixtures2), store2,
                    exporter)
    eng2.run_cycle(now=1000.0)
    counters = eng2.last_cycle_stages["partition"]["counters"]
    assert counters["pair_sort_elems"] == counters["pair_T_max"] == 0
    gauges = {name: value for name, _, value in exporter.samples()}
    assert gauges["foremastbrain:pair_sort_fill_share"] == 0.0


def test_pair_route_keys_each_window_at_its_own_bucket():
    """A rollover row (a 20-to-31-point window against the trailing 7
    days) routes to (16384, 32), and so does any window of at most 32
    points against a baseline bucket of 512 or more; a longer window, or
    any row of a narrower bucket (a T=128 canary, a 20-point window at
    T=64), is symmetric, and so is a row whose current window is the
    longer one: the baseline keeps the row's bucket."""
    from foremast_tpu.engine.analyzer import _PairItem

    rng = np.random.default_rng(2)
    eng = Analyzer(EngineConfig(), None, JobStore())
    policy = eng.config.policy_for("latency")
    route = families.family("pair").route
    for (nb, nc), key in [((10081, 20), (16384, 32)),
                          ((10081, 31), (16384, 32)),
                          ((10081, 40), (16384, 16384)),
                          ((300, 20), (512, 32)),
                          ((200, 20), (256, 256)),
                          ((100, 20), (128, 128)),
                          ((40, 20), (64, 64)),
                          ((80, 100), (128, 128)),
                          ((80, 80), (128, 128)),
                          ((20, 100), (128, 128))]:
        it = _PairItem("j", "latency", *_two_windows(rng, nb, nc), policy)
        assert route(eng, it) == (it, key), (nb, nc)


def test_split_pair_launch_counts_what_it_packs():
    """Three rows of a long baseline against a short window launch at
    (1024, 32): what crosses is the split packing's bytes, the sort is
    R x (Tb + Tc), and the three real rows count as split."""
    import jax

    from foremast_tpu.engine.analyzer import _PairItem
    from foremast_tpu.parallel import fleet as fl

    rng = np.random.default_rng(9)
    eng = Analyzer(EngineConfig(), None, JobStore())
    policy = eng.config.policy_for("latency")
    R, Tb, Tc = 16, 1024, 32
    lens = [(600, 20), (590, 31), (601, 25)]
    items = [_PairItem(f"j{i}", "latency", *_two_windows(rng, b, c), policy)
             for i, (b, c) in enumerate(lens)]
    results = families.family("pair").score(eng, items)
    assert len(results) == 3 and eng.device_launches == 1
    spec = fl.pair_arg_spec(R, Tb, Tc)
    assert eng.h2d_bytes_total == sum(a.nbytes for a in spec)
    assert eng.d2h_bytes_total == sum(
        int(np.prod(o.shape)) * o.dtype.itemsize
        for o in jax.tree_util.tree_leaves(
            jax.eval_shape(fl.score_pairs, *spec)))
    assert eng.pair_sort_elems_total == R * (Tb + Tc)
    assert eng.pack_total_elems_total == R * (Tb + Tc)
    assert eng.pair_real_elems_total == eng.pack_real_elems_total \
        == sum(b + c for b, c in lens)
    assert eng.pair_split_rows_total == 3
    assert eng._cycle_pair_T_max == Tb


def _rollover_fleet(n, n_hist, n_cur=24, seed=13):
    """`n` pair jobs judging a short current window against a long
    baseline, as foremast-trigger's rollover request does, with healthy
    data so every job is judged again next cycle: (store, fixtures)."""
    rng = np.random.default_rng(seed)
    fixtures, store = {}, JobStore()
    for i in range(n):
        cur, base = f"u/r{i}/c", f"u/r{i}/b"
        fixtures[base] = _series(rng, 0.5, n_hist)
        fixtures[cur] = _series(rng, 0.5, n_cur)
        store.create(Document(
            id=f"r{i}", app_name=f"app-r{i}", namespace="px",
            strategy="canary", start_time=to_rfc3339(0.0),
            end_time=to_rfc3339(5_000_000.0),
            metrics={"error5xx": MetricQueries(current=cur, baseline=base)}))
    return store, fixtures


def test_pair_split_rows_ride_the_score_span_and_a_gauge():
    exporter = VerdictExporter()
    store, fixtures = _rollover_fleet(5, 600)
    eng = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store,
                   exporter)
    eng.run_cycle(now=1000.0)
    score = next(sp for sp in _spans(_cycle_root(eng))
                 if sp["name"] == tracing.SPAN_ENGINE_SCORE)["attrs"]
    counters = eng.last_cycle_stages["partition"]["counters"]
    assert score["pair_split_rows"] == counters["pair_split_rows"] == 5
    assert counters["pair_sort_elems"] == 16 * (1024 + 32)
    assert counters["pair_T_max"] == 1024
    gauges = {name: value for name, _, value in exporter.samples()}
    assert gauges["foremastbrain:pair_split_rows"] == 5
    # symmetric rows are not split
    store2, fixtures2 = _mixed_fleet(n_pair=4, n_band=0, n_bi=0, n_lstm=0,
                                     n_hpa=0)
    eng2 = Analyzer(EngineConfig(), FixtureDataSource(fixtures2), store2,
                    exporter)
    eng2.run_cycle(now=1000.0)
    assert eng2.last_cycle_stages["partition"]["counters"][
        "pair_split_rows"] == 0
    gauges = {name: value for name, _, value in exporter.samples()}
    assert gauges["foremastbrain:pair_split_rows"] == 0


@pytest.mark.perf
def test_prewarm_covers_short_current_windows():
    """A pair grid prewarmed at T 64/512/16384 covers every key a short
    current window routes to: a 20-point window at T=64 (symmetric), at
    T=512 (512, 32), and the rollover's 31-point window against the
    7-day baseline (16384, 32). A cycle over them compiles nothing."""
    cfg = EngineConfig(pairwise_threshold=1e-4)
    summary = prewarm(cfg, families=("pair",), rungs=(16,),
                      t_buckets=(64, 512, 16384))
    assert summary["programs"] == 5  # (64, 64), two at 512, two at 16384
    rng = np.random.default_rng(4)
    fixtures, store = {}, JobStore()
    for i, (nb, nc) in enumerate([(40, 20), (60, 20), (300, 20),
                                  (400, 31), (10081, 31)]):
        cur, base = f"u/s{i}/c", f"u/s{i}/b"
        fixtures[cur] = _series(rng, 0.5, nc)
        fixtures[base] = _series(rng, 0.5, nb)
        store.create(Document(
            id=f"s{i}", app_name="a", namespace="n", strategy="canary",
            start_time=to_rfc3339(0.0), end_time=to_rfc3339(5_000_000.0),
            metrics={"error5xx": MetricQueries(current=cur, baseline=base)},
        ))
    eng = Analyzer(cfg, FixtureDataSource(fixtures), store)
    with CompileCounter() as cc:
        out = eng.run_cycle(now=1000.0)
    assert len(out) == 5
    assert eng.last_cycle_stages["partition"]["counters"][
        "pair_split_rows"] == 3
    assert cc.compiles == 0, (
        f"cycle after prewarm compiled {cc.compiles} program(s): a short "
        "current window's key is missing from the prewarm grid")


@pytest.mark.perf
def test_rollover_cycles_trigger_zero_recompiles():
    """A 7-day baseline against a window that grows a sample a cycle
    inside bucket 32: after one warm-up cycle, two more launch the
    (16384, 32) program already compiled."""
    store, fixtures = _rollover_fleet(3, 10081)
    eng = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store)
    eng.run_cycle(now=1000.0)
    launches = eng.device_launches
    with CompileCounter() as cc:
        for _ in range(2):
            for i in range(3):
                ts, vals = fixtures[f"u/r{i}/c"]
                fixtures[f"u/r{i}/c"] = (ts + [ts[-1] + STEP],
                                         vals + [vals[-1]])
            eng.run_cycle(now=1000.0)
            assert eng.last_cycle_stages["partition"]["counters"][
                "pair_split_rows"] == 3
    assert eng.device_launches == launches + 2
    assert cc.compiles == 0, (
        f"rollover cycles compiled {cc.compiles} program(s) after warm-up")


def test_no_span_opens_on_a_fetch_pool_thread(monkeypatch):
    """A span on a pool thread would be the innermost span over the
    device's long idle gap and rename it after a chunk of the pool: the
    pool reports through notes. (The watchdog's sacrificial thread runs a
    collect under attach and may open engine.materialize.)"""
    import threading

    opened = []
    real_span = tracing.tracer.span

    def recording_span(name, *a, **kw):
        opened.append((name, threading.current_thread().name))
        return real_span(name, *a, **kw)

    monkeypatch.setattr(tracing.tracer, "span", recording_span)
    monkeypatch.setattr(tracing, "span", recording_span)
    # 30 jobs at a cap of 4: under the probe's threshold, so every fetch
    # is a pool thread's
    store, fixtures = _mixed_fleet(n_pair=24, n_band=6, n_bi=0, n_lstm=0,
                                   n_hpa=0)
    pool_threads = set()

    class Source(FixtureDataSource):
        def fetch(self, url):
            pool_threads.add(threading.current_thread().name)
            # long enough that the executor cannot serve every chunk from
            # its first thread on a loaded machine
            time.sleep(0.002)
            return super().fetch(url)

    eng = Analyzer(EngineConfig(fetch_concurrency=4, watchdog_seconds=30.0),
                   Source(fixtures), store)
    eng.run_cycle(now=1000.0)
    me = threading.current_thread().name
    assert len(pool_threads) > 1 and me not in pool_threads
    assert {n for n, _ in opened} >= {tracing.SPAN_ENGINE_LAUNCH,
                                      tracing.SPAN_ENGINE_MATERIALIZE}
    for name, thread in opened:
        assert thread not in pool_threads, (name, thread)
        assert thread == me or (thread == "collect-watchdog"
                                and name == tracing.SPAN_ENGINE_MATERIALIZE)
    assert eng.last_cycle_stages["partition"]["pool"][
        "prep_thread_seconds"] > 0


# ------------------------- the fetch's parts and the pack's pieces (ISSUE 40)
_POOL_PARTS = ("url_thread_seconds", "cache_thread_seconds",
               "items_thread_seconds", "source_thread_seconds",
               "lock_wait_thread_seconds", "lock_held_seconds")


@pytest.mark.parametrize("forced", [1, "cap"], ids=["width1", "cap"])
def test_fetch_pool_parts_add_up_to_prep(monkeypatch, forced):
    """The pool's six parts are a partition of its `prep` thread-seconds,
    on the span and on /status, at width 1 and across a pool whose store
    sleeps; every part a fetch of this fleet works is over 0."""
    eng, _, src, _ = _probed_engine(monkeypatch, forced,
                                    source_kw={"sleep": 0.001})
    eng.run_cycle(now=1000.0)
    pool = eng.last_cycle_stages["partition"]["pool"]
    me = threading.current_thread().name
    assert ({t for t, _ in src.seen} == {me}) == (forced == 1)
    assert sum(pool[k] for k in _POOL_PARTS) == pytest.approx(
        pool["prep_thread_seconds"], abs=1e-5)
    for k in ("url_thread_seconds", "cache_thread_seconds",
              "items_thread_seconds"):
        assert pool[k] > 0, k
    # the fixture source is not a delta source: its sleep is the cache's
    assert pool["cache_thread_seconds"] >= 0.001 * len(src.seen)
    assert "fetch_seconds" not in pool
    prep = next(s for s in _spans(_cycle_root(eng))
                if s["name"] == tracing.SPAN_ENGINE_PREPROCESS)["attrs"]
    for k in (*_POOL_PARTS, "prep_thread_seconds"):
        assert prep["pool_" + k] == pool[k], k


@pytest.mark.parametrize("algorithm", ["moving_average_all", "holt_winters"],
                         ids=["band", "band_partitioned"])
def test_every_dispatch_packs_in_three_spans(algorithm):
    """Each engine.dispatch (pair, band, bivariate, hpa) holds one
    engine.pack.rows, .block and .pad, in that order before its launches,
    each with the dispatch's `rows` and the bytes it wrote; a seasonal
    band launch, partitioned by period, packs the same way. Pair, band
    and bivariate blocks are built at the rows they are launched at, the
    edge rows written in the block (attr `edge_rows`), so the pad holds
    only the (B,) vectors' padding; hpa does not pre-size."""
    store, fixtures = _mixed_fleet(n_pair=20, n_band=4, n_bi=2, n_lstm=0,
                                   n_hpa=2)
    eng = Analyzer(EngineConfig(pipeline_fire_rows=16, algorithm=algorithm),
                   FixtureDataSource(fixtures), store)
    eng.run_cycle(now=1000.0)
    root = _cycle_root(eng)
    dispatches = [s for s in _spans(root)
                  if s["name"] == tracing.SPAN_ENGINE_DISPATCH]
    assert {d["attrs"]["family"] for d in dispatches} == {
        "pair", "band", "bivariate", "hpa"}
    pieces = (tracing.SPAN_ENGINE_PACK_ROWS, tracing.SPAN_ENGINE_PACK_BLOCK,
              tracing.SPAN_ENGINE_PACK_PAD)
    for d in dispatches:
        kids = d["children"]
        names = [c["name"] for c in kids]
        assert names[:3] == list(pieces), names
        assert set(names[3:]) == {tracing.SPAN_ENGINE_LAUNCH}, names
        family, rows = d["attrs"]["family"], d["attrs"]["rows"]
        written = {}
        for c in kids[:3]:
            assert c["attrs"]["rows"] == rows
            assert isinstance(c["attrs"]["bytes"], int)
            written[c["name"]] = c["attrs"]["bytes"]
        # pair and bivariate rows are built on the stream, not here
        assert (written[pieces[0]] > 0) == (family in ("band", "hpa"))
        assert written[pieces[1]] > 0
        # a rung the rows fill is not padded
        padded = sum(s["attrs"]["padded_rows"] - s["attrs"]["rows"]
                     for s in kids[3:])
        assert (written[pieces[2]] > 0) == (padded > 0)
        if family == "hpa":
            assert "edge_rows" not in kids[1]["attrs"]
            continue
        # the block's edge rows are the launches' padding, and the pad
        # writes less than one block row a padded row: no (B, T) block
        assert kids[1]["attrs"]["edge_rows"] == padded
        row_bytes = written[pieces[1]] // (rows + padded)
        assert written[pieces[2]] < padded * row_bytes or not padded, (
            family, written[pieces[2]], padded, row_bytes)
    detects = [s for s in _spans(root)
               if s["name"] == tracing.SPAN_ENGINE_DETECT_PERIOD]
    assert bool(detects) == (algorithm == "holt_winters")


def test_cycle_root_stays_whole_with_streamed_launches():
    """Ten streamed pair dispatches and the flush: every span of the cycle
    keeps all its children (a root that dropped one reads as missing to
    every span metric of the benchmark)."""
    store, fixtures = _mixed_fleet(n_pair=160, n_band=4, n_bi=2, n_lstm=0,
                                   n_hpa=2)
    eng = Analyzer(EngineConfig(pipeline_fire_rows=16),
                   FixtureDataSource(fixtures), store)
    eng.run_cycle(now=1000.0)
    spans = list(_spans(_cycle_root(eng)))
    assert not [s["name"] for s in spans if s.get("children_dropped")]
    dispatches = [s for s in spans
                  if s["name"] == tracing.SPAN_ENGINE_DISPATCH]
    assert len(dispatches) >= 13
    assert sum(c["name"].startswith("engine.pack.")
               for d in dispatches for c in d["children"]) \
        == 3 * len(dispatches)


# ------------------------------------- the fetch pool's width (ISSUE 32)
# No case here depends on the machine's load: the rule is held to numbers
# as a pure function, and a case that drives a cycle either patches the
# rule's result or uses a store that sleeps (or burns CPU) on purpose.
@pytest.mark.parametrize("wall, cpu, cap, width", [
    (0.020, 0.020, 16, 1),      # an in-process store
    (0.028, 0.020, 16, 1),      # and one preemption on a shared host
    (0.032, 0.020, 16, 2),
    (0.040, 0.040 / 13, 16, 13),
    (0.040, 0.001, 16, 16),     # ratio 40: the cap
    (0.040, 0.0002, 16, 16),    # no CPU to speak of: nothing measured
    (0.0, 0.0, 4, 4),
], ids=["1.0", "1.4", "1.6", "13", "40", "no-cpu", "nothing"])
def test_pool_width_rule(wall, cpu, cap, width):
    assert analyzer_mod.pool_width(wall, cpu, cap) == width


class _ThreadSource(FixtureDataSource):
    """Records the thread of every fetch, and the launches fired so far;
    `sleep` seconds of waiting and `burn` seconds of CPU a fetch, or with
    `fail` a FetchError."""

    def __init__(self, fixtures, sleep=0.0, burn=0.0, fail=False):
        super().__init__(fixtures)
        self.sleep, self.burn, self.fail = sleep, burn, fail
        self.seen = []  # (thread name, device launches at that moment)
        self.eng = None

    def fetch(self, url):
        self.seen.append((threading.current_thread().name,
                          self.eng.device_launches if self.eng else 0))
        if self.fail:
            raise FetchError(f"blackout: {url}")
        if self.sleep:
            time.sleep(self.sleep)
        c0 = time.thread_time()
        while time.thread_time() - c0 < self.burn:
            pass
        return super().fetch(url)


def _probed_engine(monkeypatch, forced=None, fleet_kw=None, source_kw=None,
                   **cfg_kw):
    """(eng, store, source, stream order) over a fleet large enough to be
    probed at a cap of 4 (42 jobs; the threshold is 32). `forced` patches
    the rule's result: a width, or "cap"."""
    fleet_kw = fleet_kw or dict(n_pair=30, n_band=8, n_bi=2, n_lstm=0,
                                n_hpa=2)
    store, fixtures = _mixed_fleet(**fleet_kw)
    src = _ThreadSource(fixtures, **(source_kw or {}))
    cfg_kw.setdefault("fetch_concurrency", 4)
    eng = Analyzer(EngineConfig(pairwise_threshold=1e-4, score_batch=16,
                                **cfg_kw), src, store)
    src.eng = eng
    if forced is not None:
        monkeypatch.setattr(
            analyzer_mod, "pool_width",
            lambda wall, cpu, cap: cap if forced == "cap" else forced)
    order = []
    inner = eng._stream_prep

    def recording(*a, **kw):
        for result in inner(*a, **kw):
            order.append((result[0], result[2]))
            yield result

    eng._stream_prep = recording
    return eng, store, src, order


def _records(eng, outcomes):
    """What a cycle recorded of each job, less its clocks."""
    out = {}
    for job in outcomes:
        rec = eng.provenance.get(job)
        out[job] = {
            **{k: rec.get(k) for k in ("path", "status", "reason", "detail",
                                       "families")},
            "launches": rec["cycle"]["device_launches"],
            "fetch": {k: rec.get("fetch", {}).get(k)
                      for k in ("points", "fetches")}}
    return out


def test_width_one_starts_no_pool_and_changes_nothing(monkeypatch):
    """Forced to 1 on a probed fleet, the stream starts no thread, and
    outcomes, provenance records and the order of results are those of
    `fetch_concurrency=1` and of the forced cap, job for job."""
    runs = {}
    for name, forced, cfg in (("one", 1, {}), ("cap", "cap", {}),
                              ("serial", None, {"fetch_concurrency": 1})):
        with monkeypatch.context() as mp:
            eng, store, src, order = _probed_engine(mp, forced, **cfg)
            if name != "cap":
                def no_pool(*a, **kw):
                    raise AssertionError("a pool was started")
                mp.setattr(analyzer_mod, "ThreadPoolExecutor", no_pool)
            outcomes = eng.run_cycle(now=1000.0)
            runs[name] = (outcomes, _records(eng, outcomes), order,
                          _snapshot(store))
            threads = {t for t, _ in src.seen}
            me = threading.current_thread().name
            if name == "cap":
                assert len(threads - {me}) > 1
                assert eng.last_cycle_stages["fetch_pool_width"] == 4
            else:
                assert threads == {me}
                assert eng.last_cycle_stages["fetch_pool_width"] == 1
    assert len(runs["one"][0]) == 42
    assert runs["one"] == runs["serial"] == runs["cap"]


def test_width_one_is_still_a_stream(monkeypatch):
    """At width 1 the chunks are yielded one by one: a full rung is
    launched before the last chunk is fetched."""
    eng, _, src, _ = _probed_engine(monkeypatch, forced=1)
    eng.run_cycle(now=1000.0)
    launched = [n for _, n in src.seen]
    assert launched[0] == 0 and launched[-1] >= 1
    assert launched == sorted(launched)


def _waiting_fleet(n, seed=5):
    """(store, fixtures): n canaries of three windows (three queries)."""
    rng = np.random.default_rng(seed)
    store, fixtures = JobStore(), {}
    for i in range(n):
        urls = [f"u/w{i}/{role}" for role in ("c", "b", "h")]
        for url, n in zip(urls, (30, 30, 300)):
            fixtures[url] = _series(rng, 0.5, n)
        store.create(Document(
            id=f"w{i}", app_name=f"app-w{i}", namespace="px",
            strategy="canary", start_time=to_rfc3339(0.0),
            end_time=to_rfc3339(5_000_000.0),
            metrics={"error5xx": MetricQueries(*urls)}))
    return store, fixtures


def test_source_that_waits_keeps_the_pool_at_the_cap():
    """A store that sleeps 5 ms a query, three queries a job: each of the
    probe's two readings ends at the wall limit within three jobs (of a
    chunk of eight), and the pool is as wide as `fetch_concurrency`
    allows."""
    store, fixtures = _waiting_fleet(256)
    src = _ThreadSource(fixtures, sleep=0.005)
    eng = Analyzer(EngineConfig(fetch_concurrency=4), src, store)
    outcomes = eng.run_cycle(now=1000.0)
    assert len(outcomes) == 256
    me = threading.current_thread().name
    probed = sum(t == me for t, _ in src.seen) / 3
    assert probed in (2, 3, 4, 5, 6)
    assert len({t for t, _ in src.seen} - {me}) == 4
    st = eng.last_cycle_stages
    assert st["fetch_pool_width"] == st["partition"]["pool"]["width"] == 4
    # 0.015 s of waiting a job against a few hundred microseconds of CPU
    assert st["partition"]["pool"].get("probe_ratio", 4.0) >= 3.5


@pytest.mark.parametrize("readings, width", [
    ([1], 1), ([2, 1], 1), ([2, 2], 2), ([1, 2], 1), ([2, 2, 1], 2),
], ids=["narrow", "one-preemption", "waits", "narrow-first", "two-only"])
def test_wide_reading_is_taken_again_and_the_narrower_holds(
        monkeypatch, readings, width):
    """One preemption of the cycle thread reads as waiting: a reading over
    1 is taken once more over the next jobs. The rule is scripted; the
    store sleeps, so each reading ends at the wall limit within three jobs
    of a chunk of eight."""
    asked = []

    def rule(wall, cpu, cap):
        asked.append(cap)
        return readings[len(asked) - 1]

    monkeypatch.setattr(analyzer_mod, "pool_width", rule)
    store, fixtures = _waiting_fleet(128)
    src = _ThreadSource(fixtures, sleep=0.007)
    eng = Analyzer(EngineConfig(fetch_concurrency=2), src, store)
    assert len(eng.run_cycle(now=1000.0)) == 128
    assert asked == [2] * min(len(readings), 2 if readings[0] > 1 else 1)
    assert eng.last_cycle_stages["fetch_pool_width"] == width
    me = threading.current_thread().name
    probed = sum(t == me for t, _ in src.seen) / 3
    if width == 1:
        assert probed == 128
    else:
        assert 2 <= probed <= 6


def test_probe_that_fetches_nothing_gives_the_cap(monkeypatch):
    """Every probed job fails with FetchError (a shed probe cannot be
    driven: the cycle's first job is exempt by class or is the floor): the
    rule is not asked, the pool has the cap, outcomes are the serial
    cycle's."""
    def rule(wall, cpu, cap):
        raise AssertionError("the rule was asked about an empty probe")

    monkeypatch.setattr(analyzer_mod, "pool_width", rule)
    blackout = dict(fail=True)
    eng, store, src, order = _probed_engine(monkeypatch, source_kw=blackout)
    outcomes = eng.run_cycle(now=1000.0)
    pool = eng.last_cycle_stages["partition"]["pool"]
    assert pool["width"] == 4 and "probe_ratio" not in pool
    assert len({t for t, _ in src.seen}) > 1
    ser, ser_store, _, ser_order = _probed_engine(
        monkeypatch, source_kw=blackout, fetch_concurrency=1)
    assert ser.run_cycle(now=1000.0) == outcomes
    assert set(outcomes.values()) == {J.PREPROCESS_FAILED, J.INITIAL}
    assert ser_order == order and _snapshot(ser_store) == _snapshot(store)


def test_deadline_sheds_the_same_jobs_at_width_one_as_at_the_cap(
        monkeypatch):
    """tests/test_degraded.py's fleet, large enough to be probed: under a
    spent cycle budget the canaries and the first monitor score and every
    other monitor is shed without a fetch, at width 1 as at the cap."""
    def run(forced):
        rng = np.random.default_rng(7)
        store, fixtures = JobStore(), {}
        for i in range(40):
            job = f"canary{i}" if i % 5 == 0 else f"watch{i}"
            urls = [f"u/{job}/{role}" for role in ("c", "b", "h")]
            for url, n in zip(urls, (30, 30, 600)):
                fixtures[url] = _series(rng, 0.5, n)
            store.create(Document(
                id=job, app_name=f"app-{job}", namespace="deg",
                strategy="canary" if i % 5 == 0 else "continuous",
                start_time=to_rfc3339(0.0),
                end_time=to_rfc3339(1e7) if i % 5 == 0 else "",
                metrics={"error5xx": MetricQueries(*urls)}))
        src = _ThreadSource(fixtures)
        eng = Analyzer(EngineConfig(fetch_concurrency=4,
                                    cycle_deadline_seconds=1e-9,
                                    max_stuck_seconds=1e9), src, store)
        with monkeypatch.context() as mp:
            mp.setattr(analyzer_mod, "pool_width",
                       lambda wall, cpu, cap: forced)
            outcomes = eng.run_cycle(worker="w", now=100.0)
        assert eng.last_cycle_stages["fetch_pool_width"] == forced
        shed = sorted(j for j in outcomes if "shed" in store.get(j).reason)
        return outcomes, shed, len(src.seen), dict(eng._shed_streak)

    one, cap = run(1), run(4)
    assert one == cap
    # 8 canaries and the floor fetched their three windows; 31 shed
    assert len(one[1]) == 31 and one[2] == 27
    assert "watch1" not in one[1]


def test_fleet_under_the_threshold_is_never_probed(monkeypatch):
    """31 jobs at a cap of 4 (under 8 a thread): no fetch on the cycle
    thread, the rule is not asked, the pool has the cap."""
    def rule(wall, cpu, cap):
        raise AssertionError("probed")

    monkeypatch.setattr(analyzer_mod, "pool_width", rule)
    eng, _, src, _ = _probed_engine(
        monkeypatch, fleet_kw=dict(n_pair=21, n_band=8, n_bi=2, n_lstm=0,
                                   n_hpa=0))
    assert len(eng.run_cycle(now=1000.0)) == 31
    assert threading.current_thread().name not in {t for t, _ in src.seen}
    pool = eng.last_cycle_stages["partition"]["pool"]
    assert pool["width"] == 4 and "probe_ratio" not in pool


def test_probe_width_and_ratio_are_served(monkeypatch):
    """What the probe chose is on the engine.preprocess span, in
    `last_cycle_stages` and on /metrics. Every fetch burns 2 ms of CPU, so
    the probe has CPU to divide by on any host."""
    exporter = VerdictExporter()
    store, fixtures = _mixed_fleet(n_pair=40, n_band=0, n_bi=0, n_lstm=0,
                                   n_hpa=0)
    eng = Analyzer(EngineConfig(fetch_concurrency=4),
                   _ThreadSource(fixtures, burn=0.002), store, exporter)
    eng.run_cycle(now=1000.0)
    st = eng.last_cycle_stages
    prep = next(sp for sp in _spans(_cycle_root(eng))
                if sp["name"] == tracing.SPAN_ENGINE_PREPROCESS)["attrs"]
    width, ratio = prep["pool_width"], prep["pool_probe_ratio"]
    assert ratio >= 0.9  # wall over CPU of one thread
    assert width == analyzer_mod.pool_width(ratio, 1.0, 4)
    assert st["fetch_pool_width"] == width
    assert st["partition"]["pool"]["width"] == width
    assert st["partition"]["pool"]["probe_ratio"] == ratio
    assert f"foremastbrain:fetch_pool_width {float(width)}" in \
        exporter.render()


# -------------------------------------------------- compile-count gates
@pytest.mark.perf
def test_steady_state_cycles_trigger_zero_recompiles():
    """The regression gate for the rung/bucket design + pipeline: after
    warmup, mixed cycles launch ONLY already-compiled programs."""
    store, fixtures = _mixed_fleet()
    cfg = EngineConfig(pairwise_threshold=1e-4, lstm_epochs=2)
    eng = Analyzer(cfg, FixtureDataSource(fixtures), store)
    warm = 0
    eng.run_cycle(now=1000.0)
    while eng._lstm_trained_this_cycle > 0 and warm < 6:
        eng.run_cycle(now=1000.0)
        warm += 1
    eng.run_cycle(now=1000.0)  # one settle cycle past the last training
    with CompileCounter() as cc:
        eng.run_cycle(now=1000.0)
        eng.run_cycle(now=1000.0)
    assert cc.compiles == 0, (
        f"steady-state mixed cycles compiled {cc.compiles} fresh XLA "
        "program(s); a shape is leaking past the rung/bucket ladder"
    )


@pytest.mark.perf
def test_prewarm_grid_covers_matching_cycle_shapes():
    """After prewarm of a (rung 16, T 64/512) grid, a cycle whose fleet
    lands on those shapes compiles nothing new: the items the table
    builds for a rung pack as a cycle's do."""
    cfg = EngineConfig(pairwise_threshold=1e-4)
    prewarm(cfg, rungs=(16,), t_buckets=(64, 512))
    rng = np.random.default_rng(3)
    fixtures = {}
    store = JobStore()
    for i in range(5):  # rung 16 after padding; pair T bucket = 64
        cur, base = f"u/p{i}/c", f"u/p{i}/b"
        fixtures[cur] = _series(rng, 0.5, 60)
        fixtures[base] = _series(rng, 0.5, 60)
        store.create(Document(
            id=f"p{i}", app_name="a", namespace="n", strategy="canary",
            start_time=to_rfc3339(0.0), end_time=to_rfc3339(5_000_000.0),
            metrics={"error5xx": MetricQueries(current=cur, baseline=base)},
        ))
    for i in range(3):  # band concat 300+25 -> T bucket 1024, rung 16
        cur, hist = f"u/b{i}/c", f"u/b{i}/h"
        fixtures[cur] = _series(rng, 10.0, 25)
        fixtures[hist] = _series(rng, 10.0, 300)
        store.create(Document(
            id=f"b{i}", app_name="a", namespace="n", strategy="canary",
            start_time=to_rfc3339(0.0), end_time=to_rfc3339(5_000_000.0),
            metrics={"latency": MetricQueries(current=cur, historical=hist)},
        ))
    eng = Analyzer(cfg, FixtureDataSource(fixtures), store)
    with CompileCounter() as cc:
        out = eng.run_cycle(now=1000.0)
    assert len(out) == 8
    assert cc.compiles == 0, (
        f"cycle after prewarm compiled {cc.compiles} program(s): the "
        "prewarm grid drifted from the production packing"
    )


@pytest.mark.perf
@pytest.mark.slow
def test_compile_cache_restart_skips_compile_storm(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, a restarted process replays
    compiled programs from disk: run the same tiny cycle in two fresh
    interpreters and require the second to compile (almost) nothing fresh.
    The variable is SET here, never inherited: a driver that exports its
    own warm cache directory must not turn the cold first run warm."""
    cache = str(tmp_path / "xla-cache")
    script = r"""
import json, os, sys
import numpy as np
from foremast_tpu.engine import Analyzer, Document, EngineConfig, JobStore, MetricQueries
from foremast_tpu.engine.pipeline import CompileCounter, enable_compile_cache
from foremast_tpu.dataplane import FixtureDataSource
from foremast_tpu.utils.timeutils import to_rfc3339

assert enable_compile_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
rng = np.random.default_rng(0)
fixtures, store = {}, JobStore()
for i in range(4):
    cur, base = f"u/{i}/c", f"u/{i}/b"
    ts = (np.arange(30) * 60).tolist()
    fixtures[cur] = (ts, rng.normal(0.5, 0.05, 30).tolist())
    fixtures[base] = (ts, rng.normal(0.5, 0.05, 30).tolist())
    store.create(Document(id=f"j{i}", app_name="a", namespace="n",
                 strategy="canary", start_time=to_rfc3339(0.0),
                 end_time=to_rfc3339(5_000_000.0),
                 metrics={"error5xx": MetricQueries(current=cur, baseline=base)}))
eng = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store)
with CompileCounter() as cc:
    eng.run_cycle(now=1000.0)
print(json.dumps({"cache_misses": cc.cache_misses, "cache_hits": cc.cache_hits}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache)

    def run_once():
        out = subprocess.run(
            [sys.executable, "-c", script], env=env,
            capture_output=True, text=True, timeout=420, check=True,
        )
        return json.loads(out.stdout.strip().splitlines()[-1])

    first = run_once()
    second = run_once()
    # cold start: every program is fresh work (persistent-cache misses);
    # restart: programs replay from disk — misses (the compile storm)
    # collapse while hits take their place
    assert first["cache_misses"] > 0 and first["cache_hits"] == 0, first
    assert second["cache_hits"] > 0, second
    assert second["cache_misses"] < first["cache_misses"], (first, second)


def test_compile_cache_dir_precedence(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX's own reading of it stands and no
    code sets another directory (the two size/time gates are still
    zeroed). Unset: a source checkout uses its one fixed `.jax_cache/` —
    never a temp name, pid or timestamp."""
    import jax

    from foremast_tpu.engine import pipeline

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert pipeline.compile_cache_dir({}) == os.path.join(repo, ".jax_cache")
    assert pipeline.compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/srv/xla"}) == "/srv/xla"

    updates = []
    state = {"jax_compilation_cache_dir": "/srv/xla"}  # as JAX read it

    class FakeConfig:
        def update(self, name, value):
            updates.append((name, value))
            state[name] = value

        @property
        def jax_compilation_cache_dir(self):
            return state["jax_compilation_cache_dir"]

    monkeypatch.setattr(jax, "config", FakeConfig())
    got = pipeline.enable_compile_cache(
        {"JAX_COMPILATION_CACHE_DIR": "/srv/xla"})
    assert got == "/srv/xla"
    assert "jax_compilation_cache_dir" not in dict(updates)
    assert dict(updates) == {
        "jax_persistent_cache_min_compile_time_secs": 0.0,
        "jax_persistent_cache_min_entry_size_bytes": -1}

    updates.clear()
    state["jax_compilation_cache_dir"] = None  # variable unset at import
    got = pipeline.enable_compile_cache({})
    assert got == os.path.join(repo, ".jax_cache")
    assert dict(updates)["jax_compilation_cache_dir"] == got


def test_compile_counter_stops_counting_and_leaves_no_listener():
    """CompileCounter.__exit__ used a private unregister call that jax
    0.9.0 no longer has, under a blanket except: two listeners leaked per
    block and a counter kept counting after its block (every compile
    count in prewarm and the zero-recompile gate was suspect)."""
    import jax
    import jax.numpy as jnp
    from jax._src import monitoring

    def listeners():
        return (len(monitoring.get_event_duration_listeners()),
                len(monitoring.get_event_listeners()))

    before = listeners()
    with CompileCounter() as cc:
        assert listeners() == (before[0] + 1, before[1] + 1)
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    inside = cc.compiles
    assert inside >= 1
    assert listeners() == before
    # a fresh program compiled AFTER the block must not reach the counter
    jax.jit(lambda x: x * 5 - 2)(jnp.arange(11)).block_until_ready()
    assert cc.compiles == inside


# ------------------------------------------------------------ prewarm CLI
def test_prewarm_cli_prints_grid_summary(capsys, monkeypatch, tmp_path):
    from foremast_tpu import cli

    # keep the checkout's .jax_cache out of the pytest process: with the
    # variable set no code sets a directory, and JAX read nothing at import
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = cli.main(["prewarm", "--rungs", "16", "--buckets", "32",
                   "--families", "pair,hpa"])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["families"] == ["pair", "hpa"]
    assert rec["rungs"] == [16]
    assert rec["programs"] == 2
    assert rec["seconds"] >= 0
    # the record says where the programs were compiled
    assert rec["platform"] == "cpu" and rec["device_count"] == 8
    assert rec["device_kind"]
