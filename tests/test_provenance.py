"""Verdict provenance + incident flight recorder (ISSUE 6).

Per-(job, cycle) attribution: every verdict path the degraded-mode layer
can take (scored, memo-hit, stale-served, shed-carryover, quarantined,
watchdog-failover, blast-radius) leaves a record answering the per-job
"why", served at /jobs/<id>/explain and rendered by `foremast-tpu
explain`. The A/B identity tests pin that recording only OBSERVES the
cycle — verdicts byte-identical with PROVENANCE off. The flight recorder
half: structured event ring, auto-dump on the transition into
OVERLOADED/STALLED, dump on shutdown, /debug/flight.
"""
from __future__ import annotations

import json
import os

import numpy as np

from foremast_tpu.dataplane import FixtureDataSource, VerdictExporter
from foremast_tpu.engine import (
    Analyzer,
    Document,
    EngineConfig,
    JobStore,
    MetricQueries,
)
from foremast_tpu.engine import jobs as J
from foremast_tpu.engine import provenance as prov
from foremast_tpu.engine.flightrec import (
    EVENT_HEALTH_TRANSITION,
    EVENT_SHED,
    EVENT_STALE_SERVE,
    FlightRecorder,
)
from foremast_tpu.engine.health import HealthMonitor
from foremast_tpu.service.api import ForemastService
from foremast_tpu.utils.timeutils import to_rfc3339

STEP = 60
SEED = 20260803


def _series(rng, level, n):
    ts = np.arange(n) * STEP
    vals = np.clip(rng.normal(level, level * 0.1 + 0.01, n), 0, None)
    return ts.tolist(), vals.tolist()


def _mk_job(store, fixtures, job_id, *, bad=False, continuous=False,
            end_time=10_000_000.0, rng=None):
    rng = rng or np.random.default_rng(SEED)
    cur = f"http://prom:9090/{job_id}/cur"
    base = f"http://prom:9090/{job_id}/base"
    fixtures[cur] = _series(rng, 5.0 if bad else 0.5, 30)
    fixtures[base] = _series(rng, 0.5, 30)
    store.create(Document(
        id=job_id, app_name=f"app-{job_id}", namespace="prov",
        strategy="continuous" if continuous else "canary",
        start_time=to_rfc3339(0.0),
        end_time="" if continuous else to_rfc3339(end_time),
        metrics={"error5xx": MetricQueries(current=cur, baseline=base)},
    ))


def _analyzer(fixtures, store, **cfg):
    cfg.setdefault("max_stuck_seconds", 1e9)
    return Analyzer(EngineConfig(**cfg), FixtureDataSource(fixtures), store,
                    VerdictExporter())


class FailingSource:
    def __init__(self, fixtures):
        self.inner = FixtureDataSource(fixtures)
        self.failed = False

    def fetch(self, url):
        if self.failed:
            from foremast_tpu.dataplane.fetch import FetchError

            raise FetchError(f"blackout: {url}")
        return self.inner.fetch(url)


# ------------------------------------------------------------ verdict paths

def test_scored_path_records_families_and_fetch():
    fixtures, store = {}, JobStore()
    an = _analyzer(fixtures, store)
    _mk_job(store, fixtures, "bad-canary", bad=True, end_time=5000.0)
    out = an.run_cycle(worker="w", now=1000.0)
    assert out["bad-canary"] == J.COMPLETED_UNHEALTH

    rec = an.provenance.get("bad-canary")
    assert rec["path"] == prov.PATH_SCORED
    assert rec["status"] == J.COMPLETED_UNHEALTH
    assert rec["cycle"]["cycle_id"] == "w-c1"
    assert rec["cycle"]["jobs"] == 1
    assert rec["cycle"]["device_launches"] >= 1
    assert set(rec["cycle"]["stage_seconds"]) == {
        "preprocess", "dispatch", "collect", "fold"}
    fams = {f["family"] for f in rec["families"]}
    assert "pair" in fams
    pair = next(f for f in rec["families"] if f["family"] == "pair")
    assert pair["unhealthy"] is True
    assert pair["alpha"] == an.config.pairwise_threshold
    assert rec["fetch"]["fetches"] == 2
    assert rec["fetch"]["points"] > 0
    # terminal Documents carry the attribution into the archive field
    doc = store.get("bad-canary")
    attached = json.loads(doc.processing_content)
    assert attached["path"] == prov.PATH_SCORED
    assert attached["cycle_id"] == "w-c1"


def test_memo_hit_path_on_unchanged_second_cycle():
    fixtures, store = {}, JobStore()
    an = _analyzer(fixtures, store, score_memo=True)
    _mk_job(store, fixtures, "watch", continuous=True)
    an.run_cycle(worker="w", now=1000.0)
    assert an.provenance.get("watch")["path"] == prov.PATH_SCORED
    an.run_cycle(worker="w", now=1010.0)
    rec = an.provenance.get("watch")
    assert rec["path"] == prov.PATH_MEMO_HIT
    assert "from memo" in rec["detail"]
    assert rec["cycle"]["cycle_id"] == "w-c2"
    # the reused scores are still listed for the operator
    assert any(f["family"] == "pair" for f in rec["families"])


def test_stale_served_path_with_age_detail():
    fixtures, store = {}, JobStore()
    src = FailingSource(fixtures)
    an = Analyzer(EngineConfig(max_stuck_seconds=1e9), src, store,
                  VerdictExporter())
    _mk_job(store, fixtures, "canary", end_time=1140.0)
    an.run_cycle(worker="w", now=1000.0)  # warm on fresh data
    src.failed = True
    out = an.run_cycle(worker="w", now=1010.0)
    assert out["canary"] == J.INITIAL
    rec = an.provenance.get("canary")
    assert rec["path"] == prov.PATH_STALE_SERVED
    assert rec["detail"] == "age 10s"
    assert "stale verdict" in rec["reason"]
    # the blackout also left a flight-recorder event naming the job
    assert any(e["type"] == EVENT_STALE_SERVE
               and e["detail"]["job_id"] == "canary"
               for e in an.flight.snapshot())
    # endTime mid-blackout: completes on the stale verdict, provenance
    # follows it into the archived Document
    out = an.run_cycle(worker="w", now=1140.0)
    assert out["canary"] == J.COMPLETED_HEALTH
    rec = an.provenance.get("canary")
    assert rec["path"] == prov.PATH_STALE_SERVED
    assert rec["status"] == J.COMPLETED_HEALTH
    attached = json.loads(store.get("canary").processing_content)
    assert attached["path"] == prov.PATH_STALE_SERVED


def test_shed_carryover_path_with_streak():
    fixtures, store = {}, JobStore()
    an = _analyzer(fixtures, store, cycle_deadline_seconds=1e-9)
    _mk_job(store, fixtures, "watch1", continuous=True)
    _mk_job(store, fixtures, "watch2", continuous=True)
    an.run_cycle(worker="w", now=1000.0)
    rec = an.provenance.get("watch2")  # the tail beyond the floor
    assert rec["path"] == prov.PATH_SHED_CARRYOVER
    assert rec["detail"] == "streak 1"
    # the guaranteed-floor monitor actually scored
    assert an.provenance.get("watch1")["path"] == prov.PATH_SCORED
    assert any(e["type"] == EVENT_SHED and e["detail"]["count"] == 1
               and "watch2" in e["detail"]["jobs"]
               for e in an.flight.snapshot())


def test_quarantined_and_blast_radius_paths():
    fixtures, store = {}, JobStore()
    an = _analyzer(fixtures, store, quarantine_after=1)
    _mk_job(store, fixtures, "poison", continuous=True)

    def boom(group, T):
        raise RuntimeError("poisoned")

    # the stream's launch fails, and the per-job retry's launch after it
    an._launch_pairs = boom
    an.run_cycle(worker="w", now=1000.0)  # fails -> parked (after=1)
    rec = an.provenance.get("poison")
    assert rec["path"] == prov.PATH_BLAST_RADIUS
    assert "poisoned" in rec["reason"]
    an.run_cycle(worker="w", now=1010.0)  # parked: quarantine gate
    rec = an.provenance.get("poison")
    assert rec["path"] == prov.PATH_QUARANTINED
    assert "re-admission" in rec["detail"]


# --------------------------------------------------------- identity (A/B)

def test_verdicts_byte_identical_with_provenance_off():
    """PROVENANCE only observes: outcomes, reasons and anomaly payloads
    are byte-identical across the on/off A/B — including the memo-hit
    second cycle and a stale-served blackout cycle."""
    def build(enabled):
        rng = np.random.default_rng(SEED)
        fixtures, store = {}, JobStore()
        src = FailingSource(fixtures)
        an = Analyzer(EngineConfig(max_stuck_seconds=1e9,
                                   provenance=enabled),
                      src, store, VerdictExporter())
        _mk_job(store, fixtures, "bad-canary", bad=True, rng=rng,
                end_time=5000.0)
        _mk_job(store, fixtures, "ok-canary", rng=rng, end_time=5000.0)
        for i in range(3):
            _mk_job(store, fixtures, f"watch-{i}", continuous=True, rng=rng)
        outs = [an.run_cycle(worker="w", now=1000.0)]
        outs.append(an.run_cycle(worker="w", now=1010.0))  # memo cycle
        src.failed = True
        outs.append(an.run_cycle(worker="w", now=1020.0))  # stale cycle
        verdicts = {
            jid: (d.status, d.reason, sorted(d.anomaly.items()))
            for jid, d in ((j, store.get(j)) for j in
                           ["bad-canary", "ok-canary", "watch-0",
                            "watch-1", "watch-2"])
        }
        return outs, verdicts, an

    outs_on, verdicts_on, an_on = build(True)
    outs_off, verdicts_off, an_off = build(False)
    assert outs_on == outs_off
    assert verdicts_on == verdicts_off
    assert an_on.provenance.records_total > 0
    assert an_off.provenance.records_total == 0
    assert an_off.provenance.get("bad-canary") is None


def test_bench_provenance_ab_identity_small():
    """The bench A/B's identity claim on a miniature mixed fleet (the
    1500-job figure is `BENCH_CYCLE_PROVENANCE=1 python -m
    foremast_tpu.bench_cycle`)."""
    from foremast_tpu.bench_cycle import run

    on = run(n_jobs=40, cycles=2, mix=True, provenance=True)
    off = run(n_jobs=40, cycles=2, mix=True, provenance=False)
    assert on["verdict_digest"] == off["verdict_digest"]


# ------------------------------------------------- explain API + CLI + ring

def _served(analyzer, store):
    svc = ForemastService(store, exporter=analyzer.exporter,
                          analyzer=analyzer)
    return svc


def test_explain_endpoint_and_404():
    fixtures, store = {}, JobStore()
    an = _analyzer(fixtures, store)
    _mk_job(store, fixtures, "bad-canary", bad=True, end_time=5000.0)
    an.run_cycle(worker="w", now=1000.0)
    svc = _served(an, store)
    status, payload = svc.explain("bad-canary")
    assert status == 200
    assert payload["provenance"]["path"] == prov.PATH_SCORED
    assert payload["job"]["status"] == "anomaly"
    assert payload["provenance_enabled"] is True
    status, payload = svc.explain("nope")
    assert status == 404


def test_explain_falls_back_to_archived_document(tmp_path):
    from foremast_tpu.engine.archive import FileArchive

    fixtures = {}
    store = JobStore(archive=FileArchive(str(tmp_path / "arch.jsonl")))
    an = _analyzer(fixtures, store)
    _mk_job(store, fixtures, "bad-canary", bad=True, end_time=5000.0)
    an.run_cycle(worker="w", now=1000.0)
    # terminal + retention passed: pruned from RAM, record lives on in
    # the archive; evict the in-RAM provenance ring too
    import time as _time

    assert store.gc(max_age_seconds=0.0, now=_time.time() + 3600.0) == 1
    an.provenance._latest.clear()
    svc = _served(an, store)
    status, payload = svc.explain("bad-canary")
    assert status == 200
    assert payload["provenance"]["from_archive"] is True
    assert payload["provenance"]["path"] == prov.PATH_SCORED


def test_explain_falls_back_to_live_document_summary():
    """Recorder LRU eviction (fleet > max_jobs, or a restart) must not
    lose the "why" while the terminal Document is still in RAM: explain()
    reads the attached processing_content summary off the live doc."""
    fixtures, store = {}, JobStore()
    an = _analyzer(fixtures, store)
    _mk_job(store, fixtures, "bad-canary", bad=True, end_time=5000.0)
    an.run_cycle(worker="w", now=1000.0)
    an.provenance._latest.clear()  # simulate LRU eviction
    svc = _served(an, store)
    status, payload = svc.explain("bad-canary")
    assert status == 200
    assert payload["provenance"]["from_document"] is True
    assert payload["provenance"]["path"] == prov.PATH_SCORED
    assert payload["provenance"]["cycle_id"] == "w-c1"
    assert payload["job"]["status"] == "anomaly"


def test_explain_cli_renders_decision_chain(capsys):
    from foremast_tpu import cli
    from foremast_tpu.service.api import serve_background

    fixtures, store = {}, JobStore()
    an = _analyzer(fixtures, store)
    _mk_job(store, fixtures, "bad-canary", bad=True, end_time=5000.0)
    an.run_cycle(worker="w", now=1000.0)
    server = serve_background(_served(an, store), host="127.0.0.1", port=0)
    try:
        port = server.server_address[1]
        rc = cli.main(["explain", "bad-canary",
                       "--endpoint", f"http://127.0.0.1:{port}"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "verdict path: scored" in out
        assert "pair error5xx" in out
        assert "UNHEALTHY" in out
        assert "cycle: w-c1" in out
        # unknown job: clean one-line diagnosis, exit 1
        rc = cli.main(["explain", "missing",
                       "--endpoint", f"http://127.0.0.1:{port}"])
        assert rc == 1
        assert "not found" in capsys.readouterr().err
    finally:
        server.shutdown()


def test_explain_cli_names_each_acceptance_path(capsys):
    """ISSUE 6 acceptance, end-to-end over the wire: `foremast-tpu
    explain <job>` names the correct provenance path for a scored, a
    memo-hit, a stale-served, and a shed-carryover job."""
    from foremast_tpu import cli
    from foremast_tpu.service.api import serve_background

    def explain(server, job):
        port = server.server_address[1]
        rc = cli.main(["explain", job,
                       "--endpoint", f"http://127.0.0.1:{port}"])
        assert rc == 0
        return capsys.readouterr().out

    # scenario A (one analyzer): scored + memo-hit + shed-carryover.
    # cycle 1 has no deadline (everything scores); cycle 2 sheds the
    # monitor tail while the floor monitor memo-hits its unchanged rows.
    fixtures, store = {}, JobStore()
    an = _analyzer(fixtures, store)
    _mk_job(store, fixtures, "canary", bad=True, end_time=5000.0)
    _mk_job(store, fixtures, "watch-floor", continuous=True)
    _mk_job(store, fixtures, "watch-tail", continuous=True)
    an.run_cycle(worker="w", now=1000.0)
    an.config = EngineConfig(max_stuck_seconds=1e9,
                             cycle_deadline_seconds=1e-9)
    an.run_cycle(worker="w", now=1010.0)
    server = serve_background(_served(an, store), host="127.0.0.1", port=0)
    try:
        assert "verdict path: scored" in explain(server, "canary")
        assert "verdict path: memo-hit" in explain(server, "watch-floor")
        out = explain(server, "watch-tail")
        assert "verdict path: shed-carryover" in out
        assert "streak 1" in out
    finally:
        server.shutdown()

    # scenario B: stale-served during a source blackout
    fixtures, store = {}, JobStore()
    src = FailingSource(fixtures)
    an = Analyzer(EngineConfig(max_stuck_seconds=1e9), src, store,
                  VerdictExporter())
    _mk_job(store, fixtures, "watch", continuous=True)
    an.run_cycle(worker="w", now=1000.0)
    src.failed = True
    an.run_cycle(worker="w", now=1010.0)
    server = serve_background(_served(an, store), host="127.0.0.1", port=0)
    try:
        out = explain(server, "watch")
        assert "verdict path: stale-served" in out
        assert "age 10s" in out
    finally:
        server.shutdown()


def test_provenance_ring_and_index_bounded():
    rec = prov.ProvenanceRecorder(max_jobs=8, ring_size=16)
    rec.begin_cycle("c1")
    for i in range(100):
        rec.record(f"j{i}", prov.PATH_SCORED, status=J.INITIAL)
    assert len(rec._latest) == 8
    assert len(rec.recent(limit=100)) == 16
    assert rec.get("j99")["path"] == prov.PATH_SCORED
    assert rec.get("j0") is None  # evicted


def test_status_build_section():
    fixtures, store = {}, JobStore()
    an = _analyzer(fixtures, store)
    _mk_job(store, fixtures, "watch", continuous=True)
    an.run_cycle(worker="w", now=1000.0)
    svc = _served(an, store)
    status, payload = svc.status_summary()
    build = payload["build"]
    assert build["version"]
    assert build["uptime_s"] >= 0
    assert build["cycle_id"] == "w-c1"
    assert payload["cycle"]["cycle_id"] == "w-c1"


# ----------------------------------------------------------- flight recorder

def test_flight_ring_bounded_and_endpoint():
    fr = FlightRecorder(max_events=32)
    for i in range(100):
        fr.record_event(EVENT_SHED, count=i)
    evs = fr.snapshot(limit=1000)
    assert len(evs) == 32
    assert evs[-1]["detail"]["count"] == 99
    assert fr.events_total == 100

    fixtures, store = {}, JobStore()
    an = _analyzer(fixtures, store, cycle_deadline_seconds=1e-9)
    _mk_job(store, fixtures, "watch1", continuous=True)
    _mk_job(store, fixtures, "watch2", continuous=True)
    an.run_cycle(worker="w", now=1000.0)
    svc = _served(an, store)
    status, payload = svc.debug_flight()
    assert status == 200
    assert any(e["type"] == EVENT_SHED for e in payload["events"])


def test_auto_dump_on_stalled_transition(tmp_path):
    """Chaos-soak acceptance shape, unit-sized: a health transition into
    STALLED writes a self-contained dump naming the transition."""
    clock = {"now": 1000.0}
    recorder = FlightRecorder(dump_dir=str(tmp_path),
                              min_dump_interval_s=0.0)
    hm = HealthMonitor(cycle_seconds=1.0, stall_grace_seconds=5.0,
                       clock=lambda: clock["now"], recorder=recorder)
    hm.begin_cycle()
    hm.end_cycle()
    assert hm.state()[0] == "ok"
    clock["now"] += 10_000.0  # worker wedged: liveness window blown
    state, detail = hm.state()
    assert state == "stalled"
    assert recorder.dumps_total == 1
    dump = json.load(open(recorder.last_dump_path))
    assert dump["reason"] == "health:stalled"
    transitions = [e for e in dump["events"]
                   if e["type"] == EVENT_HEALTH_TRANSITION]
    assert transitions and transitions[-1]["detail"]["new"] == "stalled"
    assert transitions[-1]["detail"]["old"] == "ok"
    assert dump["health"]["state"] == "stalled"
    # edge-triggered: another read does not dump again
    clock["now"] += 1.0
    assert hm.state()[0] == "stalled"
    assert recorder.dumps_total == 1


def test_first_incident_dump_not_rate_limited(tmp_path):
    """A pod born broken must still leave its first incident artifact: the
    rate limiter only applies between dumps, never to the first one (a 0.0
    'last dump' sentinel compared against time.monotonic() — boot-relative
    on Linux — would suppress it for min_dump_interval_s after VM boot)."""
    recorder = FlightRecorder(dump_dir=str(tmp_path),
                              min_dump_interval_s=1e12)
    recorder.on_health_transition("ok", "stalled", {"why": "born broken"})
    assert recorder.dumps_total == 1
    # the interval does apply from the second transition on
    recorder.on_health_transition("ok", "stalled", {"why": "again"})
    assert recorder.dumps_total == 1


def test_overloaded_transition_dumps_with_provenance_and_knobs(tmp_path):
    fixtures, store = {}, JobStore()
    an = _analyzer(fixtures, store, cycle_deadline_seconds=1e-9,
                   flight_dump_dir=str(tmp_path))
    an.flight.min_dump_interval_s = 0.0
    _mk_job(store, fixtures, "watch1", continuous=True)
    _mk_job(store, fixtures, "watch2", continuous=True)
    an.run_cycle(worker="w", now=1000.0)  # sheds watch2 -> OVERLOADED
    assert an.health.state()[0] == "overloaded"
    assert an.flight.dumps_total >= 1
    dump = json.load(open(an.flight.last_dump_path))
    assert dump["reason"] == "health:overloaded"
    # provenance for the jobs the shed event names rode along
    assert "watch2" in dump["provenance"]["affected_jobs"]
    assert (dump["provenance"]["affected_jobs"]["watch2"]["path"]
            == prov.PATH_SHED_CARRYOVER)
    assert dump["knobs"]["engine"]["cycle_deadline_seconds"] == 1e-9
    assert "LOG_LEVEL" in dump["knobs"]["env"]
    # dump files prune to the newest MAX_DUMPS
    from foremast_tpu.engine import flightrec as fr

    for i in range(fr.MAX_DUMPS + 3):
        an.flight.dump(reason=f"test-{i}")
    files = [f for f in os.listdir(tmp_path)
             if f.startswith("foremast-flight-")]
    assert len(files) <= fr.MAX_DUMPS


def test_runtime_shutdown_dumps_flight_snapshot(tmp_path):
    from foremast_tpu.runtime import Runtime

    rt = Runtime(config=EngineConfig(flight_dump_dir=str(tmp_path)),
                 data_source=FixtureDataSource({}), cache=False)
    rt.stop()
    files = [f for f in os.listdir(tmp_path)
             if f.startswith("foremast-flight-") and "shutdown" in f]
    assert len(files) == 1
    dump = json.load(open(tmp_path / files[0]))
    assert dump["reason"] == "shutdown"


# ------------------------------------------------------------- histograms

def test_exporter_histogram_exposition():
    ex = VerdictExporter()
    for v in (0.003, 0.003, 0.2, 7.0):
        ex.record_histogram("foremastbrain:test_seconds", {"stage": "x"}, v,
                            help="test histogram")
    text = ex.render()
    assert "# TYPE foremastbrain:test_seconds histogram" in text
    assert ('foremastbrain:test_seconds_bucket{stage="x",le="0.005"} 2'
            in text)
    assert ('foremastbrain:test_seconds_bucket{stage="x",le="0.25"} 3'
            in text)
    assert ('foremastbrain:test_seconds_bucket{stage="x",le="+Inf"} 4'
            in text)
    assert 'foremastbrain:test_seconds_count{stage="x"} 4' in text
    assert 'foremastbrain:test_seconds_sum{stage="x"} 7.206' in text


def test_cycle_and_fetch_histograms_on_metrics():
    fixtures, store = {}, JobStore()
    an = _analyzer(fixtures, store)
    _mk_job(store, fixtures, "watch", continuous=True)
    an.run_cycle(worker="w", now=1000.0)
    svc = _served(an, store)
    _, text = svc.metrics()
    for name in ("foremastbrain:cycle_seconds",
                 "foremastbrain:fetch_seconds",
                 "foremastbrain:cycle_stage_duration_seconds"):
        assert f"{name}_bucket" in text, name
        assert f"{name}_count" in text, name
