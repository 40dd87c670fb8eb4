"""Degraded-mode operation layer (ISSUE 4): cycle deadline budget + load
shedding, stale-verdict serving, poison-job quarantine, the hung-launch
watchdog, the health state machine, /readyz, operator remediation
suppression, and graceful-shutdown lease handoff.

Fast (tier-1) coverage; the chaos-marked blackout acceptance soak lives in
tests/test_chaos_soak.py.
"""
import time

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
from foremast_tpu.engine import jobs as J
from foremast_tpu.engine.archive import FileArchive
from foremast_tpu.engine.health import (
    STATE_DEGRADED,
    STATE_OK,
    STATE_OVERLOADED,
    STATE_STALLED,
    HealthMonitor,
)
from foremast_tpu.service.api import ForemastService
from foremast_tpu.utils.timeutils import to_rfc3339

STEP = 60
SEED = 20260804


def _series(rng, level, n):
    ts = np.arange(n) * STEP
    vals = np.clip(rng.normal(level, level * 0.1 + 0.01, n), 0, None)
    return ts.tolist(), vals.tolist()


def _mk_job(store, fixtures, job_id, *, bad=False, continuous=False,
            end_time=10_000_000.0, rng=None):
    rng = rng or np.random.default_rng(SEED)
    cur = f"http://prom:9090/{job_id}/cur"
    base = f"http://prom:9090/{job_id}/base"
    hist = f"http://prom:9090/{job_id}/hist"
    fixtures[cur] = _series(rng, 5.0 if bad else 0.5, 30)
    fixtures[base] = _series(rng, 0.5, 30)
    fixtures[hist] = _series(rng, 0.5, 600)
    store.create(Document(
        id=job_id, app_name=f"app-{job_id}", namespace="deg",
        strategy="continuous" if continuous else "canary",
        start_time=to_rfc3339(0.0),
        end_time="" if continuous else to_rfc3339(end_time),
        metrics={"error5xx": MetricQueries(current=cur, baseline=base,
                                           historical=hist)},
    ))


class CountingSource:
    """FixtureDataSource wrapper counting fetches (quarantine/shed must
    prove jobs were parked WITHOUT touching the network)."""

    def __init__(self, fixtures):
        self.inner = FixtureDataSource(fixtures)
        self.fetches = 0

    def fetch(self, url):
        self.fetches += 1
        return self.inner.fetch(url)


# ------------------------------------------------------- load shedding
def test_deadline_sheds_low_priority_and_carries_over():
    """An expired cycle budget sheds the steady-state monitor TAIL
    (carry-over to INITIAL, never COMPLETED_UNKNOWN) while the canary —
    exempt by class — and the first monitor — the guaranteed-progress
    floor — still score."""
    rng = np.random.default_rng(SEED)
    fixtures = {}
    store = JobStore()
    src = CountingSource(fixtures)
    an = Analyzer(EngineConfig(cycle_deadline_seconds=1e-9,
                               max_stuck_seconds=1e9), src, store)
    _mk_job(store, fixtures, "canary", rng=rng)
    _mk_job(store, fixtures, "watch1", continuous=True, rng=rng)
    _mk_job(store, fixtures, "watch2", continuous=True, rng=rng)

    outcomes = an.run_cycle(worker="w", now=100.0)
    assert outcomes["canary"] == J.INITIAL  # scored, healthy, requeued
    # the floor is the first SHEDDABLE job, not the (exempt) canary:
    # monitors keep making progress even under deployment churn
    assert outcomes["watch1"] == J.INITIAL  # guaranteed: scored
    assert "shed" not in store.get("watch1").reason
    assert outcomes["watch2"] == J.INITIAL  # shed, carried over
    assert "shed" in store.get("watch2").reason
    assert an.jobs_shed_total == 1
    assert an._shed_streak == {"watch2": 1}
    # shed without touching the network: canary and the guaranteed watch1
    # fetched their 3 URLs each, nothing else
    assert src.fetches == 6
    # health: shedding == OVERLOADED
    assert an.health.state()[0] == STATE_OVERLOADED


def test_shed_job_completes_with_identical_verdict_next_cycle():
    """Shed-and-carry-over determinism (the PR 2/3 identity pattern): a
    job shed under the deadline produces a byte-identical verdict on the
    next cycle to the one it would have produced unshed."""
    def build(deadline):
        rng = np.random.default_rng(SEED)
        fixtures = {}
        store = JobStore()
        an = Analyzer(EngineConfig(cycle_deadline_seconds=deadline,
                                   max_stuck_seconds=1e9),
                      FixtureDataSource(fixtures), store)
        # two monitor-class jobs (only the sheddable class): healthy
        # first in claim order, the BAD monitor second (the shed tail)
        _mk_job(store, fixtures, "ok-watch", continuous=True, rng=rng)
        _mk_job(store, fixtures, "bad-watch", bad=True, continuous=True,
                rng=rng)
        return an, store

    # reference: no deadline, both score in cycle 1
    ref_an, ref_store = build(0.0)
    ref_an.run_cycle(worker="w", now=100.0)
    ref = ref_store.get("bad-watch")
    assert ref.status == J.COMPLETED_UNHEALTH

    # shed run: cycle 1 sheds bad-watch (ok-watch is the guaranteed
    # head); its shed streak promotes it to the head of cycle 2, where it
    # scores despite the still-expired budget
    an, store = build(1e-9)
    an.run_cycle(worker="w", now=100.0)
    doc = store.get("bad-watch")
    assert doc.status == J.INITIAL and "shed" in doc.reason
    an.run_cycle(worker="w", now=110.0)
    doc = store.get("bad-watch")
    assert doc.status == J.COMPLETED_UNHEALTH
    # byte-identical verdict: same reason string, same anomaly payload
    assert doc.reason == ref.reason
    assert doc.anomaly == ref.anomaly


# -------------------------------------------------- stale-verdict serving
class FailingSource:
    """Healthy until failed=True, then every fetch raises FetchError."""

    def __init__(self, fixtures):
        self.inner = FixtureDataSource(fixtures)
        self.failed = False

    def fetch(self, url):
        if self.failed:
            from foremast_tpu.dataplane.fetch import FetchError

            raise FetchError(f"blackout: {url}")
        return self.inner.fetch(url)


def test_stale_verdict_served_mid_window_and_at_end():
    """During a source blackout a warm canary re-serves its last fresh
    verdict: requeue (reason stamped with the staleness age) mid-window,
    COMPLETED_HEALTH — never COMPLETED_UNKNOWN — at endTime."""
    rng = np.random.default_rng(SEED)
    fixtures = {}
    store = JobStore()
    src = FailingSource(fixtures)
    an = Analyzer(EngineConfig(max_stuck_seconds=1e9), src, store)
    _mk_job(store, fixtures, "canary", end_time=140.0, rng=rng)
    _mk_job(store, fixtures, "watch", continuous=True, rng=rng)

    an.run_cycle(worker="w", now=100.0)  # warm: judged on fresh data
    src.failed = True
    out = an.run_cycle(worker="w", now=110.0)
    assert out["canary"] == J.INITIAL
    assert "stale verdict" in store.get("canary").reason
    assert "age 10s" in store.get("canary").reason
    assert "stale verdict" in store.get("watch").reason
    out = an.run_cycle(worker="w", now=140.0)  # endTime mid-blackout
    assert out["canary"] == J.COMPLETED_HEALTH
    assert store.get("canary").status == J.COMPLETED_HEALTH
    assert an.stale_verdicts_served_total >= 3
    assert an.health.state()[0] == STATE_DEGRADED


def test_stale_serving_bounded_by_max_stale_s():
    """Past MAX_STALE_S the job is COLD again: pre-degraded-mode behavior
    returns (fetch failure -> PREPROCESS_FAILED for a canary)."""
    rng = np.random.default_rng(SEED)
    fixtures = {}
    store = JobStore()
    src = FailingSource(fixtures)
    an = Analyzer(EngineConfig(max_stale_seconds=50.0,
                               max_stuck_seconds=1e9), src, store)
    _mk_job(store, fixtures, "canary", end_time=10_000.0, rng=rng)
    an.run_cycle(worker="w", now=100.0)
    src.failed = True
    out = an.run_cycle(worker="w", now=200.0)  # age 100 > 50: cold
    assert out.get("canary") != J.COMPLETED_HEALTH
    assert store.get("canary").status == J.PREPROCESS_FAILED
    assert an.stale_verdicts_served_total == 0


def test_empty_data_at_end_time_serves_stale_instead_of_unknown():
    """The COMPLETED_UNKNOWN flip: fetch succeeds but carries no current
    data at endTime. Warm job -> COMPLETED_HEALTH on the stale verdict."""
    rng = np.random.default_rng(SEED)
    fixtures = {}
    store = JobStore()
    an = Analyzer(EngineConfig(max_stuck_seconds=1e9),
                  FixtureDataSource(fixtures), store)
    _mk_job(store, fixtures, "canary", end_time=140.0, rng=rng)
    an.run_cycle(worker="w", now=100.0)
    # the source goes blind (empty series), not dark
    fixtures["http://prom:9090/canary/cur"] = ([], [])
    out = an.run_cycle(worker="w", now=140.0)
    assert out["canary"] == J.COMPLETED_HEALTH
    assert "stale verdict" in store.get("canary").reason

    # control: the same sequence with stale serving off flips UNKNOWN
    fixtures2 = {}
    store2 = JobStore()
    an2 = Analyzer(EngineConfig(max_stale_seconds=0.0,
                                max_stuck_seconds=1e9),
                   FixtureDataSource(fixtures2), store2)
    _mk_job(store2, fixtures2, "canary", end_time=140.0,
            rng=np.random.default_rng(SEED))
    an2.run_cycle(worker="w", now=100.0)
    fixtures2["http://prom:9090/canary/cur"] = ([], [])
    out = an2.run_cycle(worker="w", now=140.0)
    assert out["canary"] == J.COMPLETED_UNKNOWN


def test_unhealthy_is_never_stale_served():
    """Fail-fast wins: an anomaly seen on fresh data completes terminally
    the same cycle — warm state must not resurrect or soften it."""
    rng = np.random.default_rng(SEED)
    fixtures = {}
    store = JobStore()
    src = FailingSource(fixtures)
    an = Analyzer(EngineConfig(max_stuck_seconds=1e9), src, store)
    _mk_job(store, fixtures, "bad", bad=True, end_time=10_000.0, rng=rng)
    out = an.run_cycle(worker="w", now=100.0)
    assert out["bad"] == J.COMPLETED_UNHEALTH
    assert "bad" not in an._stale_state  # terminal: warm state dropped


# --------------------------------------------------- poison-job quarantine
def test_poison_job_quarantined_with_exponential_readmission():
    rng = np.random.default_rng(SEED)
    fixtures = {}
    store = JobStore()
    src = CountingSource(fixtures)
    an = Analyzer(EngineConfig(quarantine_after=2, max_stuck_seconds=1e9),
                  src, store)
    _mk_job(store, fixtures, "poison", continuous=True, rng=rng)

    poisoned = {"on": True}
    orig = an._launch_pairs

    def launch(group, T):
        # the stream's launch fails, and the per-job retry's after it
        if poisoned["on"]:
            raise RuntimeError("poisoned job")
        return orig(group, T)

    an._launch_pairs = launch

    an.run_cycle(worker="w", now=100.0)   # failure 1
    assert an.quarantined_count(100.0) == 0
    an.run_cycle(worker="w", now=110.0)   # failure 2 -> parked 30s
    assert an.quarantined_count(110.0) == 1
    assert an.jobs_quarantined_total == 1
    assert store.get("poison").status == J.INITIAL

    fetches = src.fetches
    out = an.run_cycle(worker="w", now=120.0)  # parked: no fetch, no score
    assert out["poison"] == J.INITIAL
    assert "quarantined" in store.get("poison").reason
    assert src.fetches == fetches
    assert an.health.state()[0] == STATE_DEGRADED

    # re-admission probe fails -> re-parked IMMEDIATELY, backoff doubled
    an.run_cycle(worker="w", now=141.0)   # 30s elapsed: probe runs
    q = an._quarantine["poison"]
    assert an.jobs_quarantined_total == 2
    assert q[1] == pytest.approx(141.0 + 60.0)

    # healed probe clears the record entirely
    poisoned["on"] = False
    an.run_cycle(worker="w", now=202.0)
    assert "poison" not in an._quarantine
    assert an.quarantined_count(202.0) == 0


# ---------------------------------------------------- hung-launch watchdog
def test_watchdog_times_out_hung_collect_and_fails_over():
    rng = np.random.default_rng(SEED)
    fixtures = {}
    store = JobStore()
    an = Analyzer(EngineConfig(watchdog_seconds=0.05, max_stuck_seconds=1e9),
                  FixtureDataSource(fixtures), store)
    _mk_job(store, fixtures, "bad", bad=True, end_time=10_000.0, rng=rng)

    orig = an._collect_pairs
    calls = {"n": 0}

    def hung_collect(state):
        calls["n"] += 1
        if calls["n"] == 1:
            time.sleep(0.3)  # a stuck device materialization
        return orig(state)

    an._collect_pairs = hung_collect
    out = an.run_cycle(worker="w", now=100.0)
    # the bucket failed over to the sync per-job path and still verdicted
    assert out["bad"] == J.COMPLETED_UNHEALTH
    assert an.watchdog_fires_total == 1
    assert calls["n"] >= 2
    assert an.health.state()[0] == STATE_DEGRADED


def test_watchdog_wedged_device_skips_remaining_retries():
    """ONE sync-retry timeout marks the device wedged: the remaining
    per-job retries are skipped instead of serializing N x WATCHDOG_S of
    guaranteed timeouts into the cycle."""
    rng = np.random.default_rng(SEED)
    fixtures = {}
    store = JobStore()
    an = Analyzer(EngineConfig(watchdog_seconds=0.05, max_stuck_seconds=1e9),
                  FixtureDataSource(fixtures), store)
    _mk_job(store, fixtures, "j1", continuous=True, rng=rng)
    _mk_job(store, fixtures, "j2", continuous=True, rng=rng)

    orig = an._collect_pairs
    an._collect_pairs = lambda state: (time.sleep(0.2), orig(state))[1]
    t0 = time.monotonic()
    out = an.run_cycle(worker="w", now=100.0)
    elapsed = time.monotonic() - t0
    # one collect timeout + ONE retry timeout; job 2's retry was skipped
    assert an.watchdog_fires_total == 2
    assert out["j1"] == J.INITIAL and out["j2"] == J.INITIAL
    reasons = {store.get(j).reason for j in ("j1", "j2")}
    assert any("retry skipped" in r for r in reasons)
    # bounded: nowhere near N x (collect + retry) serialized timeouts
    assert elapsed < 2.0


# --------------------------------------------------- health state machine
def test_health_state_machine_transitions():
    t = {"now": 1000.0}
    h = HealthMonitor(cycle_seconds=10.0, clock=lambda: t["now"])
    # never cycled: OK (nothing claimed yet), not STALLED out of the gate
    assert h.state()[0] == STATE_OK
    h.begin_cycle()
    h.end_cycle()
    assert h.state()[0] == STATE_OK
    h.begin_cycle()
    h.end_cycle(stale_served=2)
    assert h.state()[0] == STATE_DEGRADED
    h.begin_cycle()
    h.end_cycle(shed=3, stale_served=1)
    # severity order: shedding outranks staleness
    assert h.state()[0] == STATE_OVERLOADED
    h.begin_cycle()
    h.end_cycle()
    assert h.state()[0] == STATE_OK  # one clean cycle: full recovery
    # open breaker -> DEGRADED even with clean cycles
    h.configure(breakers_fn=lambda: {"prom:9090": "open"})
    state, detail = h.state()
    assert state == STATE_DEGRADED and detail["open_breakers"] == ["prom:9090"]
    h.configure(breakers_fn=lambda: {"prom:9090": "closed"})
    assert h.state()[0] == STATE_OK
    # liveness: nothing completes inside the window -> STALLED
    h.begin_cycle()
    t["now"] += 31.0  # > max(3 * cycle_seconds, 30s grace)
    state, detail = h.state()
    assert state == STATE_STALLED
    assert detail["seconds_since_cycle"] == pytest.approx(31.0)
    h.end_cycle()
    assert h.state()[0] == STATE_OK


def test_health_stalled_between_cycles_when_worker_wedges():
    t = {"now": 0.0}
    h = HealthMonitor(cycle_seconds=5.0, clock=lambda: t["now"])
    h.begin_cycle()
    h.end_cycle()
    t["now"] += 29.0
    assert h.state()[0] == STATE_OK  # inside the 30s grace floor
    t["now"] += 5.0
    assert h.state()[0] == STATE_STALLED


def test_health_crash_looping_cycles_go_stalled():
    """A cycle that RAISES never stamps end_cycle, so a crash-looping
    engine (worker loop swallows and retries every cadence) ages into
    STALLED instead of reporting OK on zero completed verdicts. Before
    the FIRST completed cycle the stall window is stretched (cold-start
    compile storms legitimately run minutes), so the flag lands later
    but still lands."""
    t = {"now": 0.0}
    h = HealthMonitor(cycle_seconds=5.0, clock=lambda: t["now"])
    for _ in range(20):  # every cycle begins, none completes
        h.begin_cycle()
        t["now"] += 5.0
    # inside the first-cycle warmup grace: still OK (a cold pod's first
    # cycle is allowed to run long)
    assert h.state()[0] == STATE_OK
    t["now"] += h.FIRST_CYCLE_GRACE_MIN_S
    assert h.state()[0] == STATE_STALLED


def test_run_cycle_exception_does_not_stamp_health_ok():
    rng = np.random.default_rng(SEED)
    fixtures = {}
    store = JobStore()
    t = {"now": 1000.0}
    an = Analyzer(EngineConfig(max_stuck_seconds=1e9),
                  FixtureDataSource(fixtures), store)
    an.health._clock = lambda: t["now"]
    _mk_job(store, fixtures, "watch", continuous=True, rng=rng)
    an.run_cycle(worker="w", now=100.0)  # one good cycle
    assert an.health.state()[0] == STATE_OK

    def boom(*a, **kw):
        raise RuntimeError("store exploded")

    an.store.claim_open_jobs = boom
    for _ in range(10):
        t["now"] += 10.0
        with pytest.raises(RuntimeError):
            an.run_cycle(worker="w", now=100.0)
    # 100 virtual seconds of failed cycles: liveness reference never moved
    assert an.health.state()[0] == STATE_STALLED


# ------------------------------------------------------- /readyz + metrics
def test_readyz_and_status_and_metrics_surface_health():
    rng = np.random.default_rng(SEED)
    fixtures = {}
    store = JobStore()
    exporter = VerdictExporter()
    an = Analyzer(EngineConfig(max_stuck_seconds=1e9),
                  FixtureDataSource(fixtures), store, exporter)
    svc = ForemastService(store, exporter=exporter, analyzer=an)
    _mk_job(store, fixtures, "watch", continuous=True, rng=rng)

    an.run_cycle(worker="w", now=100.0)
    code, body = svc.readyz()
    assert code == 200 and body["state"] == "ok"
    code, status = svc.status_summary()
    assert status["health"]["state"] == "ok"
    assert "stale_verdicts_served" in status["cycle"]

    # degraded: still ready (200) but flagged
    an.health.end_cycle(stale_served=1)
    code, body = svc.readyz()
    assert code == 200 and body["state"] == "degraded"
    assert svc.status_summary()[1]["status"] == "degraded"

    # overloaded / stalled: NOT ready (503)
    an.health.end_cycle(shed=5)
    code, body = svc.readyz()
    assert code == 503 and body["state"] == "overloaded"

    code, text = svc.metrics()
    assert code == 200
    assert "foremastbrain:health_state" in text
    assert "foremastbrain:quarantined_jobs 0" in text
    assert "# TYPE foremastbrain:health_state gauge" in text


def test_readyz_without_analyzer_defaults_ok():
    svc = ForemastService(JobStore())
    code, body = svc.readyz()
    assert code == 200 and body["state"] == "ok"


# ------------------------------------------- operator remediation suppression
def test_operator_suppresses_remediation_while_brain_degraded():
    from foremast_tpu.operator.kube import FakeKube
    from foremast_tpu.operator.loop import OperatorLoop
    from foremast_tpu.operator.types import (
        PHASE_UNHEALTHY,
        DeploymentMonitor,
        MonitorSpec,
        MonitorStatus,
        RemediationAction,
    )

    class ScriptedAnalyst:
        def __init__(self):
            self.health = "degraded"

        def start_analyzing(self, request):
            return "job-1"

        def get_status(self, job_id):
            from foremast_tpu.operator.analyst import StatusResponse

            return StatusResponse(phase="Running")

        def get_health(self):
            return self.health

    analyst = ScriptedAnalyst()
    kube = FakeKube()
    kube.deployments[("default", "demo")] = {
        "metadata": {"name": "demo", "namespace": "default",
                     "labels": {"app": "demo"}},
        "spec": {"selector": {"matchLabels": {"app": "demo"}},
                 "template": {"spec": {"containers": []}}},
    }
    kube.upsert_monitor(DeploymentMonitor(
        name="demo", namespace="default",
        annotations={"deployment.foremast.ai/name": "demo"},
        spec=MonitorSpec(remediation=RemediationAction(option="AutoPause")),
        status=MonitorStatus(phase=PHASE_UNHEALTHY),
    ))
    loop = OperatorLoop(kube, analyst)  # probe defaults to analyst.get_health

    loop.tick()
    m = kube.get_monitor("default", "demo")
    assert not m.status.remediation_taken
    assert kube.patches == []
    assert any(e["reason"] == "RemediationSuppressed" for e in kube.events)

    # ticks keep suppressing (phase never advanced) until the brain heals
    # — but the event/counter fire once per HELD FLIP, not per tick
    loop.tick()
    assert loop.remediations_suppressed_total == 1
    assert sum(1 for e in kube.events
               if e["reason"] == "RemediationSuppressed") == 1
    analyst.health = "ok"
    loop.tick()
    m = kube.get_monitor("default", "demo")
    assert m.status.remediation_taken
    assert any(kind == "deployment" for kind, *_ in kube.patches)


def test_http_analyst_get_health_reads_503_states():
    """The 503 readiness states (overloaded/stalled) must reach an
    HTTP-deployed operator — they are exactly the states where
    suppression matters most, and must not be flattened to "ok" by the
    error path."""
    from foremast_tpu.operator.analyst import HttpAnalyst
    from foremast_tpu.service.api import serve_background

    store = JobStore()
    an = Analyzer(EngineConfig(max_stuck_seconds=1e9),
                  FixtureDataSource({}), store)
    svc = ForemastService(store, analyzer=an)
    server = serve_background(svc, host="127.0.0.1", port=0)
    try:
        endpoint = f"http://127.0.0.1:{server.server_address[1]}"
        analyst = HttpAnalyst(endpoint)
        an.health.begin_cycle()
        an.health.end_cycle()
        assert analyst.get_health() == "ok"
        an.health.end_cycle(stale_served=1)
        assert analyst.get_health() == "degraded"
        an.health.end_cycle(shed=4)  # /readyz answers 503 here
        assert analyst.get_health() == "overloaded"
        # unreachable brain RAISES — the operator loop owns the policy
        # (an overloaded pod is pulled from its Service by the readiness
        # gate, so "unreachable" must not silently read as "ok")
        from foremast_tpu.operator.analyst import AnalystError

        with pytest.raises(AnalystError):
            HttpAnalyst("http://127.0.0.1:1").get_health()
    finally:
        server.shutdown()


def test_operator_holds_suppression_while_brain_unreachable():
    """Unreachability right after a non-ok reading (the readiness gate
    pulling the pod from the Service) keeps suppressing for the bounded
    hold window; unreachability from a healthy baseline fails open."""
    from foremast_tpu.operator.kube import FakeKube
    from foremast_tpu.operator.loop import OperatorLoop
    from foremast_tpu.operator.types import (
        PHASE_UNHEALTHY,
        DeploymentMonitor,
        MonitorSpec,
        MonitorStatus,
        RemediationAction,
    )

    class FlakyProbe:
        def __init__(self):
            self.mode = "overloaded"

        def __call__(self):
            if self.mode == "down":
                raise ConnectionError("endpoint pulled")
            return self.mode

    probe = FlakyProbe()
    kube = FakeKube()
    kube.deployments[("default", "demo")] = {
        "metadata": {"name": "demo", "namespace": "default",
                     "labels": {"app": "demo"}},
        "spec": {"selector": {"matchLabels": {"app": "demo"}},
                 "template": {"spec": {"containers": []}}},
    }
    kube.upsert_monitor(DeploymentMonitor(
        name="demo", namespace="default",
        annotations={"deployment.foremast.ai/name": "demo"},
        spec=MonitorSpec(remediation=RemediationAction(option="AutoPause")),
        status=MonitorStatus(phase=PHASE_UNHEALTHY),
    ))

    class NullAnalyst:
        def start_analyzing(self, request):
            return "job-1"

        def get_status(self, job_id):
            from foremast_tpu.operator.analyst import StatusResponse

            return StatusResponse(phase="Running")

    loop = OperatorLoop(kube, NullAnalyst(), health_probe=probe)
    loop.tick(now=1000.0)  # overloaded: suppressed
    assert loop.remediations_suppressed_total == 1
    probe.mode = "down"  # readiness gate pulled the endpoint
    loop.tick(now=1010.0)
    # hold: still suppressed (no dispatch), one event for the held flip
    assert not kube.get_monitor("default", "demo").status.remediation_taken
    assert kube.patches == []
    # past the bounded hold window, suppression fails open: a brain that
    # died for good cannot veto remediation forever
    loop.tick(now=1010.0 + loop.HEALTH_HOLD_S + 1.0)
    assert kube.get_monitor("default", "demo").status.remediation_taken
    # and unreachability from a HEALTHY baseline fails open immediately
    loop2 = OperatorLoop(kube, NullAnalyst(),
                         health_probe=FlakyProbe())
    assert loop2._probe_health(0.0) in ("ok", "overloaded")


# ------------------------------------------------- graceful shutdown handoff
def test_release_leases_makes_adoption_immediate(tmp_path):
    archive = FileArchive(str(tmp_path / "archive.jsonl"))
    a = JobStore(archive=archive)
    rng = np.random.default_rng(SEED)
    fixtures = {}
    _mk_job(a, fixtures, "j1", continuous=True, rng=rng)
    _mk_job(a, fixtures, "j2", rng=rng)
    claimed = a.claim_open_jobs("worker-a", max_stuck_seconds=90.0)
    assert len(claimed) == 2
    a.flush()  # open-lease mirror, pre-release

    # a peer scanning NOW must NOT adopt: the leases are fresh
    b = JobStore(archive=archive)
    assert b.adopt_stale_from_archive(worker="worker-b",
                                     max_stuck_seconds=90.0) == 0

    # graceful shutdown: release + drain the mirror
    released = a.release_leases(worker="worker-a")
    assert released == 2
    a.flush()
    assert a.archive_dirty_count() == 0

    # the SAME scan is now an immediate takeover — no stuck-window wait
    n = b.adopt_stale_from_archive(worker="worker-b", max_stuck_seconds=90.0)
    assert n == 2
    for jid in ("j1", "j2"):
        doc = b.get(jid)
        assert doc is not None and doc.status == J.INITIAL
    # and a claim on the adopter clears the handoff mark
    claimed = b.claim_open_jobs("worker-b", max_stuck_seconds=90.0)
    assert {d.id for d in claimed} == {"j1", "j2"}
    assert all(d.released_at == 0.0 for d in claimed)


def test_runtime_stop_releases_leases_and_drains_mirror(tmp_path):
    from foremast_tpu.runtime import Runtime

    archive = FileArchive(str(tmp_path / "archive.jsonl"))
    fixtures = {}
    rt = Runtime(data_source=FixtureDataSource(fixtures), cache=False,
                 archive=archive)
    rng = np.random.default_rng(SEED)
    _mk_job(rt.store, fixtures, "j1", continuous=True, rng=rng)
    rt.store.claim_open_jobs("worker-0")
    rt.stop(drain_seconds=5.0)
    # the archive's newest record for j1 carries the handoff mark
    rec = archive.get("j1")
    assert rec is not None
    assert rec["released_at"] > 0
    assert rec["status"] == J.INITIAL


# ------------------------------------------------------- chaos fault shapes
def test_chaos_spike_is_slow_then_succeed():
    from foremast_tpu.resilience.faults import FaultInjector, parse_chaos_spec

    seed, plans = parse_chaos_spec("seed=5;fetch.spike=1..3:0.01")
    plan = plans["fetch"]
    assert plan.spikes == [(1, 3, 0.01)]
    sleeps = []
    inj = FaultInjector(plan, seed=seed, target="fetch",
                        sleep=lambda s: sleeps.append(s))
    out = [inj.decide() for _ in range(4)]
    # calls 1..2 sit in the spike window: slow, then SUCCEED
    assert out == ["ok", "ok", "ok", "ok"]
    assert sleeps == [0.01, 0.01]
    assert inj.injected_latency == 2
    assert inj.injected_errors == 0

    with pytest.raises(ValueError):
        parse_chaos_spec("fetch.spike=1..3")  # missing :SECONDS


def test_chaos_hang_holds_then_fails():
    from foremast_tpu.resilience.faults import FaultInjector, parse_chaos_spec

    seed, plans = parse_chaos_spec("seed=5;fetch.hang=1.0:0.02")
    plan = plans["fetch"]
    assert (plan.hang_rate, plan.hang_seconds) == (1.0, 0.02)
    sleeps = []
    inj = FaultInjector(plan, seed=seed, target="fetch",
                        sleep=lambda s: sleeps.append(s))
    out = [inj.decide() for _ in range(3)]
    # every call holds for the transport timeout, then fails
    assert out == ["error", "error", "error"]
    assert sleeps == [0.02, 0.02, 0.02]
    assert inj.injected_errors == 3 and inj.injected_latency == 3

    with pytest.raises(ValueError):
        parse_chaos_spec("fetch.hang=0.5")  # missing :SECONDS


def test_chaos_spike_does_not_shift_the_random_stream():
    """A spike clause layers latency on top of the decision chain without
    consuming OR skipping randomness, so every decision — before, inside,
    and after the window — matches the spike-free plan exactly."""
    from foremast_tpu.resilience.faults import FaultInjector, parse_chaos_spec

    def stream(spec):
        seed, plans = parse_chaos_spec(spec)
        inj = FaultInjector(plans["fetch"], seed=seed, target="fetch",
                            sleep=lambda s: None)
        return [inj.decide() for _ in range(40)]

    base = stream("seed=9;fetch.error=0.4")
    spiked = stream("seed=9;fetch.error=0.4;fetch.spike=10..15:0.001")
    assert base == spiked
