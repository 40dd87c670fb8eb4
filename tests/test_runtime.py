"""Composition root + review-fix regression tests."""
import json
import time
import urllib.request

import numpy as np
import pytest

from foremast_tpu.dataplane.fetch import FixtureDataSource
from foremast_tpu.dataplane.promql import (
    MetricQuerySpec,
    build_metric_windows,
    materialize_placeholders,
)
from foremast_tpu.engine import families
from foremast_tpu.engine import jobs as J
from foremast_tpu.engine.config import EngineConfig
from foremast_tpu.engine.jobs import JobStore
from foremast_tpu.runtime import Runtime
from foremast_tpu.service.api import build_document


def test_wavefront_historical_placeholder_gets_H_marker():
    """s=START_TIME must become s=START_TIME_H on the wavefront historical
    URL, else the 7-day fit window collapses onto the 30-min judgment
    window and continuous wavefront jobs can never flag anomalies."""
    (w,) = build_metric_windows(
        "http://wf/chart/api",
        [MetricQuerySpec("latency", data_source_type="wavefront", query="ts(x)")],
        "continuous",
        0,
        1800,
        "ns",
        "app",
    )
    assert "s=START_TIME_H" in w.historical
    assert "s=START_TIME_H" not in w.current
    now = 1_700_000_000.0
    hist = materialize_placeholders(w.historical, now)
    cur = materialize_placeholders(w.current, now)
    hist_start = float(hist.split("s=")[1].split("&")[0])
    cur_start = float(cur.split("s=")[1].split("&")[0])
    assert (now - hist_start) > 6.9 * 86400
    assert (now - cur_start) <= 1800 + 60


def test_corrupt_snapshot_quarantined_not_fatal(tmp_path):
    p = str(tmp_path / "snap.json")
    with open(p, "w") as f:
        f.write('{"jobs": [{"id": "trunc')  # torn write
    store = JobStore(snapshot_path=p)
    assert store.by_status(*J.OPEN_STATUSES) == []
    import os

    assert os.path.exists(p + ".corrupt")
    # store is fully usable afterwards
    store.create(J.Document(id="a", app_name="x", strategy="canary",
                            start_time="", end_time=""))
    store.flush()
    with open(p) as f:
        assert json.load(f)["jobs"][0]["id"] == "a"


def test_hpa_flag_metric_order_deterministic():
    """Two same-priority metrics must come out in sorted order regardless of
    request dict ordering (HPA tps/sla selection tie-breaks on it)."""
    base = {
        "appName": "a",
        "strategy": "hpa",
        "metricsInfo": {
            "current": {},
            "historical": {
                "zzz_tps": {"url": "http://h/z", "priority": 0},
                "aaa_lat": {"url": "http://h/a", "priority": 0},
            },
        },
    }
    doc = build_document(base)
    assert list(doc.metrics) == ["aaa_lat", "zzz_tps"]
    # flags are read from whichever category carries the metric
    assert doc.metrics["zzz_tps"].priority == 0
    doc2 = build_document(
        {
            **base,
            "metricsInfo": {
                "current": {},
                "historical": dict(
                    reversed(list(base["metricsInfo"]["historical"].items()))
                ),
            },
        }
    )
    assert list(doc2.metrics) == list(doc.metrics)


def test_min_points_config_wired_into_pair_scoring():
    """MIN_*_DATA_POINTS must gate the pairwise tests: with 10-point windows
    a default config (MW needs 20) judges via kruskal/ks only; raising
    kruskal's gate above 10 and disabling others kills the verdict."""
    from foremast_tpu.parallel import fleet as fl

    rng = np.random.default_rng(0)
    B, T = 2, 10
    base = rng.normal(10, 1, (B, T)).astype(np.float32)
    cur = base + 50.0
    m = np.ones((B, T), bool)

    def run(min_kruskal):
        return np.asarray(
            fl.score_pairs(
                base, m, cur, m,
                np.full(B, 0.05, np.float32),
                np.full(B, fl.TEST_KRUSKAL, np.int32),
                np.full(B, fl.COMBINE_ANY, np.int32),
                np.full(B, 5, np.int32),
                np.full(B, 100.0, np.float32),  # band never fires
                np.full(B, 3, np.int32),
                np.full(B, -np.inf, np.float32),
                np.tile(np.asarray([20, 20, min_kruskal], np.int32), (B, 1)),
            )["unhealthy"]
        )

    assert run(5).all()
    assert not run(11).any()


def test_oversized_window_clamped_not_fatal():
    """>11.4 days of data at 60 s exceeds the largest compiled bucket; the
    fetch path must clamp to the most recent samples instead of poisoning
    the whole scoring cycle."""
    from foremast_tpu.engine.analyzer import Analyzer
    from foremast_tpu.ops.windowing import MAX_WINDOW_STEPS

    n = 20 * 1440  # 20 days of minutes
    now = 1_700_000_000
    fixtures = {"u": ([now - 60 * (n - i) for i in range(n)], [1.0] * n)}
    a = Analyzer(EngineConfig(), FixtureDataSource(fixtures), JobStore())
    w = a._fetch_window("u", now)
    assert w.values.shape[0] <= MAX_WINDOW_STEPS
    # most recent sample preserved
    assert w.mask[-1]


def test_isolate_contains_poison_to_one_job():
    from foremast_tpu.engine.analyzer import Analyzer

    a = Analyzer(EngineConfig(), FixtureDataSource({}), JobStore())

    class It:
        def __init__(self, job_id):
            self.job_id = job_id

    def scorer(items):
        out = {}
        for it in items:
            if it.job_id == "bad":
                raise ValueError("boom")
            out[(it.job_id, "m", "pair")] = {"ok": True}
        return out

    res, bad = a._isolate(scorer, [It("good1"), It("bad"), It("good2")])
    assert set(bad) == {"bad"} and "boom" in bad["bad"]
    assert ("good1", "m", "pair") in res and ("good2", "m", "pair") in res


def test_cache_ttl_refetches_changing_current_window():
    from foremast_tpu.dataplane.fetch import CachingDataSource

    calls = []

    class Inner:
        def fetch(self, url):
            calls.append(url)
            return ([1.0], [float(len(calls))])

    src = CachingDataSource(Inner(), ttl_seconds=0.0)
    assert src.fetch("u")[1] == [1.0]
    assert src.fetch("u")[1] == [2.0]  # expired -> refetched
    src2 = CachingDataSource(Inner(), ttl_seconds=300.0)
    calls.clear()
    src2.fetch("u")
    src2.fetch("u")
    assert len(calls) == 1  # within TTL -> cached


def test_exporter_evicts_stale_series():
    from foremast_tpu.dataplane.exporter import VerdictExporter

    exp = VerdictExporter(stale_seconds=0.0)
    exp.record_bounds("a", "ns", "m", 1, 0, 0)
    time.sleep(0.01)
    assert exp.samples() == []
    assert exp._gauges == {}  # evicted, not just filtered


def test_malformed_priority_is_400_not_500():
    from foremast_tpu.service.api import ApiError

    with pytest.raises(ApiError) as ei:
        build_document(
            {
                "appName": "a",
                "strategy": "hpa",
                "metricsInfo": {
                    "current": {"tps": {"url": "http://x", "priority": "high"}}
                },
            }
        )
    assert ei.value.status == 400
    with pytest.raises(ApiError) as ei2:
        build_document(
            {
                "appName": "a",
                "strategy": "canary",
                "metricsInfo": {"current": {"tps": "not-an-object"}},
            }
        )
    assert ei2.value.status == 400


def test_hpa_sla_metric_respects_is_increase():
    """SLA metric = first is_increase secondary, not merely group[1]."""
    from foremast_tpu.engine.analyzer import Analyzer, _HpaItem
    from foremast_tpu.ops.windowing import resample_to_grid

    now = 1_700_000_000
    hist = resample_to_grid(
        [now - 3600 + 60 * i for i in range(50)], [100.0] * 50, now - 3600, now - 600
    )
    cur = resample_to_grid(
        [now - 600 + 60 * i for i in range(10)], [100.0] * 10, now - 600, now
    )
    items = [
        _HpaItem("j", "tps", hist, cur, is_increase=True, priority=0),
        _HpaItem("j", "free_mem", hist, cur, is_increase=False, priority=1),
        _HpaItem("j", "latency", hist, cur, is_increase=True, priority=2),
    ]
    a = Analyzer(EngineConfig(), FixtureDataSource({}), JobStore())
    out = families.family("hpa").score(a, items)
    assert out["j"]["sla_metric"] == "latency"


@pytest.mark.parametrize("port", [18123])
def test_runtime_end_to_end(tmp_path, port):
    """One process: POST create -> worker cycle -> anomaly verdict +
    foremastbrain:* series on /metrics, with the shared exporter wiring."""
    rng = np.random.default_rng(3)
    now = time.time()
    fixtures = {
        "http://fix/current": (
            [now - 600 + 60 * i for i in range(10)],
            list(rng.poisson(300, 10).astype(float)),
        ),
        "http://fix/baseline": (
            [now - 1200 + 60 * i for i in range(10)],
            list(rng.poisson(30, 10).astype(float)),
        ),
        "http://fix/historical": (
            [now - 86400 + 60 * i for i in range(1440)],
            list(rng.poisson(30, 1440).astype(float)),
        ),
    }
    rt = Runtime(
        config=EngineConfig(),
        data_source=FixtureDataSource(fixtures),
        snapshot_path=str(tmp_path / "snap.json"),
        cache=False,
    )
    rt.start(host="127.0.0.1", port=port, cycle_seconds=0.2)
    try:
        req = {
            "appName": "demo",
            "namespace": "default",
            "strategy": "canary",
            "startTime": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime(now - 600)
            ),
            "endTime": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(now)),
            "metricsInfo": {
                "current": {"error5xx": {"url": "http://fix/current"}},
                "baseline": {"error5xx": {"url": "http://fix/baseline"}},
                "historical": {"error5xx": {"url": "http://fix/historical"}},
            },
        }
        r = urllib.request.urlopen(
            urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/healthcheck/create",
                json.dumps(req).encode(),
                {"Content-Type": "application/json"},
            )
        )
        job = json.loads(r.read())
        deadline = time.time() + 30
        status = "new"
        while time.time() < deadline:
            st = json.loads(
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/v1/healthcheck/id/{job['jobId']}"
                ).read()
            )
            status = st["status"]
            if status in ("success", "anomaly", "abort"):
                break
            time.sleep(0.2)
        assert status == "anomaly"
        m = urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics").read().decode()
        assert "foremastbrain:error5xx_anomaly" in m
        # /status says WHERE the scoring programs ran and what they cost to
        # compile since start() — chip_smoke.py fails unless this reads tpu
        build = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/status").read())["build"]
        assert build["platform"] == "cpu" and build["device_kind"]
        assert build["device_count"] == 8  # conftest's virtual mesh
        assert set(build["compile"]) == {
            "backend_compiles", "cache_hits", "cache_misses"}
    finally:
        rt.stop()
    # stop() takes the runtime's compile listeners back out
    assert rt.service.compile_counter is None


def test_runtime_serves_grpc_when_enabled():
    """Runtime.start(grpc_port=0) brings up the gRPC dispatch front on an
    ephemeral port alongside HTTP; a create round-trips through it."""
    from foremast_tpu.dataplane.fetch import FixtureDataSource
    from foremast_tpu.runtime import Runtime
    from foremast_tpu.service.grpc_api import DispatchClient

    rt = Runtime(data_source=FixtureDataSource({}), cache=False)
    rt.start(host="127.0.0.1", port=0, cycle_seconds=3600, grpc_port=0)
    try:
        assert rt.grpc_bound_port > 0
        with DispatchClient(f"127.0.0.1:{rt.grpc_bound_port}") as c:
            resp = c.create({
                "appName": "rt-grpc",
                "strategy": "canary",
                "metricsInfo": {"current": {"m": {"url": "http://x"}}},
            })
            assert resp["status"] == "new"
            assert c.status(resp["jobId"])["appName"] == "rt-grpc"
    finally:
        rt.stop()


def test_runtime_run_forever_exits_on_request_stop(tmp_path):
    """request_stop() (the SIGTERM seam) makes run_forever return and run
    the full stop() path — final snapshot flush included."""
    import threading

    from foremast_tpu.engine.jobs import Document, JobStore

    snap = str(tmp_path / "snap.json")
    rt = Runtime(data_source=FixtureDataSource({}), cache=False,
                 snapshot_path=snap)
    t = threading.Thread(
        target=rt.run_forever,
        kwargs=dict(host="127.0.0.1", port=0, cycle_seconds=60),
        daemon=True,
    )
    t.start()
    deadline = time.time() + 10
    while rt._server is None and time.time() < deadline:
        time.sleep(0.02)
    rt.store.create(Document(id="j", app_name="a", strategy="canary",
                             start_time="", end_time=""))
    rt.request_stop()
    t.join(15)
    assert not t.is_alive()
    assert JobStore(snapshot_path=snap).get("j") is not None  # flushed
    rt.stop()  # idempotent


def _run_daemon(target, *args, **kwargs):
    """Run a daemon loop in a thread, capturing exceptions: a loop that
    crashes must FAIL the graceful-stop assertion, not pass vacuously."""
    import threading

    errors = []

    def wrapped():
        try:
            target(*args, **kwargs)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    t = threading.Thread(target=wrapped, daemon=True)
    t.start()
    return t, errors


def test_operator_loop_run_forever_exits_on_request_stop():
    from foremast_tpu.cli import build_operator_loop
    from foremast_tpu.operator.kube import FakeKube

    class A:
        analyst = ""
        analyst_transport = ""

    loop, _ = build_operator_loop(A(), kube=FakeKube())
    t, errors = _run_daemon(loop.run_forever, interval=0.05)
    time.sleep(0.2)  # a few ticks
    loop.request_stop()
    t.join(5)
    assert not t.is_alive() and not errors, errors


def test_trigger_run_forever_exits_on_request_stop(tmp_path):
    from foremast_tpu.trigger.trigger import TriggerService

    class _Status:
        phase = "Running"
        reason = ""

    class NullAnalyst:
        def start_analyzing(self, req):
            return "jid"

        def get_status(self, job_id):
            return _Status()

    svc = TriggerService(analyst=NullAnalyst(), volume_path=str(tmp_path))
    t, errors = _run_daemon(
        svc.run_forever, [("app", {"error5xx": "q"})], poll_seconds=0.05)
    time.sleep(0.2)
    svc.request_stop()
    t.join(5)
    assert not t.is_alive() and not errors, errors


def test_env_knob_parsing_tolerates_garbage(monkeypatch):
    """Malformed/templated-empty env knobs must fall back with a log line,
    never crashloop the pod (runtime.py's stated policy, now owned by the
    knob registry — PORT='garbage' used to raise at startup)."""
    from foremast_tpu.utils import knobs

    assert knobs.read("PORT", {"PORT": "garbage"}) == 8099
    assert knobs.read("PORT", {"PORT": ""}) == 8099
    assert knobs.read("PORT", {"PORT": "17"}) == 17
    assert knobs.read("PORT", {}) == 8099
    assert knobs.read("CYCLE_SECONDS", {"CYCLE_SECONDS": "not-a-float"}) \
        == 10.0
    # optional knobs (no configured value) stay None
    assert knobs.read("HTTP_MAX_INFLIGHT", {}) is None
    # and the registry refuses reads of knobs nobody registered
    import pytest

    with pytest.raises(KeyError):
        knobs.read("NOT_A_KNOB", {})
    # process env is the default source
    monkeypatch.setenv("PORT", "1234")
    assert knobs.read("PORT") == 1234
