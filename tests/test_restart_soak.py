"""Restart-recovery soak (`make soak-restart`, ISSUE 13): kill -9 a
REAL runtime process mid-push-stream and restart it over the same
WINDOW_STORE_DIR.

The claims under test, end to end over the wire:

  * recovery replays segments + WAL (visible on /status) and the
    rebooted replica serves its covered windows with ZERO backend
    requests — no refetch storm: the pushed job's current window never
    touches the backend again, and the historical window resumes with
    narrow delta tail queries, never a full-range refetch;
  * pushes acked before the kill survive it (the WAL half of
    "/ingest 2xx means durable");
  * verdicts are byte-identical to a never-restarted baseline replica
    fed the same stream (which also runs tier-OFF, so the comparison
    pins tier-on == tier-off == restart);
  * the torn-WAL chaos shape (`wal.torn`): recovery classifies the
    damage, latches the resync fallback, and verdicts STILL match the
    baseline — the poll path heals what the WAL lost.

Marked slow+chaos so tier-1 (-m 'not slow') stays fast.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import textwrap
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from foremast_tpu.dataplane.delta import parse_range_params
from foremast_tpu.ingest import encode_remote_write, snappy_compress

pytestmark = [pytest.mark.slow, pytest.mark.chaos]

STEP = 60
HIST_STEPS = 500


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url, timeout=5.0):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _get_json(url, timeout=5.0):
    code, payload = _get(url, timeout)
    return code, json.loads(payload)


def _wait_for(predicate, budget_s, interval=0.1, what=""):
    deadline = time.monotonic() + budget_s
    last = None
    while time.monotonic() < deadline:
        try:
            last = predicate()
            if last:
                return last
        except Exception as e:  # noqa: BLE001 - booting processes 404/refuse
            last = repr(e)
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {what}: last={last!r}")


class _Backend:
    """Threaded HTTP Prometheus stand-in shared by both replicas.
    Each replica queries /<tag>/<series>?...; requests are logged as
    (tag/series, qstart, qend, monotonic) so the test can prove which
    replica fetched what, when, and how wide."""

    def __init__(self):
        self.series = {}  # "cur"/"hist" -> [(ts, val)]
        self.requests = []  # (name, qstart, qend, t_mono)
        self.lock = threading.Lock()
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # noqa: N802 - stdlib API
                pass

            def do_GET(self):  # noqa: N802 - stdlib API
                parts = self.path.split("?", 1)[0].strip("/").split("/")
                name = "/".join(parts[-2:])  # tag/series
                rng = parse_range_params(self.path)
                with outer.lock:
                    qs, qe = (rng[0], rng[1]) if rng else (0, 0)
                    outer.requests.append(
                        (name, qs, qe, time.monotonic()))
                    samples = [
                        (t, v)
                        for t, v in outer.series.get(parts[-1], [])
                        if rng is None or rng[0] <= t <= rng[1]]
                body = json.dumps({
                    "status": "success",
                    "data": {"resultType": "matrix", "result": [
                        {"metric": {"__name__": "m"},
                         "values": [[t, str(v)] for t, v in samples]}]},
                }).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.server.server_address[1]
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()

    def count(self, name, since=0.0, full_hist_floor=None):
        with self.lock:
            rows = [r for r in self.requests
                    if r[0] == name and r[3] >= since]
            if full_hist_floor is not None:
                rows = [r for r in rows if r[1] <= full_hist_floor]
            return len(rows)

    def close(self):
        self.server.shutdown()


_CHILD = textwrap.dedent("""
    import signal, sys
    from foremast_tpu.engine import Document, EngineConfig, MetricQueries
    from foremast_tpu.runtime import Runtime
    from foremast_tpu.utils.timeutils import to_rfc3339

    backend, tag, port, store_dir, t0, now0 = (
        sys.argv[1], sys.argv[2], int(sys.argv[3]), sys.argv[4],
        int(sys.argv[5]), int(sys.argv[6]))
    STEP = 60

    def url(name, s, e):
        return (f"{backend}/{tag}/{name}"
                f"?query=x&start={s:.0f}&end={e:.0f}&step={STEP}")

    rt = Runtime(
        config=EngineConfig(
            fetch_concurrency=2, max_stuck_seconds=1e9,
            retry_max_attempts=2, retry_base_delay=0.01,
            retry_max_delay=0.05, fetch_cycle_deadline_seconds=4.0),
        window_store_dir=store_dir,
        window_store_checkpoint_seconds=0.2,
        ingest_debounce_ms=20.0,
    )
    rt.store.create(Document(
        id="pushed", app_name="app-pushed", namespace="soak",
        strategy="canary",
        start_time=to_rfc3339(t0), end_time=to_rfc3339(now0 + 7 * 86400),
        metrics={"error5xx": MetricQueries(
            current=url("cur", t0, now0 + 7 * 86400),
            historical=url("hist", t0 - 500 * STEP, t0))},
    ))
    signal.signal(signal.SIGTERM, lambda *_: rt.request_stop())
    rt.run_forever(host="127.0.0.1", port=port, cycle_seconds=0.4)
""")


def _spawn(tmp_path, backend, tag, port, store_dir, t0, now0, chaos=""):
    script = tmp_path / "replica.py"
    if not script.exists():
        script.write_text(_CHILD)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", FOREMAST_CHAOS=chaos,
               FLIGHT_DUMP_DIR=str(tmp_path / "dumps"),
               PYTHONPATH=os.pathsep.join(
                   p for p in (repo_root, os.environ.get("PYTHONPATH"))
                   if p))
    return subprocess.Popen(
        [sys.executable, str(script),
         f"http://127.0.0.1:{backend.port}", tag, str(port),
         store_dir or "", str(t0), str(now0)],
        env=env, stdout=open(tmp_path / f"{tag}-{port}.log", "ab"),
        stderr=subprocess.STDOUT)


class _Harness:
    """Two replicas over one backend: `a` (durable store, the one that
    gets killed) and `b` (tier-off, never restarted — the baseline)."""

    def __init__(self, tmp_path, chaos=""):
        self.tmp_path = tmp_path
        self.be = _Backend()
        self.now0 = int(time.time()) // STEP * STEP
        self.t0 = self.now0 - 60 * STEP
        self.k = 0  # pushed-sample cursor (ts run AHEAD of wall clock)
        self.be.series["cur"] = [
            (self.t0 + j * STEP, round(5.0 + 0.01 * j, 4))
            for j in range(60)]
        self.be.series["hist"] = [
            (self.t0 - HIST_STEPS * STEP + j * STEP,
             round(5.0 + 0.01 * (j % 60), 4))
            for j in range(HIST_STEPS + 60)]
        self.store_dir = str(tmp_path / "winstore")
        self.pa, self.pb = _free_port(), _free_port()
        self.proc_a = _spawn(tmp_path, self.be, "a", self.pa,
                             self.store_dir, self.t0, self.now0,
                             chaos=chaos)
        self.proc_b = _spawn(tmp_path, self.be, "b", self.pb, "",
                             self.t0, self.now0)
        self.base_a = f"http://127.0.0.1:{self.pa}"
        self.base_b = f"http://127.0.0.1:{self.pb}"

    def wait_scored(self, budget=150.0):
        for base in (self.base_a, self.base_b):
            _wait_for(lambda b=base: self.prov_path(b) != "", budget,
                      what=f"first verdict at {base}")

    def prov_path(self, base):
        _, payload = _get(f"{base}/jobs/pushed/explain")
        return (json.loads(payload).get("provenance") or {}).get(
            "path", "")

    def status(self, base):
        return _get_json(f"{base}/status")[1]

    def push(self, n=1, value=None):
        """Push n fresh on-grid samples to BOTH replicas as ONE batch
        each (and to the backend, which stays the source of truth
        either way). One request per replica keeps the splice atomic,
        so both replicas' next scoring cycles judge the same window —
        a per-sample stream would let a conviction land mid-burst at
        different points on the two processes. Returns
        (status_a, status_b)."""
        samples = []
        for _ in range(n):
            self.k += 1
            ts = float(self.now0 + self.k * STEP)
            v = value if value is not None \
                else round(5.0 + 0.01 * self.k, 4)
            with self.be.lock:
                self.be.series["cur"].append((ts, v))
            samples.append((ts, float(v)))
        raw = snappy_compress(encode_remote_write([(
            {"foremast_job": "pushed", "foremast_metric": "error5xx"},
            samples)]))
        codes = []
        for base in (self.base_a, self.base_b):
            req = urllib.request.Request(
                f"{base}/ingest/remote-write", data=raw,
                headers={"Content-Type": "application/x-protobuf",
                         "Content-Encoding": "snappy"},
                method="POST")
            with urllib.request.urlopen(req, timeout=5) as r:
                codes.append(r.status)
        out = tuple(codes)
        assert out == (200, 200), out
        return out

    def kill_a(self):
        os.kill(self.proc_a.pid, signal.SIGKILL)
        self.proc_a.wait(10)

    def restart_a(self, chaos=""):
        self.proc_a = _spawn(self.tmp_path, self.be, "a", self.pa,
                             self.store_dir, self.t0, self.now0,
                             chaos=chaos)

    def verdict(self, base):
        """(status, sorted anomaly map) — the byte-comparable verdict."""
        _, doc = _get_json(f"{base}/v1/healthcheck/id/pushed")
        return doc["status"], {
            k: list(v) for k, v in sorted((doc.get("anomaly") or {}
                                           ).items())}

    def teardown(self):
        for proc in (self.proc_a, self.proc_b):
            try:
                proc.send_signal(signal.SIGTERM)
            except ProcessLookupError:
                pass
        for proc in (self.proc_a, self.proc_b):
            try:
                proc.wait(15)
            except subprocess.TimeoutExpired:
                proc.kill()
        self.be.close()


def test_restart_soak_kill9_recovers_without_refetch_storm(tmp_path):
    h = _Harness(tmp_path)
    try:
        h.wait_scored()
        # stream pushes until the replicas serve windows from the
        # push-fed cache AND a checkpoint has folded them into segments
        _wait_for(lambda: (h.push(2) and
                           h.status(h.base_a)["delta_fetch"]
                           ["ingest_hits"] >= 1 and
                           h.status(h.base_a)["window_store"]
                           ["checkpoints"] >= 2 and
                           h.status(h.base_a)["window_store"]
                           ["wal_appends"] >= 1),
                  90.0, interval=0.2, what="pushes spliced + checkpoint")
        ws = h.status(h.base_a)["window_store"]
        assert ws["segment_entries"] >= 1

        # one more acked push, then kill -9 IMMEDIATELY: the ack means
        # the WAL holds it, so the restart must not lose it
        h.push(1)
        t_kill = time.monotonic()
        h.kill_a()
        h.restart_a()
        _wait_for(lambda: h.status(h.base_a)["status"] == "ok", 150.0,
                  what="replica a back up")

        # recovery is visible and healthy: WAL replayed, scans clean
        rec = h.status(h.base_a)["window_store"]["recovery"]
        assert rec["wal_records_replayed"] >= 1, rec
        assert rec["wal_scan"] in ("ok", "torn_tail"), rec
        assert rec["segment_entries"] >= 1, rec
        assert rec["seconds"] < 10.0, rec

        # the stream resumes: pushes keep landing and stream-score
        _wait_for(lambda: (h.push(2) and
                           h.status(h.base_a)["scheduler"]
                           ["partial_cycles"] >= 1),
                  90.0, interval=0.2, what="post-restart stream scoring")
        _wait_for(lambda: h.prov_path(h.base_a) != "", 90.0,
                  what="post-restart verdict")

        # ZERO refetch storm: after the kill, the rebooted replica never
        # fetched its pushed current window from the backend at all, and
        # never re-downloaded the full historical body (the narrow delta
        # tail is the expected steady-state query)
        full_floor = h.t0 - HIST_STEPS * STEP + 1
        assert h.be.count("a/cur", since=t_kill) == 0, \
            "restart must serve the pushed current window from the store"
        assert h.be.count("a/hist", since=t_kill,
                          full_hist_floor=full_floor) == 0, \
            "restart must not re-download the full historical body"
        # ...and the counter is live: the rebooted replica's cold TTL
        # cache DID re-query the historical tail — just narrowly, through
        # the promoted warm-tier entry
        assert h.be.count("a/hist", since=t_kill) >= 1

        # verdict byte-identity: an anomalous burst pushed to BOTH
        # replicas convicts both, with identical anomaly evidence
        h.push(20, value=500.0)
        _wait_for(lambda: h.verdict(h.base_b)[0] == "anomaly",
                  120.0, what="baseline conviction")
        _wait_for(lambda: h.verdict(h.base_a)[0] == "anomaly",
                  120.0, what="restarted-replica conviction")
        va, vb = h.verdict(h.base_a), h.verdict(h.base_b)
        assert va == vb, f"verdict diverged: {va} vs {vb}"
    finally:
        h.teardown()


def test_restart_soak_torn_wal_falls_back_to_resync(tmp_path):
    """Every WAL frame torn (wal.torn=1): recovery classifies the damage,
    the resync latch engages store-wide, the poll path heals from the
    backend, and verdicts still match the never-restarted baseline."""
    h = _Harness(tmp_path, chaos="seed=9;wal.torn=1.0")
    try:
        h.wait_scored()
        _wait_for(lambda: (h.push(2) and
                           h.status(h.base_a)["window_store"]
                           ["checkpoints"] >= 2 and
                           h.status(h.base_a)["window_store"]
                           ["wal_torn_writes"] >= 1),
                  90.0, interval=0.2, what="torn WAL writes observed")
        h.kill_a()
        h.restart_a()  # chaos off for the reboot: the damage is on disk
        _wait_for(lambda: h.status(h.base_a)["status"] == "ok", 150.0,
                  what="replica a back up")
        rec = h.status(h.base_a)["window_store"]["recovery"]
        assert rec["wal_scan"] in ("torn_tail", "corrupt"), rec
        # healing is poll-driven: the current window comes back from the
        # backend (which always had the samples), then pushes re-arm
        _wait_for(lambda: (h.push(2) and
                           h.prov_path(h.base_a) != ""), 90.0,
                  interval=0.2, what="post-corruption scoring")
        h.push(20, value=500.0)
        _wait_for(lambda: h.verdict(h.base_b)[0] == "anomaly",
                  120.0, what="baseline conviction")
        _wait_for(lambda: h.verdict(h.base_a)[0] == "anomaly",
                  120.0, what="chaos-replica conviction")
        assert h.verdict(h.base_a) == h.verdict(h.base_b)
    finally:
        h.teardown()
