#!/usr/bin/env python3
"""chip_smoke.py: the scoring path, once, end to end, on the accelerator.

The quickest proof that the system still starts on the chip. It drives the
entry points a user would call, one chip-holding process at a time:

  1. prewarm   `python -m foremast_tpu prewarm` compiles the scoring grid
               into the persistent compile cache: every family at the T=128
               canary bucket up to the default SCORE_BATCH rung (8192), and
               every family at the T=16384 bucket of the 7-day history at
               the rungs a fleet of this shape launches;
  2. serve     `python -m foremast_tpu serve` holds the chip while THIS
               process serves a seeded simulated fleet over loopback and
               submits it over HTTP: pair canaries, band monitors over a
               7-day history, bivariate, hpa, and four-metric jobs that
               train the LSTM autoencoder on miss at its only supported
               width. Every job must be judged, the injected anomalies
               convicted, every family launched, every containment counter
               zero, and the compile cache hit from step 1's work;
  3. reference the same fleet through a `JAX_PLATFORMS=cpu` serve child:
               the verdict lists must be equal;
  4. mesh      only where the host has four chips: the fleet-mesh scorer at
               B=50,000 against single-device `score_pairs`.

This parent never initialises a JAX backend (it imports the simulator and
the native parser only) and passes its environment to its children
unchanged, apart from PORT/CYCLE_SECONDS/FLIGHT_DUMP_DIR and the reference
child's JAX_PLATFORMS=cpu. Every child says where it ran; unless every
chip-holding child reports platform `tpu` the run fails, whatever else
passed: there is no fallback that can hide the device. Under
JAX_PLATFORMS=cpu with --tiny every other check still runs and the
platform is the only failure; that is the CPU rehearsal. At full size a
run whose first child finds no accelerator stops there, failed: a CPU
cannot finish the full-size phases inside the budget.

On success the last line of stdout is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`
preceded by a one-line JSON summary of smoke OBSERVATIONS (wall times,
programs compiled, cache hits; not metrics: `"claim": null`). On failure
the summary goes to stderr, no result is printed and the exit code is 1.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
FAMILIES = "pair,band,bivariate,hpa,triage"
JUDGED_PATHS = ("scored", "stream-scored", "memo-hit", "triaged")
LSTM_METRICS = ("latency", "error5xx", "cpu", "tps")

# The fleet, by class. `full`: ~300 canary pairs at T=128,
# ~200 band monitors over a 10,034-point history (79 x 127 steps, the
# T=16384 bucket of the reference's 7-day 10,080-point window), ~50
# bivariate, ~40 hpa, 10 LSTM jobs. `tiny` keeps every family, phase,
# check and model width and cuts only the counts and the history, for the
# CPU rehearsal.
#
# `prewarm` is [(T-buckets, rungs)], one `prewarm` child each: what this
# fleet launches, plus the default SCORE_BATCH chunk at the canary bucket.
# The canary bucket gets the partial-flush rung (16), the rung 300 pairs land
# on (512) and the chunk (8192); the long bucket gets the rungs 50 and 200
# jobs land on (64, 256). It is NOT the full cross product up to
# (8192, 16384): that corner compiles and fits on the v5e (CHANGES.md PR 21:
# four more programs, about a minute each), and a smoke that every later PR
# must pass inside 1200 s, compilation included, from a cold cache spends
# its compile time on what its serve phase then has to hit.
SIZES = {
    "full": dict(pair=300, band=200, bivariate=50, hpa=40, lstm=10,
                 hist_windows=79,
                 prewarm=[("128", "16,512,8192"), ("16384", "64,256")],
                 budget_s=1100.0),
    "tiny": dict(pair=12, band=8, bivariate=4, hpa=4, lstm=10,
                 hist_windows=4, prewarm=[("128", "16"), ("1024", "16")],
                 budget_s=900.0),
}
WINDOW_STEPS = 127  # 128 samples inclusive: the canary T bucket
ANOMALY_RATE = 0.05
SEED = 20260926


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Checks:
    """Named pass/fail checks; every failure reaches the exit code."""

    def __init__(self):
        self.failed: list[str] = []
        self.passed = 0

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        if ok:
            self.passed += 1
        else:
            self.failed.append(f"{name}: {detail}" if detail else name)
            say(f"FAIL {name} {detail}")
        return bool(ok)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_text(url: str, body: dict | None = None,
              timeout: float = 30.0) -> str:
    """GET, or POST `body` as JSON; the response text."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data,
        headers={"Content-Type": "application/json"} if data else {})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read().decode()


def http_json(url: str, body: dict | None = None, timeout: float = 30.0):
    return json.loads(http_text(url, body, timeout))


def stop_child(proc: subprocess.Popen, grace_s: float = 30.0) -> int | None:
    """SIGTERM (serve's graceful path), then SIGKILL: no child outlives
    the smoke."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
    return proc.returncode


# --------------------------------------------------------------- the fleet
class Fleet:
    """The seeded fleet and its loopback metric backend. One anchor for
    every serve child: the trace is a pure function of (spec, t0), and the
    backend's clock is FROZEN, so the windows a job's explicit-range URLs
    name hold the same bytes whenever and wherever a child polls them."""

    def __init__(self, size: dict):
        from foremast_tpu.ops.windowing import align_step
        from foremast_tpu.simfleet.backend import SimBackend
        from foremast_tpu.simfleet.trace import SimTrace, preset

        n_sim = size["pair"] + size["band"] + size["bivariate"] + size["hpa"]
        self.n_sim, self.n_lstm = n_sim, size["lstm"]
        mix = (("continuous", size["band"] / n_sim),
               ("canary", size["pair"] / n_sim),
               ("hpa", size["hpa"] / n_sim),
               ("bivariate", size["bivariate"] / n_sim))
        # "steady": no diurnal term, so series do not depend on absolute
        # time; the anomaly subset is drawn over the WHOLE index space
        # (LSTM jobs included) and carries a sustained +10 sigma shift on
        # metric slot 0 from mid-current-window on
        self.spec = preset(
            "steady", n_sim + self.n_lstm, SEED, mix=mix,
            window_steps=WINDOW_STEPS, hist_windows=size["hist_windows"],
            anomaly_rate=ANOMALY_RATE)
        step = self.spec.step_s
        self.hist = self.spec.hist_windows * self.spec.window_steps
        # layout on the grid: history [0, hist], current window
        # [hist, hist + W], and the frozen clock just past its end. hpa
        # jobs are materialized against the ENGINE's wall clock (trailing
        # 7 days and 30 minutes), so the frozen instant is anchored at
        # wall-now; a query reaching back past t0 is clipped to the trace
        self.k_now = self.hist + WINDOW_STEPS
        self.t0 = align_step(time.time()) - self.k_now * step
        self.frozen_now = float(self.t0 + self.k_now * step + 5)
        self.trace = SimTrace(self.spec, self.t0, self.k_now + 64)
        self.backend = SimBackend(self.trace, clock=lambda: self.frozen_now)
        self.truth = self.trace.truth_jobs()
        self.server = None
        self._requests: list | None = None

    def serve(self) -> str:
        self.server, base = self.backend.serve(0)
        self.backend.url_base = base
        return base

    def close(self) -> None:
        if self.server is not None:
            self.server.shutdown()
            self.server.server_close()
            self.server = None

    def requests(self) -> list[tuple[str, str, dict]]:
        """[(key, class, create-body)] in job-index order. Explicit-range
        jobs go in as strategy `canary` (simfleet/driver.run_live's
        shape); hpa jobs as strategy `hpa`, the only route to that
        family, which re-materializes their windows every cycle."""
        from foremast_tpu.simfleet.driver import create_body
        from foremast_tpu.utils.timeutils import to_rfc3339

        if self._requests is not None:
            return self._requests
        be = self.backend
        out = self._requests = []
        end = to_rfc3339(int(time.time()) + 6 * 3600)
        start = to_rfc3339(self.t0)
        for idx, doc in enumerate(be.make_docs(0, self.n_sim)):
            cls = be.class_of(idx)
            out.append((f"{cls}-{idx}", cls, create_body(
                doc, "hpa" if cls == "hpa" else "canary", start, end)))
        lo, hi = 0, self.hist
        far = self.trace.horizon - 1
        for j in range(self.n_lstm):
            idx = self.n_sim + j
            info = {"current": {}, "historical": {}}
            for slot, m in enumerate(LSTM_METRICS):
                info["current"][m] = {
                    "url": be.url(idx, slot, "cur", hi, far)}
                info["historical"][m] = {
                    "url": be.url(idx, slot, "hist", lo, hi)}
            # one app per job: each trains its own autoencoder on miss
            out.append((f"lstm-{idx}", "lstm", {
                "appName": f"lstm-app-{j}", "namespace": "simfleet",
                "strategy": "canary", "startTime": start, "endTime": end,
                "metricsInfo": info}))
        return out


# ------------------------------------------------------------ serve phases
def metric_total(text: str, name: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            head, _, val = line.rpartition(" ")
            if head.split("{")[0] == name:
                total += float(val)
    return total


def run_serve(label: str, fleet: Fleet, extra_env: dict, deadline: float,
              cycle_s: float, log_dir: str, checks: Checks) -> dict:
    """One serve child driven to a judged fleet. Returns its record:
    device, verdicts, families launched, compile counters, wall times."""
    t_phase = time.time()
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    # flight-recorder dumps (a compiling first cycle trips the liveness
    # probe once) land beside the logs, not in the system temp dir
    env = dict(os.environ, PORT=str(port), CYCLE_SECONDS=str(cycle_s),
               FLIGHT_DUMP_DIR=log_dir, **extra_env)
    log_path = os.path.join(log_dir, f"serve-{label}.log")
    rec: dict = {"label": label, "log": log_path}
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "foremast_tpu", "serve"], cwd=HERE,
            env=env, stdout=log, stderr=subprocess.STDOUT)
        try:
            _drive(label, fleet, base, proc, deadline, checks, rec)
        except (urllib.error.URLError, OSError, ValueError) as e:
            # the child died or stopped answering mid-phase: a failed
            # check like any other, so the later phases still report
            checks.check(f"{label}:drive", False,
                         f"{type(e).__name__}: {e} (see {log_path})")
        finally:
            rc = stop_child(proc)
            rec["exit_code"] = rc
            rec["seconds"] = round(time.time() - t_phase, 1)
    with open(os.path.join(log_dir, f"verdicts-{label}.json"), "w") as f:
        json.dump(rec.get("verdicts") or {}, f, indent=1, sort_keys=True)
    # SIGTERM runs serve's graceful stop(): a clean exit is part of the path
    checks.check(f"{label}:clean_exit", rec.get("exit_code") == 0,
                 f"serve exited {rec.get('exit_code')} (see {log_path})")
    return rec


def _drive(label, fleet, base, proc, deadline, checks, rec):
    # -- wait for the API (the child claims its device before it binds) --
    t0 = time.time()
    status = None
    while time.time() < deadline:
        if proc.poll() is not None:
            break
        try:
            status = http_json(base + "/status", timeout=5)
            break
        except (urllib.error.URLError, OSError, ValueError):
            time.sleep(0.5)
    if not checks.check(f"{label}:started", status is not None,
                        f"serve did not answer /status (rc={proc.poll()})"):
        return
    rec["startup_s"] = round(time.time() - t0, 1)
    build = status["build"]
    rec["device"] = {"platform": build.get("platform"),
                     "kind": build.get("device_kind"),
                     "count": build.get("device_count")}
    say(f"{label}: up in {rec['startup_s']}s on {rec['device']}")

    # -- submit the fleet over HTTP --
    ids: dict[str, str] = {}  # key -> the replica's job id
    t_sub = time.time()
    errors = 0
    for key, _cls, body in fleet.requests():
        try:
            ids[key] = http_json(base + "/v1/healthcheck/create", body)["jobId"]
        except (urllib.error.URLError, OSError, ValueError, KeyError) as e:
            errors += 1
            say(f"{label}: create {key} failed: {e}")
    rec["submitted"] = len(ids)
    rec["submit_s"] = round(time.time() - t_sub, 1)
    checks.check(f"{label}:submitted", errors == 0 and
                 len(set(ids.values())) == fleet.n_sim + fleet.n_lstm,
                 f"{errors} create errors, {len(set(ids.values()))} ids")

    # -- poll until every job is judged --
    cycles: dict[str, dict] = {}
    judged: dict[str, dict] = {}  # key -> explain payload at first judging
    worst_health = "ok"
    order = {"ok": 0, "degraded": 1, "overloaded": 2, "stalled": 3}
    t_wait = time.time()
    while time.time() < deadline and proc.poll() is None:
        try:
            st = http_json(base + "/status")
        except (urllib.error.URLError, OSError, ValueError):
            time.sleep(1.0)  # a busy first cycle may miss one poll
            continue
        hs = (st.get("health") or {}).get("state", "ok")
        # `stalled` while the FIRST cycle compiles is the liveness probe
        # doing its job, not containment; it is reported, and must clear
        if order.get(hs, 9) > order.get(worst_health, 0):
            worst_health = hs
        cyc = st.get("cycle") or {}
        if cyc.get("cycle_id") and cyc["cycle_id"] not in cycles:
            # verdicts only change when a cycle folds: sweep the unjudged
            # jobs once per completed cycle, not once per poll
            cycles[cyc["cycle_id"]] = cyc
            for key, jid in ids.items():
                if key in judged:
                    continue
                ex = http_json(f"{base}/jobs/{jid}/explain")
                prov = ex.get("provenance") or {}
                job = ex.get("job") or {}
                if prov.get("path") in JUDGED_PATHS or \
                        job.get("status") in ("anomaly", "success", "abort",
                                              "unknown"):
                    judged[key] = ex
        if len(judged) == len(ids) and len(cycles) >= 2:
            break
        time.sleep(1.0)
    rec["judge_s"] = round(time.time() - t_wait, 1)
    rec["cycles_seen"] = len(cycles)
    rec["cycles"] = cycles
    missing = sorted(set(ids) - set(judged))
    checks.check(f"{label}:all_judged", not missing,
                 f"{len(missing)} of {len(ids)} unjudged, e.g. {missing[:5]}")

    # -- the verdict list, by the repo's own identity (engine/jobs.py
    # verdict_digest: status, reason, anomaly), over HTTP --
    verdicts: dict[str, dict] = {}
    bad_status, failed_scoring = [], []
    for key, jid in ids.items():
        v = http_json(f"{base}/v1/healthcheck/id/{jid}")
        status_ = v.get("status")
        # healthy watched jobs oscillate new <-> inprogress per cycle
        norm = "watching" if status_ in ("new", "inprogress") else status_
        verdicts[key] = {"status": norm, "reason": v.get("reason", ""),
                         "anomaly": v.get("anomaly") or {}}
        if status_ in ("abort", "unknown"):
            bad_status.append(f"{key}={status_}:{v.get('reason', '')[:80]}")
        if "scoring failed" in (v.get("reason") or ""):
            failed_scoring.append(f"{key}:{v['reason'][:120]}")
        if key.startswith("hpa-"):
            verdicts[key]["hpalogs"] = len(v.get("hpalogs") or [])
    rec["verdicts"] = verdicts
    checks.check(f"{label}:no_abort_unknown", not bad_status,
                 "; ".join(bad_status[:5]))
    checks.check(f"{label}:no_per_job_errors", not failed_scoring,
                 "; ".join(failed_scoring[:5]))

    # -- injected anomalies convict (hpa jobs score, they do not convict) --
    keys = [k for k, _c, _b in fleet.requests()]
    expect = [keys[j] for j in sorted(fleet.truth)
              if not keys[j].startswith("hpa-")]
    missed = [k for k in expect if verdicts[k]["status"] != "anomaly"]
    rec["anomalies_injected"] = len(expect)
    rec["anomalies_convicted"] = len(expect) - len(missed)
    checks.check(f"{label}:anomalies_convicted", bool(expect) and not missed,
                 f"{len(missed)} of {len(expect)} missed: {missed[:5]}")
    no_hpalog = [k for k, v in verdicts.items()
                 if k.startswith("hpa-") and not v["hpalogs"]]
    checks.check(f"{label}:hpa_scored", not no_hpalog,
                 f"no hpalog for {no_hpalog[:5]}")

    # -- every family launched; LSTM trained on miss over >= 2 cycles with
    # finite scores at the supported width --
    launched: dict[str, int] = {}
    for cyc in cycles.values():
        for fam, n in (cyc.get("family_launches") or {}).items():
            launched[fam] = launched.get(fam, 0) + int(n)
    rec["family_launches"] = launched
    for fam in ("pair", "band", "bivariate", "hpa"):
        checks.check(f"{label}:launched:{fam}", launched.get(fam, 0) > 0,
                     f"family_launches={launched}")
    traces = http_json(base + "/debug/traces?limit=1")
    trains = ((traces.get("stats") or {}).get("engine.lstm_train")
              or {}).get("count", 0)
    rec["lstm_train_spans"] = trains
    checks.check(f"{label}:lstm_trained_two_cycles", trains >= 2,
                 f"engine.lstm_train spans: {trains}")
    zs = []
    for key, ex in judged.items():
        if not key.startswith("lstm-"):
            continue
        fams = (ex.get("provenance") or {}).get("families") or []
        zs += [f.get("z") for f in fams if f.get("family") == "lstm"]
    finite = [z for z in zs if isinstance(z, (int, float))
              and math.isfinite(z)]
    rec["lstm_z"] = finite
    checks.check(f"{label}:lstm_finite", len(finite) == fleet.n_lstm
                 and len(zs) == fleet.n_lstm,
                 f"{len(finite)} finite of {len(zs)} lstm z-scores "
                 f"for {fleet.n_lstm} jobs")

    # -- containment stayed out of it: the safety code is there to keep
    # serve up, so a smoke must read its counters, not its uptime --
    metrics = http_text(base + "/metrics")
    counters = {
        "watchdog_fires": metric_total(
            metrics, "foremastbrain:watchdog_fires_total"),
        "quarantined": metric_total(
            metrics, "foremastbrain:jobs_quarantined_total"),
        "shed": metric_total(metrics, "foremastbrain:jobs_shed_total"),
        "stale_served": metric_total(
            metrics, "foremastbrain:stale_verdicts_served_total"),
    }
    rec["containment"] = counters
    rec["worst_health_seen"] = worst_health
    for name, val in counters.items():
        checks.check(f"{label}:{name}_zero", val == 0, f"{name}={val}")
    st = http_json(base + "/status")
    health = st.get("health") or {}
    rec["final_health"] = health.get("state")
    checks.check(f"{label}:health_ok", health.get("state") == "ok",
                 json.dumps({k: health.get(k) for k in
                             ("state", "watchdog_fires", "quarantined",
                              "shed", "stale_served", "open_breakers")}))
    rec["compile"] = (st.get("build") or {}).get("compile") or {}
    rec["backend_requests"] = fleet.backend.requests
    say(f"{label}: judged {len(judged)}/{len(ids)} in {rec['judge_s']}s over "
        f"{len(cycles)} cycles; launches {launched}; lstm trains {trains}; "
        f"compile {rec['compile']}; health {rec['final_health']} "
        f"(worst seen {worst_health})")


def verdict_identity(key: str, v: dict) -> tuple:
    """What must be EQUAL between the chip and the CPU reference: the
    status, which metrics were flagged, and the reason with its digits
    masked. The reason names the detector that fired and quotes its
    statistic (`z=320.51`, `p=0.0031`); the statistic is computed in f32
    on two different compilers (and, for the LSTM, after 30 epochs of
    training on each), so its printed digits may differ where its side of
    the threshold does not. hpa jobs are materialized against each
    child's own wall clock, so only their outcome class is comparable."""
    if key.startswith("hpa-"):
        return (v["status"], v["hpalogs"] > 0)
    return (v["status"], tuple(sorted(v["anomaly"])),
            re.sub(r"\d+(\.\d+)?(e[-+]?\d+)?", "#", v["reason"]))


# ------------------------------------------------------------- mesh phase
def mesh_child() -> int:
    """Runs in its own process (`chip_smoke.py --mesh-child`): the fleet
    mesh over every chip of the host against one device. Prints one JSON
    line."""
    import jax
    import numpy as np

    from foremast_tpu.engine.pipeline import device_info, enable_compile_cache
    from foremast_tpu.parallel import fleet as fl
    from foremast_tpu.parallel.mesh import fleet_mesh

    enable_compile_cache()
    devices = jax.devices()
    out: dict = dict(device_info())
    B, T = 50_000, 128
    rng = np.random.default_rng(SEED)
    baseline = rng.normal(10.0, 2.0, (B, T)).astype(np.float32)
    current = rng.normal(10.0, 2.0, (B, T)).astype(np.float32)
    current[: B // 20] += 3.0  # a convicting shift on 5% of the pairs
    b_mask = rng.random((B, T)) > 0.05
    c_mask = rng.random((B, T)) > 0.05
    spec = fl.pair_arg_spec(B, T)
    cfg = {"pvalue_threshold": np.full(B, 0.01, np.float32),
           "test_mask": np.full(B, 0b11111, np.int32),
           "combine": spec[6], "ma_window": spec[7],
           "band_threshold": np.full(B, 3.0, np.float32),
           "bound_mode": spec[9], "min_lower_bound": spec[10],
           "min_points": spec[11]}
    mesh = fleet_mesh(devices)
    t0 = time.time()
    run = fl.make_fleet_scorer(mesh, k=8)
    res, total, top_v, top_idx = run(baseline, b_mask, current, c_mask, cfg)
    shard_devices = sorted({s.device.id for s in
                            res["unhealthy"].addressable_shards})
    sharded = {k: np.asarray(v) for k, v in res.items()}
    out["mesh_seconds"] = round(time.time() - t0, 1)
    t0 = time.time()
    single = {k: np.asarray(v) for k, v in fl.score_pairs(
        baseline, b_mask, current, c_mask, cfg["pvalue_threshold"],
        cfg["test_mask"], cfg["combine"], cfg["ma_window"],
        cfg["band_threshold"], cfg["bound_mode"], cfg["min_lower_bound"],
        cfg["min_points"]).items()}
    out["single_seconds"] = round(time.time() - t0, 1)
    s_total, s_top_v, s_top_idx = fl.fleet_summary(
        single["unhealthy"], single["severity"], mesh, k=8)
    out.update(
        pairs=B, window=T, shard_devices=shard_devices,
        unhealthy_total=int(total),
        verdicts_equal=bool(np.array_equal(sharded["unhealthy"],
                                           single["unhealthy"])),
        pvalues_max_abs_diff=float(np.max(np.abs(
            sharded["pvalues"] - single["pvalues"]))),
        summary_equal=bool(int(s_total) == int(total)
                           and np.array_equal(np.asarray(s_top_idx),
                                              np.asarray(top_idx))),
        finite=bool(np.isfinite(sharded["severity"]).all()))
    print(json.dumps(out))
    return 0


def run_json_child(cmd: list, timeout_s: float, log_path: str):
    """(record, error, seconds) for a child that prints one JSON object
    (possibly pretty-printed) as the end of its stdout."""
    t0 = time.time()
    try:
        with open(log_path, "wb") as err:
            p = subprocess.run(cmd, cwd=HERE, env=dict(os.environ),
                               stdout=subprocess.PIPE, stderr=err,
                               timeout=max(timeout_s, 1.0))
    except subprocess.TimeoutExpired:
        return None, f"timed out after {timeout_s:.0f}s", time.time() - t0
    took = time.time() - t0
    text = p.stdout.decode(errors="replace")
    if p.returncode != 0:
        return None, f"exit {p.returncode} (see {log_path})", took
    try:
        return json.loads(text[text.index("{"):]), None, took
    except ValueError:
        return None, f"no JSON on stdout: {text[-200:]!r}", took


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="cut job counts and history for the CPU rehearsal "
                         "(every family, phase and check still runs)")
    ap.add_argument("--mesh-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.mesh_child:
        return mesh_child()

    t_start = time.time()
    size = SIZES["tiny" if args.tiny else "full"]
    deadline = t_start + size["budget_s"]
    checks = Checks()
    log_dir = os.path.join(HERE, "chiprun_out", "chip_smoke")
    os.makedirs(log_dir, exist_ok=True)
    summary: dict = {"size": "tiny" if args.tiny else "full", "phases": {}}

    # -- phase 0: what this checkout builds, it builds here --
    try:
        from foremast_tpu import native
        from foremast_tpu.engine.pipeline import compile_cache_dir
    except ImportError as e:
        # the script alone proves nothing: it drives the program beside it
        print(f"chip_smoke: no foremast_tpu to drive beside {HERE}: {e}",
              file=sys.stderr)
        return 2

    summary["parser"] = native.parser_name()
    summary["compile_cache_dir"] = compile_cache_dir()
    say(f"parser: {summary['parser']} ({native.lib_path() or 'no source'}); "
        f"compile cache: {summary['compile_cache_dir']}")
    checks.check("native_parser_built", summary["parser"] != "python",
                 "the C++ parser did not build from source here")

    # -- phase 1: compile at full width --
    chip_devices = []
    no_chip = False
    summary["phases"]["prewarm"] = []
    for n, (buckets, rungs) in enumerate(size["prewarm"]):
        name = f"prewarm[{n}]"
        say(f"{name}: families {FAMILIES} rungs {rungs} T-buckets {buckets}")
        pre, err, took = run_json_child(
            [sys.executable, "-m", "foremast_tpu", "prewarm",
             "--families", FAMILIES, "--rungs", rungs, "--buckets", buckets],
            deadline - time.time(), os.path.join(log_dir, f"prewarm-{n}.log"))
        if not checks.check(f"{name}:ran", pre is not None, err or ""):
            continue
        chip_devices.append((name, pre.get("platform"),
                             pre.get("device_kind"), pre.get("device_count")))
        summary["phases"]["prewarm"].append({
            "seconds": round(took, 1), "programs": pre["programs"],
            "backend_compiles": pre["backend_compiles"],
            "compile_cache_hits": pre["compile_cache_hits"],
            "rungs": pre["rungs"], "t_buckets": pre["t_buckets"]})
        say(f"{name}: {pre['programs']} programs, {pre['backend_compiles']} "
            f"backend compiles, {pre['compile_cache_hits']} cache hits, "
            f"{took:.0f}s on {pre.get('platform')}/{pre.get('device_kind')}"
            f" x{pre.get('device_count')}")
        if pre.get("platform") != "tpu" and not args.tiny:
            # the run has already failed, and a CPU cannot finish the
            # full-size phases inside the budget: stop here (--tiny is the
            # rehearsal that runs every phase without a chip)
            say(f"{name} ran on {pre.get('platform')}: no accelerator, "
                "skipping the remaining full-size phases")
            no_chip = True
            break
        checks.check(f"{name}:programs", pre["programs"] > 0)
        checks.check(f"{name}:cache_on",
                     pre.get("compile_cache") == summary["compile_cache_dir"],
                     f"prewarm cached in {pre.get('compile_cache')!r}, not "
                     f"in {summary['compile_cache_dir']!r}")

    if no_chip:
        return finish(summary, checks, chip_devices, t_start)

    # -- phases 2 + 3: serve on the default platform, then the CPU
    # reference, one after the other over the same frozen fleet --
    fleet = Fleet(size)
    backend_url = fleet.serve()
    say(f"fleet: {fleet.n_sim + fleet.n_lstm} jobs, seed {SEED}, "
        f"{len(fleet.truth)} injected anomalies, backend {backend_url}")
    try:
        chip = run_serve("serve", fleet, {}, deadline, 3.0, log_dir, checks)
        ref = run_serve("reference", fleet, {"JAX_PLATFORMS": "cpu"},
                        deadline, 3.0, log_dir, checks)
    finally:
        fleet.close()
    for rec in (chip, ref):
        summary["phases"][rec["label"]] = {
            k: rec.get(k) for k in
            ("seconds", "startup_s", "submit_s", "judge_s", "cycles_seen",
             "device", "submitted", "family_launches", "lstm_train_spans",
             "anomalies_injected", "anomalies_convicted", "compile",
             "containment", "worst_health_seen", "final_health",
             "exit_code")}
    if chip.get("device"):
        d = chip["device"]
        chip_devices.append(("serve", d["platform"], d["kind"], d["count"]))
    hits = (chip.get("compile") or {}).get("cache_hits", 0)
    checks.check("serve:compile_cache_hits", hits > 0,
                 f"serve replayed {hits} programs from the prewarm child's "
                 "cache")
    checks.check("reference:on_cpu",
                 (ref.get("device") or {}).get("platform") == "cpu",
                 f"reference child ran on {ref.get('device')}")
    cv, rv = chip.get("verdicts"), ref.get("verdicts")
    if checks.check("verdicts:both_collected", bool(cv) and bool(rv)):
        diff, reason_text = [], 0
        for key in sorted(cv):
            a, b = verdict_identity(key, cv[key]), None
            if key in rv:
                b = verdict_identity(key, rv[key])
                reason_text += cv[key]["reason"] != rv[key]["reason"]
            if a != b:
                diff.append((key, a, b))
        summary["verdicts_compared"] = len(cv)
        summary["verdicts_differing"] = len(diff)
        summary["reason_digits_differing"] = reason_text
        checks.check("verdicts:equal_to_cpu_reference", not diff,
                     f"{len(diff)} of {len(cv)} differ, e.g. "
                     f"{json.dumps(diff[:3])[:600]}")

    # -- phase 4: four chips, only where the host has them --
    n_dev = chip_devices[0][3] if chip_devices else 0
    if n_dev and n_dev >= 4:
        mesh, err, took = run_json_child(
            [sys.executable, os.path.abspath(__file__), "--mesh-child"],
            deadline - time.time(), os.path.join(log_dir, "mesh.log"))
        if checks.check("mesh:ran", mesh is not None, err or ""):
            chip_devices.append(("mesh", mesh.get("platform"),
                                 mesh.get("device_kind"),
                                 mesh.get("device_count")))
            summary["phases"]["mesh"] = dict(mesh, seconds=round(took, 1))
            checks.check("mesh:four_devices",
                         len(mesh["shard_devices"]) >= 4,
                         f"shards on devices {mesh['shard_devices']}")
            checks.check("mesh:equals_single_device",
                         mesh["verdicts_equal"] and mesh["summary_equal"]
                         and mesh["finite"],
                         json.dumps(mesh))
            say(f"mesh: {json.dumps(mesh)}")
    else:
        summary["phases"]["mesh"] = {
            "skipped": f"host has {n_dev} device(s); the mesh phase needs 4"}
        say(f"mesh: skipped, host has {n_dev} device(s), needs 4")

    return finish(summary, checks, chip_devices, t_start)


def finish(summary: dict, checks: Checks, chip_devices: list,
           t_start: float) -> int:
    """Where it ran (the one check no CPU run can pass), then the result:
    two JSON lines on stdout and 0, or the summary on stderr and 1."""
    summary["children"] = [list(c) for c in chip_devices]
    for name, platform, kind, count in chip_devices:
        checks.check(f"platform:{name}", platform == "tpu",
                     f"{name} child ran on {platform}/{kind} x{count}, "
                     "not on a tpu")
    devices = {c[1:] for c in chip_devices}
    checks.check("platform:children_agree", len(devices) == 1,
                 f"children disagree about the device: {sorted(devices)}")
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        checks.check("parent:no_jax_backend",
                     not xla_bridge.backends_are_initialized(),
                     "this parent initialised a JAX backend")

    summary.update(
        seconds=round(time.time() - t_start, 1), checks_passed=checks.passed,
        failed=checks.failed, ok=not checks.failed, claim=None)
    if checks.failed:
        print(json.dumps(summary), file=sys.stderr)
        say(f"FAILED {len(checks.failed)} check(s) of "
            f"{checks.passed + len(checks.failed)}: {checks.failed}")
        return 1
    platform, kind, count = next(iter(devices))
    print(json.dumps(summary))
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
