"""simfleet driver: run 100k+ simulated jobs through in-process replicas.

Measures what the ROADMAP previously projected from 500-job benches:
steady-state jobs/s, resident memory, device launches per cycle, delta
hit ratios — at fleet scale, against the REAL engine (production parse
path, delta window cache, pipeline, triage, memo), with ground-truth
anomaly accounting from the trace labels. `run_fleet_ab` is the
mega-batch acceptance harness: identical fleet and sample stream with
MEGABATCH on vs off, byte-identical verdict digests required, per-family
launch collapse and padding-waste ratio reported.

Every result dict records seed, trace shape, and fleet size up front
(docs/benchmarks.md): reproducible from the artifact alone.
"""
from __future__ import annotations

import json
import time

__all__ = ["run_fleet", "run_fleet_ab", "run_jobstore", "run_live",
           "create_body", "main"]


def _rss_bytes() -> int:
    """Current resident set (not the monotonic ru_maxrss peak — A/B legs
    share a process, so the peak would lie for the second leg)."""
    try:
        with open("/proc/self/statm") as f:
            import os

            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        import resource

        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def _digest(store) -> str:
    from ..engine.jobs import verdict_digest

    return verdict_digest(store)


class _ShardShim:
    """Static in-process ownership over a HashRing — the driver's
    multi-replica seam (the PR 8 ShardManager needs an archive medium;
    the simulator partitions the same way without one)."""

    def __init__(self, ring, me: str):
        self._ring = ring
        self._me = me

    def owns(self, job_id: str) -> bool:
        return self._ring.owner(job_id) == self._me

    def health_summary(self) -> dict:
        return {"replicas": len(self._ring.members)}


def run_fleet(jobs: int = 2000, seed: int = 0, shape: str = "diurnal",
              cycles: int = 6, cadence_s: float = 10.0, replicas: int = 1,
              megabatch: bool = False, stream: bool = False,
              spec=None, provenance: bool = True,
              anomaly_rate: float | None = None, store=None) -> dict:
    """One simfleet leg. Returns the honesty-convention bench dict.

    `store` lets a caller supply the JobStore (run_jobstore passes a
    tier-backed one so the engine's verdicts ride the WAL/segment path);
    default is the plain RAM store every other leg uses."""
    import numpy as np  # noqa: F401  (transitively required)

    from ..dataplane.delta import DeltaWindowSource
    from ..engine import jobs as J
    from ..engine.analyzer import Analyzer
    from ..engine.config import EngineConfig
    from ..engine.sharding import HashRing
    from ..utils import tracing
    from .backend import SimBackend
    from .trace import SimTrace, lead_steps, preset

    if spec is None:
        over = {}
        if anomaly_rate is not None:
            over["anomaly_rate"] = anomaly_rate
        spec = preset(shape, jobs, seed, **over)
    step = spec.step_s
    t0 = 1_700_000_000 // step * step
    lead = lead_steps(spec)
    hist = spec.hist_windows * spec.window_steps
    W = spec.window_steps
    arrivals_per_cycle = int(round(spec.churn_per_cycle * spec.jobs))
    extra = arrivals_per_cycle * cycles
    horizon = lead + hist + W + int(cycles * cadence_s) // step + 16
    trace = SimTrace(spec, t0, horizon, extra_jobs=extra)
    backend = SimBackend(trace)
    inner = backend.source()
    source = DeltaWindowSource(
        inner, max_entries=max(8192, 4 * (spec.jobs + extra)),
        clock=lambda: backend.now)
    if store is None:
        store = J.JobStore()
    for d in backend.make_docs():
        store.create(d)

    cfg = EngineConfig(megabatch=megabatch, provenance=provenance,
                       window_cache_max=max(8192, 4 * (spec.jobs + extra)))
    reps = max(int(replicas), 1)
    names = [f"sim-rep-{r}" for r in range(reps)]
    ring = HashRing(names) if reps > 1 else None
    engines = []
    for name in names:
        eng = Analyzer(cfg, source, store)
        if ring is not None:
            eng.shard = _ShardShim(ring, name)
        engines.append(eng)

    warm_now = float(t0 + (lead + hist + W) * step) + 5.0
    backend.set_now(warm_now)
    t_warm = time.perf_counter()
    for name, eng in zip(names, engines):
        eng.run_cycle(worker=name, now=backend.now)
    warm_s = time.perf_counter() - t_warm

    receiver = None
    dirty: set = set()
    if stream:
        if reps != 1:  # CLI-reachable: a typed error, not a bare assert
            raise ValueError("stream mode drives a single replica "
                             f"(got replicas={reps})")
        from ..ingest import (IngestReceiver, encode_remote_write,
                              snappy_compress)

        receiver = IngestReceiver(
            store, delta_source=source, exporter=engines[0].exporter,
            notify_fn=lambda ids: dirty.update(ids))
    tracing.tracer.reset()
    fetches0 = inner.request_count
    backend.requests = 0
    launches0 = sum(e.device_launches for e in engines)
    mega0 = [(e.megabatch_launches_total, e.megabatch_real_rows_total,
              e.megabatch_pad_rows_total) for e in engines]
    for eng in engines:
        eng.reset_slo()
    next_job = spec.jobs
    scored = 0
    tick_seen: set = set()
    fam_launches: dict[str, int] = {}
    fam_replicas: dict[str, set] = {}
    pushed_until = warm_now

    t_start = time.perf_counter()
    for _ in range(cycles):
        backend.set_now(backend.now + cadence_s)
        now = backend.now
        if arrivals_per_cycle:
            for d in backend.make_docs(next_job, arrivals_per_cycle):
                store.create(d)
            next_job += arrivals_per_cycle
        if receiver is not None:
            series = backend.push_series(pushed_until, now, 0, next_job)
            pushed_until = now
            if series:
                raw = snappy_compress(encode_remote_write(series))
                status, _ = receiver.handle(
                    "remote_write", raw,
                    content_type="application/x-protobuf",
                    content_encoding="snappy", now=now)
                if status != 200:
                    # CLI-reachable: a typed error, not a bare assert — a
                    # dropped push would mislabel the artifact "stream".
                    raise ValueError(
                        f"stream push rejected with status {status}")
                if dirty:
                    ids = frozenset(dirty)  # snapshot BEFORE clearing:
                    dirty.clear()  # the receiver repopulates it live
                    partial_ids = engines[0].run_cycle(
                        worker=names[0], now=now, job_ids=ids,
                        partial=True).keys()
                    # a job judged by the partial cycle is re-confirmed
                    # (memo-hit) by the full sweep below in the SAME
                    # cadence tick — count it once per tick, and fold the
                    # partial cycle's launches into the by-family totals
                    # (device_launches already includes them).
                    scored += len(partial_ids)
                    tick_seen.update(partial_ids)
                    fl = engines[0].last_cycle_stages.get(
                        "family_launches") or {}
                    for fam, c in fl.items():
                        fam_launches[fam] = fam_launches.get(fam, 0) + c
                        fam_replicas.setdefault(fam, set()).add(0)
        for ri, (name, eng) in enumerate(zip(names, engines)):
            scored += sum(1 for j in eng.run_cycle(worker=name, now=now)
                          if j not in tick_seen)
            fl = eng.last_cycle_stages.get("family_launches") or {}
            for fam, c in fl.items():
                fam_launches[fam] = fam_launches.get(fam, 0) + c
                fam_replicas.setdefault(fam, set()).add(ri)
        tick_seen.clear()
    wall = time.perf_counter() - t_start

    launches = sum(e.device_launches for e in engines) - launches0
    mega_l = mega_r = mega_p = 0
    for e, (l0, r0, p0) in zip(engines, mega0):
        mega_l += e.megabatch_launches_total - l0
        mega_r += e.megabatch_real_rows_total - r0
        mega_p += e.megabatch_pad_rows_total - p0
    snap = source.snapshot()
    # resident window memory: the delta cache's actual bytes — the
    # per-job figure the RSS number (which carries the process baseline)
    # cannot give at small fleets
    win_bytes = source.window_bytes()
    # ground truth: labeled job ids (hpa jobs never complete, so they are
    # outside the conviction contract) vs actual convictions
    truth_idx = trace.truth_jobs(next_job)
    labeled = {backend.job_id(j) for j in truth_idx
               if backend.class_of(j) != "hpa"}
    convicted = {d.id for d in store.by_status(J.COMPLETED_UNHEALTH)}
    tp = len(labeled & convicted)
    stats = tracing.tracer.stats()
    rss = _rss_bytes()  # one read: the two RSS fields must agree
    out = {
        # -- reproducibility header (docs/benchmarks.md convention) --
        "seed": spec.seed,
        "trace": spec.as_dict(),
        "fleet": next_job,
        "replicas": reps,
        "cycles": cycles,
        "cadence_s": cadence_s,
        "megabatch": megabatch,
        "stream": stream,
        # -- measured figures --
        "jobs_per_sec": round(scored / wall, 1) if wall > 0 else 0.0,
        "wall_s": round(wall, 3),
        "warm_s": round(warm_s, 3),
        "jobs_scored": scored,
        "preprocess_s_per_cycle": round(
            stats.get("engine.preprocess", {}).get("total_seconds", 0.0)
            / cycles, 4),
        "fetches_per_cycle": round(
            (inner.request_count - fetches0) / cycles, 1),
        "device_launches_per_cycle": round(launches / cycles, 2),
        # per cycle PER POPULATED REPLICA: each replica dispatches its
        # own mega launch for its shard slice, so a collapsed family
        # reads 1.0 at any replica count (the run_fleet_ab gate keys off
        # == 1.0). The denominator counts only replicas that ever
        # launched the family — a sparse family (bivariate at small
        # fleets) can land on fewer than `reps` shards, and the empty
        # replicas must not dilute a genuine collapse below 1.0.
        "launches_per_cycle_by_family": {
            f: round(c / (cycles * len(fam_replicas[f])), 2)
            for f, c in sorted(fam_launches.items())},
        "delta_hit_ratio": snap["hit_ratio"],
        "resident_rss_bytes": rss,
        "resident_rss_per_job": round(rss / max(next_job, 1), 1),
        "window_cache_bytes": win_bytes,
        "window_cache_bytes_per_job": round(win_bytes / max(next_job, 1),
                                            1),
        "churn_arrivals": next_job - spec.jobs,
        "truth": {
            "labeled": len(labeled),
            "convicted": len(convicted),
            "true_positives": tp,
            "false_positives": len(convicted - labeled),
            "recall": round(tp / len(labeled), 4) if labeled else None,
        },
        "verdict_digest": _digest(store),
    }
    if megabatch:
        out["megabatch_stats"] = {
            "launches_per_cycle": round(mega_l / cycles, 2),
            "real_rows_per_cycle": round(mega_r / cycles, 1),
            "padded_rows_per_cycle": round(mega_p / cycles, 1),
            "padding_waste_ratio": round(mega_p / mega_r, 6)
            if mega_r else 0.0,
        }
    if stream:
        out["ingest_spliced_points"] = snap["ingest_spliced_points"]
        out["ingest_served_windows"] = snap["ingest_hits"]
    return out


def run_fleet_ab(jobs: int = 2000, seed: int = 0, shape: str = "diurnal",
                 cycles: int = 6, cadence_s: float = 60.0,
                 replicas: int = 1, rounds: int = 2) -> dict:
    """The mega-batch acceptance A/B: identical simulated fleet with
    MEGABATCH on vs off. The contract: byte-identical verdict digests,
    the per-family launch collapse visible (families at exactly one
    launch per cycle), and the padding-waste ratio on record.

    Interleaved best-of-round like every A/B in bench_cycle (sequential
    pairs misattribute scheduling noise to one side); digests are
    checked EVERY round. `rounds=1` keeps a huge-fleet run affordable —
    at the cost of that noise sensitivity, which the artifact records.

    Default cadence is the 60 s metric step — every cycle advances every
    window (the launch-bound regime mega-batching exists for; a 10 s
    cadence mostly measures memo hits and zero launches either way)."""
    on = off = None
    identical = True
    for _ in range(max(int(rounds), 1)):
        leg_off = run_fleet(jobs, seed, shape, cycles, cadence_s,
                            replicas, megabatch=False)
        leg_on = run_fleet(jobs, seed, shape, cycles, cadence_s,
                           replicas, megabatch=True)
        identical &= (leg_on["verdict_digest"]
                      == leg_off["verdict_digest"])
        if on is None or leg_on["jobs_per_sec"] > on["jobs_per_sec"]:
            on = leg_on
        if off is None or leg_off["jobs_per_sec"] > off["jobs_per_sec"]:
            off = leg_off
    fams_on = on["launches_per_cycle_by_family"]
    # exactly ONE launch every cycle is the collapse claim the gate and
    # the artifact make; an under-1 average (quiet cadence, memo hits)
    # is absorption, not single-dispatch, and must not satisfy it
    collapsed = sorted(f for f, c in fams_on.items() if c == 1.0)
    return {
        "metric": "simfleet_megabatch_jobs_per_sec",
        "value": on["jobs_per_sec"],
        "unit": "jobs/s",
        "seed": seed,
        "rounds": max(int(rounds), 1),
        "trace": on["trace"],
        "fleet": on["fleet"],
        "verdicts_identical": identical,
        "jobs_per_sec_on": on["jobs_per_sec"],
        "jobs_per_sec_off": off["jobs_per_sec"],
        "speedup": round(on["jobs_per_sec"]
                         / max(off["jobs_per_sec"], 1e-9), 3),
        "launches_per_cycle_on": on["device_launches_per_cycle"],
        "launches_per_cycle_off": off["device_launches_per_cycle"],
        "families_single_launch": collapsed,
        "padding_waste_ratio":
            on.get("megabatch_stats", {}).get("padding_waste_ratio"),
        "on": on,
        "off": off,
    }


def run_jobstore(jobs: int = 100000, seed: int = 0, shape: str = "diurnal",
                 cycles: int = 3, cadence_s: float = 60.0,
                 tier_dir: str = "", open_jobs: int = 0,
                 hot_seconds: float = 0.0, fsync: bool = False,
                 checkpoint_every: int = 25000,
                 segment_max_mb: int = 4096) -> dict:
    """Crash-durable job-store leg at fleet scale (the 1M-per-replica
    gate). Three passes over ONE deterministic workload:

      1. **tier on** — an open subset is scored by the real engine
         (run_fleet, the production parse/score path, every transition
         WAL'd) and the terminal majority is driven through the real
         store.transition() chain with spill+evict on the checkpoint
         cadence. Measures steady jobs/s through the durable path and
         resident bytes/job after eviction.
      2. **restart** — a FRESH JobTier+JobStore over the same directory
         recovers (index rebuild + WAL replay + open-doc restore),
         timed; its verdict digest must equal leg 1's byte-for-byte.
      3. **tier off** — the identical workload into a RAM-only store;
         byte-identical digest required (durability must not change one
         verdict).

    `hot_seconds=0` evicts every spilled terminal doc at the next
    checkpoint — the configuration the resident-bytes figure is FOR.
    `tier_dir=""` uses a temp dir removed afterward."""
    import random
    import shutil
    import tempfile

    from ..engine import jobs as J
    from ..engine.jobtier import JobTier

    if open_jobs <= 0:
        open_jobs = max(min(jobs // 20, 50000), 200)
    open_jobs = min(open_jobs, jobs)
    terminal_n = max(jobs - open_jobs, 0)
    checkpoint_every = max(int(checkpoint_every), 1)

    def _drive_terminal(store, checkpoint: bool) -> float:
        """Create -> claim-advance -> terminal verdict for the cold
        majority, deterministic per seed (identical across all legs)."""
        rng = random.Random(seed * 1_000_003 + 17)
        t0 = time.perf_counter()
        for i in range(terminal_n):
            jid = f"jsb-{seed}-{i:07d}"
            store.create(J.Document(
                id=jid, app_name=f"app-{i % 997}", namespace="jobstore",
                strategy="rollingUpdate", start_time="START",
                end_time="END"))
            store.advance(jid, J.PREPROCESS_INPROGRESS,
                          J.PREPROCESS_COMPLETED,
                          J.POSTPROCESS_INPROGRESS, worker="simjobstore")
            r = rng.random()
            if r < 0.03:
                ts = 1_700_000_000 + i
                store.transition(
                    jid, J.COMPLETED_UNHEALTH,
                    reason=f"anomaly p={r:.6f}",
                    anomaly={"latency": [float(ts), round(1.0 + r, 4)]})
            elif r < 0.04:
                store.transition(jid, J.COMPLETED_UNKNOWN,
                                 reason="insufficient data")
            else:
                store.transition(jid, J.COMPLETED_HEALTH,
                                 reason="healthy")
            if checkpoint and (i + 1) % checkpoint_every == 0:
                store.tier_checkpoint(force=True)
        return time.perf_counter() - t0

    made_tmp = not tier_dir
    if made_tmp:
        tier_dir = tempfile.mkdtemp(prefix="simjobstore-")
    try:
        # ---- leg 1: tier on (runs FIRST so its RSS figure is not
        # polluted by the RAM leg's 1M-doc high-water mark — CPython
        # keeps freed arenas resident) ----
        tier = JobTier(tier_dir, fsync=fsync,
                       segment_max_bytes=max(int(segment_max_mb), 1)
                       * (1 << 20))
        store_on = J.JobStore(tier=tier, tier_hot_seconds=hot_seconds)
        open_on = run_fleet(open_jobs, seed, shape, cycles, cadence_s,
                            store=store_on)
        store_on.tier_checkpoint(force=True)
        rss_mid = _rss_bytes()  # baseline: engine warm, majority not yet
        drive_s = _drive_terminal(store_on, checkpoint=True)
        store_on.tier_checkpoint(force=True)
        rss_on = _rss_bytes()  # BEFORE the digest walk re-materializes
        with store_on._lock:
            hot_docs = len(store_on._jobs)
        digest_on = _digest(store_on)
        tier_stats = store_on.tier_snapshot()
        store_on.close()

        # ---- leg 2: restart-recovery over the same directory ----
        t0 = time.perf_counter()
        tier2 = JobTier(tier_dir, fsync=fsync,
                        segment_max_bytes=max(int(segment_max_mb), 1)
                        * (1 << 20))
        store_rec = J.JobStore(tier=tier2, tier_hot_seconds=hot_seconds)
        rec_stats = store_rec.recover_from_tier()
        recovery_s = time.perf_counter() - t0
        digest_rec = _digest(store_rec)
        store_rec.close()

        # ---- leg 3: tier off (RAM-only identity reference) ----
        store_off = J.JobStore()
        open_off = run_fleet(open_jobs, seed, shape, cycles, cadence_s,
                             store=store_off)
        drive_off_s = _drive_terminal(store_off, checkpoint=False)
        digest_off = _digest(store_off)
    finally:
        if made_tmp:
            shutil.rmtree(tier_dir, ignore_errors=True)

    on_jps = round(terminal_n / drive_s, 1) if drive_s > 0 else 0.0
    off_jps = round(terminal_n / drive_off_s, 1) if drive_off_s > 0 \
        else 0.0
    return {
        "metric": "jobstore_steady_jobs_per_sec",
        "value": on_jps,
        "unit": "jobs/s",
        # -- reproducibility header --
        "seed": seed,
        "trace": open_on["trace"],
        "fleet": jobs,
        "open_jobs": open_jobs,
        "terminal_jobs": terminal_n,
        "cycles": cycles,
        "cadence_s": cadence_s,
        "checkpoint_every": checkpoint_every,
        "hot_seconds": hot_seconds,
        "fsync": fsync,
        "segment_max_mb": segment_max_mb,
        # -- measured figures --
        "steady_jobs_per_sec": on_jps,
        "steady_jobs_per_sec_ram": off_jps,
        "durability_cost_ratio": round(off_jps / on_jps, 3)
        if on_jps else None,
        "resident_rss_bytes": rss_on,
        "resident_rss_per_job": round(rss_on / max(jobs, 1), 1),
        # the 1M claim: what the terminal majority ADDED to the warm
        # process, per job, with the cold set evicted to the segment
        "terminal_resident_delta_per_job": round(
            max(rss_on - rss_mid, 0) / max(terminal_n, 1), 1),
        "ram_docs_after_evict": hot_docs,
        "tier": tier_stats,
        "recovery": {"wall_seconds": round(recovery_s, 3), **rec_stats},
        "digests": {"tier_on": digest_on, "recovered": digest_rec,
                    "tier_off": digest_off},
        "verdicts_identical": digest_on == digest_rec == digest_off,
        "open_leg_jobs_per_sec": open_on["jobs_per_sec"],
        "open_leg_truth": open_on["truth"],
        "open_leg_truth_ram": open_off["truth"],
    }


def create_body(doc, strategy: str, start_rfc: str, end_rfc: str) -> dict:
    """The /v1/healthcheck/create request for a simulator Document: its
    per-metric query URLs by category, with the hpa flags riding every
    entry (they equal the service's defaults on non-hpa metrics). The one
    place that knows the wire shape, for `run_live` and `chip_smoke.py`."""
    info: dict = {"current": {}, "baseline": {}, "historical": {}}
    for m, q in doc.metrics.items():
        flags = {"priority": q.priority, "isIncrease": q.is_increase}
        for cat, url in (("current", q.current), ("baseline", q.baseline),
                         ("historical", q.historical)):
            if url:
                info[cat][m] = {"url": url, **flags}
    return {"appName": doc.app_name, "namespace": doc.namespace,
            "strategy": strategy, "startTime": start_rfc,
            "endTime": end_rfc, "metricsInfo": info}


def run_live(endpoint: str, jobs: int = 200, seed: int = 0,
             shape: str = "diurnal", duration_s: float = 60.0,
             push: bool = False, serve_port: int = 0) -> dict:
    """Drive a LIVE replica with a simulated fleet (docs/operations.md):
    serve the trace over HTTP, submit canary analyses whose query URLs
    point at it, and (optionally) stream the advancing samples to the
    replica's /ingest/remote-write. The replica does everything else."""
    import urllib.request

    from ..ops.windowing import align_step
    from ..utils.timeutils import to_rfc3339
    from .backend import SimBackend
    from .trace import SimTrace, lead_steps, preset

    spec = preset(shape, jobs, seed)
    step = spec.step_s
    lead = lead_steps(spec)
    hist = spec.hist_windows * spec.window_steps
    W = spec.window_steps
    horizon = lead + hist + W + int(duration_s) // step + 16
    # anchor so the current windows END around wall-now and keep growing
    t0 = align_step(time.time()) - (lead + hist + W) * step
    trace = SimTrace(spec, t0, horizon, extra_jobs=0)
    backend = SimBackend(trace, clock=time.time)
    srv, base = backend.serve(serve_port)
    backend.url_base = base
    submitted, errors = [], 0
    id_map: dict = {}  # simulator job idx -> the replica's assigned id
    try:
        start_rfc = to_rfc3339(t0)
        end_rfc = to_rfc3339(int(time.time() + duration_s + 3600))
        for idx, doc in enumerate(backend.make_docs()):
            body = create_body(doc, "canary", start_rfc, end_rfc)
            req = urllib.request.Request(
                endpoint.rstrip("/") + "/v1/healthcheck/create",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=10) as r:
                    jid = json.loads(r.read())["jobId"]
                    submitted.append(jid)
                    id_map[idx] = jid
            except Exception:  # noqa: BLE001 - count and continue
                errors += 1
        t_end = time.time() + duration_s
        pushed_until = time.time()
        while time.time() < t_end:
            time.sleep(min(step / 2, max(t_end - time.time(), 0.1)))
            if push:
                from ..ingest import encode_remote_write, snappy_compress

                series = backend.push_series(pushed_until, time.time(),
                                             id_map=id_map)
                pushed_until = time.time()
                if not series:
                    continue
                raw = snappy_compress(encode_remote_write(series))
                req = urllib.request.Request(
                    endpoint.rstrip("/") + "/ingest/remote-write",
                    data=raw,
                    headers={"Content-Type": "application/x-protobuf",
                             "Content-Encoding": "snappy"})
                try:
                    urllib.request.urlopen(req, timeout=10).read()
                except Exception:  # noqa: BLE001
                    errors += 1
    finally:
        srv.shutdown()
        srv.server_close()
    return {"seed": seed, "trace": spec.as_dict(), "fleet": jobs,
            "endpoint": endpoint, "backend_url": base,
            "submitted": len(submitted), "errors": errors,
            "backend_requests": backend.requests,
            "bytes_served": backend.bytes_served}


def main() -> None:
    """`python -m foremast_tpu.simfleet` — knobs are the SIM_* registry
    entries (docs/configuration.md); prints ONE JSON line."""
    from ..utils import knobs

    jobs = knobs.read("SIM_JOBS")
    seed = knobs.read("SIM_SEED")
    shape = knobs.read("SIM_TRACE")
    cycles = knobs.read("SIM_CYCLES")
    cadence = knobs.read("SIM_CADENCE_S")
    replicas = knobs.read("SIM_REPLICAS")
    if knobs.read("SIM_JOBSTORE"):
        out = run_jobstore(
            jobs, seed, shape, cycles, cadence,
            tier_dir=knobs.read("SIM_JOBSTORE_DIR"),
            open_jobs=knobs.read("SIM_JOBSTORE_OPEN"),
            hot_seconds=knobs.read("SIM_JOBSTORE_HOT_S"),
            fsync=knobs.read("JOB_STORE_FSYNC"))
    elif knobs.read("SIM_AB"):
        out = run_fleet_ab(jobs, seed, shape, cycles, cadence, replicas,
                           rounds=knobs.read("SIM_ROUNDS"))
    else:
        out = run_fleet(jobs, seed, shape, cycles, cadence, replicas,
                        megabatch=knobs.read("MEGABATCH"),
                        stream=knobs.read("SIM_STREAM"))
    print(json.dumps(out))  # lint: disable=thread-hygiene -- bench entry point: ONE JSON artifact line on stdout (docs/benchmarks.md)


if __name__ == "__main__":
    main()
