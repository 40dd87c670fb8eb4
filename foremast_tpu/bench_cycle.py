# lint: disable-file=knob-registry -- bench-only BENCH_* knobs, not a deployment surface (docs/benchmarks.md)
"""Host-path cycle benchmark: fetch -> parse -> resample -> pack -> score -> verdict.

The device kernel's pairs/s (bench.py headline) bounds only the score
stage; at fleet scale the reference brain spent its cycle on the host
(ES poll, HTTP fetch, JSON parse, pandas resample — SURVEY.md §3.1,
foremast-brain's worker loop). This bench measures OUR host path: a
synthetic fleet of N pair jobs whose canned Prometheus query_range
responses flow through the production parse path
(dataplane.fetch.RawFixtureDataSource) and Analyzer.run_cycle to
verdict writes and the snapshot flush.

Run as a module; prints ONE JSON line on stdout:

    FOREMAST_NATIVE=0|1 BENCH_CYCLE_JOBS=10000 python -m foremast_tpu.bench_cycle

bench.py runs it twice — native parser on and off — and merges both
numbers into the headline bench line. FOREMAST_NATIVE is latched at the
first native-library load, which is why each variant needs its own
process. Scoring runs wherever JAX lands (bench.py pins the
subprocesses to CPU so they never contend with the parent's TPU grant);
the device-side bound is bench.py's own headline measurement.
"""
from __future__ import annotations

import json
import os
import tempfile
import time


def _prom_body(ts0: int, values, step: int = 60) -> bytes:
    """A Prometheus query_range matrix response (values serialized as
    strings, as the real API does)."""
    vals = [[ts0 + i * step, f"{v:.4f}"] for i, v in enumerate(values)]
    return json.dumps(
        {
            "status": "success",
            "data": {
                "resultType": "matrix",
                "result": [
                    {"metric": {"__name__": "namespace_app_http_errors_5xx"},
                     "values": vals}
                ],
            },
        }
    ).encode()


def run(n_jobs: int = 10_000, cycles: int = 2, window_steps: int = 128,
        mix: bool = False, provenance: bool = True) -> dict:
    """mix=False: a pure pair-job fleet (round-over-round continuity with
    the r1-r3 artifacts). mix=True: a realistic model-family mix — 60%
    pair, 20% band, 10% bivariate, 5% 3-metric LSTM-AE, 5% HPA — with the
    score stage decomposed per family from the engine's tracer spans and
    the (budgeted) LSTM train-on-miss cost reported separately."""
    import numpy as np

    from .dataplane.fetch import RawFixtureDataSource
    from .engine import jobs as J
    from .engine.analyzer import Analyzer
    from .engine.config import EngineConfig
    from . import native
    from .utils import tracing
    from .utils.timeutils import to_rfc3339

    t_end = int(time.time()) // 60 * 60
    ts0 = t_end - window_steps * 60
    hist_steps = 4 * window_steps
    ts0_hist = t_end - (hist_steps + window_steps) * 60
    rng = np.random.default_rng(7)
    # 64 distinct series shapes; baseline and current of one job share a
    # body (identical samples -> provably healthy -> the fleet requeues
    # intact every cycle, keeping jobs/s denominators comparable). Band/
    # bi/LSTM/HPA jobs use "latency"-policy metrics (wide 10-sigma band)
    # with history drawn from the same distribution as current: healthy.
    bodies = [
        _prom_body(ts0, 10.0 + rng.normal(0.0, 2.0, window_steps))
        for _ in range(64)
    ]
    hist_bodies = [
        _prom_body(ts0_hist, 10.0 + rng.normal(0.0, 2.0, hist_steps))
        for _ in range(16)
    ]

    def resolver(url: str) -> bytes:
        i = int(url.rsplit("job=", 1)[1].split("&", 1)[0])
        if "w=hist" in url:
            return hist_bodies[i % len(hist_bodies)]
        return bodies[i % len(bodies)]

    source = RawFixtureDataSource(resolver=resolver)

    def pair_doc(i):
        return J.Document(
            id=f"bench-{i}", app_name=f"app-{i % 128}", namespace="bench",
            strategy="canary",
            start_time=to_rfc3339(t_end - 3600),
            end_time=to_rfc3339(t_end + 86_400),
            metrics={"http_errors_5xx": J.MetricQueries(
                current=f"http://prom/q?job={i}&w=cur",
                baseline=f"http://prom/q?job={i}&w=base",
            )},
        )

    def _mq(i, m):
        return J.MetricQueries(
            current=f"http://prom/q?job={i}&m={m}&w=cur",
            historical=f"http://prom/q?job={i}&m={m}&w=hist",
        )

    def band_doc(i):
        d = pair_doc(i)
        d.metrics = {"latency": _mq(i, "lat")}
        return d

    def bi_doc(i):
        d = pair_doc(i)
        d.metrics = {"latency": _mq(i, "lat"), "cpu": _mq(i + 1, "cpu")}
        return d

    def lstm_doc(i):
        d = pair_doc(i)
        # a bounded set of app identities so the AE cache warms across
        # cycles under the LSTM_MAX_TRAIN_PER_CYCLE budget
        d.app_name = f"lstm-app-{i % 32}"
        d.metrics = {
            m: _mq(i + k, m) for k, m in enumerate(("latency", "cpu", "tps"))
        }
        return d

    def hpa_doc(i):
        d = pair_doc(i)
        d.strategy = "hpa"
        tps = _mq(i, "tps")
        lat = _mq(i + 1, "lat")
        lat.priority, lat.is_increase = 1, True
        d.metrics = {"tps": tps, "latency": lat}
        return d

    docs = []
    fam_counts = {}
    if mix:
        makers = (("pair", pair_doc, 0.60), ("band", band_doc, 0.20),
                  ("bivariate", bi_doc, 0.10), ("lstm", lstm_doc, 0.05),
                  ("hpa", hpa_doc, 0.05))
        remaining = n_jobs
        for fam, mk, frac in makers:
            if fam == "hpa":  # absorb rounding: total is exactly n_jobs
                n = remaining
            else:  # min-1 per family, but never overrun tiny fleets
                n = min(max(int(n_jobs * frac), 1), remaining)
            remaining -= n
            fam_counts[fam] = n
            base = len(docs)
            for k in range(n):
                d = mk(base + k)
                d.id = f"bench-{fam}-{k}"
                docs.append(d)
    else:
        fam_counts["pair"] = n_jobs
        docs = [pair_doc(i) for i in range(n_jobs)]

    from .engine.pipeline import CompileCounter

    with tempfile.TemporaryDirectory() as tmp:
        store = J.JobStore(snapshot_path=os.path.join(tmp, "jobs.json"))
        for d in docs:
            store.create(d)
        # pinned EngineConfig defaults for run-over-run comparability
        from .engine.config import _env_bool as _eb

        engine = Analyzer(
            EngineConfig(
                         # mega-batch passthrough so the legacy mixed
                         # bench can A/B the single-dispatch path too
                         megabatch=_eb(os.environ, "MEGABATCH", False),
                         # this bench replays a STATIC fixture each cycle,
                         # so SCORE_MEMO=1 would measure fingerprint hits
                         # instead of scoring — the steady-state figure
                         # lives in run_steady. Off here by default,
                         # env-overridable for A/B.
                         score_memo=_eb(os.environ, "SCORE_MEMO", False),
                         provenance=provenance),
            source, store)

        with CompileCounter() as cc_warm:
            out = engine.run_cycle(now=t_end)  # warmup: jit compile + caches
            not_requeued = sum(1 for s in out.values() if s != J.INITIAL)
            # warm the LSTM train-on-miss cache to steady state before
            # timing: a bounded-identity fleet trains each identity ONCE
            # (budgeted over the first ceil(identities/budget) cycles) and
            # then scores from cache forever — that steady state is what
            # the throughput figure means. Warm-up training cost is
            # reported separately below (lstm_train_warmup_*); the timed
            # cycles then carry only the residual (usually zero) train
            # cost, decomposed as before.
            warmup_cycles = 1
            while (mix and engine._lstm_trained_this_cycle > 0
                   and warmup_cycles < 12):
                engine.run_cycle(now=t_end)
                warmup_cycles += 1
        warm_tr = tracing.tracer.stats().get("engine.lstm_train", {})
        warmup_fields = {
            "warmup_cycles": warmup_cycles,
            "lstm_train_warmup_s": round(warm_tr.get("total_seconds", 0.0), 4),
            "lstm_train_warmup_count": warm_tr.get("count", 0),
        }
        tracing.tracer.reset()
        source.requests.clear()
        launches0 = engine.device_launches
        mega0 = (engine.megabatch_launches_total,
                 engine.megabatch_real_rows_total,
                 engine.megabatch_pad_rows_total)

        t0 = time.perf_counter()
        # steady-state compile counter: the rung/bucket design promises
        # ZERO fresh XLA programs once warm (tests/test_pipeline.py
        # enforces it); a nonzero count here means a shape leaked
        with CompileCounter() as cc_steady:
            for _ in range(cycles):
                engine.run_cycle(now=t_end)
        wall = time.perf_counter() - t0
        launch_fields = {
            "device_launches_per_cycle": round(
                (engine.device_launches - launches0) / cycles, 2),
            "family_launches": dict(
                engine.last_cycle_stages.get("family_launches") or {}),
        }
        if engine.config.megabatch:
            # packing-efficiency trajectory: padded/real waste and mega
            # launches per cycle must be visible in the BENCH record so
            # padding-class regressions show up round over round
            real = engine.megabatch_real_rows_total - mega0[1]
            padded = engine.megabatch_pad_rows_total - mega0[2]
            launch_fields["megabatch"] = {
                "launches_per_cycle": round(
                    (engine.megabatch_launches_total - mega0[0]) / cycles,
                    2),
                "padding_waste_ratio": round(padded / real, 6)
                if real else 0.0,
            }
        verdict_digest = J.verdict_digest(store)

    stats = tracing.tracer.stats()
    per_cycle = lambda name: round(  # noqa: E731
        stats.get(name, {}).get("total_seconds", 0.0) / cycles, 4
    )
    # Host-only throughput: the cycle minus the score stage. This bench is
    # CPU-pinned (see module docstring), so the score stage here is CPU
    # compute that the production chip runs far faster (bench.py's headline
    # measures it on the real device with forced completion) — on CPU it
    # would otherwise swamp the host path and turn the native-vs-python
    # parser comparison into machine-load noise. wall - score is exactly
    # the part of the cycle this bench exists to measure:
    # fetch -> parse -> resample -> pack -> verdict -> snapshot.
    # (Both clocks are steady since the tracer moved to time.monotonic()
    # durations; the guard below only covers the degenerate zero-score
    # case.)
    score_total = stats.get("engine.score", {}).get("total_seconds", 0.0)
    host_wall = wall - score_total
    host_fields = (
        {"host_jobs_per_sec": round(n_jobs * cycles / host_wall, 1)}
        if host_wall > 0 else {}
    )
    mix_fields = {}
    if mix:
        mix_fields.update(warmup_fields)
        mix_fields["family_jobs"] = fam_counts
        mix_fields["family_score_s_per_cycle"] = {
            fam: per_cycle(f"engine.score.{fam}")
            for fam in ("pair", "band", "bivariate", "lstm", "hpa")
        }
        # the bounded train-on-miss figure (VERDICT r3 #3): per-cycle AE
        # training seconds and count, capped by LSTM_MAX_TRAIN_PER_CYCLE
        tr = stats.get("engine.lstm_train", {})
        mix_fields["lstm_train_s_per_cycle"] = round(
            tr.get("total_seconds", 0.0) / cycles, 4)
        mix_fields["lstm_trains_per_cycle"] = round(
            tr.get("count", 0) / cycles, 2)
    # pipeline-stage decomposition (engine.stage.* timing accumulators):
    # preprocess = fetch-wait, dispatch = pack + async launch, collect =
    # device wait + merge + the lstm family, fold = verdict writes.
    # Overlap is visible as dispatch landing INSIDE the preprocess span's
    # wall time — the separate stage numbers sum close to the cycle wall
    # only when the pipeline had nothing to overlap.
    stage_fields = {
        "stage_s_per_cycle": {
            s: per_cycle(f"engine.stage.{s}")
            for s in ("preprocess", "dispatch", "collect", "fold")
        },
        "compiles_warmup": cc_warm.compiles,
        "compiles_steady_state": cc_steady.compiles,
    }
    return {
        "metric": "engine_cycle_jobs_per_sec",
        "value": round(n_jobs * cycles / wall, 1),
        "unit": "jobs/s",
        **host_fields,
        **mix_fields,
        **stage_fields,
        **launch_fields,
        "native": native.available(),
        "jobs": n_jobs,
        "cycles": cycles,
        "fetches_per_cycle": len(source.requests) // max(cycles, 1),
        "preprocess_s_per_cycle": per_cycle("engine.preprocess"),
        "score_s_per_cycle": per_cycle("engine.score"),
        "wall_s": round(wall, 3),
        "unhealthy_or_terminal": not_requeued,
        "provenance": provenance,
        "verdict_digest": verdict_digest,
    }


def run_provenance_ab(n_jobs: int = 1500, cycles: int = 6,
                      rounds: int = 3) -> dict:
    """Provenance A/B on the mixed 1500-job bench fleet: identical fleet
    and cycles with PROVENANCE on vs off. Pins the two claims the feature
    ships under — verdicts byte-identical (recording only observes), and
    cycle overhead under 3%.

    Legs INTERLEAVE (on/off per round) and each side reports its best
    round: on a shared/preemptible host the run-to-run spread of the
    fetch-pool preprocess stage (thread scheduling) dwarfs the
    recording cost, and a single sequential pair routinely misattributes
    tens of percent of noise to whichever leg ran in the worse slot
    (measured both signs on the 2-core sandbox). Best-of-N against
    best-of-N cancels the slot lottery; the digest identity is checked
    on every round."""
    best_on = best_off = None
    identical = True
    for _ in range(max(rounds, 1)):
        on = run(n_jobs, cycles, mix=True, provenance=True)
        off = run(n_jobs, cycles, mix=True, provenance=False)
        identical &= on["verdict_digest"] == off["verdict_digest"]
        if best_on is None or on["value"] > best_on["value"]:
            best_on = on
        if best_off is None or off["value"] > best_off["value"]:
            best_off = off
    overhead = (best_off["value"] - best_on["value"]) \
        / max(best_off["value"], 1e-9)
    return {
        "metric": "provenance_overhead_pct",
        "value": round(100.0 * overhead, 2),
        "unit": "%",
        "rounds": rounds,
        "verdicts_identical": identical,
        "jobs_per_sec_on": best_on["value"],
        "jobs_per_sec_off": best_off["value"],
        "on": best_on,
        "off": best_off,
    }


def _range_body(t0: int, series, qstart: float, qend: float,
                step: int = 60) -> bytes:
    """Serialize the slots of `series` (anchored at t0) that a range query
    [qstart, qend] would return — a synthetic Prometheus that actually
    honors its start/end params, so delta queries fetch only the tail."""
    import math

    k_lo = max(int(math.ceil((qstart - t0) / step)), 0)
    k_hi = min(int(math.floor((qend - t0) / step)), len(series) - 1)
    vals = [[t0 + k * step, f"{series[k]:.4f}"] for k in range(k_lo, k_hi + 1)]
    return json.dumps(
        {
            "status": "success",
            "data": {
                "resultType": "matrix",
                "result": [
                    {"metric": {"__name__": "namespace_app_latency"},
                     "values": vals}
                ],
            },
        }
    ).encode()


def run_steady(n_jobs: int = 2000, cycles: int = 12, window_steps: int = 128,
               cadence_s: int = 10, delta: bool = True,
               memo: bool = True) -> dict:
    """Steady-state leg: N warm cycles over a range-honoring synthetic
    backend whose series gain ~1 sample per metric step while the engine
    cycles at `cadence_s` (the production CYCLE_SECONDS default) — i.e.
    most cycles see NO new samples, every 6th sees one. A/B the
    DELTA_FETCH / SCORE_MEMO pair against the full-refetch path on this
    identical stream (the driver calls this twice)."""
    import re as _re

    import numpy as np

    from .dataplane.delta import DeltaWindowSource
    from .dataplane.fetch import RawFixtureDataSource
    from .engine import jobs as J
    from .engine.analyzer import Analyzer
    from .engine.config import EngineConfig
    from .utils import tracing
    from .utils.timeutils import to_rfc3339

    step = 60
    t0 = 1_700_000_000 // step * step
    horizon = 6 * window_steps + (cycles * cadence_s) // step + 8
    rng = np.random.default_rng(9)
    shapes = 10.0 + rng.normal(0.0, 2.0, (64, horizon))
    clock = {"now": 0.0}
    rng_re = _re.compile(r"[?&]start=([0-9.]+).*[?&]end=([0-9.]+)")

    def resolver(url: str) -> bytes:
        i = int(url.rsplit("job=", 1)[1].split("&", 1)[0]) % 64
        m = rng_re.search(url)
        qs, qe = float(m.group(1)), float(m.group(2))
        return _range_body(t0, shapes[i], qs, min(qe, clock["now"]), step)

    def url(i, tag, s, e):
        return (f"http://prom/q?job={i}&w={tag}"
                f"&start={s:.0f}&end={e:.0f}&step={step}")

    # half pair (baseline frozen in the past), half band (7x history
    # frozen): current windows start full and gain one sample per step
    W = window_steps
    base_end = t0 + W * step
    cur_start = base_end
    far = t0 + (horizon - 1) * step
    docs = []
    for i in range(n_jobs):
        if i % 2 == 0:
            metrics = {"latency": J.MetricQueries(
                current=url(i, "cur", cur_start, far),
                baseline=url(i, "base", t0, base_end),
            )}
        else:
            metrics = {"latency": J.MetricQueries(
                current=url(i, "cur", t0 + 4 * W * step, far),
                historical=url(i, "hist", t0, t0 + 4 * W * step),
            )}
        docs.append(J.Document(
            id=f"steady-{i}", app_name=f"app-{i % 128}", namespace="bench",
            strategy="canary", start_time=to_rfc3339(t0),
            end_time=to_rfc3339(far + 86_400), metrics=metrics,
        ))

    from .engine.pipeline import CompileCounter

    inner = RawFixtureDataSource(resolver=resolver)
    source = DeltaWindowSource(inner) if delta else inner
    with tempfile.TemporaryDirectory() as tmp:
        store = J.JobStore(snapshot_path=os.path.join(tmp, "jobs.json"))
        for d in docs:
            store.create(d)
        engine = Analyzer(
            EngineConfig(score_memo=memo, delta_fetch=delta), source, store)
        # warm start: every current window already full at bench t=0
        clock["now"] = float(t0 + (5 * W + 1) * step)
        with CompileCounter() as cc_warm:
            engine.run_cycle(now=clock["now"])
        tracing.tracer.reset()
        inner.requests.clear()
        launches0 = engine.device_launches
        if delta:
            source.delta_hits = source.full_fetches = 0
            source.bytes_saved = source.points_saved = 0
        hits0 = dict(engine.score_memo_hits)
        # detection latency measured over the steady cycles only — the
        # warm cycle's compile storm is startup cost, not the latency
        # this PR's SLOs track. reset_slo also clears the once-per-
        # window-advance dedupe, so the first steady cycle re-observes
        # each job's current advance (the polled-latency baseline).
        engine.reset_slo()

        t_start = time.perf_counter()
        with CompileCounter() as cc_steady:
            for _ in range(cycles):
                clock["now"] += cadence_s
                engine.run_cycle(now=clock["now"])
        wall = time.perf_counter() - t_start

    stats = tracing.tracer.stats()
    out = {
        "jobs_per_sec": round(n_jobs * cycles / wall, 1),
        "wall_s": round(wall, 3),
        "jobs": n_jobs,
        "cycles": cycles,
        "cadence_s": cadence_s,
        "delta_fetch": delta,
        "score_memo": memo,
        "fetches_per_cycle": len(inner.requests) / cycles,
        "device_launches_per_cycle": round(
            (engine.device_launches - launches0) / cycles, 2),
        "score_memo_hits_per_cycle": round(sum(
            engine.score_memo_hits.get(f, 0) - hits0.get(f, 0)
            for f in engine.score_memo_hits) / cycles, 2),
        "preprocess_s_per_cycle": round(
            stats.get("engine.preprocess", {}).get("total_seconds", 0.0)
            / cycles, 4),
        "compiles_steady_state": cc_steady.compiles,
        # bench honesty for the latency SLOs: the trajectory must track
        # ingest->verdict latency alongside jobs/s (engine/slo.py)
        "detection_latency_p50_s": round(engine.slo.quantile(0.5), 4),
        "detection_latency_p99_s": round(engine.slo.quantile(0.99), 4),
    }
    if delta:
        snap = source.snapshot()
        out["delta_hit_ratio"] = snap["hit_ratio"]
        out["delta_bytes_saved"] = snap["bytes_saved"]
        out["delta_points_saved"] = snap["points_saved"]
        out["delta_fallbacks"] = snap["fallbacks"]
    return out


def run_triage(n_jobs: int = 1500, cycles: int = 4, window_steps: int = 128,
               anomaly_rate: float = 0.0, triage: bool = True,
               metrics_per_job: int = 7) -> dict:
    """Tier-0 triage leg: a steady CONTINUOUS monitor fleet whose windows
    advance one sample EVERY cycle (cadence == the 60 s metric step) — the
    regime the score memo cannot help with (every row's bytes move) and
    the triage screen exists for. Each job watches `metrics_per_job`
    golden-signal metrics (one band row each); `anomaly_rate` of the jobs
    carry a sustained sub-verdict anomaly in one metric — enough spikes to
    fail the screen every cycle, too few to cross the band verdict gate —
    which is the conservative shape for triage (suspects that never
    convict re-escalate forever, per SWIFT's incident-tail
    characterization). Returns per-cycle device launches, jobs/s, and the
    verdict digest (the A/B pins digests equal between arms)."""
    import re as _re

    import numpy as np

    from .dataplane.delta import DeltaWindowSource
    from .dataplane.fetch import RawFixtureDataSource
    from .engine import jobs as J
    from .engine.analyzer import Analyzer
    from .engine.config import EngineConfig
    from .utils import tracing

    step = 60
    t0 = 1_700_000_000 // step * step
    W = window_steps
    hist_steps = 4 * W
    horizon = hist_steps + W + cycles + 8
    rng = np.random.default_rng(11)
    # 64 healthy series shapes around level 10, sigma 1; anomalous jobs
    # overlay spikes on their own copy (below)
    shapes = 10.0 + rng.normal(0.0, 1.0, (64, horizon))
    n_anom = int(round(n_jobs * anomaly_rate))
    # sustained borderline anomaly, CURRENT region only (history stays
    # clean so the screen's scales are honest): every 16th slot spikes
    # +12 sigma, so any 128-step current window holds ~8 out-of-band
    # points — robust_z ~12 fails the screen every cycle, while the count
    # stays under the band verdict gate (max(2, 0.1*128) ~ 12.8): the
    # "suspect that never convicts" shape, triage's conservative worst
    # case (it re-escalates forever)
    anom_shape = shapes[0].copy()
    anom_shape[hist_steps::16] += 12.0
    clock = {"now": 0.0}
    rng_re = _re.compile(r"[?&]start=([0-9.]+).*[?&]end=([0-9.]+)")
    m_re = _re.compile(r"[?&]m=([a-z0-9]+)&")

    def resolver(url: str) -> bytes:
        i = int(url.rsplit("job=", 1)[1].split("&", 1)[0])
        m = rng_re.search(url)
        qs, qe = float(m.group(1)), float(m.group(2))
        mk = m_re.search(url).group(1)
        if mk == "a0" and i < n_anom:
            row = anom_shape
        else:
            mi = int(mk[1:]) if mk[1:].isdigit() else 0
            row = shapes[(i * 7 + mi) % 64]
        return _range_body(t0, row, qs, min(qe, clock["now"]), step)

    def url(i, metric, tag, s, e):
        return (f"http://prom/q?job={i}&m={metric}&w={tag}"
                f"&start={s:.0f}&end={e:.0f}&step={step}")

    hist_end = t0 + hist_steps * step
    far = t0 + (horizon - 1) * step
    # golden-signal monitor metrics; "err5xx" (a0) carries the anomaly —
    # the error5xx policy's tight 2-sigma upper band is what the spikes
    # must beat. The rest judge under their own policies.
    names = ["err5xx_a0", "err4xx", "latency_p50", "latency_p99", "cpu",
             "memory", "tps"][:max(metrics_per_job, 1)]
    docs = []
    for i in range(n_jobs):
        metrics = {}
        for k, name in enumerate(names):
            mkey = "a0" if name == "err5xx_a0" else f"m{k}"
            metrics[name] = J.MetricQueries(
                current=url(i, mkey, "cur", hist_end, far),
                historical=url(i, mkey, "hist", t0, hist_end),
            )
        docs.append(J.Document(
            id=f"triage-{i}", app_name=f"app-{i % 128}", namespace="bench",
            strategy="continuous", start_time="START_TIME",
            end_time="END_TIME", metrics=metrics,
        ))

    inner = RawFixtureDataSource(resolver=resolver)
    source = DeltaWindowSource(inner)
    with tempfile.TemporaryDirectory() as tmp:
        store = J.JobStore(snapshot_path=os.path.join(tmp, "jobs.json"))
        for d in docs:
            store.create(d)
        engine = Analyzer(EngineConfig(
            triage=triage,
            # each golden signal judges independently under the configured
            # moving-average band (the explicit-algorithm routing mode) —
            # the multimetric auto-dispatch would pool 3+-metric jobs into
            # one LSTM row, which is not the per-metric monitor fleet this
            # leg models
            multimetric_auto=False,
            # the delta window cache holds ~2 entries per (job, metric);
            # the default 8192 would thrash at 1500 jobs x 7 metrics
            window_cache_max=max(8192, 3 * n_jobs * len(names)),
        ), source, store)
        clock["now"] = float(hist_end + W * step)
        engine.run_cycle(now=clock["now"])  # warm: compiles + caches
        tracing.tracer.reset()
        launches0 = engine.device_launches
        engine.reset_slo()  # measure latency over the steady cycles only
        t_start = time.perf_counter()
        for _ in range(cycles):
            clock["now"] += step  # one new sample per series per cycle
            engine.run_cycle(now=clock["now"])
        wall = time.perf_counter() - t_start

        digest = J.verdict_digest(store)
        tr = engine.last_cycle_stages.get("triage") or {}
        return {
            "jobs_per_sec": round(n_jobs * cycles / wall, 1),
            "wall_s": round(wall, 3),
            "jobs": n_jobs,
            "cycles": cycles,
            "metrics_per_job": len(names),
            "anomaly_rate": anomaly_rate,
            "triage": triage,
            "device_launches_per_cycle": round(
                (engine.device_launches - launches0) / cycles, 2),
            "screened_per_cycle": round(tr.get("screened", 0), 1),
            "cleared_per_cycle": round(tr.get("cleared", 0), 1),
            "escalated_per_cycle": round(tr.get("escalated", 0), 1),
            "detection_latency_p50_s": round(engine.slo.quantile(0.5), 4),
            "detection_latency_p99_s": round(engine.slo.quantile(0.99), 4),
            "verdict_digest": digest,
        }


def run_triage_ab(n_jobs: int = 1500, cycles: int = 4,
                  rates: tuple = (0.0, 0.01, 0.10),
                  rounds: int = 2) -> dict:
    """Triage A/B across a synthetic anomaly-rate sweep: identical fleet
    and sample stream with TRIAGE on vs off per rate. The headline (and
    the `make perf` gate's big-fleet counterpart) is the launch cut at
    the <=1% rates; the 10% leg pins that a suspect-heavy fleet does not
    regress throughput.

    Same measurement protocol as run_provenance_ab: legs INTERLEAVE
    (on/off per round) and each side reports its best round — the 2-core
    sandbox's scheduling-slot lottery swings single sequential pairs by
    tens of percent in either direction, dwarfing the screen's real
    cost. Launch counts are deterministic (any round's will do); the
    digest identity is checked on EVERY round."""
    legs = []
    for rate in rates:
        best_on = best_off = None
        identical = True
        for _ in range(max(rounds, 1)):
            on = run_triage(n_jobs, cycles, anomaly_rate=rate, triage=True)
            off = run_triage(n_jobs, cycles, anomaly_rate=rate,
                             triage=False)
            identical &= on["verdict_digest"] == off["verdict_digest"]
            if best_on is None or on["jobs_per_sec"] > best_on["jobs_per_sec"]:
                best_on = on
            if (best_off is None
                    or off["jobs_per_sec"] > best_off["jobs_per_sec"]):
                best_off = off
        legs.append({
            "anomaly_rate": rate,
            "launch_cut": round(
                best_off["device_launches_per_cycle"]
                / max(best_on["device_launches_per_cycle"], 1e-9), 2),
            "verdicts_identical": identical,
            "jobs_per_sec_on": best_on["jobs_per_sec"],
            "jobs_per_sec_off": best_off["jobs_per_sec"],
            "on": best_on,
            "off": best_off,
        })
    quiet = [l for l in legs if l["anomaly_rate"] <= 0.01] or legs
    headline = min(quiet, key=lambda l: l["launch_cut"])
    return {
        "metric": "triage_device_launch_cut",
        "value": headline["launch_cut"],
        "unit": "x",
        "rounds": rounds,
        "verdicts_identical": all(l["verdicts_identical"] for l in legs),
        "legs": legs,
    }


def _stream_fleet(n_jobs: int, t0: int, horizon: int, step: int,
                  anomaly_rate: float = 0.0, cur_steps: int | None = None):
    """A band-monitor fleet for the streamed-ingest legs: frozen
    7x-window history + a growing current window per job, `anomaly_rate`
    of the fleet level-shifting +10 sigma for the final two steps of the
    horizon (the error5xx policy's 2-sigma upper band convicts them once
    BOTH shifted samples land — with a `cur_steps`-long trailing current
    window the band_min_points=2 gate is the binding one, so the pushed
    tail is literally the convicting evidence)."""
    import numpy as np

    from .engine import jobs as J
    from .utils.timeutils import to_rfc3339

    rng = np.random.default_rng(13)
    shapes = 10.0 + rng.normal(0.0, 1.0, (64, horizon))
    n_anom = int(round(n_jobs * anomaly_rate))

    def series_for(i):
        row = shapes[i % 64].copy()
        if i < n_anom:
            row[horizon - 2:] += 10.0
        return row

    W = 128
    hist_end = t0 + 4 * W * step
    far = t0 + (horizon - 1) * step
    cur_start = hist_end if cur_steps is None else far - cur_steps * step
    docs = []
    for i in range(n_jobs):
        docs.append(J.Document(
            id=f"stream-{i}", app_name=f"app-{i % 128}",
            namespace="bench", strategy="canary",
            start_time=to_rfc3339(t0), end_time=to_rfc3339(far + 86_400),
            metrics={"error5xx": J.MetricQueries(
                current=(f"http://prom/q?job={i}&m=e5&w=cur"
                         f"&start={cur_start:.0f}&end={far:.0f}"
                         f"&step={step}"),
                historical=(f"http://prom/q?job={i}&m=e5&w=hist"
                            f"&start={t0:.0f}&end={hist_end:.0f}"
                            f"&step={step}"),
            )},
        ))
    return docs, series_for, hist_end


def _slo_pooled_mean(slo) -> float:
    """Exact pooled mean latency across classes (quantiles are bucket-
    floored; the waterfall-sum tolerance check needs a real mean)."""
    snap = slo.snapshot()
    n = sum(c["count"] for c in snap["classes"].values())
    if not n:
        return 0.0
    return round(sum(c["mean_s"] * c["count"]
                     for c in snap["classes"].values()) / n, 4)


def run_stream(n_jobs: int = 200, cycles: int = 18, cadence_s: int = 10,
               stream: bool = True, push_latency_s: float = 0.5) -> dict:
    """Streamed-ingest LATENCY leg (BENCH_CYCLE_STREAM=1): the
    production-faithful polled baseline vs event-driven push.

    Both legs run the full production source chain — range-honoring
    backend -> DeltaWindowSource -> TTL CachingDataSource — with the TTL
    driven by the synthetic clock and each job's cache entry warmed at a
    staggered phase (exactly how production caches populate: whenever
    each job first arrived). Polled: a sample sits out the TTL plus the
    tick before any sweep sees it — p50 ~step/2, p99 ~step, the ROADMAP
    baseline. Streamed: every new sample is pushed as addressed
    remote-write `push_latency_s` after its timestamp; the receiver
    splices it into the delta cache, invalidates the TTL entry, and the
    partial cycle scores it immediately — detection latency collapses to
    push latency + in-cycle tail. Fleets, sweep schedule, and final
    clock are identical across legs; the verdict digest must match."""
    import numpy as np  # noqa: F401  (fleet builder uses it)

    from .dataplane.delta import DeltaWindowSource
    from .dataplane.fetch import CachingDataSource, RawFixtureDataSource
    from .engine import jobs as J
    from .engine.analyzer import Analyzer
    from .engine.config import EngineConfig
    from .ingest import IngestReceiver, encode_remote_write, snappy_compress

    step = 60
    t0 = 1_700_000_000 // step * step
    W = 128
    horizon = 6 * W + (cycles * cadence_s) // step + 8
    docs, series_for, hist_end = _stream_fleet(n_jobs, t0, horizon, step)
    clock = {"now": 0.0}

    def resolver(url: str) -> bytes:
        i = int(url.rsplit("job=", 1)[1].split("&", 1)[0])
        import re as _re

        m = _re.search(r"[?&]start=([0-9.]+).*[?&]end=([0-9.]+)", url)
        qs, qe = float(m.group(1)), float(m.group(2))
        return _range_body(t0, series_for(i), qs, min(qe, clock["now"]),
                           step)

    inner = RawFixtureDataSource(resolver=resolver)
    delta = DeltaWindowSource(inner, clock=lambda: clock["now"])
    source = CachingDataSource(delta, max_entries=4 * n_jobs,
                               clock=lambda: clock["now"])
    with tempfile.TemporaryDirectory() as tmp:
        store = J.JobStore(snapshot_path=os.path.join(tmp, "jobs.json"))
        for d in docs:
            store.create(d)
        engine = Analyzer(EngineConfig(), source, store)
        warm0 = float(t0 + (5 * W + 1) * step)
        clock["now"] = warm0
        engine.run_cycle(now=clock["now"])
        # stagger each job's TTL phase across one metric step (production
        # caches fill at job-arrival phases, not in one instant): re-fetch
        # job i's current window at warm0 + i-dependent offset so its
        # entry refreshes at that phase forever after
        for i, d in enumerate(docs):
            clock["now"] = warm0 + (i * 97) % step
            source.invalidate(d.metrics["error5xx"].current)
            source.fetch_window(d.metrics["error5xx"].current)
        clock["now"] = warm0 + step
        # settle sweep: observe (and thereby mark seen) every job's
        # warm-era window advance, then clear the histograms ONLY — the
        # measured legs must record post-warm advances, not the warm-up's
        # staleness (engine.reset_slo would also clear the seen map and
        # re-admit exactly those)
        engine.run_cycle(now=clock["now"])
        engine.slo.reset()
        engine.waterfall.reset()
        # sweeps run 5 s off the sample boundaries: a real deployment's
        # tick is not phase-locked to the scrape grid, and a
        # boundary-exact sweep would poll a fresh sample at ~0 latency
        clock["now"] += 5.0

        receiver = None
        dirty: set = set()
        if stream:
            receiver = IngestReceiver(
                store, delta_source=delta, cache_source=source,
                exporter=engine.exporter,
                notify_fn=lambda ids: dirty.update(ids),
                # stage attribution: accepts open waterfall records the
                # engine closes at fold — the bench emits per-stage
                # p50/p99 next to the headline latency
                waterfall=engine.waterfall)
        pushed_until = {"ts": warm0}  # newest sample ts already pushed

        def push_new_samples(now: float):
            """Addressed remote-write for every sample in
            (pushed_until, now] across the fleet, one request."""
            lo, hi = pushed_until["ts"], now
            k_lo = int(lo // step) + 1
            k_hi = int(hi // step)
            if k_hi < k_lo:
                return False
            series = []
            for i, d in enumerate(docs):
                row = series_for(i)
                # the push must carry EXACTLY the value the backend
                # serves (same scrape, same serialization) — the
                # synthetic backend serializes at 4 decimals
                samples = [(float(k * step),
                            float(f"{row[k - t0 // step]:.4f}"))
                           for k in range(k_lo, k_hi + 1)
                           if 0 <= k - t0 // step < horizon]
                if samples:
                    series.append((
                        {"foremast_job": d.id,
                         "foremast_metric": "error5xx"}, samples))
            pushed_until["ts"] = float(k_hi * step)
            if not series:
                return False
            raw = snappy_compress(encode_remote_write(series))
            status, _ = receiver.handle(
                "remote_write", raw,
                content_type="application/x-protobuf",
                content_encoding="snappy", now=now)
            assert status == 200, status
            return True

        sweep_times = [clock["now"] + k * cadence_s for k in range(cycles)]
        # every sample boundary in the measured span gets a push event —
        # including the one AT measurement start, or its sample would
        # trickle in via TTL expiry and misattribute poll latency to the
        # streamed leg
        boundaries = sorted({
            float(k * step)
            for k in range(int(sweep_times[0] // step),
                           int(sweep_times[-1] // step) + 1)})
        events = [("sweep", t) for t in sweep_times]
        if stream:
            events += [("push", b + push_latency_s) for b in boundaries]
        events.sort(key=lambda e: e[1])
        t_start = time.perf_counter()
        for kind, t in events:
            clock["now"] = t
            if kind == "push":
                if push_new_samples(t) and dirty:
                    ids, _ = frozenset(dirty), dirty.clear()
                    engine.run_cycle(now=t, job_ids=ids, partial=True)
            else:
                engine.run_cycle(now=t)
        wall = time.perf_counter() - t_start

        digest = J.verdict_digest(store)
        out = {
            "stream": stream,
            "jobs": n_jobs,
            "cycles": cycles,
            "cadence_s": cadence_s,
            "wall_s": round(wall, 3),
            "detection_latency_p50_s": round(engine.slo.quantile(0.5), 4),
            "detection_latency_p99_s": round(engine.slo.quantile(0.99), 4),
            "detection_latency_mean_s": _slo_pooled_mean(engine.slo),
            "verdict_digest": digest,
        }
        # detection-latency waterfall (PR 14): per-stage p50/p99/mean so
        # the BENCH round records stage attribution, not just the
        # headline p99; "total" is the per-observation stage sum — it
        # must sit within tolerance of detection_latency (pinned by
        # tests/test_trace_plane.py)
        wf = engine.waterfall.snapshot()
        if wf.get("observed"):
            out["waterfall_stage_s"] = wf["stages"]
        if stream:
            snap = delta.snapshot()
            out["ingest_spliced_points"] = snap["ingest_spliced_points"]
            out["ingest_served_windows"] = snap["ingest_hits"]
            out["push_latency_s"] = push_latency_s
        return out


def run_stream_identity(n_jobs: int = 120, sweeps: int = 14,
                        cadence_s: int = 10,
                        anomaly_rate: float = 0.1) -> dict:
    """Streamed-ingest IDENTITY leg: the non-negotiable A/B gate.

    Identical fleet (including convicting anomalies), identical sweep
    schedule and clock; leg A polls the backend, leg B receives every
    sample as an addressed push BEFORE the sweep and serves the windows
    from the push-fed delta cache (asserted via ingest_hits) — so any
    byte of divergence between the pushed and polled window paths shows
    up as a digest mismatch in real verdicts, unhealthy ones included."""
    import re as _re

    from .dataplane.delta import DeltaWindowSource
    from .dataplane.fetch import RawFixtureDataSource
    from .engine import jobs as J
    from .engine.analyzer import Analyzer
    from .engine.config import EngineConfig
    from .ingest import IngestReceiver, encode_remote_write, snappy_compress

    step = 60
    t0 = 1_700_000_000 // step * step
    W = 128
    horizon = 6 * W + (sweeps * cadence_s) // step + 8
    rng_re = _re.compile(r"[?&]start=([0-9.]+).*[?&]end=([0-9.]+)")

    def one_leg(pushed: bool):
        # 18-step trailing current window: the band verdict gate is
        # max(2, 0.1 * checked) = 2 points, so the two shifted samples
        # the sweeps push/poll in are exactly what convicts
        docs, series_for, _ = _stream_fleet(n_jobs, t0, horizon, step,
                                            anomaly_rate=anomaly_rate,
                                            cur_steps=18)
        clock = {"now": 0.0}

        def resolver(url: str) -> bytes:
            i = int(url.rsplit("job=", 1)[1].split("&", 1)[0])
            m = rng_re.search(url)
            qs, qe = float(m.group(1)), float(m.group(2))
            return _range_body(t0, series_for(i), qs,
                               min(qe, clock["now"]), step)

        inner = RawFixtureDataSource(resolver=resolver)
        delta = DeltaWindowSource(inner, clock=lambda: clock["now"])
        with tempfile.TemporaryDirectory() as tmp:
            store = J.JobStore(snapshot_path=os.path.join(tmp, "j.json"))
            for d in docs:
                store.create(d)
            engine = Analyzer(EngineConfig(), delta, store)
            receiver = IngestReceiver(store, delta_source=delta,
                                      exporter=engine.exporter) \
                if pushed else None
            # the fleet's current windows end 2 steps short of the
            # horizon at warm time, so the anomaly tail arrives DURING
            # the measured sweeps in both legs (the +5 keeps warm and
            # sweeps off the sample boundaries, like a real deployment)
            clock["now"] = float(t0 + (horizon - 3) * step) + 5.0
            engine.run_cycle(now=clock["now"])
            pushed_ts = clock["now"]
            for k in range(sweeps):
                now = clock["now"] + cadence_s
                clock["now"] = now
                if pushed:
                    k_lo = int(pushed_ts // step) + 1
                    k_hi = int(now // step)
                    series = []
                    for i, d in enumerate(docs):
                        row = series_for(i)
                        # push == scrape: mirror the backend's 4-decimal
                        # serialization or byte-identity is impossible
                        samples = [
                            (float(k2 * step),
                             float(f"{row[k2 - t0 // step]:.4f}"))
                            for k2 in range(k_lo, k_hi + 1)
                            if 0 <= k2 - t0 // step < horizon]
                        if samples:
                            series.append((
                                {"foremast_job": d.id,
                                 "foremast_metric": "error5xx"}, samples))
                    if series:
                        raw = snappy_compress(encode_remote_write(series))
                        status, _ = receiver.handle(
                            "remote_write", raw,
                            content_type="application/x-protobuf",
                            content_encoding="snappy", now=now)
                        assert status == 200, status
                    pushed_ts = now
                engine.run_cycle(now=now)
            unhealthy = sum(
                1 for d in store.by_status(J.COMPLETED_UNHEALTH))
            return J.verdict_digest(store), unhealthy, delta.snapshot()

    dig_polled, unhealthy_p, _ = one_leg(pushed=False)
    dig_pushed, unhealthy_s, snap = one_leg(pushed=True)
    return {
        "verdicts_identical": dig_polled == dig_pushed,
        "unhealthy_polled": unhealthy_p,
        "unhealthy_pushed": unhealthy_s,
        "ingest_served_windows": snap["ingest_hits"],
        "ingest_spliced_points": snap["ingest_spliced_points"],
        "digest_polled": dig_polled,
        "digest_pushed": dig_pushed,
    }


def run_stream_ab(n_jobs: int = 200, cycles: int = 18) -> dict:
    """The streamed-ingest A/B the perf gate and docs quote: identity
    first (pushed windows MUST equal polled windows, convicting
    anomalies included), then the latency win on the identical
    polled-vs-streamed schedule."""
    identity = run_stream_identity(max(n_jobs // 2, 40))
    polled = run_stream(n_jobs, cycles, stream=False)
    streamed = run_stream(n_jobs, cycles, stream=True)
    tracing_ab = run_tracing_overhead_ab(max(n_jobs // 2, 40),
                                         max(cycles // 2, 8))
    return {
        "metric": "stream_detection_latency_p99_s",
        "value": streamed["detection_latency_p99_s"],
        "unit": "s",
        "polled_p50_s": polled["detection_latency_p50_s"],
        "polled_p99_s": polled["detection_latency_p99_s"],
        "streamed_p50_s": streamed["detection_latency_p50_s"],
        "streamed_p99_s": streamed["detection_latency_p99_s"],
        "verdicts_identical": (
            identity["verdicts_identical"]
            and polled["verdict_digest"] == streamed["verdict_digest"]),
        "identity": identity,
        "polled": polled,
        "streamed": streamed,
        # stage attribution for the BENCH record (PR 14): where the
        # streamed leg's detection latency actually went
        "waterfall_stage_s": streamed.get("waterfall_stage_s", {}),
        # tracing+export on vs off: byte-identity + overhead figure
        "tracing": tracing_ab,
    }


def run_tracing_overhead_ab(n_jobs: int = 100, cycles: int = 9,
                            rounds: int = 2) -> dict:
    """Tracing+export ON vs OFF on the streamed leg: interleaved
    best-of-round wall clocks (sequential pairs misattribute scheduling
    noise — the PR 6 lesson) with a live local OTLP sink receiving the
    ON legs' spans. The contract: verdict digests byte-identical every
    leg, overhead below the noise floor (<3% of cycle budget is the
    acceptance gate)."""
    import http.server
    import threading

    from .dataplane.exporter import OtlpTraceExporter
    from .utils import tracing as T

    received = {"posts": 0, "bytes": 0}

    class _Sink(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            self.rfile.read(n)
            received["posts"] += 1
            received["bytes"] += n
            self.send_response(200)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"{}")

        def log_message(self, *a):
            pass

    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Sink)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    url = f"http://127.0.0.1:{server.server_address[1]}/v1/traces"
    old_rate = T.tracer.sample_rate
    on_runs, off_runs = [], []
    try:
        for _ in range(rounds):
            exp = OtlpTraceExporter(url, flush_interval=0.2)
            T.tracer.set_sample_rate(1.0)
            T.tracer.add_sink(exp.sink)
            exp.start()
            try:
                on_runs.append(run_stream(n_jobs, cycles, stream=True))
            finally:
                T.tracer.remove_sink(exp.sink)
                exp.stop(flush=True)
            T.tracer.set_sample_rate(0.0)
            off_runs.append(run_stream(n_jobs, cycles, stream=True))
    finally:
        T.tracer.set_sample_rate(old_rate)
        server.shutdown()
        server.server_close()
    best_on = min(r["wall_s"] for r in on_runs)
    best_off = min(r["wall_s"] for r in off_runs)
    digests = {r["verdict_digest"] for r in on_runs + off_runs}
    return {
        "rounds": rounds,
        "wall_on_s": best_on,
        "wall_off_s": best_off,
        "overhead_pct": round((best_on - best_off) / best_off * 100.0, 2)
        if best_off else 0.0,
        "verdicts_identical": len(digests) == 1,
        "collector_posts": received["posts"],
        "collector_bytes": received["bytes"],
    }


def run_restart(n_jobs: int = 500, window_steps: int = 128) -> dict:
    """Cold-start vs warm-restart leg (BENCH_CYCLE_RESTART=1): measure
    the refetch-storm win of the crash-durable window store
    (dataplane/winstore.py) instead of asserting it.

    Phase 1 boots a fleet COLD (empty store): the first cycle pays one
    full-body fetch per window. A checkpoint then folds the cache into
    segments and the engine is torn down — the kill. Phase 2 rebuilds
    everything over the same store dir, replays segments+WAL, and runs
    the first post-restart cycle: covered windows re-query only their
    narrow tails. The bytes/fetch deltas ARE the storm that no longer
    happens. Also reports the hot-tier RAM ceiling with the warm tier
    on (hot LRU capped at n/4, remainder spilled) vs off (everything
    resident) — the measured memory-per-job number ROADMAP item 3 asks
    for."""
    import re as _re

    import numpy as np

    from .dataplane.delta import DeltaWindowSource
    from .dataplane.fetch import RawFixtureDataSource
    from .dataplane.winstore import WindowStore
    from .engine import jobs as J
    from .engine.analyzer import Analyzer
    from .engine.config import EngineConfig
    from .utils.timeutils import to_rfc3339

    step = 60
    t0 = 1_700_000_000 // step * step
    W = window_steps
    horizon = 6 * W + 8
    rng = np.random.default_rng(17)
    shapes = 10.0 + rng.normal(0.0, 2.0, (64, horizon))
    clock = {"now": float(t0 + (5 * W + 1) * step)}
    served = {"bytes": 0}
    rng_re = _re.compile(r"[?&]start=([0-9.]+).*[?&]end=([0-9.]+)")

    def resolver(url: str) -> bytes:
        i = int(url.rsplit("job=", 1)[1].split("&", 1)[0]) % 64
        m = rng_re.search(url)
        qs, qe = float(m.group(1)), float(m.group(2))
        body = _range_body(t0, shapes[i], qs, min(qe, clock["now"]), step)
        served["bytes"] += len(body)
        return body

    def url(i, tag, s, e):
        return (f"http://prom/q?job={i}&w={tag}"
                f"&start={s:.0f}&end={e:.0f}&step={step}")

    far = t0 + (horizon - 1) * step

    def mk_docs():
        return [J.Document(
            id=f"restart-{i}", app_name=f"app-{i % 128}",
            namespace="bench", strategy="canary",
            start_time=to_rfc3339(t0), end_time=to_rfc3339(far + 86_400),
            metrics={"latency": J.MetricQueries(
                current=url(i, "cur", t0 + 4 * W * step, far),
                historical=url(i, "hist", t0, t0 + 4 * W * step))},
        ) for i in range(n_jobs)]

    def resident_bytes(src):
        with src._lock:
            return sum(
                e.win.values.nbytes + e.win.mask.nbytes + e.nan_ts.nbytes
                for e in src._cache.values())

    def boot(store_dir, max_entries):
        inner = RawFixtureDataSource(resolver=resolver)
        ws = WindowStore(store_dir, checkpoint_min_seconds=0.0) \
            if store_dir else None
        src = DeltaWindowSource(inner, max_entries=max_entries, store=ws)
        t_rec = time.perf_counter()
        rec = ws.recover(src) if ws is not None else {}
        rec_s = time.perf_counter() - t_rec
        store = J.JobStore()
        for d in mk_docs():
            store.create(d)
        engine = Analyzer(EngineConfig(), src, store)
        served["bytes"] = 0
        inner.requests.clear()
        t_cyc = time.perf_counter()
        engine.run_cycle(now=clock["now"])
        return {
            "engine": engine, "src": src, "ws": ws, "inner": inner,
            "recovery_s": round(rec_s, 3), "recovery": rec,
            "first_cycle_s": round(time.perf_counter() - t_cyc, 3),
            "fetches": len(inner.requests),
            "bytes_fetched": served["bytes"],
            "full_fetches": src.full_fetches,
            "delta_hits": src.delta_hits,
        }

    with tempfile.TemporaryDirectory() as tmp:
        store_dir = os.path.join(tmp, "winstore")
        cold = boot(store_dir, max_entries=4 * n_jobs)
        # the shutdown checkpoint (or the last sweep's) — then the kill
        cold["ws"].checkpoint(cold["src"], force=True)
        seg_bytes = cold["ws"].snapshot()["segment_bytes"]
        warm = boot(store_dir, max_entries=4 * n_jobs)

        # memory ceiling: same fleet, hot LRU capped vs uncapped
        capped = boot(store_dir, max_entries=max(n_jobs // 4, 8))
        resident_on = resident_bytes(capped["src"])
        resident_off = resident_bytes(warm["src"])

    for leg in (cold, warm, capped):
        for k in ("engine", "src", "ws", "inner", "recovery"):
            leg.pop(k, None)
    return {
        "metric": "warm_restart_first_cycle_s",
        "value": warm["first_cycle_s"],
        "unit": "s",
        "jobs": n_jobs,
        "cold": cold,
        "warm_restart": warm,
        "refetch_bytes_avoided": cold["bytes_fetched"]
        - warm["bytes_fetched"],
        "first_cycle_speedup": round(
            cold["first_cycle_s"] / max(warm["first_cycle_s"], 1e-9), 2),
        "segment_bytes": seg_bytes,
        # RAM ceiling: resident window bytes with the hot tier capped at
        # n/4 entries (warm tier holds the rest) vs everything hot —
        # multiply per-job by 1e5 for the 100k-job projection
        "resident_bytes_tier_on": resident_on,
        "resident_bytes_tier_off": resident_off,
        "resident_bytes_per_job_tier_on": round(resident_on / n_jobs, 1),
        "resident_bytes_per_job_tier_off": round(resident_off / n_jobs, 1),
    }


def run_megabatch_ab(n_jobs: int = 5000, cycles: int = 2,
                     rounds: int = 2) -> dict:
    """Mega-batch A/B on the launch-heavy mixed fleet: MEGABATCH on vs
    off with SCORE_MEMO pinned off (the static fixture would otherwise
    memo-hit every row and measure nothing) — every row scores every
    cycle, the dispatch-bound regime the mega path exists for.

    Interleaved best-of-round like every A/B in this file (sequential
    pairs misattribute scheduling noise); digests checked EVERY round.
    Also reports the satellite trajectory numbers: launches/cycle and
    the padding-waste ratio (padded rows / real rows)."""
    best_on = best_off = None
    identical = True
    prev = {k: os.environ.get(k) for k in ("MEGABATCH", "SCORE_MEMO")}
    try:
        # memo pinned OFF: the static fixture would otherwise fingerprint-
        # hit every row after the warm cycle and measure nothing
        os.environ["SCORE_MEMO"] = "0"
        for _ in range(max(rounds, 1)):
            os.environ["MEGABATCH"] = "0"
            off = run(n_jobs, cycles, mix=True)
            os.environ["MEGABATCH"] = "1"
            on = run(n_jobs, cycles, mix=True)
            identical &= on["verdict_digest"] == off["verdict_digest"]
            if best_on is None or on["value"] > best_on["value"]:
                best_on = on
            if best_off is None or off["value"] > best_off["value"]:
                best_off = off
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return {
        "metric": "megabatch_jobs_per_sec",
        "value": best_on["value"],
        "unit": "jobs/s",
        "rounds": rounds,
        "verdicts_identical": identical,
        "jobs_per_sec_on": best_on["value"],
        "jobs_per_sec_off": best_off["value"],
        "speedup": round(best_on["value"] / max(best_off["value"], 1e-9),
                         3),
        "launches_per_cycle_on": best_on["device_launches_per_cycle"],
        "launches_per_cycle_off": best_off["device_launches_per_cycle"],
        "family_launches_on": best_on["family_launches"],
        "family_launches_off": best_off["family_launches"],
        "padding_waste_ratio":
            best_on.get("megabatch", {}).get("padding_waste_ratio"),
        "on": best_on,
        "off": best_off,
    }


def run_simfleet_ab() -> dict:
    """The fleet-scale simulator leg (BENCH_CYCLE_SIMFLEET=1): delegate
    to foremast_tpu.simfleet's A/B driver, parameterized by the SIM_*
    registry knobs — seed, trace shape, and fleet size land in the
    emitted JSON per the docs/benchmarks.md honesty convention."""
    from .simfleet import run_fleet_ab
    from .utils import knobs

    return run_fleet_ab(
        jobs=knobs.read("SIM_JOBS"), seed=knobs.read("SIM_SEED"),
        shape=knobs.read("SIM_TRACE"), cycles=knobs.read("SIM_CYCLES"),
        cadence_s=knobs.read("SIM_CADENCE_S"),
        replicas=knobs.read("SIM_REPLICAS"),
        rounds=knobs.read("SIM_ROUNDS"))


def run_steady_ab(n_jobs: int = 2000, cycles: int = 12) -> dict:
    """The A/B the perf gate and docs quote: identical stream, delta+memo
    on vs. the full-refetch path."""
    on = run_steady(n_jobs, cycles, delta=True, memo=True)
    off = run_steady(n_jobs, cycles, delta=False, memo=False)
    return {
        "metric": "steady_state_jobs_per_sec",
        "value": on["jobs_per_sec"],
        "unit": "jobs/s",
        "on": on,
        "off": off,
        "speedup": round(on["jobs_per_sec"] / max(off["jobs_per_sec"], 1e-9),
                         3),
    }


def main() -> None:
    from .engine.config import _env_bool

    n = int(os.environ.get("BENCH_CYCLE_JOBS", "10000"))
    cycles = int(os.environ.get("BENCH_CYCLE_REPS", "2"))
    if _env_bool(os.environ, "BENCH_CYCLE_STEADY", False):
        print(json.dumps(run_steady_ab(n, cycles)))
        return
    if _env_bool(os.environ, "BENCH_CYCLE_STREAM", False):
        n = int(os.environ.get("BENCH_CYCLE_JOBS", "200"))
        print(json.dumps(run_stream_ab(n, max(cycles, 12))))
        return
    if _env_bool(os.environ, "BENCH_CYCLE_TRIAGE", False):
        n = int(os.environ.get("BENCH_CYCLE_JOBS", "1500"))
        print(json.dumps(run_triage_ab(n, max(cycles, 2))))
        return
    if _env_bool(os.environ, "BENCH_CYCLE_PROVENANCE", False):
        n = int(os.environ.get("BENCH_CYCLE_JOBS", "1500"))
        print(json.dumps(run_provenance_ab(n, max(cycles, 4))))
        return
    if _env_bool(os.environ, "BENCH_CYCLE_RESTART", False):
        n = int(os.environ.get("BENCH_CYCLE_JOBS", "500"))
        print(json.dumps(run_restart(n)))
        return
    if _env_bool(os.environ, "BENCH_CYCLE_MEGABATCH", False):
        n = int(os.environ.get("BENCH_CYCLE_JOBS", "5000"))
        print(json.dumps(run_megabatch_ab(n, max(cycles, 2))))
        return
    if _env_bool(os.environ, "BENCH_CYCLE_SIMFLEET", False):
        print(json.dumps(run_simfleet_ab()))
        return
    mix = _env_bool(os.environ, "BENCH_CYCLE_MIX", False)
    print(json.dumps(run(n, cycles, mix=mix)))


if __name__ == "__main__":
    main()
