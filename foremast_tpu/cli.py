"""`foremast-tpu` CLI: serve | operator | trigger | watch | unwatch | status | health | shards | top | explain | prewarm | demo.

One entrypoint covers the reference's process zoo and kubectl plugins:

  serve     the runtime (job API + TPU engine + exporter + dashboard) —
            replaces foremast-service + foremast-brain (+ES).
  operator  the reconcile loop against a real cluster — replaces
            foremast-barrelman (cmd/manager/main.go env surface: MODE,
            HPA_STRATEGY, NAMESPACE).
  trigger   the non-K8s poller — replaces foremast-trigger (REQUESTS_FILE
            CSV -> perpetual rollover analyses + daily reports).
  watch / unwatch <app>   toggle spec.continuous on the app's
            DeploymentMonitor — the bin/kubectl-watch & kubectl-unwatch
            plugins (bin/kubectl-watch:3 in the reference patched the CRD
            with kubectl; here we speak to the API server directly).
  status <app>            print the monitor's phase / job / anomaly.
  prewarm   compile the (family x rung x T-bucket) scoring grid — into
            the persistent compile cache (JAX_COMPILATION_CACHE_DIR) —
            so runtime pods start without the first-cycle compile storm
            (engine/pipeline.py, docs/performance.md).
  demo      self-contained local loop: chaos app + fake metric source +
            engine, no cluster (examples/demo_app.py).

Kube access: in-cluster service account when present, else KUBE_API/
KUBE_TOKEN env (operator/kube.py:KubeClient).
"""
from __future__ import annotations

import argparse
import json
import logging
import sys

from .utils import knobs


def _kube():
    from .operator.kube import KubeClient

    return KubeClient()


def cmd_serve(args) -> int:
    from .runtime import main

    main()
    return 0


def make_analyst(endpoint: str = "", transport: str = ""):
    """Analyst client from endpoint + transport selection.

    Transport comes from --analyst-transport / ANALYST_TRANSPORT
    (default http); a grpc:// endpoint scheme also selects gRPC, so
    pointing ANALYST_ENDPOINT at grpc://runtime:8100 needs no second
    knob. The runtime serves both fronts (:8099 HTTP, :8100 gRPC —
    deploy/stack/20-runtime.yaml), and the north-star dispatch path is
    the gRPC one.
    """
    transport = (transport or "http").lower()
    if endpoint.startswith("grpc://"):
        transport, endpoint = "grpc", endpoint[len("grpc://"):]
    if transport == "grpc":
        from .operator.analyst import GrpcAnalyst

        return GrpcAnalyst(endpoint or "localhost:8100")
    if transport != "http":
        raise ValueError(f"unknown analyst transport {transport!r} "
                         "(expected 'http' or 'grpc')")
    from .operator.analyst import HttpAnalyst

    return HttpAnalyst(endpoint or "http://localhost:8099/v1/healthcheck/")


def build_operator_loop(args, kube=None):
    """Operator loop from CLI args + env — the shipped configuration path.

    Returns (loop, description); kube is injectable for tests. The real
    KubeClient ships wrapped in the resilience layer (breaker + bounded
    retry against transport/5xx failures; FOREMAST_CHAOS can inject
    apiserver faults underneath it) — an injected test kube stays bare."""
    from .operator.loop import OperatorLoop

    if kube is None:
        from .engine.config import from_env
        from .resilience import BreakerBoard, ResilientKube, RetryPolicy
        from .resilience.faults import safe_injectors

        cfg = from_env()
        kube = _kube()
        inj = safe_injectors(knobs.read("FOREMAST_CHAOS")).get("kube")
        if inj is not None:
            from .resilience.faults import FaultyKube

            kube = FaultyKube(kube, inj)
        kube = ResilientKube(
            kube,
            retry=RetryPolicy(
                max_attempts=cfg.retry_max_attempts,
                base_delay=cfg.retry_base_delay,
                max_delay=cfg.retry_max_delay,
            ),
            breakers=BreakerBoard(
                failure_threshold=cfg.breaker_failure_threshold,
                recovery_seconds=cfg.breaker_recovery_seconds,
            ),
        )

    endpoint = args.analyst or knobs.read("ANALYST_ENDPOINT")
    transport = (
        getattr(args, "analyst_transport", "")
        or knobs.read("ANALYST_TRANSPORT")
    )
    analyst = make_analyst(endpoint, transport)
    watch = [n.strip() for n in knobs.read("WATCH_NAMESPACES").split(",")
             if n.strip()]
    loop = OperatorLoop(
        kube,
        analyst,
        mode=knobs.read("MODE"),
        hpa_strategy=knobs.read("HPA_STRATEGY"),
        watch_namespaces=watch or None,
    )
    # NAMESPACE keeps the reference's meaning (Barrelman.go:402): where the
    # deployment-metadata-default fallback record lives
    ns = knobs.read("OPERATOR_NAMESPACE") or knobs.read("NAMESPACE")
    if ns:
        loop.barrelman.operator_namespace = ns
    desc = f"analyst={type(analyst).__name__}({endpoint or 'default'})"
    return loop, desc


def cmd_operator(args) -> int:
    import signal

    loop, desc = build_operator_loop(args)
    tick = knobs.read("TICK_SECONDS")
    # pod termination finishes the current tick instead of cutting a
    # remediation in half (SIGTERM -> graceful loop exit)
    signal.signal(signal.SIGTERM, lambda *_: loop.request_stop())
    print(f"[foremast-tpu] operator: {desc} tick={tick}s", flush=True)
    loop.run_forever(interval=tick)
    return 0


def _fetch_monitor(namespace: str, app: str):
    """(kube, monitor, rc) for the CRD verbs — every failure is a one-line
    diagnosis, never a traceback (CLI boundary). KubeError.status tells
    transport problems (0: unreachable) apart from API refusals
    (403: RBAC, etc.) so the user is pointed at the right fix."""
    from .operator.kube import KubeError

    try:
        kube = _kube()
        monitor = kube.get_monitor(namespace, app)
    except KubeError as e:
        if e.status == 0:
            print(f"cannot reach the Kubernetes API: {e}\n"
                  "(status/watch/unwatch read the DeploymentMonitor CRD; run "
                  "them where kubectl works — job-level state is on the "
                  "runtime API at /v1/healthcheck/id/<jobId>)", file=sys.stderr)
        else:
            print(f"Kubernetes API refused the request (HTTP {e.status}): "
                  f"{e}", file=sys.stderr)
        return None, None, 1
    except Exception as e:  # noqa: BLE001 - client construction, bad CRDs...
        print(f"cannot talk to the Kubernetes API: {e}", file=sys.stderr)
        return None, None, 1
    if monitor is None:
        print(f"no DeploymentMonitor {namespace}/{app}", file=sys.stderr)
        return kube, None, 1
    return kube, monitor, 0


def _toggle_continuous(args, value: bool) -> int:
    from .operator.kube import KubeError

    kube, monitor, rc = _fetch_monitor(args.namespace, args.app)
    if rc:
        return rc
    try:
        # spec-only merge patch: must NOT round-trip a stale status copy
        kube.patch_monitor(args.namespace, args.app,
                           {"spec": {"continuous": value}})
    except KubeError as e:
        print(f"patch failed: {e}", file=sys.stderr)
        return 1
    print(f"{args.namespace}/{args.app}: continuous={value}")
    return 0


def cmd_watch(args) -> int:
    return _toggle_continuous(args, True)


def cmd_unwatch(args) -> int:
    return _toggle_continuous(args, False)


def cmd_status(args) -> int:
    _, monitor, rc = _fetch_monitor(args.namespace, args.app)
    if rc:
        return rc
    s = monitor.status
    out = {
        "app": args.app,
        "namespace": args.namespace,
        "phase": s.phase,
        "jobId": s.job_id,
        "continuous": monitor.spec.continuous,
        "remediationTaken": s.remediation_taken,
        "expired": s.expired,
        "anomalousMetrics": [m.name for m in s.anomaly.anomalous_metrics],
    }
    print(json.dumps(out, indent=2))
    return 0


def cmd_health(args) -> int:
    """Print the runtime's degraded-mode health state (/readyz).

    Exit code mirrors readiness: 0 for ok/degraded (serving), 1 for
    overloaded/stalled or an unreachable runtime — scriptable as a gate
    (`foremast-tpu health && kubectl ...`). Shares HttpAnalyst's probe
    transport (endpoint normalization + 503-body semantics) with the
    operator's remediation-suppression gate."""
    from .operator.analyst import AnalystError, HttpAnalyst

    endpoint = (args.endpoint or knobs.read("ANALYST_ENDPOINT")
                or "http://localhost:8099")
    analyst = HttpAnalyst(endpoint, timeout=5.0)
    try:
        status, body = analyst.probe_ready()
    except AnalystError as e:
        print(f"cannot probe {endpoint}: {e}", file=sys.stderr)
        return 1
    print(json.dumps(body, indent=2))
    return 0 if status == 200 else 1


def _resolve_base(endpoint: str) -> str:
    """Runtime base URL from --endpoint / ANALYST_ENDPOINT (analyst
    endpoints often carry the /v1/healthcheck/ suffix; the observability
    surfaces live at the server root)."""
    endpoint = (endpoint or knobs.read("ANALYST_ENDPOINT")
                or "http://localhost:8099")
    return endpoint.split("/v1/")[0].rstrip("/")


def _get_json(base: str, path: str):
    """One GET, decoded — shared by the read-only CLI verbs (shards /
    explain / top) so timeout/decoding policy cannot drift per verb."""
    import urllib.request

    with urllib.request.urlopen(f"{base}{path}", timeout=10) as r:
        return json.loads(r.read().decode())


def cmd_shards(args) -> int:
    """Print the runtime's shard-ring view (/status `shards` section):
    replica identity, live membership, owned/adopting/draining counts,
    and rebalance/handoff history — the "which slice of the fleet is this
    replica responsible for" question, scriptable."""
    base = _resolve_base(args.endpoint)
    try:
        payload = _get_json(base, "/status")
    except Exception as e:  # noqa: BLE001 - CLI boundary: diagnose, don't trace
        print(f"cannot reach {base}: {e}", file=sys.stderr)
        return 1
    snap = payload.get("shards")
    if snap is None:
        print("sharding is not active on this runtime (no archive or "
              "SHARDING=0)", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(snap, indent=2))
        return 0
    print(f"replica {snap.get('replica')} (worker {snap.get('worker')}), "
          f"membership {snap.get('membership')}"
          + ("" if snap.get("membership_fresh", True) else " [STALE VIEW]"))
    print(f"  replicas: {', '.join(snap.get('replicas', [])) or '-'}")
    print(f"  shards: {snap.get('owned')}/{snap.get('shard_count')} owned, "
          f"{snap.get('adopting')} adopting, {snap.get('draining')} draining")
    print(f"  rebalances: {snap.get('rebalances_total')}, "
          f"handoffs: {snap.get('handoffs_total')}, "
          f"adoptions: {snap.get('adoptions_total')}")
    return 0


def _render_explain(payload: dict) -> str:
    """Human-readable decision chain for one job's latest provenance
    record (the docs/operations.md "debugging a verdict" runbook walks
    each path through this rendering)."""
    lines = []
    job = payload.get("job") or {}
    if job:
        lines.append(
            f"job {job.get('jobId', '')} "
            f"[{job.get('strategy', '?')}] "
            f"{job.get('appName', '?')}/{job.get('namespace', '?')} — "
            f"status {job.get('status', '?')} "
            f"({job.get('internalStatus', '?')})")
        if job.get("reason"):
            lines.append(f"  reason: {job['reason']}")
    rec = payload.get("provenance")
    if not rec:
        if not payload.get("provenance_enabled", True):
            lines.append("  provenance recording is DISABLED "
                         "(PROVENANCE=0)")
        else:
            lines.append("  no provenance record (job not judged since "
                         "this runtime started, or record evicted)")
        return "\n".join(lines)
    cyc = rec.get("cycle") or {}
    cycle_id = cyc.get("cycle_id") or rec.get("cycle_id", "")
    src = (" (from archive)" if rec.get("from_archive")
           else " (from spilled tier)" if rec.get("from_tier")
           else " (from document summary)" if rec.get("from_document")
           else "")
    lines.append(f"  verdict path: {rec.get('path', '?')}"
                 + (f" — {rec['detail']}" if rec.get("detail") else "")
                 + src)
    lines.append(f"  cycle: {cycle_id}"
                 + (f" ({cyc.get('jobs')} jobs, "
                    f"{cyc.get('device_launches')} device launches)"
                    if cyc.get("jobs") is not None else ""))
    if rec.get("detection_latency_s") is not None:
        lines.append(
            f"  detection latency: {rec['detection_latency_s']:.3f}s "
            "(window advance -> verdict)")
    stages = rec.get("detection_stages") or {}
    if stages:
        # the waterfall arrives already in stage order (engine/slo.py
        # STAGE_ORDER — the recorder builds it ordered)
        lines.append("  waterfall: " + _fmt_waterfall(stages))
    if rec.get("trace_id"):
        lines.append(f"  trace: {rec['trace_id']} "
                     "(foremast-tpu trace <job>, or GET "
                     f"/debug/traces?trace_id={rec['trace_id']})")
    for h in rec.get("hops", []):
        # cross-replica history: each hop is one lease handoff the job
        # survived — the chain names the releasing replica AND its cycle
        lines.append(
            f"  handoff: from {h.get('replica') or h.get('worker') or '?'}"
            + (f" cycle {h['cycle_id']}" if h.get("cycle_id") else "")
            + f" ({h.get('reason') or 'handoff'}"
            + (f", last path {h['path']}" if h.get("path") else "")
            + ")")
    if rec.get("reason"):
        lines.append(f"  recorded reason: {rec['reason']}")
    for f in rec.get("families", []):
        fam = f.get("family", "?")
        verdict = "UNHEALTHY" if f.get("unhealthy") else "healthy"
        if fam == "pair":
            desc = (f"min_p {f.get('min_p')} vs alpha {f.get('alpha')}")
        elif fam == "band":
            desc = (f"{f.get('anomalous_points')} anomalous point(s), "
                    f"band {f.get('band')}")
        elif fam == "bivariate":
            desc = f"{f.get('anomalous_points')} point(s) outside ellipse"
        elif fam == "lstm":
            desc = f"z {f.get('z')} vs threshold {f.get('threshold')}"
        elif fam == "hpa":
            desc = (f"score {f.get('gated_score')} "
                    f"(raw {f.get('raw_score')}), "
                    f"sla {f.get('sla_current')}/{f.get('sla_limit')}")
        else:
            desc = json.dumps(f)
        lines.append(f"    {fam} {f.get('metric', '')}: {desc} "
                     f"-> {verdict}")
    if rec.get("families_dropped"):
        lines.append(f"    ... {rec['families_dropped']} more "
                     "(truncated)")
    fetch = rec.get("fetch") or {}
    if fetch:
        parts = []
        if fetch.get("fetches"):
            parts.append(f"{int(fetch['fetches'])} fetch(es)")
        # how each window was got: the notes of dataplane/delta.py's
        # fetch_window and of the TTL cache above it
        mode = [f"{int(fetch[note])} {label}" for note, label in (
            ("fetch_delta", "delta"), ("fetch_unmoved", "unmoved"),
            ("fetch_ingest", "pushed"), ("fetch_full", "full"),
            ("fetch_cached", "cached")) if fetch.get(note)]
        if fetch.get("fetch_delta") and fetch.get("fetch_append"):
            # of the deltas (the first label), those the append rule grew
            # without a splice
            appends = int(fetch["fetch_append"])
            mode[0] += (" (append)" if appends == fetch["fetch_delta"]
                        else f" ({appends} append)")
        if mode:
            parts.append("/".join(mode))
        if fetch.get("points"):
            parts.append(f"{int(fetch['points'])} points")
        if fetch.get("fetch_seconds") is not None:
            parts.append(f"{fetch['fetch_seconds']:.3f}s")
        lines.append("  fetch: " + ", ".join(parts))
    stages = cyc.get("stage_seconds") or {}
    if stages:
        lines.append("  cycle stages: " + ", ".join(
            f"{k} {v:.3f}s" for k, v in stages.items()))
    return "\n".join(lines)


def cmd_explain(args) -> int:
    """Fetch and render one job's verdict provenance (/jobs/<id>/explain)."""
    import urllib.error

    base = _resolve_base(args.endpoint)
    try:
        payload = _get_json(base, f"/jobs/{args.job}/explain")
    except urllib.error.HTTPError as e:
        try:
            msg = json.loads(e.read().decode()).get("error", str(e))
        except Exception:  # noqa: BLE001 - non-JSON error body
            msg = str(e)
        print(f"explain failed ({e.code}): {msg}", file=sys.stderr)
        return 1
    except Exception as e:  # noqa: BLE001 - CLI boundary: diagnose, don't trace
        print(f"cannot reach {base}: {e}", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(payload, indent=2))
    else:
        print(_render_explain(payload))
    return 0


def _fmt_waterfall(stages: dict) -> str:
    """One rendering for detection-stage waterfalls everywhere the CLI
    shows them (explain, trace tree, trace summary)."""
    return " -> ".join(f"{k} {float(v) * 1000:.1f}ms"
                       for k, v in stages.items())


def _render_trace_tree(span: dict, depth: int, lines: list):
    attrs = span.get("attrs") or {}
    extra = []
    for key in ("replica", "origin_replica", "job_id", "transport",
                "target", "worker", "status"):
        if key in attrs:
            extra.append(f"{key}={attrs[key]}")
    lines.append(f"  {'  ' * depth}{span.get('name', '?')} "
                 f"{span.get('duration_ms', 0):.1f}ms"
                 + (f"  [{', '.join(extra)}]" if extra else ""))
    wf = attrs.get("waterfall")
    if isinstance(wf, dict) and wf:
        lines.append(f"  {'  ' * (depth + 1)}waterfall: "
                     + _fmt_waterfall(wf))
    for child in span.get("children") or ():
        _render_trace_tree(child, depth + 1, lines)


def _render_trace(trace_id: str, trees: list, job_id: str) -> str:
    """Human-readable distributed trace: each locally-finished span tree
    of the trace (receive/forward on one replica, partial cycle +
    verdict on the scoring one), resource-stamped, with the closing
    verdict span's waterfall inline."""
    lines = [f"trace {trace_id} for job {job_id} — "
             f"{len(trees)} span tree(s) on this replica"]
    for tree in trees:
        res = tree.get("resource") or {}
        head = f"[{res.get('replica', 'local')}]" if res else "[local]"
        lines.append(head)
        _render_trace_tree(tree, 0, lines)
    if not trees:
        lines.append("  (no spans in this replica's ring — the trace "
                     "may live on the replica that scored the job, or "
                     "was evicted/unsampled; try the other replicas or "
                     "the TRACE_EXPORT_URL collector)")
    return "\n".join(lines)


def cmd_trace(args) -> int:
    """Fetch one job's push-to-verdict distributed trace: resolve the
    job's trace_id via /jobs/<id>/explain, then render every span tree
    of that trace from /debug/traces?trace_id= (docs/operations.md
    "Following one push to its verdict")."""
    base = _resolve_base(args.endpoint)
    explain, rec = {}, {}
    if args.trace_id:
        # explicit id: the explain hop is OPTIONAL enrichment (the job
        # may be unknown to this replica — e.g. the id came from an
        # /ingest response on the non-owner); its failure must not block
        # the /debug/traces fetch
        try:
            explain = _get_json(base, f"/jobs/{args.job}/explain")
            rec = explain.get("provenance") or {}
        except Exception:  # noqa: BLE001 - enrichment only
            pass
    else:
        try:
            explain = _get_json(base, f"/jobs/{args.job}/explain")
        except Exception as e:  # noqa: BLE001 - CLI boundary: diagnose
            print(f"cannot reach {base}: {e}", file=sys.stderr)
            return 1
        rec = explain.get("provenance") or {}
    trace_id = args.trace_id or rec.get("trace_id", "")
    if not trace_id:
        print(f"job {args.job} has no recorded trace_id "
              "(not judged since this runtime started, or provenance "
              "is off)", file=sys.stderr)
        return 1
    try:
        payload = _get_json(
            base, f"/debug/traces?trace_id={trace_id}&limit=100")
    except Exception as e:  # noqa: BLE001 - CLI boundary: diagnose
        print(f"cannot reach {base}: {e}", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps({"trace_id": trace_id, "explain": explain,
                          "traces": payload.get("traces", [])}, indent=2))
        return 0
    print(_render_trace(trace_id, payload.get("traces", []), args.job))
    stages = rec.get("detection_stages") or {}
    if stages:
        print("verdict waterfall: " + _fmt_waterfall(stages))
    if rec.get("detection_latency_s") is not None:
        print(f"detection latency: {rec['detection_latency_s']:.3f}s")
    return 0


def _render_fleet(payload: dict) -> str:
    """Human-readable fleet view (`foremast-tpu top`): one row per
    replica from its published digest, aggregate header on top — the
    operator's single place to see an N-replica brain as one system."""
    agg = payload.get("aggregate") or {}
    lines = [
        f"fleet via {payload.get('replica', '?')} — "
        f"{agg.get('replicas', 0)} replica(s), "
        f"{agg.get('replicas_fresh', 0)} fresh, "
        f"worst health {agg.get('worst_health', '?')}, "
        f"{agg.get('shards_owned', 0)} shard(s) owned, "
        f"{sum((agg.get('jobs') or {}).values())} job(s)"
    ]
    slo_worst = agg.get("slo_worst") or {}
    if slo_worst:
        lines.append("slo (worst replica per class): " + "; ".join(
            f"{cls} p50 {s.get('p50_s')}s p99 {s.get('p99_s')}s "
            f"burn {s.get('burn')}"
            for cls, s in sorted(slo_worst.items())))
    lines.append(
        f"{'REPLICA':<24} {'HEALTH':<11} {'SHARDS o/a/d':<13} "
        f"{'JOBS':>6} {'CYCLE':<14} {'DETECT p50/p99':<26} {'AGE':>9}")
    for r in payload.get("replicas", []):
        d = r.get("digest") or {}
        sh = d.get("shards") or {}
        shards = (f"{sh.get('owned', 0)}/{sh.get('adopting', 0)}/"
                  f"{sh.get('draining', 0)}" if sh else "-")
        jobs = sum((d.get("jobs") or {}).values())
        slo_d = d.get("slo") or {}
        detect = " ".join(
            f"{cls[:4]} {s.get('p50_s')}/{s.get('p99_s')}s"
            for cls, s in sorted(slo_d.items())) or "-"
        if r.get("self"):
            age = "live"
        elif r.get("age_s") is None:
            age = "static"  # launcher-fixed membership: no heartbeat age
        else:
            age = f"{r['age_s']:.0f}s"
        name = r.get("replica", "?") + (" *" if r.get("self") else "")
        health = (d.get("health") or "?") + \
            (" STALE" if r.get("stale") else "")
        lines.append(
            f"{name:<24} {health:<11} {shards:<13} {jobs:>6} "
            f"{(d.get('cycle_id') or '-'):<14} {detect:<26} {age:>9}")
    return "\n".join(lines)


def cmd_top(args) -> int:
    """Render the fleet view (GET /fleet): per-replica health, shard
    slices, detection-latency p50/p99, digest staleness — the sharded
    brain as ONE system from any replica's endpoint. `--watch N`
    re-renders every N seconds until interrupted."""
    import time as _time

    base = _resolve_base(args.endpoint)
    try:
        while True:
            try:
                payload = _get_json(base, "/fleet")
            except Exception as e:  # noqa: BLE001 - CLI boundary: diagnose
                print(f"cannot reach {base}: {e}", file=sys.stderr)
                return 1
            if args.json:
                print(json.dumps(payload, indent=2))
            else:
                print(_render_fleet(payload))
            if not args.watch:
                return 0
            _time.sleep(max(args.watch, 1.0))
            print()
    except KeyboardInterrupt:
        # ^C mid-fetch or mid-sleep is the normal way out of --watch
        return 0


def cmd_trigger(args) -> int:
    from .trigger.trigger import main

    main()
    return 0


def cmd_prewarm(args) -> int:
    """Compile the standard (family x rung x T-bucket) scoring grid.

    The compiled programs land in the persistent compile cache
    (JAX_COMPILATION_CACHE_DIR, or a source checkout's `.jax_cache/`), so
    every runtime pointed at the same directory (ReadWriteMany volume in
    the shipped manifests) starts warm; with no cache directory this is a
    dry-run that prints what a cold start would compile. The JSON names
    the device the programs were compiled for.
    """
    from .engine.config import from_env
    from .engine.pipeline import device_info, enable_compile_cache, prewarm

    # per-program progress on stderr (stdout stays the one JSON record)
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(levelname)s %(message)s")
    cfg = from_env()
    cache_dir = enable_compile_cache()
    try:
        rungs = tuple(int(r) for r in args.rungs.split(",") if r.strip())
        buckets = tuple(int(b) for b in args.buckets.split(",") if b.strip())
        families = tuple(f.strip() for f in args.families.split(",")
                         if f.strip())
    except ValueError as e:
        print(f"invalid prewarm grid: {e}", file=sys.stderr)
        return 2
    info = prewarm(cfg, families=families, rungs=rungs, t_buckets=buckets)
    info["compile_cache"] = cache_dir or None
    info.update(device_info())
    print(json.dumps(info, indent=2))
    return 0


def cmd_simfleet(args) -> int:
    """Run the fleet-scale load simulator (foremast_tpu/simfleet).

    Default: the in-process mega-batch A/B (identity + launch collapse
    + measured speedup). `--leg` runs a single leg honoring --megabatch
    / --stream. `--live ENDPOINT` instead serves the trace over HTTP,
    submits the fleet to a RUNNING replica's job API, and (with --push)
    streams the advancing samples to its /ingest/remote-write
    (docs/operations.md "Running a simulated fleet").
    """
    from .simfleet import driver

    if args.live:
        out = driver.run_live(args.live, jobs=args.jobs, seed=args.seed,
                              shape=args.shape, duration_s=args.duration,
                              push=args.push)
    elif args.leg:
        out = driver.run_fleet(args.jobs, args.seed, args.shape,
                               args.cycles, args.cadence, args.replicas,
                               megabatch=args.megabatch,
                               stream=args.stream)
    else:
        out = driver.run_fleet_ab(args.jobs, args.seed, args.shape,
                                  args.cycles, args.cadence,
                                  args.replicas, rounds=args.rounds)
    print(json.dumps(out, indent=2))
    return 0


def cmd_demo(args) -> int:
    if args.hpa:
        from .examples.demo_app import run_demo_hpa

        result = run_demo_hpa()
    else:
        from .examples.demo_app import run_demo

        result = run_demo(unhealthy=not args.healthy)
    print(json.dumps(result, indent=2, default=str))
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="foremast-tpu", description=__doc__.split("\n")[0])
    sub = p.add_subparsers(dest="command")
    sub.add_parser("serve", help="run the runtime (job API + engine)").set_defaults(
        func=cmd_serve
    )
    op = sub.add_parser("operator", help="run the K8s operator loop")
    op.add_argument("--analyst", default="",
                    help="job API endpoint (grpc:// scheme selects gRPC)")
    op.add_argument("--analyst-transport", default="",
                    choices=("http", "grpc"),
                    help="dispatch transport (env ANALYST_TRANSPORT; "
                         "default http)")
    op.set_defaults(func=cmd_operator)
    sub.add_parser(
        "trigger",
        help="run the non-K8s poller (REQUESTS_FILE CSV -> rolling analyses)",
    ).set_defaults(func=cmd_trigger)
    hp = sub.add_parser(
        "health",
        help="print the runtime's degraded-mode health state (/readyz)",
    )
    hp.add_argument("--endpoint", default="",
                    help="runtime base URL (env ANALYST_ENDPOINT; "
                         "default http://localhost:8099)")
    hp.set_defaults(func=cmd_health)
    sh = sub.add_parser(
        "shards",
        help="print the runtime's shard-ring view (replica membership, "
             "owned/adopting/draining shards, rebalance history)",
    )
    sh.add_argument("--endpoint", default="",
                    help="runtime base URL (env ANALYST_ENDPOINT; "
                         "default http://localhost:8099)")
    sh.add_argument("--json", action="store_true",
                    help="print the raw /status shards section")
    sh.set_defaults(func=cmd_shards)
    tp = sub.add_parser(
        "top",
        help="render the fleet view (/fleet): per-replica health, shard "
             "slices, detection-latency p50/p99, digest staleness",
    )
    tp.add_argument("--endpoint", default="",
                    help="any replica's base URL (env ANALYST_ENDPOINT; "
                         "default http://localhost:8099)")
    tp.add_argument("--json", action="store_true",
                    help="print the raw /fleet payload")
    tp.add_argument("--watch", type=float, default=0.0, metavar="SECONDS",
                    help="re-render every N seconds (floor 1s) until "
                         "interrupted")
    tp.set_defaults(func=cmd_top)
    ex = sub.add_parser(
        "explain",
        help="render a job's verdict provenance (which path produced the "
             "verdict, scores vs thresholds, fetch mode)",
    )
    ex.add_argument("job", help="job id (/v1/healthcheck/create's jobId)")
    ex.add_argument("--endpoint", default="",
                    help="runtime base URL (env ANALYST_ENDPOINT; "
                         "default http://localhost:8099)")
    ex.add_argument("--json", action="store_true",
                    help="print the raw /jobs/<id>/explain payload")
    ex.set_defaults(func=cmd_explain)
    trc = sub.add_parser(
        "trace",
        help="render a job's push-to-verdict distributed trace (explain's "
             "trace_id resolved against /debug/traces) with its "
             "detection-latency waterfall",
    )
    trc.add_argument("job", help="job id (/v1/healthcheck/create's jobId)")
    trc.add_argument("--trace-id", default="",
                     help="explicit trace id (skip the explain lookup — "
                          "e.g. the trace_id an /ingest response returned)")
    trc.add_argument("--endpoint", default="",
                     help="runtime base URL (env ANALYST_ENDPOINT; "
                          "default http://localhost:8099)")
    trc.add_argument("--json", action="store_true",
                     help="print the raw explain + trace payloads")
    trc.set_defaults(func=cmd_trace)
    for name, fn, help_ in (
        ("watch", cmd_watch, "enable continuous monitoring for an app"),
        ("unwatch", cmd_unwatch, "disable continuous monitoring for an app"),
        ("status", cmd_status, "print an app's monitor status"),
    ):
        sp = sub.add_parser(name, help=help_)
        sp.add_argument("app")
        sp.add_argument("-n", "--namespace", default="default")
        sp.set_defaults(func=fn)
    pw = sub.add_parser(
        "prewarm",
        help="compile the scoring-program grid into the persistent "
             "compile cache so runtimes start without the compile storm",
    )
    pw.add_argument("--families",
                    default="pair,band,bivariate,hpa,triage",
                    help="comma-separated model families to warm")
    pw.add_argument("--rungs", default="16,64,256,1024",
                    help="comma-separated batch rungs (clamped to the "
                         "engine's rung ladder)")
    pw.add_argument("--buckets", default="128,256",
                    help="comma-separated T (window-length) buckets")
    pw.set_defaults(func=cmd_prewarm)
    sf = sub.add_parser(
        "simfleet",
        help="fleet-scale load simulator: in-process mega-batch A/B, "
             "single legs, or driving a LIVE replica (--live)",
    )
    # defaults come from the SIM_* registry so the docs/configuration.md
    # contract (`SIM_JOBS=100000 foremast-tpu simfleet`) holds for the
    # CLI exactly as for `python -m foremast_tpu.simfleet`; flags win
    # over env
    from .utils import knobs as _knobs

    sf.add_argument("--jobs", type=int, default=_knobs.read("SIM_JOBS"))
    sf.add_argument("--seed", type=int, default=_knobs.read("SIM_SEED"))
    sf.add_argument("--shape", default=_knobs.read("SIM_TRACE"),
                    help="trace preset: steady | diurnal | deploy-wave "
                         "| incident | churn")
    sf.add_argument("--cycles", type=int,
                    default=_knobs.read("SIM_CYCLES"))
    sf.add_argument("--cadence", type=float,
                    default=_knobs.read("SIM_CADENCE_S"),
                    help="sim seconds per cycle (60 = every cycle "
                         "advances every window)")
    sf.add_argument("--replicas", type=int,
                    default=_knobs.read("SIM_REPLICAS"))
    sf.add_argument("--rounds", type=int,
                    default=_knobs.read("SIM_ROUNDS"),
                    help="A/B interleave rounds (SIM_ROUNDS; 1 keeps a "
                         "100k+ run affordable)")
    sf.add_argument("--leg", action="store_true",
                    help="run ONE leg instead of the on/off A/B")
    sf.add_argument("--megabatch", action="store_true",
                    help="(with --leg) enable MEGABATCH for the leg")
    sf.add_argument("--stream", action="store_true",
                    help="(with --leg) push samples through the ingest "
                         "receiver instead of poll-only")
    sf.add_argument("--live", default="",
                    help="drive a RUNNING replica at this endpoint "
                         "instead of in-process")
    sf.add_argument("--push", action="store_true",
                    help="(with --live) also stream remote-write pushes")
    sf.add_argument("--duration", type=float, default=60.0,
                    help="(with --live) seconds to serve/push")
    sf.set_defaults(func=cmd_simfleet)
    d = sub.add_parser("demo", help="local end-to-end demo, no cluster")
    variant = d.add_mutually_exclusive_group()
    variant.add_argument("--healthy", action="store_true",
                         help="run the healthy variant (no error generator)")
    variant.add_argument("--hpa", action="store_true",
                         help="run the HPA autoscaling-score loop instead")
    d.set_defaults(func=cmd_demo)
    return p


def main(argv: list[str] | None = None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if not getattr(args, "command", None):
        args = parser.parse_args(["serve"])
    return args.func(args)


if __name__ == "__main__":
    sys.exit(main())
