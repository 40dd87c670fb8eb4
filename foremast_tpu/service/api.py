"""HTTP job API — the contract of foremast-service, stdlib-only.

Endpoints (reference: foremast-service/cmd/manager/main.go:326-346):
  POST /v1/healthcheck/create          submit an analysis job
  POST /ingest/remote-write            Prometheus remote-write receiver
                                       (snappy + protobuf WriteRequest;
                                       foremast_tpu/ingest) — pushed
                                       samples splice into the window
                                       cache and wake partial cycles
  POST /ingest/otlp                    OTLP/HTTP metrics receiver (JSON
                                       encoding), same routing
  GET  /v1/healthcheck/id/<jobId>      job status + hpa logs
  GET  /alert/<app>/<namespace>/<strategy>   recent HPA logs for the app
  GET  /api/v1/<queryproxy>?...        CORS proxy to the metric store
  GET  /metrics                        foremastbrain:* verdict series
                                       (Prometheus 0.0.4 content type)
  GET  /status                         degradation view: job counts +
                                       breaker states + retry counters +
                                       health state machine + SLO section
  GET  /fleet                          cross-replica federation view:
                                       every replica's status digest
                                       (from the membership heartbeats)
                                       + staleness + an aggregate block
  GET  /debug/flight/dumps[/<name>]    on-disk incident-dump index/fetch
  GET  /healthz                        liveness (is the process up)
  GET  /readyz                         readiness: the degraded-mode health
                                       state (ok/degraded -> 200,
                                       overloaded/stalled -> 503)

Behavior contracts preserved:
  * job ids — HMAC-SHA256 over the canonical request; HPA jobs get the
    deterministic "app:namespace:hpa" id (elasticsearchstore.go:31-33,
    stringutils.go:11-17).
  * dedupe-or-create on id (elasticsearchstore.go:24-92).
  * hpa/continuous jobs swap start/end for START_TIME/END_TIME placeholders
    so windows re-materialize each cycle (main.go:59-63).
  * status mapping internal -> external (converter.go:10-29) via
    engine.jobs.to_external.
  * appName validation: non-empty, sane charset (main.go:152-162).

The reference split service (Go) from brain (Python) across an ES hop; here
the API writes straight into the in-process JobStore the engine workers
drain — one process, zero queue hops. The store stays pluggable for an
external archive.
"""
from __future__ import annotations

import json
import re
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from ..dataplane.exporter import VerdictExporter
from ..utils.promtext import escape_label_value
from ..dataplane.promql import (
    CONTINUOUS_STRATEGIES,
    END_PLACEHOLDER,
    START_PLACEHOLDER,
    placeholderize,
    prometheus_range_url,
    wavefront_url,
)
from ..engine import jobs as J
from ..engine.jobs import Document, JobStore, MetricQueries
from ..utils.ids import hmac_job_id, hpa_job_id

_APP_RE = re.compile(r"^[A-Za-z0-9_.-]{1,253}$")
_METRIC_RE = re.compile(r"^[A-Za-z0-9_:.-]{1,200}$")

VALID_STRATEGIES = {"rollingUpdate", "canary", "continuous", "hpa", "rollover"}


class ApiError(Exception):
    def __init__(self, status: int, message: str):
        super().__init__(message)
        self.status = status
        self.message = message


def _canon_time(x):
    """Collapse integral floats to int. Materialized query URLs — and the
    deterministic HMAC job ids derived from them — must be identical for
    the same logical request on every transport: gRPC carries start/end as
    protobuf doubles and JSON clients may send 1234.0, while JSON integers
    arrive as Python ints. Normalizing here, in the shared build path,
    keeps the facades transport-agnostic."""
    try:
        f = float(x)
    except (TypeError, ValueError):
        if isinstance(x, str):
            # placeholder strings (START_TIME/END_TIME) and RFC3339 pass
            # through untouched for downstream materialization
            return x
        # lists/objects would be f-string-embedded into the query URL as
        # python reprs — a garbage 200 whose fetches can never succeed
        raise ApiError(
            400, f"time parameter must be a number or string, "
                 f"got {type(x).__name__}") from None
    return int(f) if f.is_integer() else x


def _category_url(entry: dict, strategy: str) -> str:
    """One MetricQuery wire object -> concrete query URL.

    Accepts {"url": "..."} directly, or the reference's
    {dataSourceType, parameters: {endpoint?, query, start, end, step}} shape
    (constructURL dispatch, main.go:34-48).
    """
    if not entry:
        return ""
    if not isinstance(entry, dict):
        raise ApiError(400, f"metric entry must be an object, got {type(entry).__name__}")
    if entry.get("url"):
        url = entry["url"]
        if not isinstance(url, str):
            raise ApiError(400, "metric 'url' must be a string")
    else:
        params = entry.get("parameters", {})
        if not isinstance(params, dict):
            raise ApiError(400, "metric 'parameters' must be an object")
        query = params.get("query", "")
        if not query:
            return ""
        if not isinstance(query, str):
            raise ApiError(400, "metric 'parameters.query' must be a string")
        endpoint = params.get("endpoint", "http://prometheus:9090/api/v1/")
        if not isinstance(endpoint, str):
            raise ApiError(400, "metric 'parameters.endpoint' must be a string")
        start = _canon_time(params.get("start", 0))
        end = _canon_time(params.get("end", 0))
        try:
            step = int(params.get("step", 60))
        except (TypeError, ValueError):
            raise ApiError(400, f"invalid step {params.get('step')!r}") from None
        if entry.get("dataSourceType") == "wavefront":
            url = wavefront_url(endpoint, query, start, end, step)
        else:
            url = prometheus_range_url(endpoint, query, start, end, step)
    return url


def _wire_bool(flags: dict, key: str, default: bool, metric: str) -> bool:
    """Boolean wire flags that FLIP SEMANTICS (metric direction, limit
    interpretation) must never be silently mis-coerced: bool("false") is
    True, and a Go client marshalling strings would invert every verdict
    direction. Accepts real booleans and the unambiguous string forms."""
    v = flags.get(key, default)
    if isinstance(v, bool):
        return v
    if isinstance(v, int) and v in (0, 1):
        return bool(v)  # JSON 0/1 is unambiguous
    if isinstance(v, str):
        low = v.strip().lower()
        if low in ("true", "1", "yes"):
            return True
        if low in ("false", "0", "no", ""):
            return False
    raise ApiError(400, f"invalid {key} {v!r} for metric {metric}")


def _parse_provenance_blob(blob: str, source: str = "from_archive"):
    """Decode a Document's attached provenance summary (processing_content)
    back into an explain() record, tagged with where it was read from; None
    when absent or not provenance JSON (legacy docs store free text here)."""
    if not blob:
        return None
    try:
        rec = json.loads(blob)
    except ValueError:
        return None
    if not isinstance(rec, dict):
        return None
    rec[source] = True
    return rec


def _as_object(x, name: str) -> dict:
    """JSON-shape gate: real clients produce every type confusion (arrays
    for objects, strings for maps); each must be a clean 400, never a
    500 from an AttributeError deep in conversion."""
    if x is None:
        return {}
    if not isinstance(x, dict):
        raise ApiError(400, f"{name} must be a JSON object, "
                            f"got {type(x).__name__}")
    return x


def build_document(req: dict) -> Document:
    """Validate + convert a create request into a job Document."""
    req = _as_object(req, "request body")
    app = req.get("appName", "")
    if not isinstance(app, str) or not app or not _APP_RE.match(app):
        raise ApiError(400, f"invalid appName {str(app)[:128]!r}")
    strategy = req.get("strategy", "rollingUpdate")
    if strategy not in VALID_STRATEGIES:
        raise ApiError(400, f"invalid strategy {strategy!r}")
    namespace = req.get("namespace", "default")
    if not isinstance(namespace, str):
        raise ApiError(400, "namespace must be a string")
    info = _as_object(req.get("metricsInfo"), "metricsInfo")
    current = _as_object(info.get("current"), "metricsInfo.current")
    baseline = _as_object(info.get("baseline"), "metricsInfo.baseline")
    historical = _as_object(info.get("historical"), "metricsInfo.historical")
    if not current and strategy != "hpa":
        raise ApiError(400, "metricsInfo.current is required")

    continuous = strategy in CONTINUOUS_STRATEGIES
    metrics: dict[str, MetricQueries] = {}
    # sorted: set iteration is hash-randomized across processes, and the
    # HPA tps/sla selection tie-breaks on insertion order — scores must not
    # change across a restart
    for name in sorted(set(current) | set(baseline) | set(historical)):
        if not isinstance(name, str) or not _METRIC_RE.match(name):
            raise ApiError(400, f"invalid metric name {str(name)[:128]!r}")
        cur_e = _as_object(current.get(name), f"metricsInfo.current.{name}")
        base_e = _as_object(baseline.get(name), f"metricsInfo.baseline.{name}")
        hist_e = _as_object(historical.get(name),
                            f"metricsInfo.historical.{name}")
        cur = _category_url(cur_e, strategy)
        base = _category_url(base_e, strategy)
        hist = _category_url(hist_e, strategy)
        if continuous:
            cur = placeholderize(cur, historical=False)
            base = ""
            hist = placeholderize(hist, historical=True)
        # hpa flags may ride whichever category carries the metric
        flags = cur_e or base_e or hist_e
        try:
            priority = int(flags.get("priority", 0))
        except (TypeError, ValueError):
            raise ApiError(
                400, f"invalid priority {flags.get('priority')!r} for {name}"
            ) from None
        metrics[name] = MetricQueries(
            current=cur,
            baseline=base,
            historical=hist,
            priority=priority,
            is_increase=_wire_bool(flags, "isIncrease", True, name),
            is_absolute=_wire_bool(flags, "isAbsolute", False, name),
        )

    start_time = req.get("startTime", "")
    end_time = req.get("endTime", "")
    if not isinstance(start_time, str) or not isinstance(end_time, str):
        raise ApiError(400, "startTime/endTime must be RFC3339 strings")
    if continuous:
        start_time, end_time = START_PLACEHOLDER, END_PLACEHOLDER

    if strategy == "hpa":
        job_id = hpa_job_id(app, namespace)
    else:
        job_id = hmac_job_id(
            {
                "appName": app,
                "namespace": namespace,
                "strategy": strategy,
                "startTime": start_time,
                "endTime": end_time,
                "metrics": {
                    k: [v.current, v.baseline, v.historical] for k, v in sorted(metrics.items())
                },
            }
        )
    # continuous/hpa jobs re-materialize their windows every cycle; the
    # pod-count query must ride along (a concrete start/end stamped at
    # create time would go stale after the first cycle and freeze the
    # per-pod normalization at day-one replica counts). historical=True:
    # per-pod scoring needs the replica history the capacity proxy spans,
    # not just the scoring window.
    pod_count_url = req.get("podCountURL", "")
    if not isinstance(pod_count_url, str):
        raise ApiError(400, "podCountURL must be a string")
    if continuous and pod_count_url:
        pod_count_url = placeholderize(pod_count_url, historical=True)
    return Document(
        id=job_id,
        app_name=app,
        namespace=namespace,
        strategy=strategy,
        start_time=start_time,
        end_time=end_time,
        metrics=metrics,
        pod_count_url=pod_count_url,
    )


class ForemastService:
    """Route handlers over the shared store/exporter."""

    def __init__(self, store: JobStore, exporter: VerdictExporter | None = None,
                 query_endpoint: str = "", analyzer=None, resilience=None,
                 delta_source=None, cache_source=None, shard=None,
                 ingest=None, scheduler=None, window_store=None,
                 trace_exporter=None):
        self.store = store
        self.exporter = exporter or VerdictExporter()
        self.query_endpoint = query_endpoint  # metric-store base for the proxy
        # optional engine handle: lets /metrics surface analyzer-side
        # counters (LSTM budget skips, stack rebuilds) next to the store's
        self.analyzer = analyzer
        # optional resilience handle (ResilientDataSource): /status reports
        # live breaker states + retry counters from its snapshot()
        self.resilience = resilience
        # optional dataplane handles: the delta window source (hit ratio,
        # bytes saved) and the TTL CachingDataSource (hit/miss/
        # single-flight counters) — both surfaced on /metrics and /status
        self.delta_source = delta_source
        self.cache_source = cache_source
        # optional sharded-brain handle (engine/sharding.py ShardManager):
        # /status gets a shards section, /metrics the shard gauges
        self.shard = shard
        # optional push-ingest receiver (foremast_tpu/ingest): mounts the
        # /ingest/* endpoints; /status gets an ingest section, /metrics
        # the ingest counters + buffer gauge
        self.ingest = ingest
        # optional event scheduler handle (engine/scheduler.py
        # StreamScheduler, stamped by the runtime at start): /status gets
        # the partial-cycle counters and the pending-job depth
        self.scheduler = scheduler
        # optional crash-durable window store (dataplane/winstore.py):
        # /status gets segment/WAL/recovery stats, /metrics the
        # window_store gauges (docs/operations.md "Surviving a restart")
        self.window_store = window_store
        # optional OTLP trace exporter (dataplane/exporter.py
        # OtlpTraceExporter): /status gets a trace_export section
        self.trace_exporter = trace_exporter
        self.chaos_active = False  # stamped by the runtime when chaos is on
        # stamped by Runtime.start(): the CompileCounter running since
        # start, surfaced as /status build.compile
        self.compile_counter = None
        # set by make_server: () -> the HTTP admission gate's shed counter
        self.http_shed_count = None
        # /status build section: dumps and bug reports self-identify
        # (package version + uptime + the cycle they were taken during)
        self.started_at = time.time()

    # -- handlers, each returns (status, payload-dict | text) --
    def create(self, body: dict):
        doc = build_document(body)
        doc, created = self.store.create(doc)
        return 200, {"jobId": doc.id, "status": J.to_external(doc.status)}

    def status(self, job_id: str):
        doc = self.store.get(job_id)
        if doc is None:
            # a terminal job may have been gc'd from RAM after archival:
            # the id must stay resolvable as long as /search returns it
            archive = getattr(self.store, "archive", None)
            rec = archive.get(job_id) if archive is not None else None
            if rec is None:
                return 404, {"error": f"job {job_id} not found"}
            return 200, {
                "jobId": rec.get("id", job_id),
                "appName": rec.get("app_name", ""),
                "namespace": rec.get("namespace", ""),
                "strategy": rec.get("strategy", ""),
                "status": J.to_external(rec.get("status", "")),
                "statusCode": "200",
                "reason": rec.get("reason", ""),
                "anomaly": rec.get("anomaly", {}),
                "hpalogs": [],
            }
        logs = self.store.hpalogs_for(job_id)
        return 200, {
            "jobId": doc.id,
            "appName": doc.app_name,
            "namespace": doc.namespace,
            "strategy": doc.strategy,
            "status": J.to_external(doc.status),
            "statusCode": "200",
            "reason": doc.reason,
            "anomaly": doc.anomaly,
            "hpalogs": [
                {
                    "job_id": l.job_id,
                    "hpascore": l.hpascore,
                    "reason": l.reason,
                    "details": l.details,
                    "timestamp": l.timestamp,
                }
                for l in logs
            ],
        }

    def alert(self, app: str, namespace: str, strategy: str):
        job_id = hpa_job_id(app, namespace)
        logs = self.store.hpalogs_for(job_id)
        return 200, {
            "appName": app,
            "namespace": namespace,
            "strategy": strategy,
            "hpalogs": [
                {"hpascore": l.hpascore, "reason": l.reason, "details": l.details,
                 "timestamp": l.timestamp}
                for l in logs
            ],
        }

    def query_proxy(self, path_and_query: str):
        if not self.query_endpoint:
            return 502, {"error": "no query endpoint configured"}
        url = self.query_endpoint.rstrip("/") + "/" + path_and_query.lstrip("/")
        try:
            with urllib.request.urlopen(url, timeout=10) as r:
                return 200, r.read().decode()
        except Exception as e:  # noqa: BLE001 - proxy boundary
            return 502, {"error": f"query proxy failed: {e}"}

    def search(self, params: dict):
        """GET /v1/healthcheck/search — the job-audit surface ES/Kibana
        provided in the reference (design.md:49-51 there): live store plus
        the write-behind archive, filterable by app/namespace/status/
        strategy. `status` accepts internal or external names."""
        def one(key):
            v = params.get(key, [""])[0]
            return v or None

        status = one("status")
        statuses = None
        if status:
            # accept internal names and external aliases; an external name
            # ("abort") fans out to every internal it covers
            statuses = [k for k, v in J.EXTERNAL_STATUS.items()
                        if k == status or v == status]
            if not statuses:
                raise ApiError(400, f"unknown status {status!r}")
        try:
            limit = int(params.get("limit", ["50"])[0])
        except ValueError:
            raise ApiError(400, "invalid limit") from None
        if not 1 <= limit <= 500:
            raise ApiError(400, f"limit must be in [1, 500], got {limit}")
        out = [
            {
                "jobId": rec.get("id", ""),
                "appName": rec.get("app_name", ""),
                "namespace": rec.get("namespace", ""),
                "strategy": rec.get("strategy", ""),
                "status": J.to_external(rec.get("status", "")),
                "internalStatus": rec.get("status", ""),
                "reason": rec.get("reason", ""),
                "modifiedAt": rec.get("modified_at", 0.0),
            }
            for rec in self.store.search(
                app=one("appName"), namespace=one("namespace"),
                status=statuses, strategy=one("strategy"), limit=limit,
            )
        ]
        return 200, {"jobs": out}

    def metrics(self):
        from ..utils.tracing import tracer

        # re-stamp breaker-state gauges at scrape time: an idle open
        # breaker fires no transitions, and a stale-evicted state gauge
        # would clear dashboards while the circuit is still open
        for holder in (self.resilience, getattr(self.store, "archive", None),
                       getattr(self.analyzer, "slo", None), self.ingest):
            refresh = getattr(holder, "refresh_metrics", None)
            if refresh is not None:
                refresh()
        # verdict series + host-side span aggregates + engine self-gauges
        # in one scrape (the reference brain likewise self-reported on its
        # :8000 /metrics, foremast-brain.yaml:85-122)
        lines = []
        for status, n in sorted(self.store.status_counts().items()):
            lines.append(
                f'foremast_jobs{{status="{escape_label_value(status)}"}} {n}'
            )
        lines.append(
            f"foremast_snapshot_flush_seconds "
            f"{self.store.snapshot_flush_seconds}"
        )
        # RAM-only exposure (worst-case job-loss window on crash): last
        # realized window per flush, the max observed, and the live age
        # of the oldest unflushed mutation
        lines.append(
            f"foremast_loss_window_seconds "
            f"{round(self.store.loss_window_last_seconds, 4)}"
        )
        lines.append(
            f"foremast_loss_window_max_seconds "
            f"{round(self.store.loss_window_max_seconds, 4)}"
        )
        lines.append(
            f"foremast_loss_window_open_seconds "
            f"{round(self.store.loss_window_open_seconds, 4)}"
        )
        # lease lifecycle: fresh claims, stuck-lease takeover steals,
        # released handoffs (shutdown + shard rebalance), peer adoptions —
        # the previously-invisible churn cross-replica failover runs on
        lines.append(
            f"foremastbrain:lease_claims_total {self.store.lease_claims_total}"
        )
        lines.append(
            f"foremastbrain:lease_steals_total {self.store.lease_steals_total}"
        )
        lines.append(
            "foremastbrain:lease_releases_total "
            f"{self.store.lease_releases_total}"
        )
        lines.append(
            f"foremastbrain:lease_adoptions_total {self.store.adopted_total}"
        )
        if self.shard is not None:
            # snapshot() builds a fresh dict (scrape threads never touch
            # the manager's live state maps)
            snap = self.shard.snapshot()
            lines.append(f"foremastbrain:shard_owned_count {snap['owned']}")
            lines.append(
                f"foremastbrain:shard_adopting_count {snap['adopting']}")
            lines.append(
                f"foremastbrain:shard_draining_count {snap['draining']}")
            lines.append(
                f"foremastbrain:shard_replicas_live {len(snap['replicas'])}")
            lines.append(
                "foremastbrain:shard_rebalances_total "
                f"{snap['rebalances_total']}")
            lines.append(
                "foremastbrain:shard_handoffs_total "
                f"{snap['handoffs_total']}")
            lines.append(
                "foremastbrain:shard_adoptions_total "
                f"{snap['adoptions_total']}")
        if self.store.archive is not None:
            lines.append(
                "foremast_archive_errors "
                f"{getattr(self.store.archive, 'errors', 0)}"
            )
            lines.append(
                f"foremast_jobs_adopted_total {self.store.adopted_total}"
            )
            lines.append(
                "foremast_archive_mirror_failures_total "
                f"{self.store.mirror_failures_total}"
            )
            # docs currently parked in mirror-failure backoff: a persistent
            # nonzero value with a healthy archive = poisoned docs the
            # archive rejects (vs mirror_failures_total, which also counts
            # plain outage write failures)
            lines.append(
                "foremast_archive_mirror_backed_off_docs "
                f"{self.store.mirror_backed_off_docs()}"
            )
            lines.append(
                "foremast_archive_lock_degradations "
                f"{getattr(self.store.archive, 'lock_degradations', 0)}"
            )
            lines.append(
                "foremast_archive_compactions_skipped_unlocked "
                f"{getattr(self.store.archive, 'compactions_skipped_unlocked', 0)}"
            )
            # write-behind backlog: docs whose latest version the archive
            # has not confirmed yet. Graceful shutdown drains this to
            # zero (runtime.stop); a persistent nonzero value under a
            # healthy archive means mirror churn is outrunning the flush
            lines.append(
                "foremastbrain:archive_dirty_count "
                f"{self.store.archive_dirty_count()}"
            )
            # full two-generation view rebuilds (FileArchive): steady
            # state advances the read view incrementally, so this should
            # track compactions, not reads
            lines.append(
                "foremast_archive_view_rebuilds_total "
                f"{getattr(self.store.archive, 'view_rebuilds', 0)}"
            )
        if self.analyzer is not None:
            # degraded-mode gauges: the counters themselves
            # (jobs_shed_total, stale_verdicts_served_total,
            # watchdog_fires_total, jobs_quarantined_total, health_state)
            # live on the exporter registry and render above; the live
            # park count is a point-in-time gauge stamped per scrape
            health = getattr(self.analyzer, "health", None)
            if health is not None:
                health.refresh_metrics()
            lines.append(
                "foremastbrain:quarantined_jobs "
                f"{self.analyzer.quarantined_count()}"
            )
            # rising skips = the LSTM train-on-miss budget is too small for
            # the fleet's identity churn (jobs stuck warming up); zero =
            # multi-metric jobs are simply in progress
            lines.append(
                "foremast_lstm_budget_skips_total "
                f"{self.analyzer.lstm_budget_skips}"
            )
            lines.append(
                "foremast_lstm_stack_rebuilds_total "
                f"{self.analyzer.lstm_stack_rebuilds}"
            )
            # fingerprint score memo (SCORE_MEMO): verdicts served without
            # a device launch, per family + the lstm rescue paths.
            # Snapshot first: the cycle thread inserts new family keys
            # concurrently, and iterating the live dicts can raise
            # "dict changed size during iteration" mid-scrape.
            memo_hits = dict(self.analyzer.score_memo_hits)
            memo_misses = dict(self.analyzer.score_memo_misses)
            for fam in sorted(set(memo_hits) | set(memo_misses)):
                lines.append(
                    f'foremastbrain:score_memo_hits_total{{family="{fam}"}} '
                    f"{memo_hits.get(fam, 0)}"
                )
                lines.append(
                    f'foremastbrain:score_memo_misses_total{{family="{fam}"}} '
                    f"{memo_misses.get(fam, 0)}"
                )
            lines.append(
                "foremastbrain:lstm_rescore_skips_total "
                f"{self.analyzer.lstm_rescore_skips}"
            )
            lines.append(
                "foremastbrain:lstm_train_memo_hits_total "
                f"{self.analyzer.lstm_train_memo_hits}"
            )
            lines.append(
                "foremastbrain:device_launches_total "
                f"{self.analyzer.device_launches}"
            )
        if self.cache_source is not None:
            # the TTL window cache's own counters (tracked since PR 1 but
            # never exported): hit/miss plus single-flight stampede saves
            lines.append(
                "foremastbrain:window_cache_hits_total "
                f"{self.cache_source.hits}"
            )
            lines.append(
                "foremastbrain:window_cache_misses_total "
                f"{self.cache_source.misses}"
            )
            lines.append(
                "foremastbrain:window_cache_single_flight_waits_total "
                f"{self.cache_source.single_flight_waits}"
            )
        if self.delta_source is not None:
            snap = self.delta_source.snapshot()
            lines.append(
                f"foremastbrain:delta_fetch_hits_total {snap['delta_hits']}")
            # of them, windows the append rule grew by a contiguous tail
            # without rebuilding the cached timestamps
            lines.append(
                "foremastbrain:delta_fetch_append_total "
                f"{snap['append_hits']}")
            # closed, unmoved ranges answered from the window cache with
            # no backend query (a canary's fixed baseline and history)
            lines.append(
                "foremastbrain:delta_fetch_unmoved_total "
                f"{snap['unmoved_hits']}")
            lines.append(
                "foremastbrain:delta_fetch_full_total "
                f"{snap['full_fetches']}")
            lines.append(
                f"foremastbrain:delta_fetch_hit_ratio {snap['hit_ratio']}")
            lines.append(
                "foremastbrain:delta_fetch_bytes_saved_total "
                f"{snap['bytes_saved']}")
            lines.append(
                "foremastbrain:delta_fetch_points_saved_total "
                f"{snap['points_saved']}")
            # streamed path: windows served entirely from the push-fed
            # cache (zero backend queries) — the ingest analogue of a
            # delta hit
            lines.append(
                "foremastbrain:ingest_served_windows_total "
                f"{snap['ingest_hits']}")
            if self.window_store is not None:
                # warm-tier traffic lives on the delta source (one
                # snapshot serves both families)
                lines.append(
                    "foremastbrain:window_store_warm_promotes_total "
                    f"{snap['warm_promotes']}")
                lines.append(
                    "foremastbrain:window_store_warm_spills_total "
                    f"{snap['warm_spills']}")
                # evictee spills lost to the requeue bound under disk
                # pressure: each one is a key latched into resync
                lines.append(
                    "foremastbrain:window_store_warm_spill_drops_total "
                    f"{snap['warm_spill_drops']}")
        if self.window_store is not None:
            # crash-durable tier health: on-disk footprint, WAL/spill
            # traffic, and what the last boot replayed
            ws = self.window_store.snapshot()
            lines.append(
                f"foremastbrain:window_store_segment_bytes "
                f"{ws['segment_bytes']}")
            lines.append(
                "foremastbrain:window_store_segment_entries "
                f"{ws['segment_entries']}")
            lines.append(
                f"foremastbrain:window_store_wal_bytes {ws['wal_bytes']}")
            lines.append(
                "foremastbrain:window_store_wal_appends_total "
                f"{ws['wal_appends']}")
            lines.append(
                "foremastbrain:window_store_wal_errors_total "
                f"{ws['wal_errors']}")
            lines.append(
                "foremastbrain:window_store_spill_errors_total "
                f"{ws['spill_errors']}")
            lines.append(
                f"foremastbrain:window_store_spills_total {ws['spills']}")
            lines.append(
                "foremastbrain:window_store_checkpoints_total "
                f"{ws['checkpoints']}")
            lines.append(
                "foremastbrain:window_store_compactions_total "
                f"{ws['compactions']}")
            rec = ws.get("recovery") or {}
            lines.append(
                "foremastbrain:window_store_recovery_seconds "
                f"{rec.get('seconds', 0)}")
            lines.append(
                "foremastbrain:window_store_wal_replayed_total "
                f"{rec.get('wal_records_replayed', 0)}")
        if getattr(self.store, "tier", None) is not None:
            # crash-durable job tier health: on-disk footprint, WAL/spill
            # traffic, RAM evictions, and what the last boot replayed
            js = self.store.tier_snapshot()
            lines.append(
                f"foremastbrain:job_store_segment_bytes "
                f"{js['segment_bytes']}")
            lines.append(
                "foremastbrain:job_store_segment_entries "
                f"{js['segment_entries']}")
            lines.append(
                f"foremastbrain:job_store_docs {js['docs']}")
            lines.append(
                f"foremastbrain:job_store_wal_bytes {js['wal_bytes']}")
            lines.append(
                "foremastbrain:job_store_wal_records_total "
                f"{js['wal_records']}")
            lines.append(
                "foremastbrain:job_store_wal_errors_total "
                f"{js['wal_errors']}")
            lines.append(
                f"foremastbrain:job_store_spills_total {js['spills']}")
            lines.append(
                "foremastbrain:job_store_spill_errors_total "
                f"{js['spill_errors']}")
            lines.append(
                "foremastbrain:job_store_compactions_total "
                f"{js['compactions']}")
            lines.append(
                "foremastbrain:job_store_evictions_total "
                f"{js['evictions']}")
            rec = js.get("recovery") or {}
            lines.append(
                "foremastbrain:job_store_recovery_seconds "
                f"{rec.get('seconds', 0)}")
            lines.append(
                "foremastbrain:job_store_wal_replayed_total "
                f"{rec.get('wal_records_replayed', 0)}")
            lines.append(
                "foremastbrain:job_store_open_docs_restored "
                f"{rec.get('open_docs_restored', 0)}")
        if self.http_shed_count is not None:
            lines.append(f"foremast_http_shed_total {self.http_shed_count()}")
        self_gauges = "\n".join(lines) + "\n"
        return 200, self.exporter.render() + tracer.render_metrics() + self_gauges

    def status_summary(self):
        """GET /status — operator-facing degradation view: job-state
        counts plus the resilience layer's live breaker states and retry
        counters. The answer to "is the brain healthy, and if not, which
        dependency is it protecting itself from?" in one request."""
        from .. import __version__
        from ..engine.pipeline import device_info

        out = {
            "status": "ok",
            "jobs": self.store.status_counts(),
            "chaos_active": self.chaos_active,
            "build": {
                "version": __version__,
                "uptime_s": round(time.time() - self.started_at, 1),
                "cycle_id": getattr(self.analyzer, "current_cycle_id", ""),
                # where the scoring programs run, as JAX reports it
                **device_info(),
            },
        }
        cc = self.compile_counter
        if cc is not None:
            out["build"]["compile"] = {
                "backend_compiles": cc.compiles,
                "cache_hits": cc.cache_hits,
                "cache_misses": cc.cache_misses,
            }
        if self.analyzer is not None and getattr(
                self.analyzer, "last_cycle_stages", None):
            # the last cycle's stage/family timing decomposition (the
            # pipeline's preprocess/dispatch/collect/fold split) — same
            # numbers as the foremastbrain:cycle_stage_seconds gauges
            out["cycle"] = self.analyzer.last_cycle_stages
        slo = getattr(self.analyzer, "slo", None)
        if slo is not None:
            # detection-latency SLOs: per-class ingest->verdict p50/p99,
            # attainment vs target, and error-budget burn (engine/slo.py;
            # docs/operations.md "Watching the whole fleet")
            out["slo"] = slo.snapshot()
        waterfall = getattr(self.analyzer, "waterfall", None)
        if waterfall is not None:
            wf = waterfall.snapshot()
            if wf.get("observed"):
                # detection-latency waterfall: where each verdict's
                # latency went, stage by stage (docs/operations.md
                # "Following one push to its verdict")
                out["waterfall"] = wf
        if self.trace_exporter is not None:
            # OTLP trace export health: queued/exported/failed batches
            out["trace_export"] = self.trace_exporter.snapshot()
        if self.delta_source is not None:
            # steady-state incremental fetch health: hit ratio, bytes not
            # re-downloaded, and why any full refetches happened
            out["delta_fetch"] = self.delta_source.snapshot()
        if self.ingest is not None:
            # push-ingest health: accepted/rejected samples per reason,
            # forwards, buffer backpressure (docs/operations.md
            # "Running push ingestion")
            out["ingest"] = self.ingest.snapshot()
        if self.scheduler is not None:
            # event-driven scheduling: partial cycles vs sweeps, pending
            # pushed jobs awaiting their partial cycle
            out["scheduler"] = self.scheduler.snapshot()
        if self.window_store is not None:
            # crash-durable window tier: segment/WAL footprint, spill/
            # promote traffic, and the last boot's replay stats
            # (docs/operations.md "Surviving a restart")
            out["window_store"] = self.window_store.snapshot()
        if getattr(self.store, "tier", None) is not None:
            # crash-durable job tier: segment/WAL footprint, spill/evict
            # traffic, and the last boot's WAL replay stats
            # (docs/operations.md "Job store durability")
            out["job_store"] = self.store.tier_snapshot()
        if self.store.archive is not None:
            # write-behind backlog (drains to zero on graceful shutdown)
            out["archive_dirty"] = self.store.archive_dirty_count()
        if self.shard is not None:
            # sharded-brain view: which slice of the fleet this replica
            # owns, membership health, rebalance/handoff history
            # (docs/operations.md "Running multiple replicas")
            out["shards"] = self.shard.snapshot()
        screened = getattr(self.analyzer, "triage_screened_total", None)
        if screened:
            # tier-0 triage health (cumulative; the last cycle's numbers
            # ride out["cycle"]["triage"]): how much of the changed-row
            # stream the screen cleared without a family launch
            cleared = dict(self.analyzer.triage_cleared_total)
            escalated = dict(self.analyzer.triage_escalated_total)
            total = sum(screened.values())
            out["triage"] = {
                "screened": dict(screened),
                "cleared": cleared,
                "escalated": escalated,
                "escalation_ratio": (
                    round(sum(escalated.values()) / total, 6)
                    if total else 0.0),
                "screen_launches": self.analyzer.triage_launches_total,
            }
        if self.cache_source is not None:
            out["window_cache"] = {
                "hits": self.cache_source.hits,
                "misses": self.cache_source.misses,
                "single_flight_waits": self.cache_source.single_flight_waits,
            }
        health = getattr(self.analyzer, "health", None)
        if health is not None:
            state, detail = health.state()
            out["health"] = {"state": state, **detail}
            if state != "ok":
                out["status"] = "degraded"
        if self.resilience is not None:
            snap = self.resilience.snapshot()
            out["resilience"] = snap
            if any(state != "closed" for state in snap["breakers"].values()):
                out["status"] = "degraded"
        return 200, out

    def readyz(self):
        """GET /readyz — readiness, distinct from /healthz liveness.

        ok/degraded answer 200 (the brain is serving, possibly on
        second-class verdicts — consumers read `state` to decide how much
        to trust them); overloaded/stalled answer 503 so load balancers
        and peers route around a brain that is shedding or wedged."""
        health = getattr(self.analyzer, "health", None)
        if health is None:
            return 200, {"state": "ok", "detail": {}}
        state, detail = health.state()
        code = 200 if state in ("ok", "degraded") else 503
        return code, {"state": state, "detail": detail}

    def debug_traces(self, limit: int = 50, trace_id: str = ""):
        """GET /debug/traces[?trace_id=] — the tracer's finished-trace
        ring (and per-span stats). `trace_id=` narrows to one
        distributed trace's local span trees — the fetch `foremast-tpu
        trace <job>` runs after resolving the id via explain."""
        from ..utils.tracing import tracer

        if trace_id:
            return 200, {"trace_id": trace_id,
                         "traces": tracer.snapshot(limit, trace_id)}
        return 200, {"traces": tracer.snapshot(limit), "stats": tracer.stats()}

    def explain(self, job_id: str):
        """GET /jobs/<id>/explain — the per-job "why": which verdict path
        fired last cycle (scored / memo-hit / stale-served / shed /
        quarantined / watchdog-failover / blast-radius), per-family
        scores vs thresholds, fetch mode, and the cycle context. Rendered
        human-readably by `foremast-tpu explain <job>`."""
        recorder = getattr(self.analyzer, "provenance", None)
        rec = recorder.get(job_id) if recorder is not None else None
        if rec is None:
            # the recorder spills each job's CLOSED record into the
            # durable job tier (engine/jobtier.py) — a restart or ring
            # eviction loses nothing; served transparently here
            tier = getattr(self.store, "tier", None)
            trec = tier.get_prov(job_id) if tier is not None else None
            if isinstance(trec, dict):
                rec = dict(trec)
                rec["from_tier"] = True
        doc = self.store.get(job_id)
        job = None
        if doc is not None:
            job = {
                "jobId": doc.id,
                "appName": doc.app_name,
                "namespace": doc.namespace,
                "strategy": doc.strategy,
                "status": J.to_external(doc.status),
                "internalStatus": doc.status,
                "reason": doc.reason,
            }
            if rec is None and doc.processing_content:
                # recorder LRU evicted the record (fleet > max_jobs, or a
                # restart) but the terminal Document still carries the
                # attached summary
                rec = _parse_provenance_blob(doc.processing_content,
                                             source="from_document")
        elif rec is None:
            # terminal + gc'd: the archived Document still carries the
            # provenance summary in processing_content
            archive = getattr(self.store, "archive", None)
            arec = archive.get(job_id) if archive is not None else None
            if arec is None:
                return 404, {"error": f"job {job_id} not found"}
            job = {
                "jobId": arec.get("id", job_id),
                "appName": arec.get("app_name", ""),
                "namespace": arec.get("namespace", ""),
                "strategy": arec.get("strategy", ""),
                "status": J.to_external(arec.get("status", "")),
                "internalStatus": arec.get("status", ""),
                "reason": arec.get("reason", ""),
            }
            rec = _parse_provenance_blob(arec.get("processing_content", ""))
        return 200, {
            "jobId": job_id,
            "job": job,
            "provenance": rec,
            "provenance_enabled": (recorder.enabled
                                   if recorder is not None else False),
        }

    _HEALTH_ORDER = {"ok": 0, "degraded": 1, "overloaded": 2, "stalled": 3}

    def fleet(self):
        """GET /fleet — the whole fleet from ANY replica: one row per
        replica with its published status digest and the digest's age
        (stale = age past MEMBER_TTL_S, or a graceful `left` mark), plus
        an aggregate block (worst health, summed jobs, pooled SLO view).
        Digests travel on the membership heartbeat blobs every replica
        already writes into the shared archive (engine/sharding.py), so
        federation costs zero extra infrastructure. A single-replica
        runtime (no shard layer) serves its own live digest, so the
        endpoint — and `foremast-tpu top` — work identically at N=1."""
        if self.shard is not None:
            snap = self.shard.fleet_snapshot()
        else:
            digest = {}
            builder = getattr(self.analyzer, "status_digest", None)
            if builder is not None:
                digest = builder()
            snap = {
                "replica": "local",
                "membership": "solo",
                "membership_fresh": True,
                "member_ttl_seconds": 0.0,
                "heartbeat_seconds": 0.0,
                "replicas": [{
                    "replica": "local", "worker": "", "age_s": 0.0,
                    "left": False, "stale": False, "self": True,
                    "digest": digest,
                }],
            }
        rows = snap["replicas"]
        fresh = [r for r in rows if not r.get("stale")]
        digests = [r.get("digest") or {} for r in fresh]
        jobs_total: dict[str, int] = {}
        for d in digests:
            for status, n in (d.get("jobs") or {}).items():
                jobs_total[status] = jobs_total.get(status, 0) + int(n)
        healths = [d.get("health") for d in digests if d.get("health")]
        worst = max(healths, key=lambda h: self._HEALTH_ORDER.get(h, 0),
                    default="unknown")
        slo_worst: dict[str, dict] = {}
        for d in digests:
            for cls, s in (d.get("slo") or {}).items():
                cur = slo_worst.get(cls)
                if cur is None or s.get("burn", 0.0) > cur.get("burn", 0.0):
                    slo_worst[cls] = dict(s)
        shards_owned = sum((d.get("shards") or {}).get("owned", 0)
                           for d in digests)
        snap["aggregate"] = {
            "replicas": len(rows),
            "replicas_fresh": len(fresh),
            "replicas_stale": len(rows) - len(fresh),
            "worst_health": worst,
            "jobs": jobs_total,
            "shards_owned": shards_owned,
            # per class: the replica with the WORST burn speaks for the
            # fleet (an SLO is only as met as its least-met slice)
            "slo_worst": slo_worst,
        }
        return 200, snap

    def debug_flight_dumps(self, name: str = ""):
        """GET /debug/flight/dumps[/<name>] — index of the on-disk
        incident dumps (name, age, trigger), and one dump's full payload
        by name. Operators no longer shell into the pod for historical
        dumps; the live ring stays at /debug/flight."""
        flight = getattr(self.analyzer, "flight", None)
        if flight is None:
            if name:
                return 404, {"error": "no flight recorder on this runtime"}
            return 200, {"dump_dir": "", "dumps": []}
        if name:
            payload = flight.read_dump(name)
            if payload is None:
                return 404, {"error": f"no flight dump {name!r}"}
            return 200, payload
        return 200, {"dump_dir": flight.dump_dir,
                     "dumps": flight.list_dumps()}

    def debug_flight(self, limit: int = 100):
        """GET /debug/flight — the incident flight recorder's live ring
        (events newest-last) + dump bookkeeping."""
        flight = getattr(self.analyzer, "flight", None)
        if flight is None:
            return 200, {"events": [], "events_total": 0}
        return 200, {
            "events": flight.snapshot(limit),
            "events_total": flight.events_total,
            "dumps_total": flight.dumps_total,
            "last_dump_path": flight.last_dump_path,
            "dump_dir": flight.dump_dir,
        }

    _INGEST_TRANSPORTS = {
        "/ingest/remote-write": "remote_write",
        "/ingest/otlp": "otlp",
    }

    def ingest_push(self, path: str, raw: bytes,
                    headers) -> tuple[int, dict]:
        """POST /ingest/remote-write | /ingest/otlp — push receivers
        (foremast_tpu/ingest). Content-Type/-Encoding are validated by
        the receiver: wrong media answers 415, an undecodable body 400 —
        both with a machine-readable reason — and buffer backpressure
        answers 429 (the retry signal remote-write honors). 503 when the
        runtime was built without ingest (INGEST=0)."""
        if self.ingest is None:
            return 503, {"error": "push ingestion disabled (INGEST=0)",
                         "reason": "ingest_disabled"}
        from ..ingest import (
            FORWARDED_HEADER,
            ORIGIN_REPLICA_HEADER,
            ORIGIN_TS_HEADER,
        )

        transport = self._INGEST_TRANSPORTS[path]
        return self.ingest.handle(
            transport, raw,
            content_type=headers.get("Content-Type", ""),
            content_encoding=headers.get("Content-Encoding", ""),
            forwarded=bool(headers.get(FORWARDED_HEADER)),
            # W3C context propagation: the sender's trace continues
            # through this replica's receive/splice/score spans; the
            # origin stamps keep the detection clock across ring hops
            traceparent=headers.get("traceparent", "") or "",
            origin_ts=headers.get(ORIGIN_TS_HEADER),
            origin_replica=headers.get(ORIGIN_REPLICA_HEADER, "") or "",
        )

    def dashboard(self):
        try:
            from ..dashboard import index_html

            return 200, index_html()
        except OSError as e:
            return 500, {"error": f"dashboard assets unavailable: {e}"}


def make_server(service: ForemastService, host: str = "0.0.0.0",
                port: int = 8099, max_in_flight: int = 128):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # quiet
            pass

        def _send(self, status: int, payload, content_type=None,
                  extra_headers=None):
            body = (
                payload.encode()
                if isinstance(payload, str)
                else json.dumps(payload).encode()
            )
            ct = content_type or (
                "text/plain; charset=utf-8"
                if isinstance(payload, str)
                else "application/json"
            )
            self.send_response(status)
            self.send_header("Content-Type", ct)
            self.send_header("Content-Length", str(len(body)))
            self.send_header("Access-Control-Allow-Origin", "*")
            for name, value in (extra_headers or {}).items():
                self.send_header(name, value)
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            parsed = urlparse(self.path)
            parts = [p for p in parsed.path.split("/") if p]
            try:
                if parsed.path == "/healthz":
                    self._send(200, {"status": "ok"})
                elif parsed.path == "/readyz":
                    self._send(*service.readyz())
                elif parsed.path == "/status":
                    self._send(*service.status_summary())
                elif parsed.path in ("/", "/dashboard") or parsed.path.startswith(
                    "/dashboard/"
                ):
                    status, payload = service.dashboard()
                    ct = "text/html; charset=utf-8" if status == 200 else None
                    self._send(status, payload, content_type=ct)
                elif parsed.path == "/metrics":
                    status, payload = service.metrics()
                    # the Prometheus exposition content type (0.0.4) —
                    # strict scrapers (and the OpenMetrics negotiation
                    # path) key on it, not on a bare text/plain
                    self._send(status, payload, content_type=(
                        "text/plain; version=0.0.4; charset=utf-8"))
                elif parsed.path == "/fleet":
                    self._send(*service.fleet())
                elif parsed.path == "/debug/flight/dumps":
                    self._send(*service.debug_flight_dumps())
                elif parts[:3] == ["debug", "flight", "dumps"] \
                        and len(parts) == 4:
                    self._send(*service.debug_flight_dumps(parts[3]))
                elif parsed.path == "/debug/traces":
                    q = parse_qs(parsed.query)
                    try:
                        limit = int(q.get("limit", ["50"])[0])
                    except ValueError:
                        limit = 50
                    self._send(*service.debug_traces(
                        limit, q.get("trace_id", [""])[0]))
                elif parsed.path == "/debug/flight":
                    q = parse_qs(parsed.query)
                    try:
                        limit = int(q.get("limit", ["100"])[0])
                    except ValueError:
                        limit = 100
                    self._send(*service.debug_flight(limit))
                elif parts[:1] == ["jobs"] and len(parts) == 3 \
                        and parts[2] == "explain":
                    self._send(*service.explain(parts[1]))
                elif parts == ["v1", "healthcheck", "search"]:
                    self._send(*service.search(parse_qs(parsed.query)))
                elif parts[:3] == ["v1", "healthcheck", "id"] and len(parts) == 4:
                    self._send(*service.status(parts[3]))
                elif parts[:1] == ["alert"] and len(parts) == 4:
                    self._send(*service.alert(parts[1], parts[2], parts[3]))
                elif parts[:2] == ["api", "v1"]:
                    rest = "/".join(parts[2:])
                    if parsed.query:
                        rest += "?" + parsed.query
                    self._send(*service.query_proxy(rest))
                else:
                    self._send(404, {"error": "not found"})
            except ApiError as e:
                self._send(e.status, {"error": e.message})
            except Exception as e:  # noqa: BLE001
                self._send(500, {"error": str(e)})

        def do_POST(self):
            parsed = urlparse(self.path)
            try:
                length = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(length)
                if parsed.path in ForemastService._INGEST_TRANSPORTS:
                    # push bodies are binary (snappy protobuf) — they
                    # must never pass through the JSON parse below. 429s
                    # carry Retry-After: the backpressure signal
                    # remote-write queues back off on (the documented
                    # contract, matching the admission gate's 503)
                    status, payload = service.ingest_push(
                        parsed.path, raw, self.headers)
                    self._send(status, payload,
                               extra_headers={"Retry-After": "1"}
                               if status == 429 else None)
                    return
                body = json.loads(raw or b"{}")
                if parsed.path == "/v1/healthcheck/create":
                    self._send(*service.create(body))
                else:
                    self._send(404, {"error": "not found"})
            except ApiError as e:
                self._send(e.status, {"error": e.message})
            except json.JSONDecodeError:
                self._send(400, {"error": "invalid JSON body"})
            except Exception as e:  # noqa: BLE001
                self._send(500, {"error": str(e)})

    server = BoundedThreadingHTTPServer((host, port), Handler,
                                        max_in_flight=max_in_flight)
    # self-metrics seam: lets GET /metrics report the admission gate's
    # shed counter without the service owning a server reference
    service.http_shed_count = lambda: server.shed_count
    return server


class BoundedThreadingHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer with admission control.

    The stdlib server spawns one thread per accepted connection with no
    ceiling — under a create flood that is unbounded thread growth and
    eventual memory exhaustion (round-2 front-door finding). Here a
    saturation gate caps in-flight handlers: excess connections are shed
    on the ACCEPTOR thread with a minimal `503 Retry-After` and closed,
    costing one syscall rather than a thread. Clients see fast, explicit
    backpressure instead of an unbounded queue with growing latency.
    """

    daemon_threads = True

    _SHED_BODY = b'{"error": "server saturated, retry"}'
    _SHED = (
        b"HTTP/1.1 503 Service Unavailable\r\n"
        b"Content-Type: application/json\r\n"
        b"Content-Length: " + str(len(_SHED_BODY)).encode() + b"\r\n"
        b"Retry-After: 1\r\n"
        b"Connection: close\r\n\r\n" + _SHED_BODY
    )

    def __init__(self, addr, handler_cls, max_in_flight: int = 128):
        super().__init__(addr, handler_cls)
        self._slots = threading.BoundedSemaphore(max_in_flight)
        self.shed_count = 0  # observability: how often the gate fired

    def process_request(self, request, client_address):
        if not self._slots.acquire(blocking=False):
            self.shed_count += 1
            try:
                request.sendall(self._SHED)
                # lingering close: drain the unread request (line, headers,
                # body already in our receive buffer) before closing —
                # close() with unread data RSTs the connection and the
                # client sees ECONNRESET instead of the 503. This runs on
                # the ACCEPTOR thread, so it is bounded by wall-clock
                # (50 ms total), not just bytes — a 1-byte-per-15 ms
                # trickler must not pin the accept loop.
                request.settimeout(0.02)
                deadline = time.monotonic() + 0.05
                drained = 0
                while drained < 262_144 and time.monotonic() < deadline:
                    chunk = request.recv(65_536)
                    if not chunk:
                        break
                    drained += len(chunk)
            except OSError:
                pass
            self.shutdown_request(request)
            return
        try:
            super().process_request(request, client_address)
        except BaseException:
            self._slots.release()
            raise

    def process_request_thread(self, request, client_address):
        try:
            super().process_request_thread(request, client_address)
        finally:
            self._slots.release()


def serve_background(service: ForemastService, host="127.0.0.1", port=8099,
                     max_in_flight: int = 128):
    server = make_server(service, host, port, max_in_flight=max_in_flight)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    return server
