"""Composition root: one process = service + engine worker loop.

The reference ran foremast-service (Go, HTTP :8099), foremast-brain (Python
worker pool polling Elasticsearch), and the verdict /metrics exporter
(:8000) as three deployments with ES between them (SURVEY.md §1 L3-L5). The
TPU-native design collapses them into one process: the HTTP API writes into
the in-process JobStore, worker cycles drain it through the batched TPU
scorer, and the exporter serves foremastbrain:* from the same registry.

Env surface (union of the reference services'):
  ML_* family            engine knobs (engine/config.py, foremast-brain/README.md:22-38)
  MAX_CACHE_SIZE         window-fetch LRU entries (foremast-brain/README.md:30)
  QUERY_SERVICE_ENDPOINT metric-store base for the dashboard proxy
                         (foremast-service/cmd/manager/main.go:301-309)
  SNAPSHOT_PATH          job-store checkpoint file (ES's durability role)
  LSTM_CACHE_PATH        trained LSTM-AE model cache (flax msgpack blob);
                         loaded at startup, re-written after any cycle
                         that trained — a restarted pod warm-starts
                         instead of re-training every known app
  ARCHIVE_PATH           JSONL write-behind archive of terminal jobs/hpalogs
  ES_ENDPOINT            ES-compatible archive instead (reference indices
                         documents/hpalogs); takes precedence over ARCHIVE_PATH
  ARCHIVE_ADOPT_INTERVAL seconds between scans of the shared archive for a
                         crashed peer's stale open jobs (cross-replica
                         failover, reference design.md:37-43; 0 disables)
  SHARDING / REPLICA_ID  sharded multi-replica brain (engine/sharding.py):
  SHARD_COUNT /          consistent-hash job ownership over replicas
  SHARD_VNODES /         sharing one archive — membership by archive
  HEARTBEAT_S /          heartbeat (TTL'd), rebalance on join/leave with
  MEMBER_TTL_S           released_at handoffs, dead-holder adoption at
                         TTL latency (docs/operations.md "Running
                         multiple replicas")
  FLEET_DIGEST           publish the status digest in membership
                         heartbeats — the GET /fleet federation medium
                         (docs/operations.md "Watching the whole fleet")
  INGEST /               push-based streaming dataplane
  INGEST_BUFFER_SAMPLES  (foremast_tpu/ingest + engine/scheduler.py):
  INGEST_FORWARD /       remote-write + OTLP receivers on /ingest/*,
  INGEST_ADVERTISE_ADDR  pushed samples spliced into the delta window
  INGEST_DEBOUNCE_MS     cache, event-driven partial cycles for pushed
                         jobs, cross-replica forwarding via the shard
                         ring's advertised addresses (docs/operations.md
                         "Running push ingestion"); INGEST=0 restores
                         the pure poll loop exactly
  WINDOW_STORE_DIR /     crash-durable window tier (dataplane/
  WINDOW_STORE_*         winstore.py): accepted pushes WAL'd before
                         their /ingest ack, warm windows spilled to
                         columnar mmap-read segments, boot replays both
                         so a restarted replica serves covered windows
                         with zero backend refetches (docs/operations.md
                         "Surviving a restart"); unset = RAM-only
  SLO_CANARY_S /         detection-latency SLO targets per job class and
  SLO_CONTINUOUS_S /     the attainment objective the error budget
  SLO_HPA_S /            derives from (engine/slo.py; histograms + burn
  SLO_OBJECTIVE          gauges on /metrics, slo section on /status)
  TRACE_SAMPLE /         push-to-verdict distributed tracing: head-
  TRACE_EXPORT_URL       sampling for minted root traces (adopted
                         traceparent headers keep the sender's flag) and
                         the OTLP/HTTP collector finished traces POST to
                         as OTLP JSON; /debug/traces + `foremast-tpu
                         trace <job>` serve export-less deployments
                         (docs/operations.md "Following one push to its
                         verdict")
  JOB_RETENTION_SECONDS  prune archived terminal jobs from RAM after this
  PORT                   HTTP port (reference :8099)
  GRPC_PORT              gRPC dispatch port (0/unset disables; 8100 in the
                         shipped manifests) — service/grpc_api.py
  CYCLE_SECONDS          engine cycle cadence (brain poll loop)
  HTTP_MAX_INFLIGHT      HTTP admission gate: in-flight handler ceiling,
                         excess connections shed with 503 (default 128)
  GRPC_WORKERS           gRPC worker threads (default 8)
  GRPC_MAX_CONCURRENT    gRPC admission gate: maximum_concurrent_rpcs,
                         excess rejected RESOURCE_EXHAUSTED (default
                         4x GRPC_WORKERS, keeping the accepted queue
                         shallow enough to finish within deadlines)
  WAVEFRONT_PROXY        host[:port] of a Wavefront proxy to mirror the
                         verdict series to (custom.iks.foremast.*)
  RETRY_* / BREAKER_* /  resilience knobs: retry train, per-window retry
  FETCH_CYCLE_DEADLINE   budget, breaker trip/recovery, per-cycle fetch
                         deadline (engine/config.py, docs/resilience.md)
  CYCLE_DEADLINE_S /     degraded-mode operation: whole-cycle deadline
  MAX_STALE_S /          budget with priority-aware load shedding,
  QUARANTINE_AFTER /     stale-verdict serving bound, poison-job
  WATCHDOG_S             quarantine, hung-launch watchdog. Health state
                         machine on /readyz + /status + /metrics
                         (docs/resilience.md degraded-mode runbook)
  FOREMAST_CHAOS         deterministic fault-injection spec wrapping the
                         raw fetch/archive boundaries — soak runs and the
                         demo turn chaos on without code changes
                         (docs/resilience.md for the grammar)
  DELTA_FETCH            steady-state delta window fetch (default on):
                         re-fetch only each window's tail per cycle and
                         splice into the cached grid, byte-identical to a
                         full refetch (dataplane/delta.py); 0 restores
                         the full-refetch path exactly
  WINDOW_CACHE_MAX       delta window-cache entries (~3 per job)
  SCORE_MEMO             fingerprint score memoization (default on):
                         unchanged job rows reuse last cycle's verdict
                         without a device launch (engine/pipeline.py)
  JAX_COMPILATION_CACHE_DIR  JAX's own persistent compilation cache
                         dir: restarts skip the first-cycle compile
                         storm. Unset, `serve` from a source checkout
                         uses the checkout's fixed .jax_cache/
  PREWARM_ON_START       background-compile the standard (family x rung
                         x T-bucket) grid at startup (also available as
                         `foremast-tpu prewarm`)
  LOG_LEVEL              process-wide logging level (default INFO)
"""
from __future__ import annotations

import logging
import os
import socket
import threading
import time

from .dataplane.exporter import VerdictExporter
from .dataplane.fetch import CachingDataSource, PrometheusDataSource
from .engine.analyzer import Analyzer
from .engine.config import EngineConfig, from_env
from .engine.jobs import JobStore
from .engine.pipeline import CompileCounter
from .service.api import ForemastService, make_server
from .utils import knobs

__all__ = ["Runtime"]

log = logging.getLogger("foremast_tpu.runtime")


class Runtime:
    def __init__(
        self,
        config: EngineConfig | None = None,
        data_source=None,
        snapshot_path: str | None = None,
        query_endpoint: str = "",
        cache: bool = True,
        wavefront_sink=None,
        archive=None,
        job_retention_seconds: float = 24 * 3600.0,
        adopt_interval_seconds: float = 30.0,
        adopt_skew_margin_seconds: float = 15.0,
        lstm_cache_path: str | None = None,
        resilient: bool | None = None,
        chaos_spec: str | None = None,
        replica_id: str = "",
        sharding: bool | None = None,
        shard_count: int = 64,
        shard_vnodes: int = 64,
        heartbeat_seconds: float = 5.0,
        member_ttl_seconds: float = 15.0,
        static_replicas=None,
        fleet_digest: bool = True,
        ingest: bool | None = None,
        ingest_buffer_samples: int = 4096,
        ingest_forward: bool = True,
        ingest_advertise_addr: str = "",
        ingest_debounce_ms: float = 150.0,
        window_store_dir: str = "",
        window_store_segment_max_mb: int = 256,
        window_store_fsync: bool = False,
        window_store_checkpoint_seconds: float = 5.0,
        job_store_dir: str = "",
        job_store_segment_max_mb: int = 512,
        job_store_fsync: bool = False,
        job_store_checkpoint_seconds: float = 5.0,
        job_store_hot_seconds: float = 300.0,
        trace_sample: float = 1.0,
        trace_export_url: str = "",
    ):
        self.config = config or from_env()
        # -- distributed tracing (utils/tracing.py): head-sampling for
        # minted roots (TRACE_SAMPLE; adopted traceparent headers keep
        # the sender's flag) — set before anything opens spans --
        from .utils import tracing as tracing_mod

        tracing_mod.tracer.set_sample_rate(trace_sample)
        # XLA compile work between start() and stop(): /status.build says
        # whether a start was warm (persistent-cache hits) or paid the
        # compile storm (engine/pipeline.py CompileCounter)
        self.compile_counter = CompileCounter()
        self.exporter = VerdictExporter()
        source = data_source or PrometheusDataSource()
        # -- chaos layer (FOREMAST_CHAOS): deterministic fault injection
        # wraps the RAW boundaries, so the resilience layer above it is
        # exercised exactly as it would be by a real outage --
        if chaos_spec is None:
            chaos_spec = knobs.read("FOREMAST_CHAOS")
        self.chaos_injectors = {}
        if chaos_spec:
            from .resilience import FaultyArchive, FaultyDataSource
            from .resilience.faults import safe_injectors

            self.chaos_injectors = safe_injectors(chaos_spec)
            inj = self.chaos_injectors.get("fetch")
            if inj is not None:
                source = FaultyDataSource(source, inj)
            inj = self.chaos_injectors.get("archive")
            if inj is not None and archive is not None:
                archive = FaultyArchive(archive, inj)
        # -- resilience layer: breaker + retry + deadline around every
        # external boundary. Default: on for the production path (no
        # injected data_source) and whenever chaos is active; explicitly
        # injected test sources stay bare unless asked (retrying a
        # fixture miss would only slow the suite down) --
        if resilient is None:
            resilient = data_source is None or bool(self.chaos_injectors)
        self.resilience = None
        if resilient:
            from .resilience import (
                BreakerBoard,
                ResilientArchive,
                ResilientDataSource,
                RetryBudget,
                RetryPolicy,
            )

            cfg = self.config
            source = ResilientDataSource(
                source,
                retry=RetryPolicy(
                    max_attempts=cfg.retry_max_attempts,
                    base_delay=cfg.retry_base_delay,
                    max_delay=cfg.retry_max_delay,
                    budget=RetryBudget(
                        max_retries=cfg.retry_budget,
                        window_seconds=cfg.retry_budget_window_seconds,
                    ),
                ),
                breakers=BreakerBoard(
                    failure_threshold=cfg.breaker_failure_threshold,
                    recovery_seconds=cfg.breaker_recovery_seconds,
                ),
                exporter=self.exporter,
            )
            self.resilience = source
            if archive is not None:
                archive = ResilientArchive(
                    archive,
                    breakers=BreakerBoard(
                        failure_threshold=cfg.breaker_failure_threshold,
                        recovery_seconds=cfg.breaker_recovery_seconds,
                    ),
                    exporter=self.exporter,
                )
        # -- delta fetch layer (DELTA_FETCH; dataplane/delta.py): steady-
        # state cycles re-fetch only each window's tail and splice it into
        # the cached grid. Sits UNDER the TTL cache (which dedupes
        # identical URLs within a cycle) and ABOVE resilience (so delta
        # queries ride the same breaker/retry train). DELTA_FETCH=0 skips
        # the layer entirely — the full-refetch path is byte-for-byte
        # today's. --
        self.delta_source = None
        if self.config.delta_fetch:
            from .dataplane.delta import DeltaWindowSource

            source = DeltaWindowSource(
                source, max_entries=self.config.window_cache_max)
            self.delta_source = source
        # -- crash-durable window store (WINDOW_STORE_DIR;
        # dataplane/winstore.py): per-replica push WAL + columnar warm
        # segments under the delta cache. Boot replays segments+WAL so a
        # restarted replica serves its covered windows without a refetch
        # storm; every accepted push is WAL'd before its /ingest ack.
        # Empty dir (the default) = window state is RAM-only, exactly as
        # before. --
        self.window_store = None
        self._recovery_stats = None
        if window_store_dir and self.delta_source is not None:
            from .dataplane.winstore import WindowStore

            self.window_store = WindowStore(
                window_store_dir,
                segment_max_bytes=max(int(window_store_segment_max_mb), 1)
                * (1 << 20),
                fsync=window_store_fsync,
                wal_injector=self.chaos_injectors.get("wal"),
                checkpoint_min_seconds=window_store_checkpoint_seconds,
                exporter=self.exporter,
            )
            self.delta_source.store = self.window_store
            self._recovery_stats = self.window_store.recover(
                self.delta_source)
            log.info("window store recovered: %s", self._recovery_stats)
        self.cache_source = None
        if cache:
            source = CachingDataSource(source, max_entries=self.config.max_cache_size)
            self.cache_source = source
        self.source = source
        # -- crash-durable tiered job store (JOB_STORE_DIR;
        # engine/jobtier.py): live-job mutations WAL'd ahead of their
        # acknowledgement, terminal/cold Documents + closed provenance
        # spilled to newest-wins segments and evicted from RAM. Boot
        # replays WAL records through the normal transition path (stale
        # records are counted no-ops), so kill -9 mid-transition loses
        # nothing acked. Empty dir (the default) = snapshot-only store,
        # exactly as before. --
        job_tier = None
        if job_store_dir:
            from .engine.jobtier import JobTier

            job_tier = JobTier(
                job_store_dir,
                segment_max_bytes=max(int(job_store_segment_max_mb), 1)
                * (1 << 20),
                fsync=job_store_fsync,
                injector=self.chaos_injectors.get("disk"),
                exporter=self.exporter,
            )
        self.store = JobStore(
            snapshot_path=snapshot_path, archive=archive, tier=job_tier,
            tier_hot_seconds=job_store_hot_seconds,
            tier_checkpoint_min_seconds=job_store_checkpoint_seconds)
        self._job_recovery_stats = None
        if job_tier is not None:
            self._job_recovery_stats = self.store.recover_from_tier()
            log.info("job store recovered: %s", self._job_recovery_stats)
        self.job_retention_seconds = job_retention_seconds
        # cross-replica failover cadence: how often to scan the shared
        # archive for a crashed peer's stale open jobs (0 disables; the
        # archive scan is not free, so it is NOT every cycle)
        self.adopt_interval_seconds = adopt_interval_seconds
        # NTP-skew allowance added to the staleness threshold before a
        # peer's job is adopted (docs/operations.md "Clock skew")
        self.adopt_skew_margin_seconds = adopt_skew_margin_seconds
        self._last_adopt = 0.0
        self.analyzer = Analyzer(
            self.config, self.source, self.store, exporter=self.exporter
        )
        if self._recovery_stats is not None:
            # the restart self-documents: an incident dump shortly after
            # boot carries what the replica replayed from disk
            from .engine.flightrec import EVENT_STORE_RECOVERY

            self.analyzer.flight.record_event(
                EVENT_STORE_RECOVERY, **self._recovery_stats)
        if self._job_recovery_stats is not None:
            from .engine.flightrec import EVENT_STORE_RECOVERY

            self.analyzer.flight.record_event(
                EVENT_STORE_RECOVERY, store="jobs",
                **self._job_recovery_stats)
        if self.store.tier is not None:
            # closed provenance records spill into the same tier, so a
            # restarted (or long-lived) replica can still `explain` a
            # verdict whose RAM ring entry has been evicted/pruned
            self.analyzer.provenance.spill = self.store.tier.spill_prov
        # health state machine wiring (engine/health.py): merge every live
        # breaker board (data source + archive) into the DEGRADED signal;
        # cycle cadence lands in start() where it is known
        boards = []
        if self.resilience is not None:
            boards.append(self.resilience.breakers)
        if archive is not None and hasattr(archive, "breakers"):
            boards.append(archive.breakers)
        if boards:
            def _breaker_states(_boards=tuple(boards)):
                states = {}
                for b in _boards:
                    states.update(b.states())
                return states

            self.analyzer.health.configure(breakers_fn=_breaker_states)
        # -- sharded multi-replica brain (engine/sharding.py): consistent-
        # hash job ownership over the shared archive. Default: on whenever
        # there IS a shared archive — the handoff/adoption medium. Without
        # one, even a launcher-fixed multi-process world must NOT shard:
        # release_unowned would rewind a peer's jobs into a limbo no
        # adoption scan can reach (there is no shared store to reach it
        # through), silently dropping ~(N-1)/N of submissions. A
        # sole-member ring owns every shard, so a single-replica
        # deployment behaves exactly as before.
        self.replica_id = replica_id or f"{socket.gethostname()}-{os.getpid()}"
        # trace resource identity: every finished root (and every OTLP
        # export) names the replica it happened on — a cross-replica
        # push trace must name both ends
        tracing_mod.tracer.resource = {"replica": self.replica_id}
        self.shard = None
        if sharding is None:
            sharding = True
        if static_replicas and archive is None:
            log.warning(
                "multi-process world without a shared archive: sharding "
                "disabled (no handoff/adoption medium) — every process "
                "scores the jobs submitted to it, as before")
            sharding = False
        if sharding and archive is not None:
            from .engine.sharding import ShardManager

            self.shard = ShardManager(
                self.store, self.replica_id,
                shard_count=shard_count, vnodes=shard_vnodes,
                heartbeat_seconds=heartbeat_seconds,
                member_ttl_seconds=member_ttl_seconds,
                static_members=static_replicas,
                flight=self.analyzer.flight,
                # fleet federation: the status digest rides the membership
                # heartbeat blob (FLEET_DIGEST=0 keeps heartbeats minimal);
                # cycle ids correlate both sides' handoff/adoption flight
                # events; released Documents carry their provenance chain
                # (+ an explicit handoff hop) to the adopter's `explain`
                digest_fn=(self.analyzer.status_digest
                           if fleet_digest else None),
                cycle_id_fn=lambda: self.analyzer.current_cycle_id,
                handoff_content_fn=self._handoff_content("rebalance"),
            )
            self.analyzer.shard = self.shard
            self.analyzer.health.configure(
                shards_fn=self.shard.health_summary)
            if self.adopt_interval_seconds <= 0:
                # the rebalance handoff RELIES on the adoption scan: a
                # released job in a peer's shard is only ever picked up by
                # adopt_stale_from_archive. With scans disabled it would
                # sit in the archive unscored forever, so floor the
                # cadence instead of honoring the disable.
                log.warning(
                    "SHARDING is active but ARCHIVE_ADOPT_INTERVAL "
                    "disables adoption scans; forcing a 30s cadence "
                    "(shard handoffs depend on adoption)")
                self.adopt_interval_seconds = 30.0
        # LSTM model-cache warm-start (LSTM_CACHE_PATH): trained AE params
        # persist across restarts so a bounced pod skips the budgeted
        # re-training warm-up for every known app
        self.lstm_cache_path = lstm_cache_path
        self._lstm_saved_version = 0
        if lstm_cache_path:
            n = self.analyzer.load_lstm_cache(lstm_cache_path)
            self._lstm_saved_version = self.analyzer._lstm_param_version
            if n:
                log.info("warm-started %d LSTM model(s) from %s",
                         n, lstm_cache_path)
        # -- push-ingest receiver (INGEST; foremast_tpu/ingest): the
        # streaming dataplane's front half. Samples pushed to /ingest/*
        # splice into the delta window cache (byte-identical to a
        # refetch) and wake the event scheduler; unowned jobs forward to
        # the owner advertised on the shard ring. INGEST=0 skips the
        # layer entirely — the poll loop is byte-for-byte yesterday's. --
        self.ingest = None
        self.ingest_debounce_seconds = max(float(ingest_debounce_ms), 0.0) \
            / 1000.0
        self.ingest_advertise_addr = ingest_advertise_addr
        if ingest is None:
            ingest = True
        if ingest:
            from .ingest import IngestReceiver

            self.ingest = IngestReceiver(
                self.store,
                delta_source=self.delta_source,
                cache_source=self.cache_source,
                shard=self.shard,
                exporter=self.exporter,
                buffer_samples=ingest_buffer_samples,
                forward=ingest_forward,
                window_store=self.window_store,
                # push-to-verdict tracing: accepts open waterfall records
                # (with the push's W3C context) the engine closes at fold;
                # receive spans + ring forwards name this replica
                waterfall=self.analyzer.waterfall,
                replica=self.replica_id,
            )
        # -- OTLP trace export (TRACE_EXPORT_URL; dataplane/exporter.py
        # OtlpTraceExporter): finished sampled traces POST to the
        # collector in the background; empty URL = /debug/traces only --
        self.trace_exporter = None
        if trace_export_url:
            from .dataplane.exporter import OtlpTraceExporter

            self.trace_exporter = OtlpTraceExporter(
                trace_export_url, exporter=self.exporter,
                resource={"replica": self.replica_id})
            tracing_mod.tracer.add_sink(self.trace_exporter.sink)
            self.trace_exporter.start()
        # event-driven scheduler (engine/scheduler.py StreamScheduler):
        # constructed in start() where cadence + worker name are known
        self.scheduler = None
        self.service = ForemastService(
            self.store, exporter=self.exporter, query_endpoint=query_endpoint,
            analyzer=self.analyzer, resilience=self.resilience,
            delta_source=self.delta_source, cache_source=self.cache_source,
            shard=self.shard, ingest=self.ingest,
            window_store=self.window_store,
            trace_exporter=self.trace_exporter,
        )
        self.service.chaos_active = bool(self.chaos_injectors)
        self.wavefront_sink = wavefront_sink
        self._stop = threading.Event()
        self._stop_requested = False  # signal-handler seam (request_stop)
        self._stopped = False
        self._threads: list[threading.Thread] = []
        self._worker_thread: threading.Thread | None = None
        self._worker_name = "worker-0"
        self._server = None
        self._grpc_server = None
        self.grpc_bound_port: int | None = None

    def _handoff_content(self, reason: str):
        """(job_id) -> provenance handoff blob for Documents this replica
        releases — the job's decision chain plus an explicit handoff hop
        naming this replica/worker/cycle (engine/provenance.py). Returns
        a callable so the blob always stamps the CURRENT worker name
        (start() may rename it after construction)."""
        def content(job_id: str) -> str:
            return self.analyzer.provenance.handoff_json(
                job_id, replica=self.replica_id, worker=self._worker_name,
                reason=reason)

        return content

    # -- lifecycle --
    def start(self, host: str = "0.0.0.0", port: int = 8099,
              cycle_seconds: float = 10.0, worker: str | None = None,
              grpc_port: int | None = None,
              http_max_inflight: int | None = None,
              grpc_workers: int | None = None,
              grpc_max_concurrent: int | None = None):
        """Start the HTTP (and optional gRPC) servers and the engine worker
        loop (background). grpc_port=0 binds an ephemeral port (see
        grpc_bound_port); None disables the gRPC front. The admission-gate
        knobs default to the service layer's own defaults when None (env
        parsing lives in main(), like every other runtime knob).

        The default worker name is the REPLICA ID when sharding is active:
        lease stamps must identify WHICH replica holds them or a peer's
        dead-holder check can never match a killed replica (every pod
        stamping a shared "worker-0" would alias all replicas together,
        silently degrading kill -9 recovery from MEMBER_TTL_S latency back
        to the MAX_STUCK_IN_SECONDS window)."""
        if worker is None:
            worker = self.replica_id if self.shard is not None else "worker-0"
        self.cycle_seconds = cycle_seconds
        self.analyzer.health.configure(cycle_seconds=cycle_seconds)
        self.service.compile_counter = self.compile_counter.start()
        http_kw = {} if http_max_inflight is None else {
            "max_in_flight": http_max_inflight}
        self._server = make_server(self.service, host, port, **http_kw)
        t_http = threading.Thread(target=self._server.serve_forever, daemon=True)
        t_http.start()
        if grpc_port is not None:
            from .service.grpc_api import serve_grpc_background

            grpc_kw = {}
            if grpc_workers is not None:
                grpc_kw["max_workers"] = grpc_workers
            if grpc_max_concurrent is not None:
                grpc_kw["max_concurrent_rpcs"] = grpc_max_concurrent
            self._grpc_server, self.grpc_bound_port = serve_grpc_background(
                self.service, host=host, port=grpc_port, **grpc_kw
            )
        if self.shard is not None:
            # lease stamps carry the WORKER name; membership heartbeats
            # advertise it so peers' dead-holder checks can map a holder
            # back to a live replica (engine/sharding.py dead_holder)
            self.shard.worker = worker
            if self.ingest is not None and self.ingest.forward_enabled:
                # advertise this replica's ingest address on the ring so
                # peers can forward pushed samples for jobs we own
                # (INGEST_ADVERTISE_ADDR overrides the derived default —
                # 0.0.0.0 binds and NATed pods need the reachable name)
                addr = self.ingest_advertise_addr or \
                    f"http://{socket.gethostname()}:{port}"
                self.shard.advertise = {"addr": addr}
            # liveness advertisement gets its OWN thread: if it only rode
            # the worker loop, one slow cycle (cold compile, adoption
            # burst) would age the heartbeat past MEMBER_TTL_S and peers
            # would declare this replica dead and steal its in-flight
            # leases mid-cycle. heartbeat() itself rate-limits writes.
            t_hb = threading.Thread(target=self._heartbeat_loop, daemon=True)
            t_hb.start()
        t_eng = threading.Thread(
            target=self._worker_loop, args=(cycle_seconds, worker), daemon=True
        )
        t_eng.start()
        self._worker_thread = t_eng
        self._worker_name = worker
        self._threads = [t_http, t_eng]
        if self.config.prewarm_on_start:
            # background prewarm (PREWARM_ON_START): compile the standard
            # (family x rung x T-bucket) grid behind live traffic so even
            # the first real cycle of each shape skips its compile. Daemon
            # + best-effort: a prewarm failure must never take the
            # runtime down with it.
            t_warm = threading.Thread(target=self._prewarm, daemon=True)
            t_warm.start()
            self._threads.append(t_warm)
        return self

    def _prewarm(self):
        from .engine.pipeline import prewarm

        try:
            info = prewarm(self.config)
            log.info("prewarm done: %s", info)
        except Exception as e:  # noqa: BLE001 - warmup is best-effort
            log.warning("prewarm failed: %s", e)

    def _heartbeat_loop(self):
        """Keep the membership heartbeat current independent of cycle
        duration (see start()). Wakes at half the heartbeat cadence so
        the advertised age stays well inside MEMBER_TTL_S; the write
        itself is rate-limited inside ShardManager.heartbeat."""
        interval = max(min(self.shard.heartbeat_seconds / 2.0, 5.0), 0.25) \
            if self.shard.heartbeat_seconds > 0 else 0.25
        while not self._stop.is_set():
            try:
                self.shard.heartbeat()
            except Exception:  # noqa: BLE001 - liveness must keep trying
                log.exception("membership heartbeat error")
            self._stop.wait(interval)

    def _worker_loop(self, cycle_seconds: float, worker: str):
        """Event-driven engine loop (engine/scheduler.py): pushed jobs
        score immediately as partial cycles between the periodic full
        reconciliation sweeps. With no ingest traffic the scheduler
        degrades to exactly the old poll loop — one full sweep per
        CYCLE_SECONDS."""
        from .engine.scheduler import StreamScheduler

        sched = StreamScheduler(
            self.analyzer,
            full_cycle_fn=lambda: self._full_sweep(worker),
            cycle_seconds=cycle_seconds, worker=worker,
            debounce_seconds=self.ingest_debounce_seconds,
            exporter=self.exporter,
            # push-dirtied window state folds into segments between
            # sweeps too (rate-limited inside the store), bounding WAL
            # growth under sustained push traffic with a long cadence
            checkpoint_fn=(self._store_checkpoint
                           if (self.window_store is not None
                               or self.store.tier is not None) else None))
        self.scheduler = sched
        self.service.scheduler = sched
        if self.ingest is not None:
            # the receiver's wakeup tap: pushed jobs whose windows
            # advanced land in the scheduler's pending set
            self.ingest.notify_fn = sched.notify
        sched.run(self._stop)

    def _full_sweep(self, worker: str):
        """One full reconciliation lap: membership/rebalance tick,
        adoption scan, the fleet-wide engine cycle, and the per-lap
        chores (sink flush, model-cache save, store gc). This is the
        body the pre-streaming poll loop ran every CYCLE_SECONDS —
        unchanged, just invoked by the scheduler now."""
        t0 = time.time()
        if self.shard is not None:
            # membership heartbeat + rebalance; a membership change
            # forces an IMMEDIATE adoption scan (the new owner must
            # pick up handed-off/dead-peer jobs now, not on the
            # leisurely adopt cadence). Own try: a broken shard
            # layer must degrade to sole-owner behavior, never
            # stop the scoring loop.
            try:
                tick = self.shard.tick()
                if tick.get("membership_changed"):
                    self._last_adopt = 0.0
                    log.info(
                        "shard rebalance: %d replica(s), "
                        "+%d/-%d shard(s), %d handoff(s)",
                        len(tick["replicas"]),
                        tick["gained_shards"], tick["lost_shards"],
                        tick["handoffs"])
            except Exception:  # noqa: BLE001
                log.exception("shard tick error")
        if (self.adopt_interval_seconds > 0
                and self.store.archive is not None
                and t0 - self._last_adopt >= self.adopt_interval_seconds):
            self._last_adopt = t0
            adopted_ids: list[str] = []

            def _on_adopt(doc):
                # handoff-surviving provenance: the blob the
                # releasing replica attached travels back into
                # our recorder, so `explain` here shows the full
                # chain including the handoff hop
                adopted_ids.append(doc.id)
                self.analyzer.provenance.adopt(
                    doc.id, doc.processing_content)

            n = self.store.adopt_stale_from_archive(
                worker=worker,
                max_stuck_seconds=self.config.max_stuck_seconds,
                skew_margin_seconds=self.adopt_skew_margin_seconds,
                owns_fn=(self.shard.owns
                         if self.shard is not None else None),
                dead_holder_fn=(self.shard.dead_holder
                                if self.shard is not None else None),
                on_adopt=_on_adopt,
            )
            if self.shard is not None:
                self.shard.mark_adopt_complete(n, jobs=adopted_ids)
            if n:
                log.info("adopted %d stale job(s) from the archive",
                         n)
        self.analyzer.run_cycle(worker=worker)
        if self.wavefront_sink is not None:
            self.wavefront_sink.flush()
        if (self.lstm_cache_path
                and self.analyzer._lstm_param_version
                != self._lstm_saved_version):
            # only sweeps that actually trained write (bounded by
            # the per-cycle train budget; LRU reorders don't).
            # Own try: an unwritable cache path must not skip the
            # gc below every sweep and grow RAM without bound.
            try:
                self.analyzer.save_lstm_cache(self.lstm_cache_path)
                self._lstm_saved_version = \
                    self.analyzer._lstm_param_version
            except Exception as e:  # noqa: BLE001
                log.warning("lstm cache save failed: %s", e)
        self.store.gc(max_age_seconds=self.job_retention_seconds)
        self._store_checkpoint()

    def _store_checkpoint(self, force: bool = False):
        """Fold dirty window/job state into the warm segments and rotate
        the WALs (dataplane/winstore.py; engine/jobtier.py). Own try per
        store: a full disk must degrade durability, never stop the
        scoring loop."""
        if self.window_store is not None:
            try:
                self.window_store.checkpoint(self.delta_source, force=force)
            except Exception:  # noqa: BLE001 - durability is best-effort
                log.exception("window-store checkpoint failed")
        if self.store.tier is not None:
            try:
                self.store.tier_checkpoint(force=force)
            except Exception:  # noqa: BLE001 - durability is best-effort
                log.exception("job-store checkpoint failed")

    def request_stop(self):
        """Signal-safe: ask run_forever to exit and shut down cleanly
        (installed as the SIGTERM handler by main() — K8s pod termination
        must flush the snapshot, not just die). A plain attribute write
        ONLY: Event.set() takes the event's condition lock, and a handler
        that lands while the main thread holds it (inside Event.wait's
        acquire/release bookkeeping) deadlocks the very shutdown it
        requests."""
        self._stop_requested = True

    def stop(self, drain_seconds: float | None = None):
        """Graceful shutdown: drain, hand off, then exit.

        1. The in-flight engine cycle finishes (bounded by the degraded-
           mode deadline budget — a cycle that honors CYCLE_DEADLINE_S
           cannot hold shutdown hostage past it).
        2. The HTTP/gRPC fronts stop accepting work.
        3. Every open job's lease is RELEASED (released_at handoff mark)
           and the archive write-behind backlog drains, so a peer's
           adopt_stale_from_archive takes the fleet over immediately
           instead of waiting out MAX_STUCK_IN_SECONDS.
        4. The store closes (final snapshot flush).
        """
        if self._stopped:
            return
        self._stopped = True
        self._stop.set()
        if self.service.compile_counter is not None:  # start() ran
            self.compile_counter.stop()
            self.service.compile_counter = None
        if drain_seconds is None:
            drain_seconds = max(self.config.cycle_deadline_seconds,
                                self.config.fetch_cycle_deadline_seconds,
                                5.0)
        t = self._worker_thread
        if (t is not None and t.is_alive()
                and t is not threading.current_thread()):
            t.join(timeout=drain_seconds)
            if t.is_alive():
                log.warning("engine cycle did not drain within %.1fs; "
                            "proceeding with shutdown", drain_seconds)
        if self._server is not None:
            self._server.shutdown()
        if self._grpc_server is not None:
            self._grpc_server.stop(grace=2.0)
        if self.shard is not None:
            # membership half of the handoff: peers rebalance immediately
            # on the `left` mark instead of waiting out MEMBER_TTL_S
            self.shard.withdraw()
        if self.store.archive is not None:
            released = self.store.release_leases(
                worker=self._worker_name,
                # the shutdown handoff carries each job's provenance chain
                # + an explicit handoff hop to the adopting peer's explain
                content_fn=self._handoff_content("shutdown"))
            if released:
                from .engine.flightrec import EVENT_LEASE_HANDOFF

                self.analyzer.flight.record_event(
                    EVENT_LEASE_HANDOFF, released=released,
                    worker=self._worker_name,
                    cycle_id=self.analyzer.current_cycle_id)
                log.info("released %d open lease(s) for peer adoption",
                         released)
            # drain the write-behind mirror: the release stamps above (and
            # any backlog) must actually REACH the archive for a peer to
            # adopt them. Bounded two ways: the drain budget, and a
            # PROGRESS check — when a flush leaves the dirty count where
            # it was (archive down, or docs the archive rejects), more
            # flushes are no-ops and shutdown must not spin them until
            # the deadline.
            deadline = time.time() + drain_seconds
            prev = None
            while time.time() < deadline:
                n = self.store.archive_dirty_count()
                if n == 0 or (prev is not None and n >= prev):
                    break
                prev = n
                self.store.flush()
                time.sleep(0.05)
        # final window-store checkpoint: the next boot recovers every
        # window this process ever cached, not just the last sweep's
        self._store_checkpoint(force=True)
        if self.trace_exporter is not None:
            # flush queued traces to the collector before exit (a
            # SIGTERM mid-incident must not drop the incident's traces)
            from .utils import tracing as tracing_mod

            tracing_mod.tracer.remove_sink(self.trace_exporter.sink)
            self.trace_exporter.stop(flush=True)
        # incident flight recorder: a SIGTERM mid-incident must leave a
        # self-contained artifact (events + traces + provenance + knobs)
        # even when nobody was watching the pod. Best-effort by design.
        self.analyzer.flight.dump(reason="shutdown")
        self.store.close()

    def run_forever(self, **kw):
        self.start(**kw)
        try:
            # short signal-safe poll (sleep is interrupted by signals; the
            # handler only flips a bool, so there is no lock to deadlock on)
            while not (self._stop_requested or self._stop.is_set()):
                time.sleep(0.5)
        except KeyboardInterrupt:
            pass
        self.stop()


def _tolerant(raw: str, cast, default, label: str):
    """Tolerant parse for COMPOUND spec pieces (e.g. the port half of
    WAVEFRONT_PROXY): empty/malformed values fall back to the default with
    a log line — a garbage value must not crashloop the pod. Whole-knob
    reads route through utils/knobs.py, which applies the same policy."""
    try:
        return cast(raw) if raw else default
    except ValueError:
        log.warning("ignoring invalid %s=%r; using %s", label, raw, default)
        return default


def main():
    # one logging config for the whole process (worker loop, operator
    # modules, this banner); no-op when the embedding app configured
    # handlers already. LOG_LEVEL parses tolerantly like every other env
    # knob here — a typo'd level must not crashloop the pod.
    name = knobs.read("LOG_LEVEL").strip().upper()
    level = getattr(logging, name, None)
    logging.basicConfig(
        level=level if isinstance(level, int) else logging.INFO,
        format="%(asctime)s [%(name)s] %(levelname)s "
               "%(message)s%(trace_ctx)s",
    )
    # trace-context log correlation: every record carries the current
    # thread's cycle_id/job_id (empty string when unbound), so
    # `grep cycle_id=<id>` lines the log up with /debug/traces and
    # /jobs/<id>/explain. Must follow basicConfig — the filter attaches
    # to the root handlers it created.
    from .utils.tracing import install_log_filter

    install_log_filter()

    from .engine.pipeline import device_info, enable_compile_cache
    from .parallel.distributed import host_info, initialize, replica_identity

    # before anything jits: a restarted process replays compiled programs
    # from the persistent cache instead of re-paying the compile storm
    cache_dir = enable_compile_cache()
    log.info("compile cache: %s", cache_dir or "off")
    # multi-host (DCN) deploys join the jax.distributed world here; plain
    # single-host deploys fall straight through
    if initialize():
        hi = host_info()
        log.info(
            "multi-host: process %d/%d, %d local / %d global devices",
            hi.process_id, hi.num_processes, hi.local_devices,
            hi.global_devices,
        )
    # claim the device NOW: a process that cannot reach its accelerator
    # dies at boot with JAX's own error, instead of serving while every
    # launch fails into per-job errors
    log.info("device: %s", device_info())
    archive = None
    es = knobs.read("ES_ENDPOINT")
    archive_path = knobs.read("ARCHIVE_PATH")
    if es:
        from .engine.archive import EsArchive

        archive = EsArchive(es)
    elif archive_path:
        from .engine.archive import FileArchive

        archive = FileArchive(archive_path)
    # replica identity on the shard ring: explicit REPLICA_ID wins; a
    # multi-process world derives proc-<rank> with launcher-fixed static
    # membership; otherwise hostname-pid with archive-heartbeat membership
    replica = knobs.read("REPLICA_ID")
    static_replicas = None
    if not replica:
        replica, static_replicas = replica_identity()
    rt = Runtime(
        snapshot_path=knobs.read("SNAPSHOT_PATH") or None,
        query_endpoint=knobs.read("QUERY_SERVICE_ENDPOINT"),
        archive=archive,
        job_retention_seconds=knobs.read("JOB_RETENTION_SECONDS"),
        adopt_interval_seconds=knobs.read("ARCHIVE_ADOPT_INTERVAL"),
        adopt_skew_margin_seconds=knobs.read("ARCHIVE_ADOPT_SKEW_MARGIN"),
        lstm_cache_path=knobs.read("LSTM_CACHE_PATH") or None,
        replica_id=replica,
        sharding=knobs.read("SHARDING"),
        shard_count=knobs.read("SHARD_COUNT"),
        shard_vnodes=knobs.read("SHARD_VNODES"),
        heartbeat_seconds=knobs.read("HEARTBEAT_S"),
        member_ttl_seconds=knobs.read("MEMBER_TTL_S"),
        static_replicas=static_replicas,
        fleet_digest=knobs.read("FLEET_DIGEST"),
        ingest=knobs.read("INGEST"),
        ingest_buffer_samples=knobs.read("INGEST_BUFFER_SAMPLES"),
        ingest_forward=knobs.read("INGEST_FORWARD"),
        ingest_advertise_addr=knobs.read("INGEST_ADVERTISE_ADDR"),
        ingest_debounce_ms=knobs.read("INGEST_DEBOUNCE_MS"),
        window_store_dir=knobs.read("WINDOW_STORE_DIR"),
        window_store_segment_max_mb=knobs.read("WINDOW_STORE_SEGMENT_MAX_MB"),
        window_store_fsync=knobs.read("WINDOW_STORE_FSYNC"),
        window_store_checkpoint_seconds=knobs.read(
            "WINDOW_STORE_CHECKPOINT_S"),
        job_store_dir=knobs.read("JOB_STORE_DIR"),
        job_store_segment_max_mb=knobs.read("JOB_STORE_SEGMENT_MAX_MB"),
        job_store_fsync=knobs.read("JOB_STORE_FSYNC"),
        job_store_checkpoint_seconds=knobs.read("JOB_STORE_CHECKPOINT_S"),
        job_store_hot_seconds=knobs.read("JOB_STORE_HOT_S"),
        trace_sample=knobs.read("TRACE_SAMPLE"),
        trace_export_url=knobs.read("TRACE_EXPORT_URL"),
    )
    proxy = knobs.read("WAVEFRONT_PROXY")
    if proxy:
        from .dataplane.wavefront_sink import WavefrontSink

        host, _, wf_port = proxy.partition(":")
        rt.wavefront_sink = WavefrontSink(
            rt.exporter, host=host,
            port=_tolerant(wf_port, int, 2878, "WAVEFRONT_PROXY port"),
        )
    port = knobs.read("PORT")
    grpc_port = knobs.read("GRPC_PORT") or None
    cycle = knobs.read("CYCLE_SECONDS")

    import signal

    # K8s terminates pods with SIGTERM (and operators ^C with SIGINT):
    # exit the wait loop and run the full graceful stop() path — drain
    # the in-flight cycle, release leases + flush the archive mirror for
    # immediate peer adoption, final snapshot — instead of dying mid-write
    signal.signal(signal.SIGTERM, lambda *_: rt.request_stop())
    signal.signal(signal.SIGINT, lambda *_: rt.request_stop())
    log.info(
        "serving :%d%s, cycle=%ss",
        port, f" grpc :{grpc_port}" if grpc_port else "", cycle,
    )
    rt.run_forever(
        port=port, cycle_seconds=cycle, grpc_port=grpc_port,
        http_max_inflight=knobs.read("HTTP_MAX_INFLIGHT"),
        grpc_workers=knobs.read("GRPC_WORKERS"),
        grpc_max_concurrent=knobs.read("GRPC_MAX_CONCURRENT"),
    )


if __name__ == "__main__":
    main()
