"""Config-knob registry: the ONE place env vars become values.

PRs 1-4 accreted env knobs across the tree (runtime, CLI, operator,
trigger, native loader, ops module constants) with three different parse
policies and no single inventory — the devtools knob-registry checker
found 48 direct ``os.environ`` reads outside ``engine/config.py``. This
module is the enforcement seam behind that checker:

  * every knob read outside ``engine/config.py`` resolves through
    ``knobs.read(name)`` against a registration carrying its default,
    cast, and help text (``register`` below);
  * every registered knob must have a row in ``docs/configuration.md``
    (the checker cross-references the doc);
  * parsing is tolerant everywhere: a templated-empty or garbage value
    falls back to the default with a log line instead of crashlooping the
    pod (the policy ``runtime.py`` established in PR 4, now shared).

``engine/config.py`` keeps its own env surface (the reference brain's
ML_* contract, including the indexed ``metric_type{N}`` overrides whose
names are dynamic) — it and this module are the only files the checker
allows to touch ``os.environ`` directly.

Reads are cheap (one dict lookup + parse) and deliberately NOT cached:
tests monkeypatch env vars and expect the next read to see the change.
"""
from __future__ import annotations

import logging
import os
from dataclasses import dataclass

log = logging.getLogger("foremast_tpu.knobs")

__all__ = ["Knob", "register", "get", "read", "all_knobs"]


def parse_bool(raw: str) -> bool:
    """One definition of env truthiness (mirrors engine/config._env_bool:
    operators write 0/1, true/false, yes/no, on/off in any case)."""
    return raw.strip().lower() not in ("0", "false", "no", "off", "")


@dataclass(frozen=True)
class Knob:
    name: str
    default: object
    cast: type | object  # callable str -> value
    help: str
    scope: str  # "runtime" | "operator" | "trigger" | "build" | "devtools"

    def read(self, env=None):
        env = os.environ if env is None else env
        raw = env.get(self.name)
        if raw is None or raw == "":
            return self.default
        try:
            return self.cast(raw)
        except (ValueError, TypeError):
            log.warning("ignoring invalid %s=%r; using %r",
                        self.name, raw, self.default)
            return self.default


_REGISTRY: dict[str, Knob] = {}


def register(name: str, default, cast=str, help: str = "",
             scope: str = "runtime") -> Knob:
    """Register a knob. Idempotent for identical re-registration (module
    reloads); conflicting double registration is a programming error."""
    k = Knob(name=name, default=default, cast=cast, help=help, scope=scope)
    old = _REGISTRY.get(name)
    if old is not None and (old.default, old.cast, old.scope) != (
            k.default, k.cast, k.scope):
        raise ValueError(f"knob {name!r} already registered with "
                         f"different default/cast/scope")
    _REGISTRY[name] = k
    return k


def get(name: str) -> Knob:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unregistered knob {name!r}: add it to "
                       "foremast_tpu/utils/knobs.py (default + help + "
                       "docs/configuration.md row)") from None


def read(name: str, env=None):
    """Tolerantly read a registered knob from the environment."""
    return get(name).read(env)


def all_knobs() -> dict[str, Knob]:
    """Snapshot of the registry (docs tooling / tests)."""
    return dict(_REGISTRY)


# ---------------------------------------------------------------------------
# Registrations. Grouped by the process that reads them; every name here
# must have a row in docs/configuration.md (enforced by
# `python -m foremast_tpu.devtools`, rule knob-registry).
# ---------------------------------------------------------------------------

# -- runtime composition root (foremast-tpu serve; runtime.py) --
register("PORT", 8099, int, "HTTP port (job API + dashboard + /metrics)")
register("GRPC_PORT", 0, int, "gRPC dispatch port; unset/0 disables")
register("CYCLE_SECONDS", 10.0, float, "engine cycle cadence")
register("HTTP_MAX_INFLIGHT", None, int,
         "HTTP admission gate: in-flight handler ceiling")
register("GRPC_WORKERS", None, int, "gRPC worker threads")
register("GRPC_MAX_CONCURRENT", None, int,
         "gRPC admission gate (maximum_concurrent_rpcs)")
register("QUERY_SERVICE_ENDPOINT", "", str,
         "metric-store base URL for the dashboard query proxy")
register("SNAPSHOT_PATH", "", str, "job-store checkpoint file")
register("LSTM_CACHE_PATH", "", str, "trained LSTM-AE model cache path")
register("ARCHIVE_PATH", "", str, "JSONL write-behind archive path")
register("ES_ENDPOINT", "", str,
         "ES-compatible archive endpoint (wins over ARCHIVE_PATH)")
register("JOB_RETENTION_SECONDS", 24 * 3600.0, float,
         "prune archived terminal jobs from RAM after this")
register("ARCHIVE_ADOPT_INTERVAL", 30.0, float,
         "seconds between stale-peer-job archive scans (0 disables)")
register("ARCHIVE_ADOPT_SKEW_MARGIN", 15.0, float,
         "extra staleness seconds before adopting a peer's job")
register("WAVEFRONT_PROXY", "", str,
         "host[:port] to mirror verdict series to Wavefront")
register("LOG_LEVEL", "INFO", str, "process-wide logging level")
register("FOREMAST_CHAOS", "", str,
         "deterministic fault-injection spec (docs/resilience.md)")
register("FOREMAST_DEBUG_LOCKS", False, parse_bool,
         "wrap runtime locks in the devtools lock-order tracer "
         "(devtools/locktrace.py); off = plain threading locks",
         scope="devtools")

# -- operator CLI (foremast-tpu operator; cli.py) --
register("ANALYST_ENDPOINT", "", str,
         "analyst (brain) endpoint the operator consults",
         scope="operator")
register("ANALYST_TRANSPORT", "", str,
         "analyst transport override: http | grpc | inprocess",
         scope="operator")
register("WATCH_NAMESPACES", "", str,
         "comma-separated namespace allowlist for the operator watch",
         scope="operator")
register("MODE", "hpa_and_healthy_monitoring", str,
         "operator mode (reference barrelman contract)", scope="operator")
register("HPA_STRATEGY", "hpa_exists", str,
         "operator HPA enrollment strategy", scope="operator")
register("OPERATOR_NAMESPACE", "", str,
         "namespace of the deployment-metadata-default fallback record",
         scope="operator")
register("NAMESPACE", "", str,
         "legacy alias for OPERATOR_NAMESPACE (reference Barrelman.go:402)",
         scope="operator")
register("TICK_SECONDS", 10.0, float, "operator reconcile tick",
         scope="operator")
register("KUBERNETES_SERVICE_HOST", "kubernetes.default.svc", str,
         "in-cluster apiserver host (injected by kubelet)",
         scope="operator")
register("KUBERNETES_SERVICE_PORT", "443", str,
         "in-cluster apiserver port (injected by kubelet)",
         scope="operator")

# -- trigger sidecar (foremast_tpu.trigger) --
register("REQUESTS_FILE", "requests.csv", str,
         "trigger request-list CSV path", scope="trigger")
register("FOREMAST_ENDPOINT", "http://127.0.0.1:8099", str,
         "brain endpoint the trigger submits jobs to", scope="trigger")
register("WAVEFRONT_ENDPOINT", "", str,
         "Wavefront endpoint for trigger-side series", scope="trigger")
register("VOLUME_PATH", ".", str,
         "trigger scratch volume for request bookkeeping", scope="trigger")

# -- instrumentation starters --
register("APP_NAME", "", str,
         "app label stamped on instrumentation metrics / demo app")

# -- native extension loader (build-time toolchain; native/__init__.py) --
register("FOREMAST_NATIVE", True, parse_bool,
         "0 disables the C++ data-plane extension", scope="build")
register("FOREMAST_NATIVE_SO", "", str,
         "alternate prebuilt extension path (ASAN fuzz leg test seam)",
         scope="build")
register("CXX", "g++", str,
         "compiler for the native extension's build-on-first-use",
         scope="build")
register("FOREMAST_NATIVE_CXXFLAGS", "", str,
         "extra compile flags for the native extension build",
         scope="build")

# -- sharded multi-replica brain (engine/sharding.py; runtime.py) --
register("SHARDING", True, parse_bool,
         "consistent-hash job ownership across replicas sharing an "
         "archive; a sole replica owns every shard (no behavior change)")
register("REPLICA_ID", "", str,
         "stable replica identity on the shard ring (default: "
         "hostname-pid; multi-process worlds derive proc-<rank>)")
register("SHARD_COUNT", 64, int,
         "logical shards over the job-id hash space (ownership/rebalance "
         "granularity)")
register("SHARD_VNODES", 64, int,
         "virtual nodes per replica on the shard ring (assignment balance)")
register("HEARTBEAT_S", 5.0, float,
         "replica membership heartbeat interval (archive state writes)")
register("MEMBER_TTL_S", 15.0, float,
         "heartbeat age past which a replica is presumed dead and its "
         "shards rebalance")
register("FLEET_DIGEST", True, parse_bool,
         "publish this replica's status digest (health, golden signals, "
         "SLO attainment) in its membership heartbeat blob — the GET "
         "/fleet federation medium; 0 keeps heartbeats liveness-only")

# -- push-based streaming dataplane (foremast_tpu/ingest; runtime.py) --
register("INGEST", True, parse_bool,
         "push ingestion endpoints (/ingest/remote-write, /ingest/otlp) "
         "+ event-driven partial cycles; 0 restores the pure poll loop")
register("INGEST_BUFFER_SAMPLES", 4096, int,
         "per-job ingest staging-buffer sample ceiling; overfill answers "
         "429 (backpressure) and the poll path remains source of truth")
register("INGEST_FORWARD", True, parse_bool,
         "forward pushed samples for non-owned jobs to the owning "
         "replica advertised on the shard ring; 0 rejects them instead")
register("INGEST_ADVERTISE_ADDR", "", str,
         "ingest address advertised in membership heartbeats for "
         "cross-replica forwarding (default: http://<hostname>:<PORT>)")
register("INGEST_DEBOUNCE_MS", 150.0, float,
         "partial-cycle debounce: how long the event scheduler lets a "
         "push burst coalesce before scoring the advanced jobs")

# -- crash-durable window store (dataplane/winstore.py; runtime.py) --
register("WINDOW_STORE_DIR", "", str,
         "directory for the crash-durable window tier (per-replica push "
         "WAL + columnar warm segments); empty disables — window state "
         "is RAM-only exactly as before")
register("WINDOW_STORE_SEGMENT_MAX_MB", 256, int,
         "warm-segment file size (MB) past which it compacts "
         "newest-wins per query identity")
register("WINDOW_STORE_FSYNC", False, parse_bool,
         "fsync every WAL append: survives machine crashes, not just "
         "process death (kill -9 needs no fsync), at a per-push cost")
register("WINDOW_STORE_CHECKPOINT_S", 5.0, float,
         "minimum seconds between window-store checkpoints (WAL "
         "rotation + dirty-entry spill); the sweep and partial cycles "
         "both try, this floors the disk churn")

# -- crash-durable tiered job store (engine/jobtier.py; runtime.py) --
register("JOB_STORE_DIR", "", str,
         "directory for the crash-durable job tier (mutation WAL + "
         "newest-wins job/provenance segments; terminal jobs spill "
         "there and evict from RAM); empty disables — the job store "
         "is snapshot-only exactly as before")
register("JOB_STORE_SEGMENT_MAX_MB", 512, int,
         "job-segment file size (MB) past which it compacts "
         "newest-wins per job id")
register("JOB_STORE_FSYNC", False, parse_bool,
         "fsync every job-WAL append: survives machine crashes, not "
         "just process death (kill -9 needs no fsync), at a "
         "per-mutation cost")
register("JOB_STORE_CHECKPOINT_S", 5.0, float,
         "minimum seconds between job-store checkpoints (WAL rotation "
         "+ dirty-doc spill + cold eviction); the sweep calls every "
         "pass, this floors the disk churn")
register("JOB_STORE_HOT_S", 300.0, float,
         "seconds a terminal job stays RAM-resident after its last "
         "modification before evicting to the warm tier (reads fall "
         "through transparently)")

# -- distributed tracing (utils/tracing.py; runtime.py) --
register("TRACE_SAMPLE", 1.0, float,
         "head-sampling probability for freshly minted root traces "
         "(0..1); adopted `traceparent` headers keep the sender's "
         "sampled flag. Unsampled spans are measured (stats) but never "
         "ringed at /debug/traces or exported")
register("TRACE_EXPORT_URL", "", str,
         "OTLP/HTTP collector endpoint (e.g. http://otel:4318/v1/traces) "
         "finished traces are POSTed to as OTLP JSON; empty disables "
         "export — /debug/traces and `foremast-tpu trace` still work")

# -- single-dispatch mega-batching (engine/pipeline.py; read by
#    engine/config.from_env like the other ML_*/engine knobs — registered
#    here for the inventory + docs contract) --
register("MEGABATCH", False, parse_bool,
         "collapse per-family/per-T-bucket rung launches into one padded "
         "mega-batch launch per family per cycle (padding classes, "
         "byte-identical verdicts); off keeps the streamed rung path")
register("MEGABATCH_MAX_ROWS", 32768, int,
         "mega-launch row ceiling at T<=1024 (scaled ~1/T beyond); "
         "fleets past it chunk at the ceiling")

# -- fleet-scale load simulator (foremast_tpu/simfleet; `make perf`
#    BENCH_CYCLE_SIMFLEET leg and `python -m foremast_tpu.simfleet`) --
register("SIM_JOBS", 2000, int,
         "simulated fleet size the simfleet driver runs", scope="bench")
register("SIM_SEED", 0, int,
         "trace seed; every simfleet artifact records it so runs are "
         "reproducible from the JSON alone", scope="bench")
register("SIM_TRACE", "diurnal", str,
         "trace shape preset: steady | diurnal | deploy-wave | incident "
         "| churn (simfleet/trace.py)", scope="bench")
register("SIM_CYCLES", 6, int,
         "measured engine cycles per simfleet leg", scope="bench")
register("SIM_CADENCE_S", 60.0, float,
         "sim-clock seconds advanced per cycle (CYCLE_SECONDS twin; the "
         "default equals the metric step so every cycle advances every "
         "window — the launch-bound regime the mega-batch A/B measures)",
         scope="bench")
register("SIM_REPLICAS", 1, int,
         "in-process replicas the simulated fleet partitions across "
         "(hash-ring ownership, one shared store)", scope="bench")
register("SIM_ROUNDS", 2, int,
         "interleaved off/on rounds per simfleet A/B (best-of per side, "
         "digests checked every round); 1 keeps a 100k+ run affordable",
         scope="bench")
register("SIM_AB", True, parse_bool,
         "run the mega-batch on/off A/B (identity + launch collapse); "
         "0 runs a single leg honoring MEGABATCH/SIM_STREAM",
         scope="bench")
register("SIM_STREAM", False, parse_bool,
         "single-leg mode: push the advancing samples through the "
         "ingest receiver (remote-write) instead of poll-only",
         scope="bench")
register("SIM_JOBSTORE", False, parse_bool,
         "run the crash-durable job-store leg (tier on / restart-"
         "recovery / tier off over one deterministic workload) instead "
         "of the mega-batch A/B", scope="bench")
register("SIM_JOBSTORE_DIR", "", str,
         "job-store leg tier directory (empty = fresh temp dir, "
         "removed after the leg)", scope="bench")
register("SIM_JOBSTORE_OPEN", 0, int,
         "engine-scored open subset of the job-store leg's fleet "
         "(0 = auto: SIM_JOBS/20 capped at 50k)", scope="bench")
register("SIM_JOBSTORE_HOT_S", 0.0, float,
         "job-store leg hot window; 0 evicts every spilled terminal "
         "doc at the next checkpoint (the resident-bytes "
         "configuration)", scope="bench")

# -- multi-host world (parallel/distributed.py) --
register("COORDINATOR_ADDRESS", "", str,
         "jax.distributed coordinator (multi-host deploys)")
register("NUM_PROCESSES", 0, int, "jax.distributed world size")
register("PROCESS_ID", -1, int, "this process's jax.distributed rank")
register("LOCAL_DEVICE_IDS", "", str,
         "comma-separated local device ids for jax.distributed")
register("TPU_WORKER_HOSTNAMES", "", str,
         "Cloud TPU pod metadata: more than one worker selects "
         "auto-initialize; a single host starts local")
register("JAX_COMPILATION_CACHE_DIR", "", str,
         "JAX's own persistent compilation-cache directory; read here "
         "only to decide that no code sets another")

# -- kernel-grid constants read at module import (ops/) --
register("FOREMAST_KS_EXACT_MAX_T", 256, int,
         "max per-side sample count served by the exact finite-n KS null")
register("FOREMAST_WILCOXON_EXACT_MAX_N", 50, int,
         "max n served by the exact Wilcoxon signed-rank null")
