"""The analysis engine: jobs -> device batches -> verdicts.

This collapses the reference's L3 brain worker loop (poll ES -> fetch
Prometheus -> scipy per job -> write verdict, SURVEY.md §2.4/§3.1) into a
batched cycle: every runnable job's windows are fetched, packed into dense
(B, T) buckets, and scored by ONE jitted program per bucket — pairwise tests
and forecast-band checks fused (parallel.fleet), HPA scores batched
(ops.hpa). Verdict semantics preserved:

  * two judgment modes (foremast-brain/README.md:7-10): pairwise
    baseline-vs-current, and historical-model band anomaly detection.
  * fail-fast: completed_unhealth the moment an anomaly is seen; otherwise
    keep re-checking until endTime (docs/guides/design.md:43) — implemented
    by re-queuing unfinished healthy jobs each cycle.
  * insufficient data by endTime -> completed_unknown.
  * continuous jobs re-materialize START_TIME/END_TIME windows per cycle
    (foremast-service/cmd/manager/main.go:59-63); hpa jobs additionally emit
    hpalogs + the foremastbrain:..hpa_score series every cycle.
"""
from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from functools import partial

import numpy as np

from ..utils.locks import make_lock
from ..dataplane.exporter import VerdictExporter
from ..dataplane.fetch import FetchError, grid_from_series
from ..dataplane.promql import (
    CONTINUOUS_STRATEGIES,
    STRATEGY_HPA,
    materialize_placeholders,
)
from ..models import lstm_ae
from ..ops import bivariate as bv
from ..ops import forecast as fc
from ..ops import seqscan as sq
from ..ops import hpa as hpa_ops
from ..ops.windowing import (
    MAX_WINDOW_STEPS,
    Window,
    bucket_length,
    pack_windows,
)
from ..parallel import fleet as fl
from ..resilience.policy import Deadline
from ..utils import tracing
from ..utils.timeutils import from_rfc3339
from . import jobs as J
from . import flightrec
from . import provenance as prov
from . import slo as slo_mod
from .config import EngineConfig, MetricPolicy
from .health import HealthMonitor


class WatchdogTimeout(Exception):
    """A device materialization (or its per-job retry) overran WATCHDOG_S.

    Raised by Analyzer._watchdog_call; the pipeline's collect phase treats
    it like any collect failure — the bucket fails over to the sync
    per-job path — so one hung launch costs one bucket's timeout, not the
    whole cycle."""


# shed marker carried through the preprocess stream in the `failed` slot:
# distinguishable from every real FetchError string (which the analyzer
# stamps into job reasons) by identity, never shown to users directly
_SHED = "__cycle_deadline_shed__"

# the fetch pool's probe (_stream_prep): a cycle's first jobs are fetched
# alone on the cycle thread until they have used this much of its CPU or
# of the wall clock (under POOL_PROBE_MIN_CPU_S nothing was measured),
# and a fleet under this many jobs a thread of the cap is not probed. A
# reading wider than 1 is taken again over the next jobs and the narrower
# holds: one preemption of the cycle thread on a host that shares its
# cores reads as waiting (it did in 1 cycle of 63 on the v5e's host, whose
# thread clock ticks in 10 ms steps: PERF.md section 6), two in a row do
# not. Not env knobs: FETCH_CONCURRENCY is the operator's control, the
# most threads a cycle may use.
POOL_PROBE_CPU_S = 0.020
POOL_PROBE_WALL_S = 0.040
POOL_PROBE_MIN_CPU_S = 0.001
POOL_PROBE_READINGS = 2
POOL_PROBE_MIN_JOBS_PER_THREAD = 8


# the per-job notes the fetch pool sums (_stream_prep): a job's whole
# preprocess, its fetches' wall time, and the fetches' named parts, in that
# order; `Analyzer._book_pieces` turns the sums into the partition of
# tracing.POOL_SPANS
_POOL_NOTES = ("prep_thread_seconds", "fetch_seconds", "url_thread_seconds",
               "source_thread_seconds", "lock_wait_thread_seconds",
               "lock_held_seconds")


def pool_width(wall: float, cpu: float, cap: int) -> int:
    """Threads that keep one interpreter busy while the others wait on
    the store: the probe's wall seconds over its CPU seconds, at most
    `cap`. A probe that used under a millisecond of CPU measured nothing
    and gets `cap`."""
    if cpu < POOL_PROBE_MIN_CPU_S:
        return cap
    return min(cap, max(1, round(wall / cpu)))


# poison-job quarantine re-admission backoff: first parking sits out
# QUARANTINE_BASE_S, doubling per subsequent parking up to the cap. Not
# env knobs — QUARANTINE_AFTER is the operator-facing control; the
# backoff shape only needs to be sane (docs/resilience.md).
QUARANTINE_BASE_S = 30.0
QUARANTINE_MAX_S = 3600.0


@dataclass
class _PairItem:
    job_id: str
    metric: str
    baseline: Window
    current: Window
    policy: MetricPolicy


@dataclass
class _BandItem:
    job_id: str
    metric: str
    historical: Window
    current: Window
    policy: MetricPolicy


@dataclass
class _BiItem:
    """Two-metric joint job (ML_ALGORITHM=bivariate_normal; design.md:53-88)."""

    job_id: str
    metrics: tuple  # (name1, name2)
    hist: tuple  # (Window, Window)
    cur: tuple  # (Window, Window)
    policies: tuple  # (MetricPolicy, MetricPolicy)


@dataclass
class _MultiItem:
    """3+-metric LSTM-autoencoder job (faq.md:8-10)."""

    job_id: str
    cache_key: str  # app/namespace identity for the model cache
    metrics: list
    hist: list  # [Window]
    cur: list  # [Window]


@dataclass
class _HpaItem:
    job_id: str
    metric: str
    historical: Window
    current: Window
    is_increase: bool = True
    priority: int = 0
    # wire isAbsolute (models.go:179-183): static SLA limit is a value on
    # the metric's own scale vs a multiple of the healthy historical mean
    is_absolute: bool = False
    # ready-pod-count Window from the job's podCountURL, stamped on every
    # item of the job by _preprocess; None = no pod data (neutral 1/1).
    # Split into (pods_now, pods_hist) at score time against the job's own
    # current-window boundary (_pod_count_stats).
    pod_window: object = None


def _fp_counted(parts) -> tuple:
    """(fingerprint, window bytes hashed, windows hashed, window digests
    reused) of scorer inputs (SCORE_MEMO), order-sensitive.

    A Window contributes its own digest of its full identity (start, step,
    length, values, mask: `Window.digest`), hashed once per object, so a
    window the fetch layer hands back unmoved costs nothing to fingerprint
    again; ndarrays their bytes; everything else its repr. blake2b-128 —
    the memo only ever compares fingerprints of the SAME key, so 128 bits
    is far past accidental-collision territory, and hashing is ~100x
    cheaper than the device launch it elides."""
    h = hashlib.blake2b(digest_size=16)
    nbytes = hashed = reused = 0
    for p in parts:
        if p is None:
            h.update(b"\xffN")
        elif isinstance(p, Window):
            if p.digested:
                reused += 1
            else:
                hashed += 1
                nbytes += p.values.nbytes + p.mask.nbytes
            h.update(p.digest())
        elif isinstance(p, np.ndarray):
            h.update(np.int64(p.shape).tobytes())
            h.update(p.tobytes())
        else:
            h.update(repr(p).encode())
        h.update(b"|")
    return h.digest(), nbytes, hashed, reused


def _fp(*parts) -> bytes:
    """`_fp_counted`'s fingerprint alone."""
    return _fp_counted(parts)[0]


def _nbytes(arrays) -> int:
    """Bytes a pack piece wrote into these host arrays (the `bytes` attr
    of the engine.pack.* spans)."""
    return sum(a.nbytes for a in arrays)


def _concat_trimmed(hist: Window, cur: Window):
    """(values, mask, n_h) of hist+current, hist left-trimmed so the concat
    fits the largest compiled bucket (static-shape ceiling)."""
    n_c = cur.values.shape[0]
    max_h = max(MAX_WINDOW_STEPS - n_c, 0)
    h_vals = hist.values[-max_h:] if max_h else hist.values[:0]
    h_mask = hist.mask[-max_h:] if max_h else hist.mask[:0]
    vals = np.concatenate([h_vals, cur.values[: MAX_WINDOW_STEPS]])
    mask = np.concatenate([h_mask, cur.mask[: MAX_WINDOW_STEPS]])
    return vals, mask, h_vals.shape[0]


def _joint_grid(hists: list, curs: list):
    """Stack a job's metrics onto one shared concat grid.

    Metrics of one job are fetched with identical start/end/step parameters,
    so their grids line up; residual off-by-a-few length skew (scrape lag)
    is resolved by trimming every series to the common length. Current
    windows are HEAD-trimmed so concat index n_h + j maps to each current
    window's own index j — the invariant the anomaly-timestamp math
    (cur.start + (idx - n_h) * step) depends on. History keeps its tail
    (most recent points). Returns (values (F, T), masks (F, T), n_h, n_c).
    """
    n_c = min(c.values.shape[0] for c in curs)
    n_c = min(n_c, MAX_WINDOW_STEPS)
    n_h = min(h.values.shape[0] for h in hists)
    n_h = min(n_h, MAX_WINDOW_STEPS - n_c)
    vals, masks = [], []
    for h, c in zip(hists, curs):
        hv = h.values[-n_h:] if n_h else h.values[:0]
        hm = h.mask[-n_h:] if n_h else h.mask[:0]
        vals.append(np.concatenate([hv, c.values[:n_c]]))
        masks.append(np.concatenate([hm, c.mask[:n_c]]))
    return np.stack(vals), np.stack(masks), n_h, n_c


def _concat_ts(cur: Window, n_h: int, j: int) -> float:
    """Translate a concat-grid index onto the CURRENT window's own time grid.

    Anomalies lie in the current region; the historical grid ends days
    earlier, so extrapolating it would stamp anomalies in the future. Valid
    because concat index n_h + k maps to current index k (history is
    tail-kept, current head-kept — _concat_trimmed/_joint_grid invariant).
    """
    return float(cur.start + (j - n_h) * cur.step)


def _flagged_pairs(flags_row, cur: Window, n_h: int, values) -> list:
    """[ts, value, ...] of a row's first 50 flagged points: the one use a
    collect has for the elements of a (B, T) output."""
    pairs = []
    for j in np.nonzero(flags_row)[0][:50].tolist():
        pairs += [_concat_ts(cur, n_h, j), float(values[j])]
    return pairs


def _pod_count_stats(win, split_ts: float):
    """(pods_now, pods_hist) from a ready-pod-count Window, or None.

    `split_ts` is the start of the job's CURRENT (scoring) window, so the
    recent/older split aligns exactly with the region the demand estimate
    covers and the history the capacity proxy averages — no second copy
    of the materialization-window constant. Single-sided data falls back
    to the other side so a short fetch still normalizes consistently
    rather than mixing a real pods_now with a fabricated pods_hist.
    """
    if win is None or win.n_valid == 0:
        return None
    t = win.start + np.arange(win.values.shape[0]) * win.step
    recent = win.mask & (t >= split_ts)
    older = win.mask & ~recent
    n_now = float(win.values[recent].mean()) if recent.any() else None
    n_hist = float(win.values[older].mean()) if older.any() else None
    if n_now is None and n_hist is None:
        return None
    n_now = n_hist if n_now is None else n_now
    n_hist = n_now if n_hist is None else n_hist
    return (max(n_now, 1e-6), max(n_hist, 1e-6))


@dataclass
class _JobState:
    doc: J.Document
    unhealthy: list = field(default_factory=list)  # (metric, detail, anomaly pairs)
    judged_any: bool = False
    failed: str = ""
    # per-job fetch accounting from the preprocess thread's trace notes
    # (delta vs full, points, seconds) — provenance's "fetch mode" block
    fetch: dict = field(default_factory=dict)
    # ingest marker (monotonic): set as the job's preprocess result
    # streams in — the job was freshly ingested this cycle (0 = shed
    # before fetch / quarantined: no latency observation).
    ingest_at: float = 0.0
    # window-advance stamp: the newest VALID sample timestamp across the
    # job's judged current windows (the data's own clock). Detection
    # latency = (cycle `now` − this, the poll/scrape wait) + the
    # measured in-cycle tail — see Analyzer._observe_latency.
    newest_ts: float = 0.0


class Analyzer:
    def __init__(self, config: EngineConfig, data_source, store: J.JobStore,
                 exporter: VerdictExporter | None = None,
                 breath: hpa_ops.BreathState | None = None):
        self.config = config
        self.source = data_source
        self.store = store
        self.exporter = exporter or VerdictExporter()
        if breath is None:
            # restart-safe cooldowns: hydrate armed breath timers from the
            # store snapshot (persisted at every cycle boundary below), so
            # a runtime bounce mid-cooldown still suppresses the flip
            # (dynamic_autoscaling.md:117-126)
            breath = hpa_ops.BreathState()
            breath.load(store.get_state("breath") or {})
        self.breath = breath
        # LSTM-AE model cache (MAX_CACHE_SIZE semantics,
        # foremast-brain/README.md:30): key -> (params, err_mu, err_sigma);
        # insertion-ordered dict doubles as the LRU eviction queue.
        self._lstm_cache: dict = {}
        self._lstm_models: dict = {}  # (F, hidden, latent) -> module instance
        # fleet-scoring support: every trained entry gets a version, and
        # stacked parameter pytrees are cached per (shape, members) — the
        # 256-way eager jnp.stack costs ~20x the fleet launch itself, so
        # it must happen only when membership/params change, not per cycle
        self._lstm_param_version = 0
        self._lstm_stack_cache: dict = {}
        self.lstm_stack_rebuilds = 0  # observability: stack-cache churn
        # per-CYCLE train-on-miss counter (reset in _run_cycle); lives on
        # the instance so the _isolate per-job retry path cannot reset it
        self._lstm_trained_this_cycle = 0
        # jobs left unjudged because the cycle's train budget was spent —
        # distinguishes "fleet warming up" (rising counter: budget too
        # small for the churn) from "jobs simply in progress" (zero);
        # cumulative like lstm_stack_rebuilds, also stamped per cycle on
        # the engine.score.lstm span. Tracked as a per-cycle ID SET, not a
        # counter: the _isolate per-job retry path re-invokes the scorer
        # within one cycle and a counter would double-count every skipped
        # job after a batch failure.
        self.lstm_budget_skips = 0
        self._lstm_budget_skipped_ids: set = set()
        # last cycle's stage/family timing decomposition (served on
        # /status; gauges on /metrics) — empty until the first cycle
        self.last_cycle_stages: dict = {}
        # -- fingerprint score memoization (SCORE_MEMO) --
        # (family, result_key) -> (fingerprint, result dict). Survives
        # across cycles on the analyzer; the per-cycle CyclePipeline
        # consults it so unchanged rows skip their device launch entirely.
        # LRU-bounded at 4x WINDOW_CACHE_MAX (~one entry per job window).
        self._score_memo: OrderedDict = OrderedDict()
        self.score_memo_hits: dict[str, int] = {}    # family -> cumulative
        self.score_memo_misses: dict[str, int] = {}
        # lstm memo tables: deterministic-training reuse (train-window
        # fingerprint -> trained entry; PRNGKey(0) + identical data =>
        # identical params, so reuse == retrain) and verdict reuse
        # ((job, metrics) -> (score-input fingerprint, z))
        self._lstm_train_memo: OrderedDict = OrderedDict()
        self._lstm_z_memo: OrderedDict = OrderedDict()
        self.lstm_train_memo_hits = 0
        self.lstm_rescore_skips = 0
        # total device-program launches (chunk launches across every
        # family, lstm scoring, training, and the tier-0 triage screen) —
        # the steady-state no-change gate asserts this stays flat over a
        # memo-hit cycle
        self.device_launches = 0
        # -- what crosses to and from the chip, counted where it crosses
        # (cumulative; per-cycle deltas land on the engine.score span and
        # in last_cycle_stages["partition"]): nbytes of every host array
        # handed to a jitted program (_call) or put on the device for a
        # launch closure's programs (_put) and of every device value
        # brought back (_host), over the four batch families, period
        # detection and the triage screen (the lstm family's eager
        # training path is not counted); and the real samples against
        # the padded (rung, T) blocks of the packed value arrays
        self.h2d_bytes_total = 0
        self.d2h_bytes_total = 0
        self.pack_real_elems_total = 0
        self.pack_total_elems_total = 0
        # -- the pair program's sort (cumulative): elements each launched
        # row sorts (each window at its bucket, Tb + Tc) against the valid
        # samples of the real rows' windows, and the real rows whose
        # current window was packed narrower than their baseline; and the
        # widest bucket a pair launch of the cycle used (per cycle)
        self.pair_sort_elems_total = 0
        self.pair_real_elems_total = 0
        self.pair_split_rows_total = 0
        self._cycle_pair_T_max = 0
        # -- seasonal band launches: partitions by detected period
        # (cumulative), and the largest seasonal state a Holt-Winters fit
        # of the cycle held on the device, from its shapes (per cycle);
        # the columns of the cycle's seasonal-trend fits and the batched
        # solves they enqueued (per cycle)
        self.period_partitions_total = 0
        self._cycle_hw_state_bytes = 0
        self._cycle_st_columns = 0
        self._cycle_st_solves = 0
        # -- single-dispatch mega-batching (MEGABATCH) cumulative
        # counters: launches through the mega path, real rows carried and
        # padding rows added (the packing-efficiency signal satellite
        # benches track as padded/real waste ratio). Per-cycle deltas
        # land in last_cycle_stages["megabatch"].
        self.megabatch_launches_total = 0
        self.megabatch_real_rows_total = 0
        self.megabatch_pad_rows_total = 0
        # donated-kernel twins for the mega path: fn id -> jax.jit twin
        # with the big (B, T) input buffers donated, so a 100k-row mega
        # launch does not hold input AND output copies live at once.
        # Only populated on non-CPU backends (CPU XLA does not alias
        # donated buffers; donating there just warns per program).
        self._donated_twins: dict = {}
        # -- tier-0 triage (TRIAGE; engine/triage.py) cumulative counters:
        # rows screened / cleared / escalated per family, and fused
        # screen launches. Per-cycle deltas land in last_cycle_stages.
        self.triage_screened_total: dict[str, int] = {}
        self.triage_cleared_total: dict[str, int] = {}
        self.triage_escalated_total: dict[str, int] = {}
        self.triage_launches_total = 0
        # -- observability: provenance + flight recorder + trace ids --
        # per-(job, cycle) verdict attribution (engine/provenance.py):
        # which verdict path fired, per-family scores vs thresholds,
        # fetch mode — served at /jobs/<id>/explain. enabled=False (the
        # PROVENANCE=0 A/B leg) turns every call into a no-op.
        self.provenance = prov.ProvenanceRecorder(enabled=config.provenance)
        # incident flight recorder (engine/flightrec.py): bounded ring of
        # structured engine events, auto-dumped on the transition into
        # OVERLOADED/STALLED and on graceful shutdown
        self.flight = flightrec.FlightRecorder(
            dump_dir=config.flight_dump_dir,
            tracer=tracing.tracer, provenance=self.provenance,
            knobs_fn=self._dump_knobs)
        # cycle correlation id: worker-scoped monotonic sequence, bound
        # into the tracer (spans + log records) and stamped on provenance
        self._cycle_seq = 0
        self.current_cycle_id = ""
        # monotonic stamp of the current cycle's start: the in-cycle half
        # of each detection-latency observation (_observe_latency)
        self._cycle_mono0 = 0.0
        # jobs whose lstm verdict was served from the z-memo this cycle
        # (provenance memo-hit classification); reset per cycle
        self._lstm_memo_jobs: set = set()
        # -- degraded-mode operation state (docs/resilience.md) --
        # health state machine: the runtime wires cycle cadence + breaker
        # boards in; standalone analyzers still compute shed/stale/
        # watchdog-driven states. The flight recorder hears its
        # transitions (and dumps on OVERLOADED/STALLED).
        self.health = HealthMonitor(exporter=self.exporter,
                                    recorder=self.flight)
        self.flight.health_fn = self.health.state
        # detection-latency SLOs (engine/slo.py): ingest->verdict latency
        # per job class, with per-class targets and error-budget burn —
        # the latency baseline the streaming-dataplane roadmap item must
        # beat. Pure observation; burn rides the health detail
        # (informational) and /status, histograms ride /metrics.
        self.slo = slo_mod.DetectionSLO(
            exporter=self.exporter,
            targets={
                "canary": config.slo_canary_seconds,
                "continuous": config.slo_continuous_seconds,
                "hpa": config.slo_hpa_seconds,
            },
            objective=config.slo_objective)
        self.health.configure(slo_fn=self.slo.burn_summary)
        # detection-latency waterfall (engine/slo.py DetectionWaterfall):
        # the per-stage decomposition of each SLO observation. The ingest
        # receiver opens records at push accept (with the push's W3C
        # trace context + origin timestamp), the stream scheduler stamps
        # the debounce/schedule waits, and _observe_latency closes each
        # record at verdict fold — exporting
        # foremastbrain:detection_stage_seconds{stage=} histograms and
        # the verdict span that ends the push's distributed trace.
        self.waterfall = slo_mod.DetectionWaterfall(exporter=self.exporter)
        # monotonic stamp of the current cycle's fold start: splits the
        # in-cycle tail into the waterfall's score and fold stages
        self._cycle_fold_mono = 0.0
        # once-per-window-advance SLO dedupe: job_id -> newest judged
        # sample ts already observed (_observe_latency). Entries die with
        # the job (_prune_degraded_state).
        self._slo_seen: dict[str, float] = {}
        # load shedding (CYCLE_DEADLINE_S): cumulative shed count + the
        # consecutive-shed streak per open job (a shed job sorts ahead of
        # its priority class next cycle, so a permanently-blown budget
        # still round-robins the fleet instead of starving the tail)
        self.jobs_shed_total = 0
        self._shed_streak: dict[str, int] = {}
        # stale-verdict serving (MAX_STALE_S): job_id -> last cycle
        # timestamp at which the job was judged healthy on FRESH data.
        # Entries die with the job (terminal transitions pop them).
        self.stale_verdicts_served_total = 0
        self._stale_state: dict[str, float] = {}
        # poison-job quarantine (QUARANTINE_AFTER): job_id ->
        # [consecutive_failures, quarantined_until, times_quarantined]
        self.jobs_quarantined_total = 0
        self._quarantine: dict[str, list] = {}
        # hung-launch watchdog (WATCHDOG_S): fires counter + the live
        # count of abandoned sacrificial threads (each still parked on a
        # hung device call); bounded by _WATCHDOG_MAX_ABANDONED
        self.watchdog_fires_total = 0
        self._wd_lock = make_lock("engine.analyzer.watchdog")
        self._watchdog_abandoned = 0
        # sharded multi-replica brain (engine/sharding.py): the runtime
        # wires a ShardManager in; its ownership predicate then gates the
        # per-cycle claim so N replicas partition the fleet instead of
        # racing for it. None = single-replica (own everything), unchanged.
        self.shard = None

    def _memo_put(self, table: OrderedDict, key, val):
        """Insert-and-bound for the memo tables (LRU, shared ceiling)."""
        table[key] = val
        table.move_to_end(key)
        bound = max(4 * self.config.window_cache_max, 64)
        while len(table) > bound:
            table.popitem(last=False)

    def _memo_key_fp(self, fam, entry, T: int):
        """(result_key, *`_fp_counted`) for one routed accumulator entry
        of family `fam` (engine/families.py says what the fingerprint
        covers)."""
        return (fam.entry_key(entry),
                *_fp_counted(fam.fp_parts(entry, T)))

    def _dump_knobs(self) -> dict:
        """Knob values folded into flight-recorder dumps: the degraded-mode
        and observability controls an incident post-mortem needs."""
        cfg = self.config
        from ..utils import knobs as _knobs

        return {
            "engine": {
                "cycle_deadline_seconds": cfg.cycle_deadline_seconds,
                "max_stale_seconds": cfg.max_stale_seconds,
                "quarantine_after": cfg.quarantine_after,
                "watchdog_seconds": cfg.watchdog_seconds,
                "fetch_cycle_deadline_seconds":
                    cfg.fetch_cycle_deadline_seconds,
                "score_memo": cfg.score_memo,
                "delta_fetch": cfg.delta_fetch,
                "provenance": cfg.provenance,
                "max_claim_per_cycle": cfg.max_claim_per_cycle,
                "fetch_concurrency": cfg.fetch_concurrency,
            },
            "env": {name: k.read()
                    for name, k in sorted(_knobs.all_knobs().items())
                    if k.scope in ("runtime", "devtools")},
        }

    def status_digest(self) -> dict:
        """Compact JSON-safe status digest this replica publishes in its
        membership heartbeat blob (engine/sharding.py digest_fn) — the
        cross-replica federation medium GET /fleet aggregates: health
        state, job counts, last-cycle golden signals, lease/triage
        counters, and per-class detection-latency SLO attainment. Must
        stay small (re-written every HEARTBEAT_S into the shared archive)
        and cheap (runs on the heartbeat thread). Dicts mutated by the
        cycle thread are snapshotted before summing."""
        state, _detail = self.health.state()
        stats = self.last_cycle_stages or {}
        store = self.store
        digest = {
            "v": 1,
            "health": state,
            "cycle_id": self.current_cycle_id,
            "jobs": store.status_counts(),
            "cycle": {
                "jobs": stats.get("jobs", 0),
                "device_launches": stats.get("device_launches", 0),
                "shed": stats.get("jobs_shed", 0),
                "stale_served": stats.get("stale_verdicts_served", 0),
                "watchdog_fires": stats.get("watchdog_fires", 0),
                "quarantined": stats.get("quarantined_jobs", 0),
            },
            "lease": {
                "claims": store.lease_claims_total,
                "steals": store.lease_steals_total,
                "releases": store.lease_releases_total,
                "adoptions": store.adopted_total,
            },
            "triage": {
                "screened": sum(dict(self.triage_screened_total).values()),
                "cleared": sum(dict(self.triage_cleared_total).values()),
                "escalated": sum(dict(self.triage_escalated_total).values()),
            },
            "slo": self.slo.digest(),
        }
        if self.shard is not None:
            digest["shards"] = self.shard.health_summary()
        return digest

    # ------------------------------------------------------------------ fetch
    def _fetch_window(self, url: str, now: float) -> Window | None:
        if not url:
            return None
        # `fetch_seconds` is the whole call, the URL's materialization
        # with it; that materialization is URL seconds, as the delta
        # source's own URL work is (dataplane/delta.py)
        t0 = time.perf_counter()
        url = materialize_placeholders(url, now)
        t1 = time.perf_counter()
        try:
            # byte-level sources expose fetch_window: body -> grid Window
            # in one fused native call, skipping the intermediate
            # (ts, vals) arrays (fetch.window_from_prometheus_body).
            # Series-level sources (fixture dicts, wavefront) go through
            # fetch() + grid_from_series — the two paths are asserted
            # equivalent in tests/test_native.py.
            fw = getattr(self.source, "fetch_window", None)
            if fw is not None:
                win = fw(url)
            else:
                win = None
            if win is None:
                ts, vals = self.source.fetch(url)
                win = grid_from_series(ts, vals)
            if win is not None:
                tracing.tracer.add_note("points", int(win.values.shape[0]))
            return win
        finally:
            dt = time.perf_counter() - t0
            tracing.tracer.add_note("url_thread_seconds", t1 - t0)
            tracing.tracer.add_note("fetches", 1)
            tracing.tracer.add_note("fetch_seconds", dt)
            self.exporter.record_histogram(
                "foremastbrain:fetch_seconds", {}, dt,
                help="Per-window metric fetch latency (seconds).")

    def _preprocess(self, doc: J.Document, now: float):
        """Fetch all windows for a job; returns {family name: items}. Band
        candidates route by the configured model family and
        metric count (design.md:53-88): bivariate_normal pairs 2-metric jobs,
        lstm_autoencoder pools 3+-metric jobs; everything else (and any job
        not matching its family's metric count) scores univariate bands."""
        pairs, bands, bis, multis, hpas = [], [], [], [], []
        candidates = []  # (name, hist, cur, policy) judgeable by history
        pod_window = None
        if doc.strategy == STRATEGY_HPA and doc.pod_count_url:
            # podCountURL (metricsquery.go:149-169): ready-pod counts over
            # the job window, fetched once per job and folded into a true
            # per-pod score (see ops.hpa.hpa_scores pods_now/pods_hist).
            # Best-effort: a missing count series degrades to the
            # aggregate score, never fails the job. Catches ANY failure,
            # not just FetchError — a proxy can flatten errors to a 200
            # with an unparseable body, and a garbage pod endpoint must
            # not abort the cycle (prep_many only converts FetchError).
            try:
                pod_window = self._fetch_window(doc.pod_count_url, now)
            except Exception:  # noqa: BLE001 - optional signal, never fatal
                pod_window = None
        for name, mq in doc.metrics.items():
            policy = self.config.policy_for(name)
            cur = self._fetch_window(mq.current, now)
            base = self._fetch_window(mq.baseline, now)
            hist = self._fetch_window(mq.historical, now)
            if cur is None or cur.n_valid == 0:
                # no current data -> nothing judgeable for this metric; the
                # job ends COMPLETED_UNKNOWN at endTime, never "healthy"
                continue
            if doc.strategy == STRATEGY_HPA:
                if hist is not None:
                    hpas.append(
                        _HpaItem(doc.id, name, hist, cur, mq.is_increase,
                                 mq.priority, mq.is_absolute, pod_window)
                    )
                continue
            if base is not None and base.n_valid > 0:
                pairs.append(_PairItem(doc.id, name, base, cur, policy))
            if hist is not None and hist.n_valid >= self.config.min_historical_points:
                candidates.append((name, hist, cur, policy))
        algo = self.config.algorithm
        # the reference dispatches the historical model by METRIC COUNT
        # (docs/guides/design.md:53-88: one metric -> MA/ES/DES/HW/Prophet,
        # two -> bivariate normal, 3+ -> LSTM); ML_ALGORITHM names the
        # univariate forecaster. multimetric_auto=False restores the
        # explicit-algorithm-only routing.
        auto = self.config.multimetric_auto
        if (auto or algo.startswith("bivariate")) and len(candidates) == 2:
            (n1, h1, c1, p1), (n2, h2, c2, p2) = candidates
            bis.append(_BiItem(doc.id, (n1, n2), (h1, h2), (c1, c2), (p1, p2)))
        elif (auto or algo.startswith("lstm")) and len(candidates) >= 3:
            multis.append(
                _MultiItem(
                    doc.id,
                    f"{doc.app_name}/{doc.namespace}",
                    [c[0] for c in candidates],
                    [c[1] for c in candidates],
                    [c[2] for c in candidates],
                )
            )
        else:
            for name, hist, cur, policy in candidates:
                bands.append(_BandItem(doc.id, name, hist, cur, policy))
        return {"pair": pairs, "band": bands, "bivariate": bis,
                "hpa": hpas, "lstm": multis}

    # ------------------------------------------------------------- scoring
    def _isolate(self, score_fn, items):
        """Run a batch scorer with per-job blast-radius containment.

        Scorers batch many jobs into one device program, so one poisoned
        item would otherwise fail the whole cycle for everyone — and the
        stuck-job takeover would re-claim and re-crash it forever. On batch
        failure, retry per JOB (not per item: hpa scores a job's
        metrics jointly — splitting them would misassign tps/sla roles) and
        report {job_id: error} for the offenders only.
        """
        try:
            return score_fn(items), {}
        except Exception:  # noqa: BLE001 - fall back to per-job isolation
            results, bad = {}, {}
            by_job: dict[str, list] = {}
            for it in items:
                by_job.setdefault(it.job_id, []).append(it)
            for job_id, group in by_job.items():
                try:
                    results.update(score_fn(group))
                except Exception as e:  # noqa: BLE001
                    bad[job_id] = f"{type(e).__name__}: {e}"
            return results, bad

    def _watchdog_call(self, fn, *args):
        """Run a collect-phase materialization bounded by WATCHDOG_S.

        JAX device waits have no timeout parameter, so the bound comes
        from outside: the call runs on a sacrificial daemon thread and
        the caller waits at most the budget. On expiry the thread is
        ABANDONED (a truly hung runtime call cannot be interrupted from
        Python) and WatchdogTimeout raised — the pipeline fails the
        bucket over to the sync per-job path, which is wrapped too, so a
        poisoned device stalls one bucket per cycle, never the cycle.
        Disabled (WATCHDOG_S=0) this is a plain call with zero overhead.
        """
        timeout = self.config.watchdog_seconds
        if timeout <= 0:
            return fn(*args)
        with self._wd_lock:
            if self._watchdog_abandoned >= self._WATCHDOG_MAX_ABANDONED:
                # a persistently wedged device would otherwise accumulate
                # abandoned threads (and their pinned launch state)
                # without bound across cycles; at the cap, new guarded
                # calls fast-fail as watchdog fires — same failover and
                # the same DEGRADED health signal, zero new threads
                self._record_watchdog_fire()
                raise WatchdogTimeout(
                    f"{self._watchdog_abandoned} abandoned watchdog "
                    "threads (device wedged); call skipped")
        out: list = []
        err: list = []
        done = threading.Event()
        abandoned = {"flag": False}
        # cross-thread trace correlation: the sacrificial thread adopts
        # this thread's trace context, so spans it opens parent under the
        # cycle trace (and its log lines carry cycle_id) instead of
        # orphaning; an ABANDONED thread can at worst append late,
        # silently-dropped children — never corrupt another stack
        ctx = tracing.tracer.context()

        def run():
            try:
                with tracing.tracer.attach(ctx):
                    out.append(fn(*args))
            except BaseException as e:  # noqa: BLE001 - relayed to caller
                err.append(e)
            finally:
                done.set()
                # flag read UNDER the lock, pairing with the timed-out
                # main thread's locked {is_set check -> flag set}: without
                # it, a call completing exactly at the timeout boundary
                # could read the flag before main sets it and leak the
                # abandoned slot forever (8 leaks = watchdog wedged shut)
                with self._wd_lock:
                    if abandoned["flag"]:
                        # the hung call eventually returned: free its slot
                        self._watchdog_abandoned -= 1

        t = threading.Thread(target=run, name="collect-watchdog", daemon=True)
        t.start()
        if not done.wait(timeout):
            with self._wd_lock:
                if not done.is_set():
                    abandoned["flag"] = True
                    self._watchdog_abandoned += 1
            if abandoned["flag"]:
                self._record_watchdog_fire()
                raise WatchdogTimeout(
                    f"device materialization exceeded {timeout:g}s "
                    "(watchdog)")
        if err:
            raise err[0]
        return out[0]

    # abandoned-thread ceiling: past this many never-returned device
    # calls the watchdog stops spawning and fast-fails instead
    _WATCHDOG_MAX_ABANDONED = 8

    def _record_watchdog_fire(self):
        self.watchdog_fires_total += 1
        self.flight.record_event(flightrec.EVENT_WATCHDOG,
                                 abandoned=self._watchdog_abandoned)
        self.exporter.record_counter(
            "foremastbrain:watchdog_fires_total", {},
            help="device materializations timed out by the collect "
                 "watchdog (WATCHDOG_S)")

    def _observe_latency(self, st: _JobState, now: float):
        """One window-advance -> verdict detection-latency observation
        for a judged job (engine/slo.py), annotated onto its provenance
        record BEFORE the terminal transition attaches the summary to
        the Document.

        Two addends, each in a self-consistent clock domain:
          * poll/scrape wait — cycle `now` minus the newest judged
            sample's own timestamp (how long fresh evidence sat waiting
            to be LOOKED at; under poll-driven operation this is the
            TTL-cache + cycle-tick wait the streaming dataplane removes);
          * in-cycle tail — monotonic fold time minus the cycle start
            (fetch + dispatch + collect + fold for this job's cycle).

        Each WINDOW ADVANCE is observed once: a cycle that re-judges a
        job on the same newest sample is a re-confirmation of an
        already-detected state, not a new detection, and counting its
        ever-growing staleness would drown the latency of the advance
        itself (with streaming, a verdict landing 0.5 s after the push
        must not be followed by sweeps re-reporting the same sample at
        10/20/30 s). Jobs with NO judgeable samples (newest_ts == 0)
        keep the per-cycle observation — there is no advance to key on.

        No-op for jobs that ingested nothing this cycle (shed,
        quarantined, stale-served)."""
        if not st.ingest_at:
            return
        tail0 = self._cycle_mono0 or st.ingest_at
        mono_now = time.monotonic()
        lat = max(mono_now - tail0, 0.0)
        if st.newest_ts > 0:
            if self._slo_seen.get(st.doc.id, 0.0) >= st.newest_ts:
                st.ingest_at = 0.0
                # a re-confirmation consumes nothing: drop any waterfall
                # record a redundant push opened (its watermark is
                # independent of the SLO dedupe), or its stages would
                # leak into the job's NEXT genuine observation
                self.waterfall.discard(st.doc.id)
                return  # this advance was already observed
            self._slo_seen[st.doc.id] = st.newest_ts
            lat += max(now - st.newest_ts, 0.0)
        st.ingest_at = 0.0  # at most one observation per cycle
        self.slo.observe(slo_mod.classify(st.doc.strategy), lat)
        # waterfall: split the in-cycle tail at the fold boundary and
        # close this job's stage record (push stages came from the
        # receiver/scheduler; polled jobs synthesize the poll wait)
        fold0 = self._cycle_fold_mono or mono_now
        wf = self.waterfall.observe(
            st.doc.id, now=now, newest_ts=st.newest_ts,
            score_s=max(fold0 - tail0, 0.0),
            fold_s=max(mono_now - fold0, 0.0))
        ann = {"detection_latency_s": round(lat, 6)}
        if wf["stages"]:
            ann["detection_stages"] = {
                k: round(v, 6) for k, v in wf["stages"].items()}
        if wf["trace_id"]:
            # the push's trace beats the cycle's own: `explain` must
            # link the verdict to the distributed trace that carried it
            ann["trace_id"] = wf["trace_id"]
        self.provenance.annotate(st.doc.id, **ann)
        ctx = wf["ctx"]
        if ctx is not None and ctx.sampled:
            # close the push's distributed trace AT the verdict: a
            # remote-parented span under the receive/forward chain
            # carrying the waterfall, so one trace runs push -> verdict
            # across every replica it touched
            with tracing.tracer.span(
                    tracing.SPAN_ENGINE_VERDICT, _remote=ctx,
                    job_id=st.doc.id, status=st.doc.status,
                    detection_latency_s=round(lat, 4),
                    waterfall={k: round(v, 6)
                               for k, v in wf["stages"].items()}):
                pass

    def reset_slo(self):
        """Clear SLO observations AND the once-per-advance dedupe map
        (bench legs isolate measured cycles from warm-up; resetting the
        histograms without the map would mute the first post-reset
        observation per job). The waterfall follows — stage
        distributions must cover exactly the observations the SLO does."""
        self._slo_seen.clear()
        self.slo.reset()
        self.waterfall.reset()

    def _prov_content(self, job_id: str) -> str | None:
        """Compact provenance JSON for a terminal Document's
        processing_content (None keeps the field untouched when
        provenance is off — the A/B identity contract covers
        status/reason/anomaly; the attachment itself is the feature)."""
        if not self.provenance.enabled:
            return None
        return self.provenance.summary_json(job_id) or None

    def quarantined_count(self, now: float | None = None) -> int:
        """Jobs currently parked in poison quarantine. Snapshot first
        (list() is atomic under the GIL): /metrics scrapes call this from
        HTTP threads while the cycle thread inserts/pops entries, and
        iterating the live dict would raise mid-scrape."""
        now = time.time() if now is None else now
        return sum(1 for q in list(self._quarantine.values()) if q[1] > now)

    # ladder continues past the default chunk so a LARGE configured
    # score_batch still pads small fleets to the nearest rung, never to
    # the full chunk (10k rows must not pad to a 1M-row launch). The
    # 512 rung exists for the expensive per-row families (LSTM fleet
    # scoring: a 500-job fleet padding to 1024 doubles the scan work;
    # measured 6.8 s -> ~3.5 s per mixed cycle on CPU).
    _BATCH_BUCKETS = (16, 64, 256, 512, 1024, 4096, 16384, 65536)

    @classmethod
    def _rung_for(cls, n: int, cap: int) -> int:
        """Smallest batch rung >= n from the ladder, capped at `cap`.
        The ONE ladder walk — the family chunker and the triage screen
        (engine/triage.py, whose prewarm rung set in pipeline.prewarm is
        derived from the same ladder) both route through it."""
        for b in cls._BATCH_BUCKETS:
            if b >= cap:
                break
            if n <= b:
                return b
        return cap

    def _bucket_rows(self, n: int) -> int:
        """Smallest batch rung >= n, capped at the configured chunk."""
        return self._rung_for(n, max(16, self.config.score_batch))

    # mega padding classes (MEGABATCH): below this the classic rung
    # ladder bounds tiny-program churn; above it classes are mantissa-
    # quantized so a big fleet pads by at most 1/16 — the rung ladder's
    # power-of-4 gaps would waste up to 4x compute at mega batch sizes
    # (a 1500-row fleet padding to 4096), which on a compute-bound
    # backend costs more than the launches the mega path saves.
    _MEGA_MANTISSA_FLOOR = 512

    @classmethod
    def _mega_rows(cls, n: int) -> int:
        """Smallest mega padding class >= n: rung-ladder snapped up to
        _MEGA_MANTISSA_FLOOR, then ceil to 5-bit-mantissa granularity
        (m * 2^e with m in [16, 32)) — waste <= 6.25%, program count
        bounded at 16 classes per octave (and a steady fleet only ever
        compiles the one class its size lands in)."""
        n = max(int(n), 1)
        if n <= cls._MEGA_MANTISSA_FLOOR:
            for b in cls._BATCH_BUCKETS:
                if n <= b:
                    return b
        e = max(n.bit_length() - 5, 0)  # keeps the mantissa in [16, 32)
        return -(-n // (1 << e)) << e

    def _mega_cap(self, T) -> int:
        """Mega-launch row ceiling for a T bucket: MEGABATCH_MAX_ROWS at
        T <= 1024, scaled ~1/T beyond (floor 1024) so a long-history
        bucket's mega launch costs the same peak bytes as a short one.
        T may be a route key: a pair's (Tb, Tc) is capped at its wider
        side, as `_launch_chunks` chunks it."""
        if isinstance(T, tuple):
            T = max(T)
        max_rows = max(int(self.config.megabatch_max_rows), 1024)
        budget = max_rows * 1024  # row-steps at the base T
        return int(min(max_rows, max(budget // max(int(T), 1024), 1024)))

    def _call(self, fn, *args, **kw):
        """Call a jitted program, counting the host arrays handed to it
        (each one is a transfer to the device)."""
        self.h2d_bytes_total += sum(
            a.nbytes for a in (*args, *kw.values())
            if isinstance(a, np.ndarray))
        return fn(*args, **kw)

    def _put(self, *arrays) -> list:
        """Host arrays put on the device once, counted, for a launch
        closure whose programs share them: a device array handed on to
        `_call` is not a transfer and is not counted again."""
        import jax

        self.h2d_bytes_total += sum(a.nbytes for a in arrays)
        return [jax.device_put(a) for a in arrays]

    def _host(self, x) -> np.ndarray:
        """`np.asarray` of a device value, counted: blocks until the
        program has run and copies the result to the host."""
        if isinstance(x, np.ndarray):
            return x
        a = np.asarray(x)
        self.d2h_bytes_total += a.nbytes
        return a

    def _chunk_plan(self, B: int, T: int) -> list:
        """[(first row, real rows, launched rows)] of each chunk
        `_launch_chunks` cuts from B real rows of T-wide blocks: full
        chunks of C rows, then the last one padded to its target (a rung,
        or under MEGABATCH a mega class)."""
        mega = self.config.megabatch
        C = self._mega_cap(T) if mega else self._bucket_rows(B)
        plan = []
        for i in range(0, B, C):
            n = min(C, B - i)
            plan.append((i, n, min(self._mega_rows(n), C) if mega
                         else self._bucket_rows(n)))
        return plan

    def _launch_rows(self, B: int, T: int) -> int:
        """Rows `_launch_chunks` hands its launches for B real rows of
        T-wide blocks: the row count a launch half allocates its blocks
        at, so that a chunk is a view of them and not a padded copy."""
        plan = self._chunk_plan(B, T)
        return plan[-1][0] + plan[-1][2] if plan else 0

    def _launch_chunks(self, fn, arrays: list, donate: int = 0,
                       row_elems=None, with_rows: bool = False,
                       rows: int | None = None) -> list:
        """Row-chunk packed (B, ...) arrays into FIXED batch buckets and
        call fn per chunk WITHOUT materializing the outputs.

        XLA specializes every jitted program on the batch dimension, so
        launching the raw fleet size compiles a fresh program whenever the
        claim count changes — and CPU compile time itself grows with B
        (measured ~33 s at B=10k vs ~133 s at B=50k). Fixed batch rungs
        amortize to ONE compiled program per (rung, T bucket) for the life
        of the process and bound peak memory at any fleet size. Partial
        chunks (small fleets AND the tail of a big one) pad up to the
        smallest rung that fits — never to the full chunk — with edge
        padding (repeat of the last row — always semantically valid
        inputs); padded rows are trimmed on merge.

        Who writes the pad rows: `rows` is the real row count (default:
        the first array's). An array that already reaches a chunk's
        target, because its caller allocated it at `_launch_rows(rows, T)`
        with the rows past `rows` written as copies of the last real row
        (the (B, T) blocks of the band, pair and bivariate launch halves),
        is handed on as a view of its rows, with no copy; any other array
        (the (B,) vectors, every array of a caller that did not pre-size)
        is cut and edge-padded here.

        Returns [(out_dict, n_valid_rows)] in row order. The out dicts
        hold whatever fn returned — for jitted scorers these are
        async-dispatch device values; nothing blocks until
        `_collect_chunks` materializes them, so the caller can keep
        packing the next bucket while the device drains this one.

        `donate` > 0 says fn is a jitted program that takes the chunk
        itself, so the chunk's bytes cross to the device here; a host
        closure (band, hpa, period detection) counts what it hands on.
        `row_elems` (a family's launch half passes it) holds each row's
        real samples over the packed value arrays, the chunk's float
        (rows, T) blocks: the pack's fill is their sum against the
        blocks' padded size. `with_rows` hands fn the chunk's real row
        count as `rows=` (the band closure partitions the real rows, not
        the edge padding).
        """
        B = arrays[0].shape[0] if rows is None else rows
        mega = self.config.megabatch
        # single-dispatch mega-batching: ONE launch for the whole
        # accumulated batch (chunked only at the memory-aware cap of its
        # T), padded to the fine mega class instead of rung-chunked.
        # Row-wise scorers make the launch boundary verdict-neutral
        # (the same argument the streamed-vs-flushed determinism
        # tests pin), so this changes launch count, never results.
        T = max((a.shape[1] for a in arrays if a.ndim > 1), default=1024)
        # every chunk's rows, cut and padded before the first launch: one
        # span a dispatch, whether or not a chunk needs padding
        chunks = []
        with tracing.span(tracing.SPAN_ENGINE_PACK_PAD, rows=B) as sp:
            written = 0
            for i, n, target in self._chunk_plan(B, T):
                sl = []
                for a in arrays:
                    if a.shape[0] >= i + target:
                        sl.append(a[i:i + target])
                        continue
                    a = np.pad(a[i:i + n], ((0, target - n),)
                               + ((0, 0),) * (a.ndim - 1), mode="edge")
                    written += a.nbytes
                    sl.append(a)
                chunks.append((i, sl, n, target))
            sp.attrs["bytes"] = written
        launches = []
        for i, sl, n, target in chunks:
            self.device_launches += 1
            call = partial(fn, rows=n) if with_rows else fn
            if row_elems is not None:
                self.pack_real_elems_total += int(row_elems[i:i + n].sum())
                self.pack_total_elems_total += sum(
                    a.size for a in sl if a.ndim == 2 and a.dtype.kind == "f")
            h0 = self.h2d_bytes_total
            with tracing.span(tracing.SPAN_ENGINE_LAUNCH, rows=n,
                              padded_rows=target) as sp:
                if mega:
                    self.megabatch_launches_total += 1
                    self.megabatch_real_rows_total += n
                    self.megabatch_pad_rows_total += target - n
                    out = self._mega_call(call, sl, donate)
                elif donate:
                    out = self._call(call, *sl)
                else:
                    out = call(*sl)
                sp.attrs["h2d_bytes"] = self.h2d_bytes_total - h0
            launches.append((out, n))
        return launches

    def _mega_call(self, fn, sl: list, donate: int):
        """Invoke one mega launch, through a donated-buffer jit twin
        when the kernel is a pure jitted program (`donate` leading array
        args) and the backend aliases donated inputs (TPU/GPU). The big
        packed (B, T) arrays are dead after the launch, so donation
        halves the mega launch's peak footprint. CPU XLA does not alias
        (donating there only warns per program), and the host-composite
        band/hpa closures cannot be re-jitted — both take the plain
        call, same results."""
        if donate:
            import jax

            if jax.default_backend() != "cpu":
                tw = self._donated_twins.get(id(fn))
                if tw is None:
                    tw = jax.jit(fn, donate_argnums=tuple(range(donate)))  # lint: disable=jit-hygiene -- donate_argnums is the leading-array count a launch half passes as a literal (4/5), never a traced value
                    self._donated_twins[id(fn)] = tw
                args = [jax.device_put(a) if i < donate else a
                        for i, a in enumerate(sl)]
                self.h2d_bytes_total += sum(a.nbytes for a in sl)
                return tw(*args)
            return self._call(fn, *sl)
        return fn(*sl)

    def _collect_chunks(self, launches: list) -> dict:
        """Materialize `_launch_chunks` output: block on the device values,
        trim padded rows, concatenate chunks back into one (B, ...) dict."""
        d0 = self.d2h_bytes_total
        with tracing.span(tracing.SPAN_ENGINE_MATERIALIZE) as sp:
            outs = [
                {k: self._host(v)[:n] for k, v in out.items()}
                for out, n in launches
            ]
            sp.attrs["d2h_bytes"] = self.d2h_bytes_total - d0
        if len(outs) == 1:
            return outs[0]
        return {k: np.concatenate([o[k] for o in outs]) for k in outs[0]}

    # ------------------------------------------------ family launch/collect
    # Each batch family (pair, band, bivariate, hpa) is split into a
    # `_launch_*` half (pack + async device dispatch; returns an opaque
    # state tuple whose [0] is the claim-ordered entry list) and a
    # `_collect_*` half (materialize + per-item postprocess), with the
    # rule that gives an item its T bucket beside them. The table in
    # engine/families.py delegates to these by name at call time; the
    # per-job retry and prewarm (`Family.score`) are launch + immediate
    # collect over the same code, so the paths cannot drift.

    # A pair row's current window is packed narrower than its baseline at
    # one width alone, and only under a baseline bucket at least
    # _PAIR_SPLIT_RATIO times that width: the row then carries at most
    # 17/32 of its symmetric elements (the 7-day baseline against a
    # 20-to-31-point window: 16,416 of 32,768). In the narrower buckets
    # the saving (at most 96 of 256 elements a row at T=128) would not
    # pay for a second launch key, its own partly filled rung and its own
    # program, so their rows stay symmetric. One width a bucket keeps
    # prewarm's grid to two programs a (rung, T).
    _PAIR_SHORT_T = 32
    _PAIR_SPLIT_RATIO = 16

    @classmethod
    def _pair_short_T(cls, Tb: int) -> int:
        """The bucket a short current window is packed at under baseline
        bucket Tb: `_PAIR_SHORT_T`, or Tb itself where no row splits."""
        s = cls._PAIR_SHORT_T
        return s if Tb >= cls._PAIR_SPLIT_RATIO * s else Tb

    @classmethod
    def _pair_T(cls, it: _PairItem) -> tuple:
        """(Tb, Tc): the baseline at the bucket of the row's longer
        window; the current window at `_pair_short_T(Tb)` where it fits
        there, else at Tb. The baseline keeps the row's bucket, so the
        band's judged region starts where it always did."""
        nc = it.current.values.shape[0]
        Tb = bucket_length(max(it.baseline.values.shape[0], nc))
        Tc = cls._pair_short_T(Tb)
        return (Tb, Tc if nc <= Tc else Tb)

    def _launch_pairs(self, group: list, T: tuple):
        cfg = self.config
        Tb, Tc = T
        B = len(group)
        # a pair's rows are its routed windows: nothing is built a row
        with tracing.span(tracing.SPAN_ENGINE_PACK_ROWS, rows=B, bytes=0):
            pass
        with tracing.span(tracing.SPAN_ENGINE_PACK_BLOCK, rows=B) as sp:
            R = self._launch_rows(B, Tb)
            bvals, bm = pack_windows([it.baseline for it in group],
                                     pad_to=Tb, rows=R)
            cv, cm = pack_windows([it.current for it in group], pad_to=Tc,
                                  rows=R)
            arrays = [
                bvals, bm, cv, cm,
                np.full(B, cfg.pairwise_threshold, np.float32),
                np.full(B, cfg.enabled_tests(), np.int32),
                np.full(
                    B,
                    fl.COMBINE_ALL if cfg.pairwise_combine_all else fl.COMBINE_ANY,
                    np.int32,
                ),
                np.full(B, cfg.ma_window, np.int32),
                np.asarray([it.policy.threshold for it in group], np.float32),
                np.asarray([it.policy.bound for it in group], np.int32),
                np.asarray([it.policy.min_lower_bound for it in group], np.float32),
                np.tile(
                    np.asarray(
                        [
                            cfg.min_mann_whitney_points,
                            cfg.min_wilcoxon_points,
                            cfg.min_kruskal_points,
                            cfg.min_friedman_points,
                        ],
                        np.int32,
                    ),
                    (B, 1),
                ),
            ]
            row_elems = np.asarray(
                [it.baseline.values.shape[0] + it.current.values.shape[0]
                 for it in group])
            sp.attrs.update(bytes=_nbytes(arrays), edge_rows=R - B)
        self.pair_sort_elems_total += R * (Tb + Tc)
        self.pair_real_elems_total += sum(
            int(np.count_nonzero(it.baseline.mask))
            + int(np.count_nonzero(it.current.mask)) for it in group)
        if Tb != Tc:
            self.pair_split_rows_total += B
        self._cycle_pair_T_max = max(self._cycle_pair_T_max, Tb)
        launches = self._launch_chunks(fl.score_pairs, arrays, donate=4,
                                       row_elems=row_elems, rows=B)
        return (group, launches)

    def _collect_pairs(self, state) -> dict:
        group, launches = state
        out = self._collect_chunks(launches)
        results = {}
        # one bulk .tolist() per field instead of 5 boxed numpy scalar
        # reads per row: at 100k rows the boxed reads alone cost more
        # host time than the merge (tolist yields the same Python
        # bool/float/int values bool()/float()/int() did — byte-identical
        # verdicts, pinned by the mega A/B)
        unhealthy = out["unhealthy"].tolist()
        min_p = out["min_p"].tolist()
        pw = out["pairwise_unhealthy"].tolist()
        band = out["band_unhealthy"].tolist()
        band_count = out["band_count"].tolist()
        for i, it in enumerate(group):
            results[(it.job_id, it.metric, "pair")] = {
                "unhealthy": unhealthy[i],
                "min_p": min_p[i],
                "pairwise_unhealthy": pw[i],
                "band_unhealthy": band[i],
                "band_count": band_count[i],
            }
        return results

    def _needs_period(self) -> bool:
        return self.config.algorithm.startswith(
            ("holt_winters", "seasonal_trend", "prophet")
        )

    def _detect_periods(self, xv_d, hist_mask, rows: int):
        """Per-series seasonal period of a band chunk (auto-detection),
        from the chunk's device values: the (rows,) int32 periods on the
        host, or None when the configured algorithm has no period or
        auto-detection is off. The one wait a band launch keeps: a
        compiled period is a static shape, so the host has to know which
        rows share one before it can enqueue their fit; it waits here for
        the chunk's upload and the detection program, and (B,) int32 come
        down. The fallback for unsupported or aperiodic series is the
        static HW_PERIOD, clamped the same way the static path clamps it."""
        cfg = self.config
        cands = tuple(p for p in cfg.hw_period_candidates if p >= 2)
        # an empty candidate set (operator set HW_PERIOD_CANDIDATES="") is
        # an explicit "static period only" — same as auto off
        if not (self._needs_period() and cfg.hw_period_auto and cands):
            return None
        fallback = min(cfg.hw_period, max(xv_d.shape[1] // 2, 2))
        d0 = self.d2h_bytes_total
        with tracing.span(tracing.SPAN_ENGINE_DETECT_PERIOD, rows=rows) as sp:
            period, _ = self._call(
                fc.detect_period, xv_d, hist_mask, cands,
                np.int32(fallback), np.float32(cfg.hw_min_seasonal_acf),
                alias_margin=np.float32(cfg.hw_alias_margin),
                contrast_margin=np.float32(cfg.hw_contrast_margin),
            )
            chosen = self._host(period)[:rows]
            periods, counts = np.unique(chosen, return_counts=True)
            sp.attrs.update(
                d2h_bytes=self.d2h_bytes_total - d0, partitions=len(periods),
                period_rows={str(p): n for p, n in zip(periods.tolist(),
                                                       counts.tolist())})
        return chosen

    def _predict(self, xv, hist_mask, data_steps: int | None = None,
                 period_override: int | None = None):
        """Forecaster dispatch on config.algorithm (history-only fit):
        the (B, T) predictions, left where the program wrote them.

        `data_steps` steers the long-window kernel gate; the band path
        passes its bucket T so the choice is a pure function of the
        compiled bucket — identical for every chunking of the same
        bucket (streamed and flushed launches must agree bit-for-bit).
        `period_override` carries a detected seasonal period (already
        support-gated against the series length by detect_period);
        without it the static HW_PERIOD config is clamped to the window.
        """
        algo = self.config.algorithm
        B = xv.shape[0]
        # long windows: same smoother, time-parallel (associative scan).
        # SES only — the DES associative form compounds f32 rounding on
        # trending series (~4e-3 relative at T>=4096, enough to flip a
        # borderline band verdict), so DES always runs sequentially here.
        long = (data_steps if data_steps is not None
                else xv.shape[1]) >= self.config.long_window_steps
        if algo.startswith("exponential_smoothing"):
            ses = sq.ses_predictions_assoc if long else fc.ses_predictions
            preds = self._call(ses, xv, hist_mask,
                               np.full(B, 0.3, np.float32))
        elif algo.startswith("double_exponential"):
            preds = self._call(
                fc.des_predictions, xv, hist_mask,
                np.full(B, 0.5, np.float32), np.full(B, 0.1, np.float32))
        elif algo.startswith("holt_winters"):
            period = (period_override if period_override is not None
                      else min(self.config.hw_period, max(xv.shape[1] // 2, 2)))
            fitm = self._call(fc.hw_fit_mask, hist_mask, np.int32(period))
            _, preds = self._call(fc.fit_holt_winters, xv, hist_mask, fitm,
                                  period)
            self._cycle_hw_state_bytes = max(
                self._cycle_hw_state_bytes,
                fc.hw_state_bytes(period, fc.HW_CANDIDATES, B))
        elif algo.startswith("seasonal_trend") or algo.startswith("prophet"):
            period = (period_override if period_override is not None
                      else min(self.config.hw_period, max(xv.shape[1] // 2, 2)))
            order, cps = self.config.st_order, self.config.st_changepoints
            _, preds = self._call(
                fc.fit_seasonal_trend,
                xv, hist_mask, hist_mask, period, order, n_changepoints=cps,
            )
            self._cycle_st_columns = fc.st_columns(order, cps)
            self._cycle_st_solves += fc.st_solves(cps)
        else:  # moving_average_all default
            preds = self._call(fc.moving_average_predictions, xv, hist_mask,
                               self.config.ma_window)
        return preds

    @staticmethod
    def _band_T(it: _BandItem) -> int:
        return bucket_length(
            min(
                it.historical.values.shape[0] + it.current.values.shape[0],
                MAX_WINDOW_STEPS,
            )
        )

    def _launch_bands(self, group: list, T: int):
        B = len(group)
        with tracing.span(tracing.SPAN_ENGINE_PACK_ROWS, rows=B) as sp:
            concats = []
            n_hs = []
            written = 0
            for it in group:
                h, c = it.historical, it.current
                vals, mask, n_h = _concat_trimmed(h, c)
                n_hs.append(n_h)
                concats.append(Window(vals, mask, h.start, h.step))
                written += vals.nbytes + mask.nbytes
            sp.attrs["bytes"] = written
        with tracing.span(tracing.SPAN_ENGINE_PACK_BLOCK, rows=B) as sp:
            R = self._launch_rows(B, T)
            xv, xm = pack_windows(concats, pad_to=T, rows=R)
            ns = np.asarray([c.values.shape[0] for c in concats], np.int32)
            arrays = [
                xv, xm, np.asarray(n_hs, np.int32), ns,
                np.asarray([it.policy.threshold for it in group], np.float32),
                np.asarray([it.policy.bound for it in group], np.int32),
                np.asarray([it.policy.min_lower_bound for it in group],
                           np.float32),
            ]
            sp.attrs.update(bytes=_nbytes(arrays), edge_rows=R - B)

        def band_fn(xv_c, xm_c, nh_c, n_c, thr_c, bnd_c, mlb_c, rows):
            # the chunk crosses to the device once and the launch's programs
            # read it there; the judged region [n_h, n) of each row, the
            # predictions and sigma never visit the host.
            # The long-window kernel gate is a function of the BUCKET (T),
            # not of the rows sharing a chunk: a data-dependent gate (max
            # real length in the chunk) would make a row's smoother choice
            # depend on its chunk-mates, so streamed launches (different
            # chunk boundaries) could flip a borderline band verdict vs.
            # a flush at stream end. T is already what the program compiles
            # on; buckets only reach 4096 when their members are >2048
            # points, where the assoc scan is the right kernel anyway.
            xv_d, xm_d = self._put(xv_c, xm_c)
            region, hist_mask = self._call(fc.region_masks, xm_d, nh_c, n_c)

            def score(xv_p, xm_p, region_p, hist_p, thr_p, bnd_p, mlb_p,
                      period=None):
                preds = self._predict(xv_p, hist_p, T, period_override=period)
                sigma = self._call(
                    fc.residual_sigma, xv_p, preds, hist_p, hist_p)
                return self._call(
                    fc.band_anomalies,
                    xv_p, xm_p, region_p, preds, sigma, thr_p, bnd_p, mlb_p)

            chosen = self._detect_periods(xv_d, hist_mask, rows)
            if chosen is None:
                return score(xv_d, xm_d, region, hist_mask,
                             thr_c, bnd_c, mlb_c)
            # a compiled period is a static shape: each period's rows are
            # gathered from the DEVICE block (row count a batch rung, the
            # index edge-padded: a padded slot repeats the partition's last
            # row, so its scatter writes that row's own results again),
            # fitted, and their results scattered back into claim order on
            # the device; the collect reads one dict, as for any band.
            out = None
            for p in np.unique(chosen):
                idx = np.nonzero(chosen == p)[0].astype(np.int32)
                idx = np.pad(idx, (0, self._bucket_rows(len(idx)) - len(idx)),
                             mode="edge")
                part = score(
                    *self._call(fc.take_rows, idx,
                                xv_d, xm_d, region, hist_mask),
                    thr_c[idx], bnd_c[idx], mlb_c[idx], period=int(p))
                out = self._call(fc.scatter_rows, out, part, idx,
                                 xv_d.shape[0])
                self.period_partitions_total += 1
            return out

        launches = self._launch_chunks(band_fn, arrays, row_elems=ns,
                                       with_rows=True, rows=B)
        return (group, launches, xv, n_hs)

    def _collect_bands(self, state) -> dict:
        group, launches, xv, n_hs = state
        out = self._collect_chunks(launches)
        results = {}
        # bulk tolist for the per-row fields (see _collect_pairs); of the
        # (B, T) flags only the rows that flagged a point are read
        counts = out["count"].tolist()
        firsts = out["first_index"].tolist()
        uppers = out["upper"].tolist()
        lowers = out["lower"].tolist()
        flags = out["flags"]
        checked = out["checked"].tolist()
        for i, it in enumerate(group):
            n_h = n_hs[i]
            first = firsts[i]
            results[(it.job_id, it.metric, "band")] = {
                "count": counts[i],
                "unhealthy": counts[i] >= self._gate(checked[i]),
                "first_ts": (
                    _concat_ts(it.current, n_h, first) if first >= 0 else -1.0
                ),
                "upper": uppers[i],
                "lower": lowers[i],
                "anomaly_pairs": (
                    _flagged_pairs(flags[i], it.current, n_h, xv[i])
                    if counts[i] > 0 else []),
            }
        return results

    def _gate(self, checked) -> float:
        """Unhealthy-verdict gate: min anomalous points for a band-style
        scorer to condemn a window (see EngineConfig.band_min_points)."""
        return max(
            self.config.band_min_points,
            self.config.band_violation_fraction * float(checked),
        )

    @staticmethod
    def _bi_prep(it: _BiItem):
        """((x, m, n_h, n_c) joint grid, T bucket) for one bivariate item."""
        pre = _joint_grid(list(it.hist), list(it.cur))
        return pre, bucket_length(pre[0].shape[1])

    def _launch_bivariate(self, entries: list, T: int):
        """entries: [(item, joint-grid prep)] — one launch state per bucket."""
        B = len(entries)
        # a row's joint grid was made on the stream (_bi_prep, in route)
        with tracing.span(tracing.SPAN_ENGINE_PACK_ROWS, rows=B, bytes=0):
            pass
        with tracing.span(tracing.SPAN_ENGINE_PACK_BLOCK, rows=B) as sp:
            # the blocks at the rows they are launched at (pack_windows'
            # rule: a pad row repeats the last real row's samples)
            R = self._launch_rows(B, T)
            x1 = np.zeros((R, T), np.float32)
            x2 = np.zeros((R, T), np.float32)
            m1 = np.zeros((R, T), bool)
            m2 = np.zeros((R, T), bool)
            n_hist = np.empty(B, np.int32)
            n_total = np.empty(B, np.int32)
            thr = np.empty(B, np.float32)
            mlb1 = np.empty(B, np.float32)
            mlb2 = np.empty(B, np.float32)
            bm1 = np.empty(B, np.int32)
            bm2 = np.empty(B, np.int32)
            for i, (it, (x, m, n_h, n_c)) in enumerate(entries):
                n = x.shape[1]
                x1[i, :n], x2[i, :n] = x[0], x[1]
                m1[i, :n], m2[i, :n] = m[0], m[1]
                n_hist[i], n_total[i] = n_h, n
                # the pair shares one ellipse: use the stricter (smaller)
                # radius of the two metric policies
                thr[i] = min(it.policies[0].threshold, it.policies[1].threshold)
                mlb1[i] = it.policies[0].min_lower_bound
                mlb2[i] = it.policies[1].min_lower_bound
                bm1[i] = it.policies[0].bound
                bm2[i] = it.policies[1].bound
            n = n_total[B - 1]
            for a in (x1, m1, x2, m2):
                a[B:, :n] = a[B - 1, :n]
            arrays = [x1, m1, x2, m2, n_hist, n_total, thr, mlb1, mlb2, bm1,
                      bm2]
            sp.attrs.update(bytes=_nbytes(arrays), edge_rows=R - B)
        launches = self._launch_chunks(bv.bivariate_normal_anomalies,
                                       arrays, donate=4,
                                       row_elems=2 * n_total, rows=B)
        return (entries, launches)

    def _collect_bivariate(self, state) -> dict:
        entries, launches = state
        out = self._collect_chunks(launches)
        results = {}
        # as _collect_bands: lists of the per-row fields, and a row of
        # the (B, T) flags only where it flagged a point
        counts = out["count"].tolist()
        firsts = out["first_index"].tolist()
        checked = out["checked"].tolist()
        flags = out["flags"]
        upper1 = out["upper1"].tolist()
        lower1 = out["lower1"].tolist()
        upper2 = out["upper2"].tolist()
        lower2 = out["lower2"].tolist()
        for i, (it, (x, m, n_h, n_c)) in enumerate(entries):
            cur0 = it.cur[0]
            first = firsts[i]
            results[(it.job_id, "&".join(it.metrics), "bivariate")] = {
                "count": counts[i],
                "unhealthy": counts[i] >= self._gate(checked[i]),
                "first_ts": (
                    _concat_ts(cur0, n_h, first) if first >= 0 else -1.0
                ),
                "anomaly_pairs": (
                    _flagged_pairs(flags[i], cur0, n_h, x[0])
                    if counts[i] > 0 else []),
                "bounds": {
                    it.metrics[0]: (upper1[i], lower1[i]),
                    it.metrics[1]: (upper2[i], lower2[i]),
                },
            }
        return results

    def _lstm_model(self, F: int, unroll: int = 8):
        """Module instance per (F, dims, unroll). Scoring uses unroll=8
        (fleet-launch dispatch bound); training passes unroll=1 (the
        unrolled fwd+bwd compiles slower and runs ~2x slower). Both share
        one param tree shape — see LstmAutoencoder.unroll."""
        key = (F, self.config.lstm_hidden, self.config.lstm_latent, unroll)
        if key not in self._lstm_models:
            self._lstm_models[key] = lstm_ae.LstmAutoencoder(
                hidden=self.config.lstm_hidden,
                latent=self.config.lstm_latent,
                features=F,
                unroll=unroll,
            )
        return self._lstm_models[key]

    def _score_multi(self, items: list[_MultiItem]):
        """LSTM-autoencoder scoring for 3+-metric jobs (faq.md:8-10).

        Per job: standardize each metric on its history, train the AE on
        non-overlapping historical subwindows (cached per app, LRU-bounded by
        MAX_CACHE_SIZE), then z-score the current window's reconstruction
        error against the healthy-error distribution."""
        cfg = self.config
        results = {}
        memo_on = cfg.score_memo
        memo_zs: list = []   # (item, z) reused without a device launch
        zfp_by_job: dict = {}  # (job_id, metrics) -> score-input fp
        # (item, params, err_mu, err_sd, version, cwin, cmask)
        scoreable: list = []
        # (item, cache_key, hwin, hmask, cwin, cmask, train_fp) — budgeted
        # misses
        pending: list = []
        pending_keys: set = set()
        # same-cycle duplicates of a pending cache_key (N jobs of one app
        # share the app/metrics/W key): they ride the leader's training —
        # one budget slot, one model — and resolve from the cache after
        followers: list = []
        budget = cfg.lstm_max_train_per_cycle
        for it in items:
            x, m, n_h, n_c = _joint_grid(it.hist, it.cur)
            F, T = x.shape
            W = min(cfg.lstm_window, max(n_h // 2, 1))
            if W < 4 or n_h < 2 * W:
                # not enough history to learn from: leave the job unjudged
                # (COMPLETED_UNKNOWN at endTime), same as sparse band jobs
                continue
            hist_m = m[:, :n_h]
            hw = hist_m.astype(np.float64)
            n = np.maximum(hw.sum(axis=1), 1.0)
            # float64 reductions: any f32-finite history (<= 3.4e38)
            # squares and sums without overflow in f64, so mu/sd stay
            # finite and the standardized series is well-defined — no
            # NaN edge, no warning suppression needed (review r05)
            xh = x[:, :n_h].astype(np.float64)
            mu = (xh * hw).sum(axis=1) / n
            sd = np.sqrt((((xh - mu[:, None]) * hw) ** 2).sum(axis=1) / n)
            sd = np.maximum(sd, 1e-6)
            xs = ((x - mu[:, None]) / sd[:, None]).T.astype(np.float32)  # (T, F)
            ms = m.T  # (T, F)

            k = n_h // W
            h0 = n_h - k * W
            hwin = xs[h0:n_h].reshape(k, W, F)
            hmask = ms[h0:n_h].reshape(k, W, F)
            # score windows tiling the WHOLE current region (not just the
            # last W steps); a final tail window may dip into history — its
            # history steps are mask-zeroed so they add no reconstruction
            # error and cannot dilute the z-score
            starts = list(range(n_h, T - W + 1, W))
            if not starts or starts[-1] + W < T:
                starts.append(max(T - W, 0))
            cwin = np.stack([xs[s : s + W] for s in starts])
            cmask = np.stack([ms[s : s + W] for s in starts])
            for k_i, s in enumerate(starts):
                if s < n_h:
                    cmask[k_i, : n_h - s] = False

            cache_key = (it.cache_key, tuple(it.metrics), W)
            entry = self._lstm_cache.pop(cache_key, None)
            train_fp = _fp(b"lstm-train", hwin, hmask, cfg.lstm_epochs,
                           cfg.lstm_hidden, cfg.lstm_latent) if memo_on \
                else None
            if entry is None and memo_on:
                # train-window fingerprint memo: training is deterministic
                # (PRNGKey(0), fixed epochs), so identical train windows
                # reproduce identical params — reuse the previous entry
                # instead of re-paying the train (the restart/eviction/
                # key-churn case BENCH_r05 measured at 25.8 s of warmup)
                entry = self._lstm_train_memo.get(train_fp)
                if entry is not None:
                    self._lstm_train_memo.move_to_end(train_fp)
                    self.lstm_train_memo_hits += 1
            if entry is None:
                if cache_key in pending_keys:
                    # a leader is already training this key this cycle:
                    # no extra budget slot, no redundant training
                    followers.append((it, cache_key, cwin, cmask))
                    continue
                # the counter lives on the analyzer and resets per CYCLE,
                # not per call: the _isolate per-job retry path re-invokes
                # this scorer many times within one cycle, and a per-call
                # counter would let one poisoned job convert the budgeted
                # warm-up into the full unbounded training burst
                if budget > 0 and self._lstm_trained_this_cycle >= budget:
                    # train-on-miss budget spent (VERDICT r3: a cold
                    # multi-metric fleet must not blow the cycle budget on
                    # unbounded AE training): leave the job unjudged; it
                    # stays in progress and warms up on a later cycle.
                    self._lstm_budget_skipped_ids.add(it.job_id)
                    continue
                self._lstm_trained_this_cycle += 1
                # defer: same-shape misses train together in one vmapped
                # loop (lstm_ae.train_fleet) after the collection pass
                pending.append((it, cache_key, hwin, hmask, cwin, cmask,
                                train_fp))
                pending_keys.add(cache_key)
                continue
            self._lstm_cache[cache_key] = entry  # re-insert = mark recent
            while len(self._lstm_cache) > cfg.max_cache_size:
                self._lstm_cache.pop(next(iter(self._lstm_cache)))
            params, err_mu, err_sd, version = entry
            if memo_on:
                # verdict memo: unchanged score windows against unchanged
                # params (version pins them) reuse the previous z without
                # a device launch — the steady-state common case for jobs
                # whose train window hasn't moved
                jkey = (it.job_id, tuple(it.metrics))
                zfp = _fp(b"lstm-z", cwin, cmask, err_mu, err_sd, version)
                prev = self._lstm_z_memo.get(jkey)
                if prev is not None and prev[0] == zfp:
                    self._lstm_z_memo.move_to_end(jkey)
                    self.lstm_rescore_skips += 1
                    self._lstm_memo_jobs.add(it.job_id)
                    memo_zs.append((it, prev[1]))
                    continue
                zfp_by_job[jkey] = zfp
            scoreable.append((it, params, err_mu, err_sd, version,
                              cwin, cmask))

        scoreable.extend(self._train_pending(pending))
        for it, cache_key, cwin, cmask in followers:
            entry = self._lstm_cache.get(cache_key)
            if entry is None:
                continue  # the leader's training failed: follower waits too
            params, err_mu, err_sd, version = entry
            scoreable.append((it, params, err_mu, err_sd, version,
                              cwin, cmask))
        if memo_on:
            # freshly trained / follower rows get their score fingerprint
            # recorded too, so the NEXT cycle's unchanged windows memo-hit
            for it, _p, mu_, sd_, version, cwin, cmask in scoreable:
                jkey = (it.job_id, tuple(it.metrics))
                zfp_by_job.setdefault(
                    jkey, _fp(b"lstm-z", cwin, cmask, mu_, sd_, version))
        import itertools

        for (it, z) in itertools.chain(
                memo_zs, self._score_multi_fleet(scoreable)):
            results[(it.job_id, "+".join(it.metrics), "lstm")] = {
                "unhealthy": z > cfg.lstm_threshold,
                "z": z,
            }
            if memo_on:
                jkey = (it.job_id, tuple(it.metrics))
                zfp = zfp_by_job.get(jkey)
                if zfp is not None:
                    self._memo_put(self._lstm_z_memo, jkey, (zfp, z))
        return results

    def _train_pending(self, pending):
        """Train this cycle's budgeted cache-misses, same-shape groups in
        one vmapped loop (lstm_ae.train_fleet: E dispatches for the whole
        group instead of J*E — measured 6.7x for 8 jobs on CPU). Each
        job's sliced params land in the LRU cache exactly like the
        single-job path. Yields scoreable tuples."""
        import jax as _jax

        cfg = self.config
        groups: dict[tuple, list] = {}
        for rec in pending:
            hwin = rec[2]
            groups.setdefault(hwin.shape, []).append(rec)
        def train_one(rec):
            it, cache_key, hwin, hmask, cwin, cmask = rec[:6]
            self.device_launches += 1
            state, tx = lstm_ae.init_state(
                model, _jax.random.PRNGKey(0), T=hwin.shape[1])
            state, _ = lstm_ae.train(
                model, state, tx, hwin, hmask, epochs=cfg.lstm_epochs)
            mu_, sd_ = lstm_ae.fit_score_normalizer(
                state.params, hwin, hmask, model.apply)
            return (state.params, float(mu_), float(sd_))

        for (k, W, F), recs in groups.items():
            model = self._lstm_model(F, unroll=1)  # training: no unroll
            with tracing.span("engine.lstm_train", jobs=len(recs),
                              features=F, window=W):
                trained: list
                if len(recs) == 1:
                    try:
                        trained = [train_one(recs[0])]
                    except Exception:  # noqa: BLE001 - poisoned job skips;
                        trained = [None]  # it retries on a later budget
                else:
                    try:
                        Xh = np.stack([r[2] for r in recs])
                        Mh = np.stack([r[3] for r in recs])
                        self.device_launches += 1
                        pstack, mus, sds = lstm_ae.train_fleet(
                            model, _jax.random.PRNGKey(0), Xh, Mh,
                            epochs=cfg.lstm_epochs)
                        trained = [
                            (_jax.tree.map(lambda a, j=j: a[j], pstack),
                             float(mus[j]), float(sds[j]))
                            for j in range(len(recs))
                        ]
                    except Exception:  # noqa: BLE001 - blast-radius per job
                        # batched training poisoned by one member: retry
                        # per JOB so the healthy majority still trains and
                        # caches this cycle (the _isolate contract); the
                        # offender alone is skipped (its budget slot is
                        # spent — it retries on a later cycle's budget)
                        trained = []
                        for rec in recs:
                            try:
                                trained.append(train_one(rec))
                            except Exception:  # noqa: BLE001
                                trained.append(None)
            for rec, result in zip(recs, trained):
                if result is None:
                    continue
                it, cache_key, _hw, _hm, cwin, cmask = rec[:6]
                params, mu_, sd_ = result
                self._lstm_param_version += 1
                entry = (params, mu_, sd_, self._lstm_param_version)
                self._lstm_cache[cache_key] = entry
                while len(self._lstm_cache) > cfg.max_cache_size:
                    self._lstm_cache.pop(next(iter(self._lstm_cache)))
                train_fp = rec[6] if len(rec) > 6 else None
                if train_fp is not None:
                    # params are shared refs with the LRU cache, so this
                    # index adds no param memory; bound it like the cache
                    self._lstm_train_memo[train_fp] = entry
                    self._lstm_train_memo.move_to_end(train_fp)
                    while len(self._lstm_train_memo) > cfg.max_cache_size:
                        self._lstm_train_memo.popitem(last=False)
                yield (it, params, mu_, sd_, entry[3], cwin, cmask)

    # fleet scoring engages above this group size; smaller groups take the
    # per-job path (rung padding would waste more than it saves)
    _LSTM_FLEET_MIN = 4

    def _score_multi_fleet(self, scoreable):
        """Score collected multi-metric jobs, batching same-shape groups.

        Each job owns its own trained AE params, so a warm fleet's scoring
        was J per-job device dispatches per cycle — the dominant cost of
        the multi family once training is cached. Jobs whose score windows
        share a (F, W, K) shape stack into ONE vmapped launch over a
        stacked parameter pytree (lstm_ae.anomaly_scores_fleet), with the
        job axis padded to the fixed batch rungs so XLA compiles one
        program per (rung, shape) for the life of the process.

        Yields (item, z) pairs.
        """
        import jax as _jax
        import jax.numpy as jnp

        groups: dict[tuple, list] = {}
        for rec in scoreable:
            cwin = rec[5]
            key = (cwin.shape[2], cwin.shape[1], cwin.shape[0])  # (F, W, K)
            groups.setdefault(key, []).append(rec)
        chunk_cap = self._bucket_rows(self.config.score_batch)
        for (F, W, K), recs in groups.items():
            model = self._lstm_model(F)
            if len(recs) < self._LSTM_FLEET_MIN:
                for it, params, mu, sd, _ver, cwin, cmask in recs:
                    self.device_launches += 1
                    z = float(np.max(np.asarray(lstm_ae.anomaly_scores(
                        params, cwin, cmask, mu, sd, model.apply))))
                    yield it, z
                continue
            # chunk like _launch_chunks: groups beyond the configured batch
            # cap split into full chunks (pad can never go negative)
            for lo in range(0, len(recs), chunk_cap):
                chunk = recs[lo:lo + chunk_cap]
                J = len(chunk)
                rung = self._bucket_rows(J)
                pad = rung - J
                # stacked-params cache: the stack itself costs ~20x the
                # fleet launch, so reuse it while the member set +
                # versions hold (stable for a warm continuous fleet;
                # rebuilt on retrain, membership change, or rung move).
                # LRU with re-insert on hit, so concurrently-live shape
                # groups cannot evict each other cycle over cycle.
                stack_key = (F, W, K, rung, tuple(r[4] for r in chunk))
                pstack = self._lstm_stack_cache.pop(stack_key, None)
                if pstack is None:
                    self.lstm_stack_rebuilds += 1

                    def stack(leaves):
                        arr = jnp.stack(leaves)
                        if pad:
                            reps = jnp.repeat(arr[-1:], pad, axis=0)
                            arr = jnp.concatenate([arr, reps])
                        return arr

                    pstack = _jax.tree.map(
                        lambda *xs: stack(list(xs)), *[r[1] for r in chunk])
                self._lstm_stack_cache[stack_key] = pstack  # mark recent
                while len(self._lstm_stack_cache) > 32:
                    self._lstm_stack_cache.pop(
                        next(iter(self._lstm_stack_cache)))
                X = np.stack([r[5] for r in chunk])
                M = np.stack([r[6] for r in chunk])
                mus = np.asarray([r[2] for r in chunk], np.float32)
                sds = np.asarray([r[3] for r in chunk], np.float32)
                if pad:
                    X = np.concatenate([X, np.repeat(X[-1:], pad, axis=0)])
                    M = np.concatenate([M, np.repeat(M[-1:], pad, axis=0)])
                    mus = np.concatenate([mus, np.repeat(mus[-1:], pad)])
                    sds = np.concatenate([sds, np.repeat(sds[-1:], pad)])
                self.device_launches += 1
                zs = np.asarray(lstm_ae.anomaly_scores_fleet(
                    pstack, X, M, mus, sds, model.apply))[:J]
                for (it, *_), z in zip(chunk, zs.max(axis=1)):
                    yield it, float(z)

    # ------------------------------------------- LSTM model-cache persistence
    def save_lstm_cache(self, path: str, max_entries: int | None = None) -> int:
        """Persist trained LSTM-AE models (params + score normalizers) so
        a restarted runtime warm-starts instead of re-paying the budgeted
        train-on-miss warm-up for every known app. The reference brain
        kept its model cache in RAM only (MAX_CACHE_SIZE,
        foremast-brain/README.md:30) — every restart retrained the fleet.

        One flax msgpack blob, written atomically (tmp + rename, same
        crash rule as the job snapshot). ``max_entries`` caps the write
        to the most-recent entries in LRU order; the default (None)
        persists the whole cache — it is already bounded by
        MAX_CACHE_SIZE, and a silent lower cap would quietly re-pay the
        warm-up for every app past it after a restart. Returns the
        number of entries written."""
        import json

        import flax.serialization as fser
        import jax

        items = list(self._lstm_cache.items())
        if max_entries is not None and len(items) > max_entries:
            items = items[-max_entries:]
        cfg = self.config
        payload = {
            "format": 1,
            # architecture fingerprint: params from a different geometry
            # must never be offered to this engine's modules
            "arch": {"hidden": cfg.lstm_hidden, "latent": cfg.lstm_latent,
                     "lstm_window": cfg.lstm_window},
            "keys": json.dumps(
                [[k[0], list(k[1]), int(k[2])] for k, _ in items]),
            "mu": np.asarray([e[1] for _, e in items], np.float64),
            "sd": np.asarray([e[2] for _, e in items], np.float64),
        }
        for idx, (_, e) in enumerate(items):
            payload[f"p{idx}"] = jax.device_get(e[0])
        blob = fser.msgpack_serialize(payload)
        tmp = f"{path}.tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        import os

        os.replace(tmp, path)
        return len(items)

    def load_lstm_cache(self, path: str) -> int:
        """Load a save_lstm_cache blob into the warm cache. Absent,
        corrupt, or architecture-mismatched files load 0 entries and
        never raise — a bad cache file must degrade to the ordinary
        cold-start warm-up, not crash startup. Returns entries loaded."""
        import json

        import flax.serialization as fser

        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            return 0
        cfg = self.config
        try:
            payload = fser.msgpack_restore(blob)
            if payload.get("format") != 1:
                return 0
            arch = payload.get("arch") or {}
            if (int(arch.get("hidden", -1)) != cfg.lstm_hidden
                    or int(arch.get("latent", -1)) != cfg.lstm_latent
                    or int(arch.get("lstm_window", -1)) != cfg.lstm_window):
                return 0
            keys = json.loads(payload["keys"])
            mu, sd = payload["mu"], payload["sd"]
            loaded = 0
            for idx, k in enumerate(keys):
                params = payload.get(f"p{idx}")
                if params is None:
                    continue
                key = (str(k[0]), tuple(str(m) for m in k[1]), int(k[2]))
                self._lstm_param_version += 1
                self._lstm_cache[key] = (
                    params, float(mu[idx]), float(sd[idx]),
                    self._lstm_param_version,
                )
                loaded += 1
            while len(self._lstm_cache) > cfg.max_cache_size:
                self._lstm_cache.pop(next(iter(self._lstm_cache)))
            return loaded
        except Exception:  # noqa: BLE001 — corrupt cache file: cold-start
            return 0

    @staticmethod
    def _hpa_rows(items: list[_HpaItem]) -> list:
        """[(job_id, tps_item, sla_item)] — primary (priority 0 / tps-like)
        metric drives the traffic model; an SLA metric (is_increase &
        priority>0) the reward."""
        by_job: dict[str, list[_HpaItem]] = {}
        for it in items:
            by_job.setdefault(it.job_id, []).append(it)
        rows = []
        for job_id, group in by_job.items():
            group.sort(key=lambda it: it.priority)
            tps_it = group[0]
            # SLA metric contract: is_increase (a "more is worse" signal)
            # with priority > 0; fall back to any secondary, then primary
            sla_candidates = [it for it in group[1:] if it.is_increase]
            if sla_candidates:
                sla_it = sla_candidates[0]
            else:
                sla_it = group[1] if len(group) > 1 else group[0]
            rows.append((job_id, tps_it, sla_it))
        return rows

    @staticmethod
    def _hpa_row_T(row) -> int:
        """Pack-length bucket for one HPA row: the max of the job's OWN tps
        and sla series (lengths are data-driven and independent) like every
        other fleet scorer — one global max-T would pad a whole
        heterogeneous fleet to its single longest member (a lone
        7-day-history job would inflate every 2-hour job's scan 128x)."""
        return max(
            bucket_length(
                min(
                    it.historical.values.shape[0]
                    + it.current.values.shape[0],
                    MAX_WINDOW_STEPS,
                )
            )
            for it in (row[1], row[2])
        )

    def _launch_hpa(self, rows, T: int):
        """Pack + launch one pack-length bucket of HPA jobs."""

        def build(it):
            vals, mask, n_h = _concat_trimmed(it.historical, it.current)
            # carry the series' own step: a non-default-step job must not
            # silently snap back to the 60 s DEFAULT_STEP
            return Window(vals, mask, it.historical.start,
                          it.historical.step), n_h

        B = len(rows)
        with tracing.span(tracing.SPAN_ENGINE_PACK_ROWS, rows=B) as sp:
            tps_w, n_hs = zip(*[build(t) for _, t, _ in rows])
            sla_w = [build(s)[0] for _, _, s in rows]
            sp.attrs["bytes"] = sum(w.values.nbytes + w.mask.nbytes
                                    for w in (*tps_w, *sla_w))
        with tracing.span(tracing.SPAN_ENGINE_PACK_BLOCK, rows=B) as sp:
            tv, tm = pack_windows(list(tps_w), pad_to=T)
            sv, sm = pack_windows(list(sla_w), pad_to=T)

            # per-job SLA criteria (dynamic_autoscaling.md:45-56): mode from
            # ML_SLA_MODE, limit from the SLA metric's policy (sla_limit{N})
            # falling back to ML_SLA_LIMIT; a static/min mode with no limit
            # configured degrades to dynamic (there is nothing static to hold
            # the metric against), never to a fake 1e9 "static" limit that
            # would make SLA_MIN collapse to dynamic silently.
            mode_cfg = {"static": hpa_ops.SLA_STATIC, "min": hpa_ops.SLA_MIN}.get(
                self.config.sla_mode, hpa_ops.SLA_DYNAMIC)
            limits = np.empty(len(rows), np.float32)
            modes = np.empty(len(rows), np.int32)
            absolutes = np.empty(len(rows), bool)
            pods_now = np.ones(len(rows), np.float32)
            pods_hist = np.ones(len(rows), np.float32)
            had_pods = [False] * len(rows)
            for i, (_job_id, tps_it, sla_it) in enumerate(rows):
                lim = self.config.policy_for(sla_it.metric).sla_limit
                if lim <= 0.0:
                    lim = self.config.sla_limit
                if lim <= 0.0:
                    limits[i], modes[i] = 1e9, hpa_ops.SLA_DYNAMIC
                else:
                    limits[i], modes[i] = lim, mode_cfg
                # limit interpretation: ABSOLUTE (the deploy convention quotes
                # latency SLAs in ms) unless the operator opts the fleet into
                # relative limits (ML_SLA_LIMIT_RELATIVE); a wire
                # isAbsolute=true still pins that metric absolute under the
                # relative default. The bare wire default (false) must NOT
                # silently turn ML_SLA_LIMIT=250ms into 250*mean.
                absolutes[i] = (sla_it.is_absolute
                                or not self.config.sla_limit_relative)
                # pod counts split at the job's own current-window boundary —
                # the exact region/history split the demand and capacity use
                pc = _pod_count_stats(tps_it.pod_window, tps_it.current.start)
                if pc is not None:
                    pods_now[i], pods_hist[i] = pc
                    had_pods[i] = True

            arrays = [tv, tm, np.asarray(n_hs, np.int32),
                      np.asarray([w.values.shape[0] for w in tps_w], np.int32),
                      sv, sm, limits, modes, absolutes, pods_now, pods_hist]
            row_elems = np.asarray([t.values.shape[0] + s.values.shape[0]
                                    for t, s in zip(tps_w, sla_w)])
            sp.attrs["bytes"] = _nbytes(arrays)

        def hpa_fn(tv_c, tm_c, nh_c, n_c, sv_c, sm_c, lim_c, mode_c, abs_c,
                   pn_c, ph_c):
            # as band_fn: the traffic chunk crosses once, and the judged
            # region, the predictions and sigma stay on the device
            n = tv_c.shape[0]
            tv_d, tm_d = self._put(tv_c, tm_c)
            region, hist_mask = self._call(fc.region_masks, tm_d, nh_c, n_c)
            preds = self._call(
                fc.ses_predictions, tv_d, hist_mask,
                np.full(n, 0.3, np.float32))
            sigma = self._call(
                fc.residual_sigma, tv_d, preds, hist_mask, hist_mask)
            return self._call(
                hpa_ops.hpa_scores,
                tv_d, tm_d, region, preds, sigma, sv_c, sm_c,
                lim_c, mode_c,
                np.full(n, self.config.threshold, np.float32),
                np.full(n, self.config.sla_headroom_safe, np.float32),
                pods_now=pn_c, pods_hist=ph_c, sla_absolute=abs_c,
            )

        launches = self._launch_chunks(hpa_fn, arrays, row_elems=row_elems)
        return (rows, launches, had_pods)

    def _collect_hpa(self, state) -> dict:
        rows, launches, had_pods = state
        res = self._collect_chunks(launches)
        # bulk tolist (see _collect_pairs); int()/float() coercions kept
        # where the kernel dtype is not already the Python target type
        lists = {k: res[k].tolist() for k in (
            "score", "reason", "current_tps", "tps_upper", "tps_lower",
            "sla_current", "sla_limit", "pods_now", "demand_per_pod")}
        out: dict = {}
        for i, (job_id, tps_it, sla_it) in enumerate(rows):
            out[job_id] = {
                "raw_score": float(lists["score"][i]),
                "reason_code": int(lists["reason"][i]),
                "tps_metric": tps_it.metric,
                "sla_metric": sla_it.metric,
                "current_tps": float(lists["current_tps"][i]),
                "upper": float(lists["tps_upper"][i]),
                "lower": float(lists["tps_lower"][i]),
                "sla_current": float(lists["sla_current"][i]),
                "sla_limit": float(lists["sla_limit"][i]),
                "pods_now": float(lists["pods_now"][i]),
                "demand_per_pod": float(lists["demand_per_pod"][i]),
                "has_pod_data": had_pods[i],
            }
        return out

    # ------------------------------------------------------------- verdict
    def _serve_stale(self, doc: J.Document, failure: str, worker: str,
                     now: float, in_postprocess: bool = False) -> str | None:
        """Re-serve a warm job's last fresh verdict during a source outage.

        A job is warm when it was judged healthy on FRESH data at most
        MAX_STALE_S ago. Serving means: mid-window, requeue with the
        staleness age stamped in the reason (no PREPROCESS_FAILED flap);
        past endTime, complete COMPLETED_HEALTH on the last fresh verdict
        instead of flipping COMPLETED_UNKNOWN. Unhealthy verdicts are
        never stale-served — they complete terminally the cycle they are
        seen, so a live job's last verdict is always "healthy so far".
        Returns the applied status, or None when the job is not warm
        (callers fall through to the pre-degraded-mode behavior).
        """
        max_stale = self.config.max_stale_seconds
        at = self._stale_state.get(doc.id)
        if max_stale <= 0 or at is None or now - at > max_stale:
            return None
        age = now - at
        self.stale_verdicts_served_total += 1
        self.exporter.record_counter(
            "foremastbrain:stale_verdicts_served_total", {},
            help="verdicts re-served from warm state during source "
                 "outages (bounded by MAX_STALE_S)")
        reason = (f"stale verdict served (age {age:.0f}s, last judged "
                  f"healthy): {failure}")
        self.flight.record_event(flightrec.EVENT_STALE_SERVE,
                                 job_id=doc.id, age=round(age, 1))
        try:
            end_time = from_rfc3339(doc.end_time)
        except (ValueError, TypeError):
            end_time = (float("inf")
                        if doc.strategy in CONTINUOUS_STRATEGIES else now)
        if doc.strategy not in CONTINUOUS_STRATEGIES and now >= end_time:
            # the watch window closed during the outage: the job watched
            # healthy right up to the blackout, and the last fresh verdict
            # is younger than MAX_STALE_S — complete on it
            if not in_postprocess:
                self.store.advance(doc.id, J.PREPROCESS_COMPLETED,
                                   J.POSTPROCESS_INPROGRESS, worker=worker)
            self._stale_state.pop(doc.id, None)
            self.provenance.record(
                doc.id, prov.PATH_STALE_SERVED, status=J.COMPLETED_HEALTH,
                detail=f"age {age:.0f}s", reason=reason)
            self.store.transition(doc.id, J.COMPLETED_HEALTH, reason=reason,
                                  worker=worker,
                                  processing_content=self._prov_content(doc.id))
            return J.COMPLETED_HEALTH
        self.provenance.record(
            doc.id, prov.PATH_STALE_SERVED, status=J.INITIAL,
            detail=f"age {age:.0f}s", reason=reason)
        self.store.transition(doc.id, J.INITIAL, reason=reason, worker=worker)
        return J.INITIAL

    def _record_scoring_failure(self, job_id: str, now: float):
        """Quarantine bookkeeping for one _isolate per-job retry failure.

        QUARANTINE_AFTER consecutive failures park the job; each parking
        doubles the re-admission backoff (QUARANTINE_BASE_S..MAX). A job
        that was quarantined before re-parks on its FIRST post-probe
        failure — the probe answered the only open question."""
        qa = self.config.quarantine_after
        if qa <= 0:
            return
        q = self._quarantine.setdefault(job_id, [0, 0.0, 0])
        q[0] += 1
        if q[2] > 0 or q[0] >= qa:
            q[2] += 1
            q[0] = 0
            delay = min(QUARANTINE_BASE_S * (2.0 ** (q[2] - 1)),
                        QUARANTINE_MAX_S)
            q[1] = now + delay
            self.jobs_quarantined_total += 1
            self.flight.record_event(flightrec.EVENT_QUARANTINE,
                                     job_id=job_id, delay_s=delay,
                                     times=q[2])
            self.exporter.record_counter(
                "foremastbrain:jobs_quarantined_total", {},
                help="poison-job quarantine parkings (QUARANTINE_AFTER "
                     "consecutive scoring failures)")

    def run_cycle(self, worker: str = "worker-0", now: float | None = None,
                  job_ids=None, partial: bool = False) -> dict:
        """One engine cycle. Returns {job_id: new_status} for observability.

        ``job_ids``/``partial`` are the event-driven scheduler's seam
        (engine/scheduler.py StreamScheduler): a PARTIAL cycle claims
        only the named jobs — the ones whose windows just advanced via
        push ingest — and runs them through the identical pipeline
        rungs, so a partial cycle's verdicts are exactly the ones the
        next full sweep would have produced, just earlier. Partial and
        full cycles share this entry point and must never run
        concurrently (the scheduler serializes them on one thread)."""
        # cycle correlation id: bound into the tracer BEFORE the cycle
        # span opens, so the span's attrs, every cross-thread child span,
        # every log record (TraceContextFilter), and every provenance
        # record of this cycle carry the same grep-able id. Partial
        # cycles mint `-p` ids so a grep separates the two cycle kinds.
        self._cycle_seq += 1
        cycle_id = f"{worker}-{'p' if partial else 'c'}{self._cycle_seq}"
        self.current_cycle_id = cycle_id
        t_cycle0 = time.perf_counter()
        self._cycle_mono0 = time.monotonic()
        self._cycle_fold_mono = 0.0
        # a partial cycle triggered by ONE push adopts that push's W3C
        # context: its engine.cycle span (and every child) continues the
        # push's distributed trace instead of minting its own. Bursts
        # spanning several traces keep their own root — each job's
        # verdict span still closes its own push trace.
        remote_ctx = (self.waterfall.single_context(job_ids)
                      if partial and job_ids else None)
        with tracing.tracer.bind(cycle_id=cycle_id), \
                tracing.tracer.adopt_remote(remote_ctx), \
                tracing.span(tracing.SPAN_ENGINE_CYCLE, worker=worker):
            now = time.time() if now is None else now
            self.provenance.begin_cycle(cycle_id, worker=worker)
            # degraded mode: the whole-cycle deadline budget
            # (CYCLE_DEADLINE_S). Burns down through fetch -> preprocess ->
            # dispatch; once expired, un-preprocessed jobs are shed in
            # reverse priority order and carried to the next cycle.
            cd = self.config.cycle_deadline_seconds
            cycle_dl = Deadline.after(cd) if cd > 0 else None
            # resilience: arm a per-cycle fetch deadline so retry/backoff
            # trains inside a ResilientDataSource can never overrun the
            # cycle budget (every fetch thread shares the one Deadline;
            # plain sources have no set_cycle_deadline and skip this)
            sd = getattr(self.source, "set_cycle_deadline", None)
            budget = self.config.fetch_cycle_deadline_seconds
            fetch_dl = Deadline.after(budget) if budget > 0 else None
            if cycle_dl is not None:
                # the fetch retry train must never outlive the CYCLE budget
                fetch_dl = (cycle_dl if fetch_dl is None
                            else Deadline(min(fetch_dl.at, cycle_dl.at)))
            if sd is not None:
                sd(fetch_dl)
            self.health.begin_cycle()
            try:
                outcomes = self._run_cycle(worker, now, cycle_dl,
                                           job_ids=job_ids, partial=partial)
            finally:
                if sd is not None:
                    sd(None)
            # end_cycle only on SUCCESS: a raising cycle must not refresh
            # the liveness reference, so a crash-looping engine (worker
            # loop swallows and retries) ages into STALLED instead of
            # reporting OK on zero completed verdicts. The per-cycle
            # deltas come straight from the cycle stats _run_cycle just
            # published — ONE computation feeds /status and the health
            # machine, so the two surfaces can never drift.
            stats = self.last_cycle_stages
            self.health.end_cycle(
                shed=stats.get("jobs_shed", 0),
                stale_served=stats.get("stale_verdicts_served", 0),
                watchdog_fires=stats.get("watchdog_fires", 0),
                quarantined=self.quarantined_count(now),
                deadline_overrun=(cycle_dl is not None
                                  and cycle_dl.expired()),
            )
            # cycle-duration distribution (p50/p99 on /metrics — the
            # last-cycle stage gauges alone can't answer tail questions)
            self.exporter.record_histogram(
                "foremastbrain:cycle_seconds", {},
                time.perf_counter() - t_cycle0,
                help="End-to-end engine cycle duration (seconds).")
            return outcomes

    def _job_priority(self, doc: J.Document) -> tuple:
        """Load-shedding sort key: lower scores FIRST.

        New-deployment analyses (rollingUpdate/canary/rollover) lead —
        their verdict gates a live rollout, and they are exempt from
        shedding entirely (_stream_prep's class gate); steady-state
        monitors (continuous/hpa) watch forever and can carry a cycle.
        Within the monitor class, a job shed on recent cycles sorts
        ahead, so a permanently blown budget round-robins the fleet
        instead of starving the tail.
        """
        return (1 if doc.strategy in CONTINUOUS_STRATEGIES else 0,
                -self._shed_streak.get(doc.id, 0))

    def _stream_prep(self, claimed: list, now: float,
                     deadline: Deadline | None = None,
                     pool: dict | None = None):
        """Yield (doc_id, items, failed, fetch_notes) per job, in claim
        order, as the fetch pool completes chunks. `fetch_notes` is the
        tracer's per-job fetch accounting (delta/full/cached counts,
        points, seconds) for the provenance record; shed jobs yield
        `(doc.id, None, _SHED, {})`. `pool` collects the POOL_SPANS
        seconds of those notes, summed per chunk on the pool's own
        threads (thread-seconds: no span is opened there, it would name
        the device's long idle gap after a chunk of the pool), and the
        `width` the cycle chose with the `probe_ratio` it chose it from.

        Per-job fetches overlap on a bounded pool: fetch is network-bound
        in production (and the native parser releases the GIL during its C
        scan), so cycle time tracks store latency, not fleet size. Jobs are
        mapped in CHUNKS (several per worker for tail-balance) — at 10k+
        fleet sizes, per-job task dispatch costs more GIL time than the
        preprocess itself. ex.map preserves submission order, and chunks
        are cut in claim order, so the yielded stream — and with it bucket
        packing and verdict folding — stays deterministic; consuming it
        incrementally is what lets the pipeline dispatch bucket N while
        bucket N+1 is still fetching.

        The pool is as wide as the source's waiting justifies, and
        `fetch_concurrency` is the most it may be. A thread that waits on
        a socket uses no CPU while it waits; a source that never leaves
        the interpreter (an in-process store, ingest-served windows, the
        delta cache's unmoved ranges) gains nothing from threads and pays
        for every hand-over of the interpreter lock, the cycle thread's
        routing and fingerprinting included. So every cycle of a fleet
        large enough to convoy fetches its first jobs alone on the cycle
        thread (POOL_PROBE_*), and `pool_width` sizes the pool from their
        wall-to-CPU ratio (the narrower of two readings, where the first
        says more than 1); at width 1 there is no pool and the chunks run
        on the cycle thread. Nothing is remembered: a store that slows
        down, or a fleet that goes from polled to pushed, is followed
        within one cycle. Other busy Python threads in the process read as
        waiting and widen the pool, which is what it was before the probe.

        `deadline` is the cycle budget (CYCLE_DEADLINE_S): once expired,
        STEADY-STATE jobs (continuous/hpa) not yet fetched yield the
        _SHED marker WITHOUT touching the network and carry over to the
        next cycle. New-deployment analyses are never shed — their
        verdict gates a live rollout, and because chunks run concurrently
        on the fetch pool, a class-based gate is the only one that holds
        under interleaving (a position-based cutoff could shed a canary
        while monitors on other workers complete). A canary-heavy
        overrun therefore shows as `deadline_overrun`, not shedding. The
        first MONITOR-class job of the cycle is additionally exempt — the
        guaranteed-progress floor. It must be the first SHEDDABLE job,
        not claimed[0]: in a mixed fleet the sort puts a (class-exempt)
        canary first, and guaranteeing that one would leave monitors with
        no floor at all — permanently starved whenever deployment churn
        alone burns the budget. The sort puts the longest-shed monitor at
        the head of its class, so the floor round-robins the fleet.
        """
        guaranteed = next(
            (d.id for d in claimed if d.strategy in CONTINUOUS_STRATEGIES),
            None)
        # trace-context handle captured on the cycle thread: every fetch
        # pool worker attaches it, so spans opened during preprocess parent
        # under the cycle trace and dataplane log lines carry cycle_id —
        # the PR 2 thread pool no longer orphans its spans
        ctx = tracing.tracer.context()

        def prep_many(chunk):
            out = []
            sums = dict.fromkeys(_POOL_NOTES, 0.0)
            with tracing.tracer.attach(ctx):
                for doc in chunk:
                    if (deadline is not None and doc.id != guaranteed
                            and doc.strategy in CONTINUOUS_STRATEGIES
                            and deadline.expired()):
                        out.append((doc.id, None, _SHED, {}))
                        continue
                    with tracing.tracer.bind(job_id=doc.id):
                        tracing.tracer.begin_notes()
                        t0 = time.perf_counter()
                        try:
                            items, failed = self._preprocess(doc, now), ""
                        except FetchError as e:
                            items, failed = None, str(e)
                        notes = tracing.tracer.take_notes()
                        notes["prep_thread_seconds"] = (
                            time.perf_counter() - t0)
                        for k in sums:
                            sums[k] += notes.get(k, 0.0)
                        out.append((doc.id, items, failed, notes))
            return out, sums

        def merged(result):
            out, sums = result
            if pool is not None:
                for k, v in sums.items():
                    pool[k] = pool.get(k, 0.0) + v
            return out

        cap = min(max(self.config.fetch_concurrency, 1), len(claimed) or 1)
        step = max(1, -(-len(claimed) // (cap * 8)))
        width, probed = cap, 0
        if cap > 1 and len(claimed) >= cap * POOL_PROBE_MIN_JOBS_PER_THREAD:
            # nothing else runs yet, so this thread's wall seconds less
            # its CPU seconds are what these jobs waited on their store
            results, ratio = [], float("inf")
            for _ in range(POOL_PROBE_READINGS):
                first = probed
                c0, t0 = time.thread_time(), time.perf_counter()
                cpu = wall = 0.0
                while (probed < step and cpu < POOL_PROBE_CPU_S
                       and wall < POOL_PROBE_WALL_S):
                    with tracing.annotate(tracing.SPAN_ENGINE_FETCH):
                        result = prep_many(claimed[probed:probed + 1])
                    results += merged(result)
                    probed += 1
                    cpu = time.thread_time() - c0
                    wall = time.perf_counter() - t0
                if all(items is None for _, items, _, _ in results[first:]):
                    break  # nothing fetched (or no job left): no reading
                width = min(width, pool_width(wall, cpu, cap))
                if cpu >= POOL_PROBE_MIN_CPU_S:
                    ratio = min(ratio, wall / cpu)
                if width == 1:
                    break
            if pool is not None and ratio < float("inf"):
                pool["probe_ratio"] = ratio
            yield from results
        if pool is not None:
            pool["width"] = width
        chunks = [claimed[i:i + step]
                  for i in range(0, len(claimed), step)]
        if probed:
            # the probe's jobs come off the first chunk, so the boundaries
            # do not depend on how far it went
            chunks[0] = chunks[0][probed:]
        if width <= 1:
            # no pool: one chunk after another on this thread, each yielded
            # as it completes, so full rungs still launch between fetches.
            # The annotation names the chunk's fetch in a device trace; it
            # closes before the yield, so the routing stays outside it
            for chunk in chunks:
                with tracing.annotate(tracing.SPAN_ENGINE_FETCH):
                    result = prep_many(chunk)
                yield from merged(result)
            return
        with ThreadPoolExecutor(max_workers=width) as ex:
            for result in ex.map(prep_many, chunks):
                yield from merged(result)

    @staticmethod
    def _book_pieces(prep_sp, pipe, wait: float, busy: float,
                     busy_cpu: float, pool: dict):
        """Name what interleaved per job inside engine.preprocess: the
        seconds (wait, route, route_cpu, memo_fp, triage) and the memo's
        counts, written on the span's attrs with the pool's thread-seconds,
        width and probe ratio, and folded into the per-name stats. `busy`
        is the cycle thread between two results of the stream; what `feed`
        booked under a name of its own comes off, and the rest is route.
        `busy_cpu` is the thread's CPU over the same stream; streamed
        fires and screens come off, the fingerprint's CPU stays in (no
        clock reads it), so `route + memo_fp - route_cpu` is the wait for
        the interpreter lock. (At pool width 1 the preprocess itself runs
        on this thread: its wall seconds are `wait`, and its CPU is in
        `busy_cpu`.) The pool's `fetch_seconds` sum becomes its two
        remainders, `cache` and `items` (tracing.POOL_SPANS), so that the
        pool's parts add up to its `prep` thread-seconds."""
        fetch = pool.pop("fetch_seconds", 0.0)
        pool["cache_thread_seconds"] = fetch - sum(
            pool.get(k, 0.0) for k in _POOL_NOTES[2:])
        pool["items_thread_seconds"] = (
            pool.get("prep_thread_seconds", 0.0) - fetch)
        # streamed screens and fires so far (finish() adds its own)
        screens = pipe.triage.seconds if pipe.triage else 0.0
        named = pipe.stage_seconds["dispatch"] + pipe.memo_seconds + screens
        part = {"wait": wait, "memo_fp": pipe.memo_seconds,
                "triage": screens, "route": busy - named,
                "route_cpu": busy_cpu - pipe.fired_cpu_seconds}
        memo_counts = {"memo_lookups": pipe.memo_lookups,
                       "memo_hits": sum(pipe.memo_hits.values()),
                       "memo_fp_bytes": pipe.memo_fp_bytes,
                       "memo_fp_reused": pipe.memo_fp_reused}
        prep_sp.attrs.update(
            {k + "_s": round(v, 6) for k, v in part.items()},
            **memo_counts,
            **{"pool_" + k: round(v, 6) for k, v in pool.items()})
        tracing.tracer.add_timing(tracing.SPAN_ENGINE_ROUTE, part["route"])
        tracing.tracer.add_timing(tracing.SPAN_ENGINE_ROUTE_CPU,
                                  part["route_cpu"])
        tracing.tracer.add_timing(tracing.SPAN_ENGINE_MEMO_FP,
                                  part["memo_fp"],
                                  count=memo_counts["memo_lookups"])
        for key in tracing.POOL_SPANS:
            tracing.tracer.add_timing(tracing.POOL_SPANS[key],
                                      pool.get(key, 0.0))
        return part, memo_counts

    def _run_cycle(self, worker: str, now: float,
                   cycle_dl: Deadline | None = None, job_ids=None,
                   partial: bool = False) -> dict:
        from .families import newest_sample_ts
        from .pipeline import CyclePipeline

        with tracing.span(tracing.SPAN_ENGINE_CLAIM) as claim_sp:
            claimed = self.store.claim_open_jobs(
                worker,
                limit=self.config.max_claim_per_cycle,
                max_stuck_seconds=self.config.max_stuck_seconds,
                owns_fn=self.shard.owns if self.shard is not None else None,
                only_ids=set(job_ids) if job_ids is not None else None,
            )
        outcomes: dict[str, str] = {}
        if self._quarantine:
            # poison-job quarantine gate: parked jobs requeue untouched —
            # not one fetch, not one _isolate retry — until their
            # re-admission time; everyone else proceeds normally
            admitted = []
            for doc in claimed:
                q = self._quarantine.get(doc.id)
                if q is not None and now < q[1]:
                    self.provenance.record(
                        doc.id, prov.PATH_QUARANTINED, status=J.INITIAL,
                        detail=(f"re-admission in {q[1] - now:.0f}s, "
                                f"parked {q[2]}x"))
                    self.store.transition(
                        doc.id, J.INITIAL, worker=worker,
                        reason=(f"quarantined: scoring poisoned; "
                                f"re-admission in {q[1] - now:.0f}s"))
                    outcomes[doc.id] = J.INITIAL
                else:
                    admitted.append(doc)
            claimed = admitted
        # priority order (stable, so claim order breaks ties): deployment
        # canaries score first; steady-state monitors shed first when the
        # cycle deadline burns down
        if cycle_dl is not None:
            claimed.sort(key=self._job_priority)
        states: dict[str, _JobState] = {}
        self._lstm_trained_this_cycle = 0
        self._lstm_budget_skipped_ids = set()
        self._lstm_memo_jobs = set()
        launches0 = self.device_launches
        mega_l0 = self.megabatch_launches_total
        mega_r0 = self.megabatch_real_rows_total
        mega_p0 = self.megabatch_pad_rows_total
        rescore_skips0 = self.lstm_rescore_skips
        h2d0, d2h0 = self.h2d_bytes_total, self.d2h_bytes_total
        real0, total0 = self.pack_real_elems_total, self.pack_total_elems_total
        sort0 = self.pair_sort_elems_total
        pair_real0 = self.pair_real_elems_total
        split0 = self.pair_split_rows_total
        self._cycle_pair_T_max = 0
        parts0 = self.period_partitions_total
        self._cycle_hw_state_bytes = 0
        self._cycle_st_columns = self._cycle_st_solves = 0
        shed_cycle0 = self.jobs_shed_total
        stale_cycle0 = self.stale_verdicts_served_total
        wd_cycle0 = self.watchdog_fires_total
        pipe = CyclePipeline(self)
        stages = {"preprocess": 0.0, "dispatch": 0.0, "collect": 0.0,
                  "fold": 0.0}
        # the cycle thread between two results of the fetch stream, in
        # wall seconds, its CPU seconds over the whole stream (waiting on
        # the pool burns none; one clock pair a cycle, because
        # `time.thread_time` is a system call), and the pool's notes
        pool: dict = {}
        busy = 0.0
        appends = 0  # delta fetches the append rule served (their notes)
        with tracing.span(tracing.SPAN_ENGINE_PREPROCESS,
                          jobs=len(claimed)) as prep_sp:
            for doc in claimed:
                states[doc.id] = _JobState(doc)
            c_stream = time.thread_time()
            t_wait = time.perf_counter()
            for doc_id, items, failed, fetch_notes in self._stream_prep(
                    claimed, now, cycle_dl, pool):
                t_got = time.perf_counter()
                stages["preprocess"] += t_got - t_wait
                if fetch_notes:
                    states[doc_id].fetch = fetch_notes
                    appends += fetch_notes.get("fetch_append", 0)
                if failed:
                    states[doc_id].failed = failed
                else:
                    # detection-latency stamps: the job was freshly
                    # ingested this cycle, and its window last advanced
                    # at the newest judged sample's own timestamp
                    # (engine/slo.py; _observe_latency)
                    states[doc_id].ingest_at = time.monotonic()
                    states[doc_id].newest_ts = newest_sample_ts(items)
                    # streamed dispatch: full bucket rungs launch here,
                    # overlapping the remaining fetches (the pipeline
                    # accounts its own dispatch time)
                    pipe.feed(items, strategy=states[doc_id].doc.strategy)
                t_wait = time.perf_counter()
                busy += t_wait - t_got
            part, memo_counts = self._book_pieces(
                prep_sp, pipe, stages["preprocess"], busy,
                time.thread_time() - c_stream, pool)
            prep_sp.attrs["splice_appends"] = int(appends)
        with tracing.span(tracing.SPAN_ENGINE_ADVANCE) as advance_sp:
            shed_ids: list = []
            for doc_id, st in states.items():
                if not st.failed:
                    self._shed_streak.pop(doc_id, None)
                    self.store.advance(doc_id, J.PREPROCESS_COMPLETED,
                                       J.POSTPROCESS_INPROGRESS, worker=worker)
                    continue
                doc = st.doc
                if st.failed == _SHED:
                    # load shedding (CYCLE_DEADLINE_S): the budget burned down
                    # before this job's fetch started. Carry it to the next
                    # cycle — the shed streak promotes it within its class, so
                    # it completes with a verdict byte-identical to the one it
                    # would have produced unshed (tests/test_degraded.py).
                    self.jobs_shed_total += 1
                    self._shed_streak[doc_id] = self._shed_streak.get(doc_id, 0) + 1
                    shed_ids.append(doc_id)
                    self.provenance.record(
                        doc_id, prov.PATH_SHED_CARRYOVER, status=J.INITIAL,
                        detail=f"streak {self._shed_streak[doc_id]}")
                    self.exporter.record_counter(
                        "foremastbrain:jobs_shed_total", {},
                        help="jobs shed by the cycle deadline budget and "
                             "carried to the next cycle")
                    self.store.transition(
                        doc_id, J.INITIAL, worker=worker,
                        reason="shed: cycle deadline budget exhausted; "
                               "carried over")
                    outcomes[doc_id] = J.INITIAL
                    continue
                # real fetch failure (retries exhausted / breaker open /
                # garbage body): a warm job re-serves its last fresh verdict
                # instead of flapping (stale-verdict serving, MAX_STALE_S)
                served = self._serve_stale(doc, st.failed, worker, now)
                if served is not None:
                    outcomes[doc_id] = served
                elif doc.strategy in CONTINUOUS_STRATEGIES:
                    # perpetual jobs survive transient fetch errors: requeue
                    # instead of dying terminally on one network blip
                    self.provenance.record(
                        doc_id, prov.PATH_FETCH_RETRY, status=J.INITIAL,
                        reason=st.failed, fetch=st.fetch)
                    self.store.transition(
                        doc_id, J.INITIAL, reason=f"fetch retry: {st.failed}",
                        worker=worker,
                    )
                    outcomes[doc_id] = J.INITIAL
                else:
                    self.provenance.record(
                        doc_id, prov.PATH_NO_DATA, status=J.PREPROCESS_FAILED,
                        reason=st.failed, fetch=st.fetch)
                    self.store.transition(
                        doc_id, J.PREPROCESS_FAILED, reason=st.failed,
                        worker=worker,
                        processing_content=self._prov_content(doc_id))
                    outcomes[doc_id] = J.PREPROCESS_FAILED
            if shed_ids:
                self.flight.record_event(flightrec.EVENT_SHED,
                                         count=len(shed_ids),
                                         jobs=shed_ids[:16])

        live = {k: v for k, v in states.items() if not v.failed}
        table = pipe.fams
        with tracing.span(tracing.SPAN_ENGINE_SCORE, **{
                f.count_attr: len(pipe.items[f.name])
                for f in table}) as score_sp:
            results, scoring_failed = pipe.finish()
            for k, v in pipe.stage_seconds.items():
                stages[k] += v
            fam_seconds = pipe.family_seconds
            # the bench's per-family decomposition reads these stats
            # (engine.score.<fam>), span or not
            for f in pipe.streamed:
                tracing.tracer.add_timing(
                    tracing.SCORE_SPANS[f.name], fam_seconds.get(f.name, 0.0))
            self.lstm_budget_skips += len(self._lstm_budget_skipped_ids)
            # every launch of the cycle is collected by now, the streamed
            # ones too: what crossed to and from the chip, and the pack
            counters = {
                "h2d_bytes": self.h2d_bytes_total - h2d0,
                "d2h_bytes": self.d2h_bytes_total - d2h0,
                "pack_real_elems": self.pack_real_elems_total - real0,
                "pack_total_elems": self.pack_total_elems_total - total0,
                "pair_sort_elems": self.pair_sort_elems_total - sort0,
                "pair_real_elems": self.pair_real_elems_total - pair_real0,
                "pair_split_rows": self.pair_split_rows_total - split0,
                "pair_T_max": self._cycle_pair_T_max,
                "period_partitions": self.period_partitions_total - parts0,
                "hw_candidates": (fc.HW_CANDIDATES
                                  if self._cycle_hw_state_bytes else 0),
                "hw_state_bytes": self._cycle_hw_state_bytes,
                "st_columns": self._cycle_st_columns,
                "st_solves": self._cycle_st_solves}
            score_sp.attrs.update(counters)

        with tracing.span(tracing.SPAN_ENGINE_FOLD):
            t_fold = time.perf_counter()
            # waterfall boundary: everything before this instant is the
            # `score` stage, everything after is `fold` (_observe_latency)
            self._cycle_fold_mono = time.monotonic()
            # -- provenance collection (zero work when recording is off) --
            # per-family score-vs-threshold entries and judged-result counts
            # per job; counts vs the pipeline's memo-hit map classify each
            # verdict as fresh-scored or memo-served.
            prov_on = self.provenance.enabled
            fam_entries: dict[str, list] = {}
            judged_items: dict[str, int] = {}
            memo_job_hits = pipe.memo_job_hits
            triage_gate = pipe.triage
            triage_job_hits = triage_gate.job_hits if triage_gate is not None \
                else {}
            # per-result screen statistics for cleared rows, keyed by the
            # family result key — folded into the provenance family entries so
            # `explain` shows the screen's numbers vs its thresholds
            triage_stats = triage_gate.stats if triage_gate is not None else {}

            # a partial (event-driven) cycle's fresh scores carry their own
            # path tag: `explain` answers "did this verdict wait for the
            # tick, or did the push wake it?" without cycle-id archaeology
            scored_path = prov.PATH_STREAM_SCORED if partial \
                else prov.PATH_SCORED

            def _vpath(job_id: str) -> tuple:
                """(path, detail) for a judged job: memo-hit when EVERY result
                came from the fingerprint memo, triaged when the tier-0
                screen cleared the rest, scored otherwise."""
                n = judged_items.get(job_id, 0)
                m = memo_job_hits.get(job_id, 0) + (
                    1 if job_id in self._lstm_memo_jobs else 0)
                t = triage_job_hits.get(job_id, 0)
                if n and m >= n:
                    return prov.PATH_MEMO_HIT, f"{m}/{n} results from memo"
                if n and t and m + t >= n:
                    detail = f"{t}/{n} screened clear"
                    if m:
                        detail += f", {m} memo"
                    return prov.PATH_TRIAGED, detail
                if t:
                    return (scored_path,
                            f"{n - m - t}/{n} fresh, {m} memo, {t} triaged")
                if m:
                    return scored_path, f"{n - m}/{n} fresh, {m} memo"
                return scored_path, ""

            # fold per-metric results into per-job verdicts, in the table's
            # order (it shows in a job's reason)
            record_bounds = self.exporter.record_bounds
            for fam in table:
                res = results[fam.name]
                if fam.provenance is None:
                    # hpa results fold inside _finish_hpa; count them here
                    # so the memo-vs-fresh classification sees them
                    if prov_on:
                        for job_id in res:
                            if job_id in live:
                                judged_items[job_id] = (
                                    judged_items.get(job_id, 0) + 1)
                    continue
                key_of, bounds_of = fam.key, fam.bounds
                entry_of, cause_of = fam.provenance, fam.unhealthy
                for it in pipe.items[fam.name]:
                    key = key_of(it)
                    r = res.get(key)
                    if r is None:
                        continue
                    st = live[it.job_id]
                    st.judged_any = True
                    if prov_on:
                        judged_items[it.job_id] = (
                            judged_items.get(it.job_id, 0) + 1)
                        entry = entry_of(self, it, r)
                        entry.update(triage_stats.get(key, {}))
                        fam_entries.setdefault(it.job_id, []).append(entry)
                    if bounds_of is not None:
                        for metric, upper, lower in bounds_of(it, r):
                            record_bounds(
                                st.doc.app_name, st.doc.namespace, metric,
                                upper, lower, float(r["unhealthy"]))
                    if r["unhealthy"]:
                        st.unhealthy.append(cause_of(self, it, r))

            for job_id, st in live.items():
                doc = st.doc
                if job_id in scoring_failed:
                    reason = f"scoring failed: {scoring_failed[job_id]}"
                    if scoring_failed[job_id].startswith("WatchdogTimeout"):
                        # watchdog fires are INFRASTRUCTURE evidence (a hung
                        # or wedged device), not job poison: every strategy
                        # requeues for the next cycle — quarantining the job
                        # (or aborting a canary) would misattribute the
                        # device's fault to the workload and blank coverage
                        # long after the device recovers
                        self.provenance.record(
                            job_id, prov.PATH_WATCHDOG_FAILOVER,
                            status=J.INITIAL, reason=reason, fetch=st.fetch)
                        self.store.transition(
                            job_id, J.INITIAL, reason=reason, worker=worker)
                        outcomes[job_id] = J.INITIAL
                        continue
                    if doc.strategy in CONTINUOUS_STRATEGIES:
                        # perpetual jobs retry next cycle (data may heal) —
                        # but a job that keeps poisoning its per-job retry is
                        # parked (quarantine) instead of re-burning the
                        # _isolate fallback every cycle forever
                        self._record_scoring_failure(job_id, now)
                        self.provenance.record(
                            job_id, prov.PATH_BLAST_RADIUS, status=J.INITIAL,
                            reason=reason, fetch=st.fetch)
                        self.store.transition(job_id, J.INITIAL, reason=reason, worker=worker)
                        outcomes[job_id] = J.INITIAL
                    else:
                        self._quarantine.pop(job_id, None)  # terminal: moot
                        self.provenance.record(
                            job_id, prov.PATH_BLAST_RADIUS, status=J.ABORT,
                            reason=reason, fetch=st.fetch)
                        self.store.transition(
                            job_id, J.ABORT, reason=reason, worker=worker,
                            processing_content=self._prov_content(job_id))
                        outcomes[job_id] = J.ABORT
                    continue
                # scored cleanly: full quarantine reset (consecutive = 0)
                self._quarantine.pop(job_id, None)
                if doc.strategy == STRATEGY_HPA:
                    res = results["hpa"].get(job_id)
                    outcomes[job_id] = self._finish_hpa(
                        st, res, worker, now,
                        path_info=_vpath(job_id) if prov_on else None)
                    if res is not None:
                        # a scored hpa cycle IS the detection; annotates the
                        # record _finish_hpa just wrote
                        self._observe_latency(st, now)
                    continue
                try:
                    end_time = from_rfc3339(doc.end_time)
                except (ValueError, TypeError):
                    # continuous jobs carry END_TIME placeholders: never expire
                    end_time = float("inf") if doc.strategy in CONTINUOUS_STRATEGIES else now
                if st.unhealthy:
                    metrics = ", ".join(dict.fromkeys(m for m, _, _ in st.unhealthy))
                    reason = "; ".join(f"{m}: {d}" for m, d, _ in st.unhealthy)
                    anomaly = {m: pairs for m, _, pairs in st.unhealthy if pairs}
                    self._stale_state.pop(job_id, None)
                    reason = f"anomaly detected on {metrics} :: {reason}"
                    if prov_on:
                        path, detail = _vpath(job_id)
                        self.provenance.record(  # lint: disable=trace-registry -- path from _vpath (registered constants only)
                            job_id, path, status=J.COMPLETED_UNHEALTH,
                            detail=detail, reason=reason,
                            families=fam_entries.get(job_id),
                            fetch=st.fetch)
                    # observed between record and transition: the latency
                    # annotation must land before the summary is attached
                    self._observe_latency(st, now)
                    self.store.transition(
                        job_id, J.COMPLETED_UNHEALTH,
                        reason=reason,
                        anomaly=anomaly, worker=worker,
                        processing_content=self._prov_content(job_id),
                    )
                    outcomes[job_id] = J.COMPLETED_UNHEALTH
                elif now < end_time:
                    # healthy so far; keep watching until endTime (fail-fast
                    # rule); continuous jobs loop here forever. A judged cycle
                    # refreshes the job's warm stale-serving state.
                    if st.judged_any:
                        self._stale_state[job_id] = now
                    if prov_on and st.judged_any:
                        path, detail = _vpath(job_id)
                        self.provenance.record(  # lint: disable=trace-registry -- path from _vpath (registered constants only)
                            job_id, path, status=J.INITIAL, detail=detail,
                            families=fam_entries.get(job_id), fetch=st.fetch)
                    if st.judged_any:
                        # "healthy so far" is a verdict too: the monitor fleet's
                        # steady-state latency is exactly this path
                        self._observe_latency(st, now)
                    self.store.requeue(job_id, worker=worker)
                    outcomes[job_id] = J.INITIAL
                elif st.judged_any:
                    self._stale_state.pop(job_id, None)
                    if prov_on:
                        path, detail = _vpath(job_id)
                        self.provenance.record(  # lint: disable=trace-registry -- path from _vpath (registered constants only)
                            job_id, path, status=J.COMPLETED_HEALTH,
                            detail=detail, families=fam_entries.get(job_id),
                            fetch=st.fetch)
                    self._observe_latency(st, now)
                    self.store.transition(
                        job_id, J.COMPLETED_HEALTH, worker=worker,
                        processing_content=self._prov_content(job_id))
                    outcomes[job_id] = J.COMPLETED_HEALTH
                else:
                    # no judgeable data at endTime: a warm job re-serves its
                    # last fresh verdict (zero UNKNOWN flips during a bounded
                    # source blackout); cold jobs keep the reference semantics
                    served = self._serve_stale(
                        doc, "insufficient data points to judge", worker, now,
                        in_postprocess=True)
                    if served is not None:
                        outcomes[job_id] = served
                        continue
                    self.provenance.record(
                        job_id, prov.PATH_NO_DATA, status=J.COMPLETED_UNKNOWN,
                        reason="insufficient data points to judge",
                        fetch=st.fetch)
                    self.store.transition(
                        job_id, J.COMPLETED_UNKNOWN,
                        reason="insufficient data points to judge", worker=worker,
                        processing_content=self._prov_content(job_id),
                    )
                    outcomes[job_id] = J.COMPLETED_UNKNOWN
            stages["fold"] = time.perf_counter() - t_fold
        with tracing.span(tracing.SPAN_ENGINE_PUBLISH) as publish_sp:
            # per-stage observability: tracer stats (foremast_trace_* on
            # /metrics, bench decomposition) + foremastbrain gauges + /status
            for name, secs in stages.items():
                tracing.tracer.add_timing(tracing.STAGE_SPANS[name], secs)
            self.exporter.record_cycle_stages(stages, fam_seconds)
            self.exporter.record_gauge(
                "foremastbrain:fetch_pool_width", {}, pool["width"],
                help="Fetch pool threads the last cycle used: sized from "
                     "its probe, at most FETCH_CONCURRENCY (1 = no pool).")
            fp_windows = pipe.memo_fp_hashed + pipe.memo_fp_reused
            self.exporter.record_gauge(
                "foremastbrain:memo_fp_reuse_share", {},
                round(pipe.memo_fp_reused / fp_windows, 6)
                if fp_windows else 0.0,
                help="Share of the windows the last cycle's score memo "
                     "fingerprinted whose digest the Window already held "
                     "(0: none fingerprinted).")
            self.exporter.record_gauge(
                "foremastbrain:pair_sort_fill_share", {},
                round(counters["pair_real_elems"]
                      / counters["pair_sort_elems"], 6)
                if counters["pair_sort_elems"] else 0.0,
                help="Share of the elements the last cycle's pair launches "
                     "sorted that were valid samples of real rows (0: no "
                     "pair launch).")
            self.exporter.record_gauge(
                "foremastbrain:pair_split_rows", {},
                counters["pair_split_rows"],
                help="Pair rows the last cycle launched with the current "
                     "window packed at a narrower bucket than its "
                     "baseline.")
            self.exporter.record_gauge(
                "foremastbrain:period_partitions", {},
                counters["period_partitions"],
                help="Partitions by detected seasonal period that the last "
                     "cycle's band launches split into (0: no detection).")
            self.exporter.record_gauge(
                "foremastbrain:hw_state_bytes", {},
                counters["hw_state_bytes"],
                help="Seasonal state the largest Holt-Winters fit of the "
                     "last cycle held on the device, bytes (0: none ran).")
            self.exporter.record_gauge(
                "foremastbrain:st_columns", {}, counters["st_columns"],
                help="Columns of the last cycle's seasonal-trend (Prophet) "
                     "fits: 2 + ST_CHANGEPOINTS + 2 x ST_ORDER (0: none "
                     "ran).")
            triage_cycle = None
            if triage_gate is not None and triage_gate.active:
                tg = triage_gate
                tracing.tracer.add_timing(tracing.SPAN_ENGINE_TRIAGE, tg.seconds)
                screened = sum(tg.screened.values())
                cleared = sum(tg.cleared.values())
                escalated = sum(tg.escalated.values())
                for fam in sorted(set(tg.screened) | set(tg.cleared)
                                  | set(tg.escalated)):
                    self.triage_screened_total[fam] = (
                        self.triage_screened_total.get(fam, 0)
                        + tg.screened.get(fam, 0))
                    self.triage_cleared_total[fam] = (
                        self.triage_cleared_total.get(fam, 0)
                        + tg.cleared.get(fam, 0))
                    self.triage_escalated_total[fam] = (
                        self.triage_escalated_total.get(fam, 0)
                        + tg.escalated.get(fam, 0))
                    self.exporter.record_triage(
                        fam, tg.screened.get(fam, 0), tg.cleared.get(fam, 0),
                        tg.escalated.get(fam, 0))
                self.triage_launches_total += tg.launches
                # recorded even when this cycle screened 0 rows (everything
                # memo-hit): the "(last cycle)" gauge must not go stale at the
                # previous cycle's ratio while triage_seconds keeps updating
                self.exporter.record_gauge(
                    "foremastbrain:triage_escalation_ratio", {},
                    round(escalated / screened, 6) if screened else 0.0,
                    help="Fraction of screened rows escalated to the "
                         "full scorers (last cycle).")
                self.exporter.record_gauge(
                    "foremastbrain:triage_seconds", {},
                    round(tg.seconds, 6),
                    help="Tier-0 triage screen stage seconds (last cycle).")
                triage_cycle = {
                    "screened": screened,
                    "cleared": cleared,
                    "escalated": escalated,
                    "escalation_ratio": (round(escalated / screened, 6)
                                         if screened else 0.0),
                    "launches": tg.launches,
                    "seconds": round(tg.seconds, 6),
                }
            mega_cycle = None
            if self.config.megabatch:
                real = self.megabatch_real_rows_total - mega_r0
                padded = self.megabatch_pad_rows_total - mega_p0
                mega_launches = self.megabatch_launches_total - mega_l0
                waste = round(padded / real, 6) if real else 0.0
                mega_cycle = {
                    "launches": mega_launches,
                    "real_rows": real,
                    "padded_rows": padded,
                    # the packing-efficiency signal: padding rows added per
                    # real row this cycle (0 = every launch landed exactly
                    # on its padding class)
                    "padding_waste_ratio": waste,
                }
                self.exporter.record_gauge(
                    "foremastbrain:megabatch_padding_waste_ratio", {}, waste,
                    help="Mega-batch padding rows per real row (last cycle).")
                if mega_launches:
                    self.exporter.record_counter(
                        "foremastbrain:megabatch_launches_total", {},
                        inc=mega_launches,
                        help="device launches through the single-dispatch "
                             "mega-batch path (MEGABATCH)")
                    self.exporter.record_counter(
                        "foremastbrain:megabatch_real_rows_total", {},
                        inc=real,
                        help="real rows carried by mega-batch launches")
                    self.exporter.record_counter(
                        "foremastbrain:megabatch_padded_rows_total", {},
                        inc=padded,
                        help="padding rows added to reach mega padding "
                             "classes (waste = padded/real)")
            self.provenance.finish_cycle(
                stage_seconds=stages,
                device_launches=self.device_launches - launches0,
                jobs=len(claimed))
            self.last_cycle_stages = stats = {
                "cycle_id": self.current_cycle_id,
                "jobs": len(claimed),
                "partial": partial,
                "stage_seconds": {k: round(v, 6) for k, v in stages.items()},
                "family_score_seconds": {
                    k: round(v, 6) for k, v in fam_seconds.items()},
                # steady-state memo observability: launches actually fired
                # this cycle and verdicts served straight from fingerprints
                "device_launches": self.device_launches - launches0,
                # per-family launch counts (pipelined cycles): the dispatch-
                # collapse observability the mega-batch A/B reads — but
                # recorded for the rung path too, so the two are comparable
                "family_launches": dict(pipe.family_launches),
                "score_memo_hits": dict(pipe.memo_hits),
                # tier-0 triage: this cycle's screened/cleared/escalated rows,
                # escalation ratio, fused screen launches, and stage seconds
                # (None when the gate is off or inactive)
                "triage": triage_cycle,
                # single-dispatch mega-batching: launches / real vs padded
                # rows / per-family launch counts (None when MEGABATCH=0)
                "megabatch": mega_cycle,
                "lstm_rescore_skips": self.lstm_rescore_skips - rescore_skips0,
                # threads the fetch stream used (1: the cycle thread alone)
                "fetch_pool_width": pool["width"],
                # degraded-mode signals (cumulative totals live on /metrics;
                # these are this cycle's contribution + the live park count)
                "jobs_shed": self.jobs_shed_total - shed_cycle0,
                "stale_verdicts_served":
                self.stale_verdicts_served_total - stale_cycle0,
                "watchdog_fires": self.watchdog_fires_total - wd_cycle0,
                "quarantined_jobs": self.quarantined_count(now),
            }
            self._prune_degraded_state(outcomes, orphan_sweep=not partial)
            self.store.put_state("breath", self.breath.export())
            self.store.flush()
        # the whole cycle by name, in cycle order. `wait`, `dispatch`,
        # `collect` and `fold` are the four stage counters; `uncovered` is
        # what is left of the cycle's wall time, so the seconds sum to it
        seconds = {
            "claim": claim_sp.duration, "wait": part["wait"],
            "route": part["route"], "memo_fp": part["memo_fp"],
            "triage": triage_gate.seconds if triage_gate is not None else 0.0,
            "advance": advance_sp.duration, "dispatch": stages["dispatch"],
            "collect": stages["collect"], "fold": stages["fold"],
            "publish": publish_sp.duration}
        seconds["uncovered"] = (time.monotonic() - self._cycle_mono0
                                - sum(seconds.values()))
        self.last_cycle_stages = {**stats, "partition": {
            "seconds": {k: round(v, 6) for k, v in seconds.items()},
            "route_cpu_seconds": round(part["route_cpu"], 6),
            # summed over the fetch pool's threads, not wall seconds;
            # and the pool's `width` with the `probe_ratio` behind it
            "pool": {k: round(v, 6) for k, v in pool.items()},
            "counters": {**counters, **memo_counts}}}
        return outcomes

    def _prune_degraded_state(self, outcomes: dict,
                              orphan_sweep: bool = True):
        """Drop per-job degraded-mode state for jobs that can never come
        back: terminal outcomes this cycle, plus jobs deleted out from
        under the analyzer (store gc, unwatch) — without the sweep the
        maps grow one orphan per churned canary id for the life of the
        process. O(map sizes) per cycle; the maps hold open jobs only
        once this runs. Partial cycles skip the orphan sweep (they would
        re-scan fleet-sized maps per push burst); the next full sweep
        covers it."""
        for jid, status in outcomes.items():
            if status in J.TERMINAL_STATUSES:
                self._stale_state.pop(jid, None)
                self._quarantine.pop(jid, None)
                self._shed_streak.pop(jid, None)
                self._slo_seen.pop(jid, None)
        if not orphan_sweep:
            return
        for table in (self._stale_state, self._quarantine,
                      self._shed_streak, self._slo_seen):
            for jid in [j for j in table
                        if j not in outcomes and self.store.get(j) is None]:
                table.pop(jid, None)

    def _finish_hpa(self, st: _JobState, res, worker: str, now: float,
                    path_info: tuple | None = None) -> str:
        doc = st.doc
        if res is None:
            self.provenance.record(
                doc.id, prov.PATH_NO_DATA, status=J.INITIAL,
                detail="no scoreable hpa window", fetch=st.fetch)
            self.store.requeue(doc.id, worker=worker)
            return J.INITIAL
        self._stale_state[doc.id] = now  # scored on fresh data this cycle
        gated = self.breath.apply(doc.id, res["raw_score"], now=now)
        reason_names = {0: "predicted trend", 1: "anomaly trend",
                        2: "SLA violation", 3: "SLA headroom"}
        reason = (
            f"hpa score {gated:.1f} (raw {res['raw_score']:.1f}) via "
            f"{reason_names.get(res['reason_code'], '?')} on {res['tps_metric']}"
        )
        if self.provenance.enabled:
            path, detail = path_info if path_info is not None \
                else (prov.PATH_SCORED, "")
            self.provenance.record(  # lint: disable=trace-registry -- path from _vpath (registered constants only)
                doc.id, path, status=J.INITIAL, detail=detail,
                reason=reason, fetch=st.fetch,
                families=[{
                    "family": "hpa", "metric": res["tps_metric"],
                    "raw_score": round(float(res["raw_score"]), 2),
                    "gated_score": round(float(gated), 2),
                    "sla_metric": res["sla_metric"],
                    "sla_current": round(float(res["sla_current"]), 4),
                    "sla_limit": round(float(res["sla_limit"]), 4),
                }])
        if res.get("has_pod_data"):
            # per-pod normalization context rides the FREE-FORM reason;
            # details stay strictly {current, upper, lower} band entries —
            # letter templating and wire consumers (models.go:194-209)
            # format every detail as a metric-vs-band sentence, which a
            # replicas-vs-demand tuple would turn into nonsense
            reason += (
                f" [per-pod: {res['pods_now']:.1f} pods, "
                f"demand/pod {res['demand_per_pod']:.1f}]"
            )
        self.store.add_hpalog(
            J.HpaLog(
                job_id=doc.id,
                hpascore=gated,
                reason=reason,
                details=[
                    {
                        "metricType": res["tps_metric"],
                        "current": res["current_tps"],
                        "upper": res["upper"],
                        "lower": res["lower"],
                    },
                    {
                        "metricType": res["sla_metric"],
                        "current": res["sla_current"],
                        "upper": res["sla_limit"],
                        "lower": 0.0,
                    },
                ],
                timestamp=now,
            )
        )
        self.exporter.record_hpa_score(doc.app_name, doc.namespace, gated)
        self.store.requeue(doc.id, worker=worker)
        return J.INITIAL
