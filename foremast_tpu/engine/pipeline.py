"""Bucket-granular scoring pipeline: stream, dispatch, collect.

The pre-pipeline cycle was a chain of full barriers — every job fetched
and packed before ANY scoring started, the five model families scored
strictly sequentially, and each chunk launch blocked on materialization
before the next chunk was even packed. This module turns that chain into
a pipeline at three levels:

  1. **streaming preprocess -> dispatch** — `Analyzer._run_cycle` feeds
     each job's preprocessed items into `CyclePipeline` the moment its
     fetch-pool chunk completes. Items route into per-family /
     per-T-bucket accumulators, and a device program launches as soon as
     an accumulator fills a full batch rung (partials flush at stream
     end), so device execution of bucket N overlaps the fetch+pack of
     bucket N+1.
  2. **async dispatch** — launches go through the analyzer's
     `_launch_*` halves, which return JAX async-dispatch device values;
     nothing blocks until the final collect phase materializes them, so
     the four batch families interleave freely on the device queue.
  3. **persistent compile cache + prewarm** — `enable_compile_cache`
     keeps XLA's persistent compilation cache where
     JAX_COMPILATION_CACHE_DIR says (a source checkout defaults to its
     own `.jax_cache/`) so a restarted process skips the first-cycle
     compile storm, and `prewarm` compiles the standard (family x rung x
     T-bucket) grid up front (CLI: `foremast-tpu prewarm`; runtime:
     PREWARM_ON_START).

Two contracts are preserved exactly:

  * **deterministic folding** — accumulators fill in claim order, fire at
    the same chunk boundaries the barriered `_score_chunks` would cut
    (full rungs mid-stream, rung-padded partials at flush), and results
    are keyed dicts folded in claim order, so verdicts are byte-identical
    to the sequential path regardless of device completion order.
  * **`_isolate` blast radius** — a launch- or collect-time failure
    retries that group per JOB through the family's synchronous scorer;
    only the offending jobs report errors, everyone else's results stand.
"""
from __future__ import annotations

import logging
import os
import time
from contextlib import contextmanager

from ..utils import knobs, tracing

log = logging.getLogger("foremast_tpu.engine.pipeline")

__all__ = ["CyclePipeline", "CompileCounter", "compile_cache_dir",
           "enable_compile_cache", "device_info", "prewarm",
           "STANDARD_RUNGS", "STANDARD_T_BUCKETS"]


class CyclePipeline:
    """One engine cycle's streaming dispatch state. Not thread-safe by
    design: `feed` is called from the single consumer of the (ordered)
    preprocess stream, which is what keeps launches deterministic."""

    FAMILIES = ("pair", "band", "bivariate", "hpa")

    def __init__(self, analyzer):
        self.an = analyzer
        # fire threshold: an accumulator launches the moment it holds a
        # full batch rung, so device execution overlaps the remaining
        # fetches. Snapped to the rung ladder (and capped at the chunk
        # size) so streamed launches hit the same compiled programs as the
        # flush; scorers are row-wise, so launch boundaries cannot change
        # verdicts (the determinism test pins pipeline == barriered).
        cap = max(16, analyzer.config.score_batch)
        fire = min(max(analyzer.config.pipeline_fire_rows, 16), cap)
        self.cap = analyzer._bucket_rows(fire)
        # single-dispatch mega-batching: accumulators hold the WHOLE
        # cycle's rows and flush as one padded launch per (family, T) at
        # finish — trading the mid-stream fetch/score overlap for launch
        # count, which is the winning trade once dispatch overhead
        # dominates (docs/performance.md §6). The fire threshold is the
        # PER-T memory-aware _mega_cap, not the global row ceiling:
        # _fire packs its whole bucket into (n, T) host arrays before
        # _launch_chunks re-chunks, so a T-blind cap would let a
        # long-window bucket materialize multi-GB packed arrays that the
        # launch-time cap then bounds too late. Firing at _mega_cap(T)
        # partitions rows exactly as the launch-time re-chunk would
        # (chunks of C + padded remainder), so launch counts and
        # verdicts are unchanged — only pack-time peak memory moves.
        self._mega = bool(analyzer.config.megabatch)
        self._mega_caps: dict = {}  # T -> analyzer._mega_cap(T)
        self.acc: dict = {f: {} for f in self.FAMILIES}  # family -> T -> []
        self.pending: list = []  # (family, entries, launch_state)
        self.failed: list = []   # (family, entries) awaiting per-job retry
        self.multis: list = []   # lstm items score at collect (train+cache)
        self.stage_seconds = {"dispatch": 0.0, "collect": 0.0}
        self.family_seconds: dict = {}
        self.launches = 0
        # device launches per family this cycle (from the analyzer's
        # device_launches delta around each _fire, so chunk-level splits
        # and the band family's period-detection launches count) — the
        # mega-batch "one launch per family per cycle" claim reads this
        self.family_launches: dict = {}
        # fingerprint score memo (SCORE_MEMO): unchanged rows resolve
        # straight from the analyzer's cross-cycle memo and never enter an
        # accumulator — buckets hold only changed rows, so steady-state
        # cycles fire fewer, smaller programs (and a no-change cycle fires
        # none at all). Routing/bucketing is unchanged for the rows that
        # do score, so launch boundaries — and verdicts — stay identical
        # to the memo-off path.
        self.memo = analyzer._score_memo if analyzer.config.score_memo \
            else None
        self.memo_results: dict = {f: {} for f in self.FAMILIES}
        # tier-0 triage gate (TRIAGE; engine/triage.py): composes after
        # the memo check — memo skips unchanged rows, triage screens the
        # changed-but-unremarkable ones in one fused kernel and
        # short-circuits CLEAR rows to synthesized healthy results;
        # SUSPECT rows fall through to the family accumulators unchanged.
        self.triage = None
        if analyzer.config.triage:
            from .triage import TriageGate

            gate = TriageGate(analyzer)
            if gate.active:
                self.triage = gate
        self.memo_hits: dict = {}  # family -> hits this cycle
        # provenance: which JOBS had items served from the memo this cycle
        # (job_id -> hit count) — lets /jobs/<id>/explain attribute a
        # verdict to the memo-hit path instead of a fresh device score
        self.memo_job_hits: dict = {}
        self._fps: dict = {}       # (family, result_key) -> fingerprint
        # the cycle's partition (Analyzer._run_cycle): wall seconds inside
        # _memo_check with its lookups and the bytes it fingerprinted, and
        # the thread-CPU seconds of streamed fires and screens, which the
        # route piece's CPU leaves out. The memo check itself reads no CPU
        # clock: `time.thread_time` is a system call (6 us on the chip's
        # host), and two a check cost a measurable share of the cycle.
        self.memo_seconds = 0.0
        self.memo_lookups = 0
        self.memo_fp_bytes = 0
        self.fired_cpu_seconds = 0.0

    def _memo_check(self, family: str, entry, T: int) -> bool:
        """True when this entry's verdict was served from the memo."""
        if self.memo is None:
            return False
        t0 = time.perf_counter()
        try:
            return self._memo_lookup(family, entry, T)
        finally:
            self.memo_seconds += time.perf_counter() - t0

    def _memo_lookup(self, family: str, entry, T: int) -> bool:
        key, fp, nbytes = self.an._memo_key_fp(family, entry, T)
        self.memo_lookups += 1
        self.memo_fp_bytes += nbytes
        hit = self.memo.get((family, key))
        if hit is not None and hit[0] == fp:
            self.memo.move_to_end((family, key))
            self.memo_results[family][key] = hit[1]
            self.memo_hits[family] = self.memo_hits.get(family, 0) + 1
            self.an.score_memo_hits[family] = (
                self.an.score_memo_hits.get(family, 0) + 1)
            job_id = key[0] if isinstance(key, tuple) else key
            self.memo_job_hits[job_id] = self.memo_job_hits.get(job_id, 0) + 1
            return True
        self._fps[(family, key)] = fp
        self.an.score_memo_misses[family] = (
            self.an.score_memo_misses.get(family, 0) + 1)
        return False

    # ------------------------------------------------------------- feeding
    def feed(self, pairs, bands, bis, multis, hpas, strategy: str = ""):
        """Route one job's preprocessed items (claim order) into the
        accumulators; launch any bucket that filled its rung.

        `strategy` is the owning job's strategy: the triage gate screens
        only steady-state (continuous/hpa-class) jobs — canary-class
        verdicts gate live rollouts and always take the full path.

        Routing (bucket keys, joint-grid prep, hpa row building, triage
        screening) is guarded per item like every scoring step: a
        malformed item lands in the per-job retry list instead of
        aborting the whole cycle — the `_isolate` blast-radius contract
        starts here, not at launch.
        """
        an = self.an
        tg = self.triage
        self.multis += multis
        for it in pairs:
            try:
                T = an._pair_T(it)
                if not self._memo_check("pair", it, T):
                    if tg is not None and tg.accepts("pair", strategy):
                        tg.add("pair", T, it, self)
                    else:
                        self._add("pair", T, it)
            except Exception:  # noqa: BLE001 - retried per job at collect
                self.failed.append(("pair", [it]))
        for it in bands:
            try:
                T = an._band_T(it)
                if not self._memo_check("band", it, T):
                    if tg is not None and tg.accepts("band", strategy):
                        tg.add("band", T, it, self)
                    else:
                        self._add("band", T, it)
            except Exception:  # noqa: BLE001
                self.failed.append(("band", [it]))
        for it in bis:
            try:
                pre, T = an._bi_prep(it)
                if not self._memo_check("bivariate", (it, pre), T):
                    if tg is not None and tg.accepts("bivariate", strategy):
                        tg.add("bivariate", T, (it, pre), self)
                    else:
                        self._add("bivariate", T, (it, pre))
            except Exception:  # noqa: BLE001
                self.failed.append(("bivariate", [it]))
        if hpas:
            try:
                rows = an._hpa_rows(hpas)
            except Exception:  # noqa: BLE001
                self.failed.append(("hpa", list(hpas)))
                rows = []
            for row in rows:
                try:
                    T = an._hpa_row_T(row)
                    if not self._memo_check("hpa", row, T):
                        self._add("hpa", T, row)
                except Exception:  # noqa: BLE001
                    self.failed.append(("hpa", [row]))

    def _add(self, family: str, T: int, entry):
        bucket = self.acc[family].setdefault(T, [])
        bucket.append(entry)
        if self._mega:
            cap = self._mega_caps.get(T)
            if cap is None:
                cap = self._mega_caps[T] = self.an._mega_cap(T)
        else:
            cap = self.cap
        if len(bucket) >= cap:
            self.acc[family][T] = []
            self._fire(family, T, bucket)

    def _fire(self, family: str, T: int, entries: list):
        t0, c0 = time.perf_counter(), time.thread_time()
        d0 = self.an.device_launches
        # its self time is the pack; engine.launch under it is the call
        with tracing.span(tracing.SPAN_ENGINE_DISPATCH, family=family, T=T,
                          rows=len(entries)):
            try:
                if family == "pair":
                    st = self.an._launch_pairs(entries, T)
                elif family == "band":
                    st = self.an._launch_bands(entries, T)
                elif family == "bivariate":
                    st = self.an._launch_bivariate(entries, T)
                else:
                    st = self.an._launch_hpa(entries, T)
                self.pending.append((family, entries, st))
            except Exception:  # noqa: BLE001 - blast radius: retry per job
                self.failed.append((family, entries))
        dt = time.perf_counter() - t0
        self.fired_cpu_seconds += time.thread_time() - c0
        self.stage_seconds["dispatch"] += dt
        self.family_seconds[family] = self.family_seconds.get(family, 0.0) + dt
        self.launches += 1
        self.family_launches[family] = (
            self.family_launches.get(family, 0)
            + (self.an.device_launches - d0))

    @staticmethod
    def _entry_items(entries: list) -> list:
        """Flatten accumulator entries back to scorer items (for the
        per-job retry path): pair/band entries ARE items, bivariate
        entries are (item, prep), hpa entries are (job_id, tps, sla)."""
        items = []
        for e in entries:
            if hasattr(e, "job_id"):
                items.append(e)
            elif len(e) == 2:
                items.append(e[0])
            else:
                items.append(e[1])
                if e[2] is not e[1]:
                    items.append(e[2])
        return items

    # ----------------------------------------------------------- collecting
    def finish(self):
        """Flush partial buckets, materialize every launch, retry failures
        per job, and score the lstm family. Returns
        (pair_res, band_res, bi_res, multi_res, hpa_res, scoring_failed)."""
        an = self.an
        if self.triage is not None:
            # screen the remaining partial triage buckets FIRST: suspects
            # route into the family accumulators below and flush with
            # everyone else; cleared rows land in triage.results
            self.triage.flush(self)
        for family in self.FAMILIES:
            buckets, self.acc[family] = self.acc[family], {}
            for T, bucket in buckets.items():
                if bucket:
                    self._fire(family, T, bucket)
        results: dict = {f: {} for f in self.FAMILIES}
        bad: dict = {}
        collect = {"pair": an._collect_pairs, "band": an._collect_bands,
                   "bivariate": an._collect_bivariate, "hpa": an._collect_hpa}
        sync = {"pair": an._score_pairs, "band": an._score_bands,
                "bivariate": an._score_bivariate, "hpa": an._score_hpa}
        from .analyzer import WatchdogTimeout

        t0 = time.perf_counter()
        # Hung-launch watchdog budget: each materialization (and each
        # per-job retry below) runs under WATCHDOG_S (no-op when 0), and
        # the cycle pays for at most TWO timeouts total. One timeout can
        # be a single poisoned program; a second — from another bucket or
        # from a fresh sync retry — is device-level evidence, after which
        # every remaining watchdog-guarded wait is skipped instantly
        # (buckets fall through to the requeue path). Without the cap, a
        # wedged device would serialize one full WATCHDOG_S per pending
        # bucket plus one per retried job into a single cycle.
        wd0 = an.watchdog_fires_total

        def wedged() -> bool:
            return an.watchdog_fires_total - wd0 >= 2

        # materialize in launch order: completion order is the device's
        # business; claim-order folding happens downstream off keyed dicts
        for family, entries, st in self.pending:
            t1 = time.perf_counter()
            # its self time is the per-row Python of the family's collect;
            # engine.materialize under it is the wait and the copy back
            with tracing.span(tracing.SPAN_ENGINE_COLLECT, family=family,
                              rows=len(entries)):
                try:
                    if wedged():
                        raise WatchdogTimeout(
                            "device wedged (2+ watchdog timeouts this "
                            "cycle); bucket skipped")
                    results[family].update(
                        an._watchdog_call(collect[family], st))
                except Exception:  # noqa: BLE001 - deferred device error
                    self.failed.append((family, entries))
            dt = time.perf_counter() - t1
            self.family_seconds[family] = (
                self.family_seconds.get(family, 0.0) + dt)
        # blast-radius fallback: a failed group retries per JOB through the
        # family's synchronous scorer (same launch/collect code, barriered;
        # watchdog-bounded under the same two-timeout cycle budget)
        for family, entries in self.failed:
            by_job: dict[str, list] = {}
            for it in self._entry_items(entries):
                by_job.setdefault(it.job_id, []).append(it)
            for job_id, group in by_job.items():
                if wedged():
                    bad[job_id] = ("WatchdogTimeout: device wedged "
                                   "(2+ watchdog timeouts this cycle); "
                                   "retry skipped")
                    continue
                try:
                    results[family].update(
                        an._watchdog_call(sync[family], group))
                except Exception as e:  # noqa: BLE001
                    bad[job_id] = f"{type(e).__name__}: {e}"
        if self.triage is not None:
            # fold triage-cleared rows in BEFORE memoization: a cleared
            # row's synthesized result is the healthy result the scorer
            # would have produced, so memoizing it keeps the steady chain
            # (unchanged next cycle -> memo hit, no re-screen)
            for family, cleared in self.triage.results.items():
                results[family].update(cleared)
        if self.memo is not None:
            # memoize every freshly scored verdict (collect + retries) for
            # the next cycle, then fold the memo-served ones back in
            for family in self.FAMILIES:
                for key, res in results[family].items():
                    fp = self._fps.get((family, key))
                    if fp is not None:
                        an._memo_put(self.memo, (family, key), (fp, res))
                results[family].update(self.memo_results[family])
        # lstm scores here, not in the stream: training mutates the model
        # cache under a per-cycle budget whose order must match claim order
        with tracing.span(tracing.SCORE_SPANS["lstm"],
                          n=len(self.multis)) as lsp:
            t1 = time.perf_counter()
            multi_res, multi_bad = an._isolate(an._score_multi, self.multis)
            lsp.attrs["budget_skips"] = len(an._lstm_budget_skipped_ids)
            self.family_seconds["lstm"] = time.perf_counter() - t1
        # collect = everything after the stream: device wait + merge +
        # retries + the lstm family — the same work the barriered mode
        # books under collect, so SCORE_PIPELINE A/Bs compare like stages
        self.stage_seconds["collect"] += time.perf_counter() - t0
        bad.update(multi_bad)
        return (results["pair"], results["band"], results["bivariate"],
                multi_res, results["hpa"], bad)


# ---------------------------------------------------------------- compiles
class CompileCounter:
    """Counts XLA compilation work via jax.monitoring events.

    `compiles` counts backend_compile invocations — in a process WITHOUT
    the persistent cache this is exactly the number of fresh XLA
    compilations (in-memory jit cache hits never re-enter the backend),
    which is what the steady-state zero-recompile gate asserts. With the
    persistent cache enabled, backend_compile wraps retrieval too, so the
    compile-storm question becomes `cache_misses` (fresh work) vs
    `cache_hits` (replayed from the persistent cache directory).
    """

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
    CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.compiles = 0
        self.compile_seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event, duration, **kw):
        if event == self.COMPILE_EVENT:
            self.compiles += 1
            self.compile_seconds += duration

    def _on_event(self, event, **kw):
        if event == self.CACHE_HIT_EVENT:
            self.cache_hits += 1
        elif event == self.CACHE_MISS_EVENT:
            self.cache_misses += 1

    def start(self):
        """Begin counting (also the `with` entry). A long-lived owner —
        the runtime, between start() and stop() — calls this directly."""
        import jax.monitoring as jm

        jm.register_event_duration_secs_listener(self._on_duration)
        jm.register_event_listener(self._on_event)
        return self

    def stop(self):
        """Stop counting and take both listeners back out."""
        import jax.monitoring as jm

        jm.unregister_event_duration_listener(self._on_duration)
        jm.unregister_event_listener(self._on_event)

    __enter__ = start

    def __exit__(self, *exc):
        self.stop()
        return False


# the in-checkout persistent cache: ONE fixed path (the directory is part
# of XLA's cache key, so a moving path never hits), gitignored
_CHECKOUT_CACHE = ".jax_cache"


def compile_cache_dir(env: dict | None = None) -> str:
    """Where the persistent XLA compilation cache lives, or "".

    JAX_COMPILATION_CACHE_DIR wins whenever it is set — JAX reads it
    itself and no code here sets another directory. Unset, a process run
    from a source checkout (pyproject.toml beside the package) uses the
    checkout's fixed `.jax_cache/`; an installed package gets no
    persistent cache until the deployment sets the variable."""
    explicit = knobs.read("JAX_COMPILATION_CACHE_DIR", env)
    if explicit:
        return explicit
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if os.path.isfile(os.path.join(root, "pyproject.toml")):
        return os.path.join(root, _CHECKOUT_CACHE)
    return ""


def enable_compile_cache(env: dict | None = None) -> str:
    """Turn the persistent compilation cache on for this process and
    return the directory in effect ("" = none). Entry points call this
    before anything jits (`serve`, `prewarm`, the bench children).

    Zeroes the min-compile-time/entry-size gates so even the small
    per-(rung, T) programs persist — they are exactly what the first-cycle
    compile storm is made of. The directory itself is only ever set here
    when JAX_COMPILATION_CACHE_DIR is NOT (see `compile_cache_dir`)."""
    import jax

    if not knobs.read("JAX_COMPILATION_CACHE_DIR", env):
        default = compile_cache_dir(env)
        if default:
            jax.config.update("jax_compilation_cache_dir", default)
    # what JAX itself holds is the answer: its own reading of the
    # variable at import, or the checkout default set just above
    path = jax.config.jax_compilation_cache_dir or ""
    if path:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_info() -> dict:
    """Where this process computes, as JAX reports it — stamped on
    `/status.build` and `prewarm`'s JSON so no result can pass for a
    chip's without naming one. Initializes the backend."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


# ----------------------------------------------------------------- prewarm
# the default prewarm grid: small rungs cover flush partials, 1024 covers
# the default PIPELINE_FIRE_ROWS streamed launches, and the T buckets the
# common 2h-current / short-history windows. Big fleets should prewarm
# their real rungs (e.g. --rungs 16,64,256,1024,8192) and their historical
# T buckets — see docs/performance.md for sizing.
STANDARD_RUNGS = (16, 64, 256, 1024)
STANDARD_T_BUCKETS = (128, 256)


def prewarm(config=None,
            families=("pair", "band", "bivariate", "hpa", "triage"),
            rungs=STANDARD_RUNGS, t_buckets=STANDARD_T_BUCKETS) -> dict:
    """Compile the (family x rung x T-bucket) scoring grid up front.

    Drives the REAL production entry points — the analyzer's family
    scorers on synthetic items — so the compiled signatures are exactly
    the ones steady-state cycles launch (dtype or packing drift would show
    up as a failed zero-recompile regression test, not a silent miss).
    With the persistent compile cache enabled the work is also banked for
    every future process. Blocks until the grid is compiled; run it in a
    background thread to prewarm behind live traffic (PREWARM_ON_START).
    """
    import numpy as np

    from ..ops import hpa as hpa_ops
    from ..ops import triage as triage_ops
    from ..ops.windowing import Window, bucket_length
    from ..parallel import fleet as fl
    from .analyzer import Analyzer, _BandItem, _BiItem, _HpaItem
    from .config import EngineConfig, from_env
    from .triage import screen_cap

    cfg = config if config is not None else from_env()
    if not isinstance(cfg, EngineConfig):
        raise TypeError(f"prewarm wants an EngineConfig, got {type(cfg)!r}")
    an = Analyzer(cfg, data_source=None, store=None,
                  breath=hpa_ops.BreathState())
    rng = np.random.default_rng(0)
    # clamp BOTH axes to their ladders: off-ladder values would compile
    # programs no cycle ever launches (the chunker pads rows to batch
    # rungs, pack_windows pads lengths to the window buckets) while the
    # real bucket stayed cold
    rungs = sorted({an._bucket_rows(int(r)) for r in rungs})
    t_buckets = sorted({bucket_length(int(t)) for t in t_buckets})
    policy = cfg.policy_for("latency")

    def win(T):
        return Window(rng.normal(10.0, 1.0, T).astype(np.float32),
                      np.ones(T, bool), 0)

    t0 = time.perf_counter()
    programs = 0

    @contextmanager
    def program(family, rung, T):
        # one log line per program: on the chip a single program can take
        # a minute to compile, and a silent prewarm looks like a hang
        nonlocal programs
        t1 = time.perf_counter()
        yield
        programs += 1
        log.info("prewarm %s rung=%d T=%d: %.1fs", family, rung, T,
                 time.perf_counter() - t1)

    # one root for the grid's engine.launch / engine.materialize spans:
    # outside a cycle each would finish as a root trace of its own
    with CompileCounter() as cc, \
            tracing.span(tracing.SPAN_ENGINE_SCORE, prewarm=True):
        for T in t_buckets:
            n_c = max(T // 4, 8)
            n_h = T - n_c
            if "triage" in families:
                # the fused tier-0 screen launches at exactly the rungs
                # TriageGate._rung can return: every _BATCH_BUCKETS entry
                # below the memory-aware cap, plus the cap itself (the
                # steady-state rung a big fleet's screen actually fires) —
                # deriving from the family rung list missed 512/4096 and
                # left mid-size buckets compiling at cycle time
                cap = screen_cap(cfg.triage_fire_rows, T)
                t_rungs = sorted(
                    {b for b in Analyzer._BATCH_BUCKETS if b < cap}
                    | {cap})
                for r in t_rungs:
                    with program("triage", r, T):
                        np.asarray(triage_ops.screen_rows(
                            *triage_ops.triage_arg_spec(r, T),
                            cfg.ma_window)["count"])
            for r in rungs:
                if "pair" in families:
                    # the fused pairwise program straight at the kernel:
                    # fleet.pair_arg_spec mirrors _launch_pairs' packing
                    with program("pair", r, T):
                        np.asarray(fl.score_pairs(*fl.pair_arg_spec(r, T))
                                   ["unhealthy"])
                if "band" in families:
                    with program("band", r, T):
                        an._score_bands([
                            _BandItem(f"w{i}", "latency", win(n_h),
                                      win(n_c), policy)
                            for i in range(r)
                        ])
                if "bivariate" in families:
                    with program("bivariate", r, T):
                        an._score_bivariate([
                            _BiItem(f"w{i}", ("latency", "cpu"),
                                    (win(n_h), win(n_h)),
                                    (win(n_c), win(n_c)), (policy, policy))
                            for i in range(r)
                        ])
                if "hpa" in families:
                    items = []
                    for i in range(r):
                        items.append(_HpaItem(f"w{i}", "tps", win(n_h),
                                              win(n_c), True, 0))
                        items.append(_HpaItem(f"w{i}", "latency", win(n_h),
                                              win(n_c), True, 1))
                    with program("hpa", r, T):
                        an._score_hpa(items)
    return {
        "families": list(families),
        "rungs": list(rungs),
        "t_buckets": list(t_buckets),
        "programs": programs,
        "backend_compiles": cc.compiles,
        "compile_cache_hits": cc.cache_hits,
        "seconds": round(time.perf_counter() - t0, 3),
    }
