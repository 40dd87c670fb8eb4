"""Bucket-granular scoring pipeline: stream, dispatch, collect.

The cycle's one scoring path, a pipeline at three levels:

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
     the batch families interleave freely on the device queue.
  3. **persistent compile cache + prewarm** — `enable_compile_cache`
     keeps XLA's persistent compilation cache where
     JAX_COMPILATION_CACHE_DIR says (a source checkout defaults to its
     own `.jax_cache/`) so a restarted process skips the first-cycle
     compile storm, and `prewarm` compiles the standard (family x rung x
     T-bucket) grid up front (CLI: `foremast-tpu prewarm`; runtime:
     PREWARM_ON_START).

Two contracts are preserved exactly:

  * **deterministic folding** — accumulators fill in claim order, fire at
    rung boundaries (full rungs mid-stream, rung-padded partials at
    flush), and results are keyed dicts folded in claim order, so
    verdicts do not depend on where a launch was cut or on device
    completion order (a fire threshold at or above the fleet is the
    barrier: nothing launches before `finish`).
  * **`_isolate` blast radius** — a launch- or collect-time failure
    retries that group per JOB through the family's `score`; only the
    offending jobs report errors, everyone else's results stand.
"""
from __future__ import annotations

import logging
import os
import time
from contextlib import contextmanager
from functools import partial

from ..utils import knobs, tracing
from . import families as fam_table
from .analyzer import WatchdogTimeout

log = logging.getLogger("foremast_tpu.engine.pipeline")

__all__ = ["CyclePipeline", "CompileCounter", "compile_cache_dir",
           "enable_compile_cache", "device_info", "prewarm",
           "STANDARD_RUNGS", "STANDARD_T_BUCKETS"]


class CyclePipeline:
    """One engine cycle's streaming dispatch state. Not thread-safe by
    design: `feed` is called from the single consumer of the (ordered)
    preprocess stream, which is what keeps launches deterministic. What
    a family is, it reads from `engine/families.py`: the table as it stands
    when the cycle starts, in its order."""

    def __init__(self, analyzer):
        self.an = analyzer
        self.fams = fam_table.FAMILIES
        self.streamed = [f for f in self.fams if f.streams]
        # fire threshold: an accumulator launches the moment it holds a
        # full batch rung, so device execution overlaps the remaining
        # fetches. Snapped to the rung ladder (and capped at the chunk
        # size) so streamed launches hit the same compiled programs as the
        # flush; scorers are row-wise, so launch boundaries cannot change
        # verdicts (the determinism tests pin streamed == flushed at once).
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
        # every item routed, by family, in claim order: the fold walks
        # them, and a family that does not stream is scored from them
        self.items: dict = {f.name: [] for f in self.fams}
        self.acc: dict = {f.name: {} for f in self.streamed}  # -> T -> []
        self.pending: list = []  # (family, entries, launch_state)
        self.failed: list = []   # (family, items) awaiting per-job retry
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
        self.memo_results: dict = {f.name: {} for f in self.streamed}
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
        # _memo_check with its lookups, the windows (and their bytes) it
        # hashed and the window digests it reused (Window.digest), and the
        # thread-CPU seconds of streamed fires and screens, which the route
        # piece's CPU leaves out. The memo check itself reads no CPU clock:
        # `time.thread_time` is a system call (6 us on the chip's host),
        # and two a check cost a measurable share of the cycle.
        self.memo_seconds = 0.0
        self.memo_lookups = 0
        self.memo_fp_bytes = 0
        self.memo_fp_hashed = 0
        self.memo_fp_reused = 0
        self.fired_cpu_seconds = 0.0

    def _memo_check(self, fam, entry, T: int) -> bool:
        """True when this entry's verdict was served from the memo."""
        if self.memo is None:
            return False
        t0 = time.perf_counter()
        try:
            return self._memo_lookup(fam, entry, T)
        finally:
            self.memo_seconds += time.perf_counter() - t0

    def _memo_lookup(self, fam, entry, T: int) -> bool:
        family = fam.name
        key, fp, nbytes, hashed, reused = self.an._memo_key_fp(
            fam, entry, T)
        self.memo_lookups += 1
        self.memo_fp_bytes += nbytes
        self.memo_fp_hashed += hashed
        self.memo_fp_reused += reused
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
    def feed(self, routed: dict, strategy: str = ""):
        """Route one job's preprocessed items (family name -> items, claim
        order) into the accumulators; launch any bucket that filled its
        rung. A family that does not stream only has its items kept.

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
        for fam in self.fams:
            items = routed.get(fam.name)
            if not items:
                continue
            self.items[fam.name] += items
            route = fam.route
            if route is None:
                continue
            try:
                rows = fam.rows(an, items)
            except Exception:  # noqa: BLE001 - retried per job at collect
                self.failed.append((fam, list(items)))
                continue
            screened = tg is not None and tg.accepts(fam.name, strategy)
            for row in rows:
                try:
                    entry, T = route(an, row)
                    if not self._memo_check(fam, entry, T):
                        if screened:
                            tg.add(fam, T, entry, self)
                        else:
                            self._add(fam, T, entry)
                except Exception:  # noqa: BLE001 - retried per job at collect
                    self.failed.append((fam, fam.row_items(row)))

    def _add(self, fam, T: int, entry):
        acc = self.acc[fam.name]
        bucket = acc.setdefault(T, [])
        bucket.append(entry)
        if self._mega:
            cap = self._mega_caps.get(T)
            if cap is None:
                cap = self._mega_caps[T] = self.an._mega_cap(T)
        else:
            cap = self.cap
        if len(bucket) >= cap:
            acc[T] = []
            self._fire(fam, T, bucket)

    def _fire(self, fam, T: int, entries: list):
        family = fam.name
        t0, c0 = time.perf_counter(), time.thread_time()
        d0 = self.an.device_launches
        # its self time is the pack; engine.launch under it is the call
        with tracing.span(tracing.SPAN_ENGINE_DISPATCH, family=family, T=T,
                          rows=len(entries)):
            try:
                self.pending.append(
                    (fam, entries, fam.launch(self.an, entries, T)))
            except Exception:  # noqa: BLE001 - blast radius: retry per job
                self.failed.append((fam, fam.items_of(entries)))
        dt = time.perf_counter() - t0
        self.fired_cpu_seconds += time.thread_time() - c0
        self.stage_seconds["dispatch"] += dt
        self.family_seconds[family] = self.family_seconds.get(family, 0.0) + dt
        self.launches += 1
        self.family_launches[family] = (
            self.family_launches.get(family, 0)
            + (self.an.device_launches - d0))

    # ----------------------------------------------------------- collecting
    def finish(self):
        """Flush partial buckets, materialize every launch, retry failures
        per job, and score the families that do not stream. Returns
        (results, scoring_failed): family name -> {result key: result},
        and job_id -> error for the jobs whose scoring failed."""
        an = self.an
        if self.triage is not None:
            # screen the remaining partial triage buckets FIRST: suspects
            # route into the family accumulators below and flush with
            # everyone else; cleared rows land in triage.results
            self.triage.flush(self)
        for fam in self.streamed:
            buckets, self.acc[fam.name] = self.acc[fam.name], {}
            for T, bucket in buckets.items():
                if bucket:
                    self._fire(fam, T, bucket)
        results: dict = {f.name: {} for f in self.fams}
        bad: dict = {}

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
        for fam, entries, st in self.pending:
            family = fam.name
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
                        an._watchdog_call(fam.collect, an, st))
                except Exception:  # noqa: BLE001 - deferred device error
                    self.failed.append((fam, fam.items_of(entries)))
            dt = time.perf_counter() - t1
            self.family_seconds[family] = (
                self.family_seconds.get(family, 0.0) + dt)
        # blast-radius fallback: a failed group retries per JOB through the
        # family's `score` (same launch/collect code, at once;
        # watchdog-bounded under the same two-timeout cycle budget)
        for fam, items in self.failed:
            by_job: dict[str, list] = {}
            for it in items:
                by_job.setdefault(it.job_id, []).append(it)
            for job_id, group in by_job.items():
                if wedged():
                    bad[job_id] = ("WatchdogTimeout: device wedged "
                                   "(2+ watchdog timeouts this cycle); "
                                   "retry skipped")
                    continue
                try:
                    results[fam.name].update(
                        an._watchdog_call(fam.score, an, group))
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
            for family, served in self.memo_results.items():
                for key, res in results[family].items():
                    fp = self._fps.get((family, key))
                    if fp is not None:
                        an._memo_put(self.memo, (family, key), (fp, res))
                results[family].update(served)
        # a family that does not stream is scored here, over the cycle's
        # items in claim order, under the same per-job blast radius
        for fam in (f for f in self.fams if not f.streams):
            items = self.items[fam.name]
            with tracing.span(tracing.SCORE_SPANS[fam.name],
                              n=len(items)) as sp:
                t1 = time.perf_counter()
                results[fam.name], fam_bad = an._isolate(
                    partial(fam.score, an), items)
                sp.attrs.update(fam.score_attrs(an))
                self.family_seconds[fam.name] = time.perf_counter() - t1
            bad.update(fam_bad)
        # collect = everything after the stream: device wait + merge +
        # retries + the families scored at finish
        self.stage_seconds["collect"] += time.perf_counter() - t0
        return results, bad


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

    Drives the REAL production entry points — each family's `score` on
    the table's items for a rung — so the compiled signatures are exactly
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
    from .analyzer import Analyzer
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
    table = [f for f in fam_table.FAMILIES
             if f.name in families and f.prewarm_items is not None]

    def win(T):
        return Window(rng.normal(10.0, 1.0, T).astype(np.float32),
                      np.ones(T, bool), 0)

    t0 = time.perf_counter()
    programs = 0

    @contextmanager
    def program(family, rung, T, cur=None):
        # one log line per program: on the chip a single program can take
        # a minute to compile, and a silent prewarm looks like a hang
        nonlocal programs
        t1 = time.perf_counter()
        yield
        programs += 1
        log.info("prewarm %s rung=%d T=%d%s: %.1fs", family, rung, T,
                 "" if cur is None else f" current={cur}",
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
                for fam in table:
                    for cur in fam.prewarm_currents(an, T, n_c):
                        with program(fam.name, r, T,
                                     None if cur == n_c else cur):
                            fam.score(an, fam.prewarm_items(
                                r, n_h, cur, win, policy))
    return {
        "families": list(families),
        "rungs": list(rungs),
        "t_buckets": list(t_buckets),
        "programs": programs,
        "backend_compiles": cc.compiles,
        "compile_cache_hits": cc.cache_hits,
        "seconds": round(time.perf_counter() - t0, 3),
    }
