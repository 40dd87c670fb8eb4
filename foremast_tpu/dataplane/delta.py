"""Delta window fetch: steady-state incremental range queries.

The engine's hot loop re-fetches the same (job, url) windows cycle after
cycle, yet each 60 s step only appends ~1 sample to the current window
while everything older is frozen. This module keeps the last grid
``Window`` per query identity and, on the next cycle, issues a NARROW
range query for only the tail (``last_end - overlap -> end``), splicing
the fresh tail into the cached grid. The spliced window is byte-identical
to a full refetch — enforced by the randomized property test in
tests/test_delta.py — or the source falls back to a real full refetch.

Why byte-identity is provable here: the engine grids every response with
``grid_from_series`` semantics (span from the data's own min/max
timestamps, f32 value cast per slot, later-samples-win). When every
sample timestamp lies EXACTLY on its grid slot (the normal case — our
query builder floor-aligns start/end, and Prometheus evaluates
query_range at ``start + k*step``), slot times ARE sample times, so the
full-refetch grid geometry can be reconstructed from the cached grid
plus the delta response. Off-grid samples break that equivalence, so any
response carrying them simply disables splicing for that key (full
refetch every cycle — exactly today's behavior).

Fallback-to-full triggers (each counted on the source):

  * ``DELTA_FETCH=0`` / no cached entry / cache eviction (miss)
  * off-grid sample timestamps in the cached or delta response
  * step-param change between cycles
  * the requested range extends backwards past the cached range
  * splice mismatch: the delta's overlap region disagrees with the
    cached grid (the backend rewrote or dropped history — retention gap,
    counter reset backfill, proxy weirdness)
  * too many NaN-valued samples to track span anchors exactly

Coherence assumption (shared with every incremental fetcher): samples
OLDER than the overlap window are immutable. Rewrites inside the overlap
are detected (-> full refetch); rewrites beyond it are invisible until
the entry is evicted — the same staleness contract as the TTL cache, but
with a self-checking seam.

The closed-range rule follows from the same assumption: a request for
exactly the range the entry already holds, whose tail is full and whose
end is older than the overlap window, can only be answered with the
window the entry holds, so ``fetch_window`` returns it without a backend
query, a splice or the splice lock (``_unmoved``; counted as
``unmoved_hits``). A fixed baseline or historical range of a canary is
one query in its life, not one a cycle.

The append rule (``_append_tail``; counted as ``append_hits``): when the
first slot the range keeps and the last slot of the cached exact grid
hold valid samples, and the tail the delta query returned is contiguous,
on the grid, starts on the first slot it re-read and equals the cached
slots over the overlap, the new window is the cached slots below the tail
plus the tail: scalars decide it and slice copies build it. The query is
still made and the overlap still compared; what is no longer rebuilt is
every cached sample's timestamp and the grid geometry. Any other entry or
tail is spliced as before, from the same response.
"""
from __future__ import annotations

import logging
import re
import time
from collections import OrderedDict

import numpy as np

from ..ops.windowing import (
    DEFAULT_STEP,
    MAX_WINDOW_STEPS,
    Window,
    align_step,
    resample_to_grid,
)
from .fetch import TS_SPAN_CAP, grid_from_series
from ..utils import tracing
from ..utils.locks import make_lock

log = logging.getLogger("foremast_tpu.delta")

__all__ = ["DeltaWindowSource", "strip_range_params", "parse_range_params"]

# start/end query params across both URL dialects (prometheus start=/end=,
# wavefront s=/e=) — the same split placeholderize() keys on
_RANGE_RE = re.compile(r"([?&])(start|end|s|e)=([^&]*)")

# NaN/inf-valued samples occupy grid span without setting mask, so their
# timestamps must be carried per entry to reconstruct full-fetch geometry;
# a body carrying more than this many is pathological — don't cache it
_MAX_NAN_TS = 512


def strip_range_params(url: str) -> str:
    """Query identity: the URL with start/end values blanked. Two cycles'
    materializations of one job window differ only in these values."""
    return _RANGE_RE.sub(lambda m: f"{m.group(1)}{m.group(2)}=", url)


def parse_range_params(url: str):
    """(qstart, qend, step) floats parsed from the URL, or None when the
    URL carries no complete numeric range (fixture keys, placeholders) —
    such URLs are not delta-capable and always fetch in full."""
    qstart = qend = step = None
    for m in _RANGE_RE.finditer(url):
        try:
            v = float(m.group(3))
        except ValueError:
            return None
        if m.group(2) in ("start", "s"):
            qstart = v
        else:
            qend = v
    m = re.search(r"[?&]step=([^&]*)", url)
    if m:
        try:
            step = float(m.group(1))
        except ValueError:
            return None
    if qstart is None or qend is None:
        return None
    return qstart, qend, step


def _set_range(url: str, qstart, qend) -> str:
    """Rewrite the URL's range params (both dialects) to [qstart, qend]."""
    def sub(m):
        val = qstart if m.group(2) in ("start", "s") else qend
        return f"{m.group(1)}{m.group(2)}={val:.0f}"

    return _RANGE_RE.sub(sub, url)


class _Entry:
    """One cached window: the grid plus everything needed to reconstruct
    full-refetch geometry next cycle."""

    __slots__ = ("win", "qstart", "qend", "url_step", "nan_ts",
                 "full_bytes", "full_points", "pushed_until",
                 "push_blocked", "dirty")

    def __init__(self, win, qstart, qend, url_step, nan_ts,
                 full_bytes, full_points):
        self.win = win
        self.qstart = qstart
        self.qend = qend
        self.url_step = url_step  # the URL's step= param (None if absent)
        self.nan_ts = nan_ts  # finite ts of non-finite-valued samples
        self.full_bytes = full_bytes  # last full response size (0 unknown)
        self.full_points = full_points
        # crash-durability bookkeeping (dataplane/winstore.py): True when
        # this entry's state has changed since it was last spilled to the
        # warm segment tier (a fresh entry has never been spilled)
        self.dirty = True
        # newest PUSHED sample timestamp spliced in by ingest_append
        # (0 = poll-only entry). While the requested range end stays
        # inside the pushed horizon, fetch_window serves straight from
        # the cache — zero backend queries on the streamed path. Any
        # poll-driven refresh (full refetch or delta splice) resets it:
        # the poll re-established the backend as the source of truth,
        # and the next push re-arms the horizon.
        self.pushed_until = 0.0
        # resync latch (ingest_block): set when the receiver had to DROP
        # spliceable samples for this query (buffer overfill, a mixed
        # off-grid batch) — the push stream now has a hole the backend
        # does not, so further splices must wait until a poll re-syncs
        # the entry (the _splice/_full_grid refresh clears it)
        self.push_blocked = False


def _copy_frozen(out, w, boundary: int) -> None:
    """Transplant the cached grid `w`'s slots below `boundary` into the
    freshly resampled `out` — the frozen-region copy shared by the delta
    splice and the ingest splice. ONE implementation on purpose: the
    byte-identity contract depends on both splice paths computing the
    same geometry, so a future fix here fixes both."""
    off = int((out.start - w.start) // w.step)
    n = out.values.shape[0]
    src_lo, src_hi = off, off + min(boundary, n)
    lo_clip = max(0, -src_lo)
    src_lo += lo_clip
    src_hi = min(max(src_hi, src_lo), w.values.shape[0])
    if src_hi > src_lo:
        dst_lo = lo_clip
        dst_hi = dst_lo + (src_hi - src_lo)
        out.values[dst_lo:dst_hi] = w.values[src_lo:src_hi]
        out.mask[dst_lo:dst_hi] = w.mask[src_lo:src_hi]


def _exact(ts: np.ndarray, step: int) -> bool:
    """Every timestamp lies exactly on a step boundary (slot time == ts)."""
    if ts.size == 0:
        return True
    # 2**53: past float64's exact-integer range `%` itself goes inexact
    return bool(np.all(ts >= 0) and np.all(ts % step == 0)
                and np.all(ts < min(TS_SPAN_CAP, 2.0**53)))


def _split_finite(ts, vals):
    """(ts, vals, nan_ts) with non-finite-ts samples dropped and the
    finite-ts / non-finite-VALUE sample times split out — mirrors the
    finiteness rules of grid_from_series + resample_to_grid exactly."""
    ts = np.asarray(ts, np.float64)
    vals = np.asarray(vals, np.float64)
    n = min(ts.size, vals.size)  # resample_to_grid's mismatched-series trim
    ts, vals = ts[:n], vals[:n]
    keep = np.isfinite(ts)
    ts, vals = ts[keep], vals[keep]
    with np.errstate(over="ignore"):  # the f32 cast IS the finiteness check
        bad = ~np.isfinite(vals.astype(np.float32))
    return ts, vals, np.unique(ts[bad])


def _cached_sample_ts(w, nan_ts, qstart):
    """(valid_ts, sample_ts): the timestamp of every sample the cached
    grid `w` holds (slot time == sample time on an exact grid), and of
    those and the NaN-valued samples at or after `qstart`."""
    valid_ts = (w.start
                + np.nonzero(w.mask)[0].astype(np.float64) * w.step)
    sample_ts = np.concatenate([valid_ts, nan_ts])
    return valid_ts, sample_ts[sample_ts >= qstart]


def _note_fetch_seconds(lock_wait: float, lock_held: float,
                        source: float, url: float) -> None:
    """Where one fetch's seconds went, on the caller's open per-job notes
    (the engine's fetch pool; a no-op on any other thread): queued for the
    splice lock, inside the inner source's call and on the URL (the
    range's parse, the cache key, the delta query's range) are
    THREAD-seconds, summed over the pool's threads; the lock is serial, so
    the seconds it was held sum to wall seconds. One flush per fetch, from
    timestamps taken inline: a timed context manager at every lock site
    cost ten times as much under the pool's contention for the interpreter
    lock."""
    note = tracing.tracer.add_note
    note("lock_wait_thread_seconds", lock_wait)
    note("lock_held_seconds", lock_held)
    note("source_thread_seconds", source)
    note("url_thread_seconds", url)


class DeltaWindowSource:
    """fetch_window with per-query delta fetch + splice.

    Wraps any inner source exposing ``fetch`` (and optionally
    ``fetch_series`` for byte accounting). ``fetch``/``set_cycle_deadline``
    pass through untouched; only the engine's grid-Window path is
    incrementalized. The LRU is bounded by ``max_entries``
    (WINDOW_CACHE_MAX) and guarded by a lock — the engine's fetch pool
    calls in from many threads.
    """

    def __init__(self, inner, max_entries: int = 8192,
                 overlap_steps: int = 5, step: int = DEFAULT_STEP,
                 clock=None, store=None):
        self.inner = inner
        self.max_entries = max_entries
        # crash-durable warm tier (dataplane/winstore.py WindowStore;
        # None = today's RAM-only cache, byte-for-byte). With a store,
        # LRU eviction SPILLS dirty entries to the columnar segment
        # instead of dropping them, a cache miss PROMOTES from the
        # segment before falling back to a backend fetch, and the
        # runtime checkpoints dirty entries every sweep.
        self.store = store
        # entries evicted under a lock, awaiting their spill write (file
        # I/O must not run under the cache/cpu locks)
        self._spill_pending: list = []
        # keys whose queued evictee spill was DROPPED under sustained
        # disk pressure (the requeue bound): their acked pushes may
        # exist only in a WAL generation a later checkpoint retires, so
        # any warm state promoted for these keys comes back latched into
        # resync until a poll re-establishes the backend as truth
        self._dropped_spill_keys: set[str] = set()
        self.overlap_steps = max(int(overlap_steps), 1)
        self.step = int(step)
        # wall clock for the ingest-serve coverage proof (_try_ingest_
        # serve): a query whose end lies in the future can still be
        # served from the pushed cache when no NEW on-grid sample can
        # exist yet (clock < pushed_until + step). Injectable for the
        # bench/tests' synthetic time.
        self.clock = clock or time.time
        self._cache: OrderedDict[str, _Entry] = OrderedDict()
        self._lock = make_lock("dataplane.delta.cache")
        # splice/grid work is pure Python+numpy on small arrays: the GIL
        # serializes it anyway, but letting the engine's 16 fetch threads
        # CONTEND for it causes a switch convoy (measured ~49 ms/fetch at
        # 16 threads vs 0.6 ms single-threaded on 2 cores). One coarse
        # lock makes threads queue on a futex instead; only the inner
        # (network) fetch runs outside it, which is the part that
        # genuinely parallelizes.
        self._cpu_lock = make_lock("dataplane.delta.splice_cpu")
        # observability (served on /metrics and /status)
        self.delta_hits = 0        # spliced windows
        self.append_hits = 0       # of them, by the append rule
        self.unmoved_hits = 0      # closed, unmoved ranges served as cached
        self.full_fetches = 0      # misses + fallbacks + non-capable URLs
        self.fallbacks: dict[str, int] = {}  # reason -> count
        self.bytes_delta = 0       # bytes actually fetched on delta queries
        self.bytes_saved = 0       # est. full-body bytes NOT re-downloaded
        self.points_saved = 0      # samples not re-fetched/re-parsed
        # push-ingest seam (foremast_tpu/ingest): samples spliced in by
        # ingest_append, fetches served entirely from the pushed cache,
        # and per-reason append rejections
        self.ingest_spliced_points = 0
        self.ingest_hits = 0
        self.ingest_rejects: dict[str, int] = {}
        # warm-tier traffic (store is None => all stay 0)
        self.warm_spills = 0
        self.warm_promotes = 0
        self.warm_spill_drops = 0  # evictee spills lost to the requeue bound

    # ------------------------------------------------------------ plumbing
    def fetch(self, url: str):
        return self.inner.fetch(url)

    def set_cycle_deadline(self, deadline):
        sd = getattr(self.inner, "set_cycle_deadline", None)
        if sd is not None:
            sd(deadline)

    def snapshot(self) -> dict:
        """Live view for /status."""
        hits = self.delta_hits + self.ingest_hits + self.unmoved_hits
        total = hits + self.full_fetches
        with self._lock:
            entries = len(self._cache)
        return {
            "entries": entries,
            "delta_hits": self.delta_hits,
            "append_hits": self.append_hits,
            "unmoved_hits": self.unmoved_hits,
            "full_fetches": self.full_fetches,
            "hit_ratio": round(hits / total, 4) if total else 0.0,
            "bytes_saved": self.bytes_saved,
            "points_saved": self.points_saved,
            "fallbacks": dict(self.fallbacks),
            "ingest_spliced_points": self.ingest_spliced_points,
            "ingest_hits": self.ingest_hits,
            "ingest_rejects": dict(self.ingest_rejects),
            "warm_spills": self.warm_spills,
            "warm_promotes": self.warm_promotes,
            "warm_spill_drops": self.warm_spill_drops,
        }

    def window_bytes(self) -> int:
        """Resident bytes held by the hot-tier window cache (values +
        mask + nan-ts columns), computed under the cache lock."""
        with self._lock:
            return sum(
                e.win.values.nbytes + e.win.mask.nbytes + e.nan_ts.nbytes
                for e in self._cache.values())

    def _series(self, url: str):
        """(ts, vals, nbytes) through the inner source; nbytes 0 when the
        inner has no byte-level seam (plain fixture dicts)."""
        fs = getattr(self.inner, "fetch_series", None)
        if fs is not None:
            out = fs(url)
            if out is not None:
                return out
        ts, vals = self.inner.fetch(url)
        return ts, vals, 0

    def _cache_key(self, url: str, rng) -> str:
        """The ONE cache-key derivation (fetch_window / ingest_append /
        ingest_block): URL minus start/end values, plus the log2 bucket
        of the range span — see fetch_window for why the span bucket
        separates a query's current/historical window roles."""
        span = max(int(round((rng[1] - rng[0]) / self.step)), 1)
        return f"{strip_range_params(url)}#span={span.bit_length()}"

    def _count_fallback(self, reason: str):
        with self._lock:
            self.fallbacks[reason] = self.fallbacks.get(reason, 0) + 1

    def _count_ingest_reject(self, reason: str):
        with self._lock:
            self.ingest_rejects[reason] = \
                self.ingest_rejects.get(reason, 0) + 1

    # ---------------------------------------------------------- warm tier
    def _entry_state(self, key: str, entry: _Entry) -> dict:
        """Serializable snapshot of one entry for the columnar segment.
        References only — ``entry.win``/``nan_ts`` are replaced, never
        mutated in place, so taking them under ``_lock`` is enough."""
        w = entry.win
        return {
            "key": key, "qstart": entry.qstart, "qend": entry.qend,
            "url_step": entry.url_step, "start": w.start, "step": w.step,
            "values": w.values, "mask": w.mask, "nan_ts": entry.nan_ts,
            "full_bytes": entry.full_bytes,
            "full_points": entry.full_points,
            "pushed_until": entry.pushed_until,
            "push_blocked": entry.push_blocked,
        }

    def _evict_overflow_locked(self) -> None:
        """LRU trim (caller holds ``_lock``). With a warm tier, dirty
        evictees queue for a spill write OUTSIDE the locks (the caller
        runs ``_flush_spills`` after releasing them); without one they
        drop exactly as before."""
        while len(self._cache) > self.max_entries:
            key, entry = self._cache.popitem(last=False)
            if self.store is not None and entry.dirty:
                self._spill_pending.append((key, entry))

    def _requeue_spills(self, items) -> None:
        """Put unwritten evictee spills back for a later retry, bounded:
        a permanently-full disk must degrade durability, not grow RAM.
        The overflow is NOT silent — a dropped state may hold acked
        pushes whose WAL records a later checkpoint retires, so its key
        latches (counted, logged): whatever warm state later promotes
        for it comes back in resync mode, and the poll path re-
        establishes the backend as truth before any push is trusted."""
        with self._lock:
            queue = items + self._spill_pending
            self._spill_pending, dropped = queue[:4096], queue[4096:]
            for k, _e in dropped:
                self._dropped_spill_keys.add(k)
            self.warm_spill_drops += len(dropped)
        if dropped:
            log.warning("spill queue overflow: %d evictee state(s) "
                        "dropped under disk pressure; their keys are "
                        "latched into resync", len(dropped))

    def spill_debt(self) -> int:
        """Keys whose evictee spill was dropped at the requeue bound and
        has not yet healed. While non-zero, ``winstore.checkpoint`` must
        not retire WAL generations: their records are these keys' acked
        pushes' ONLY durable copy (replay is idempotent, so keeping them
        is free of double-splice risk)."""
        with self._lock:
            return len(self._dropped_spill_keys)

    def _flush_spills(self) -> None:
        """Write queued evictee spills (no cache lock held). A failed
        write (disk full) degrades — counted and REQUEUED, never raised:
        this runs on the FETCH path after a successful backend fetch,
        and durability I/O must not fail the cycle that already has its
        data. The requeue matters: these entries may hold acked pushes
        whose WAL records a checkpoint wants to retire, so their state
        must stay flushable until it lands (spill_dirty drains this
        queue before any WAL generation is dropped)."""
        if self.store is None:
            return
        with self._lock:
            if not self._spill_pending:
                return
            pending, self._spill_pending = self._spill_pending, []
            states = [self._entry_state(k, e) for k, e in pending]
        for i, st in enumerate(states):
            try:
                self.store.spill(st)
            except OSError as e:
                self.store.count_spill_error(e)
                self._requeue_spills(pending[i:])
                return
            with self._lock:
                self.warm_spills += 1
                # a successfully spilled queued state is at least as new
                # as whatever drop latched this key: debt settled
                self._dropped_spill_keys.discard(pending[i][0])

    def _promote(self, key: str) -> _Entry | None:
        """Load ``key`` back into the hot LRU (cache miss path): the
        pending-spill queue first, then the warm segment. Returns the
        hot entry, or None when neither tier has it. The segment read
        happens before the cache lock; a racing prime wins and the load
        is discarded."""
        if self.store is None:
            return None
        with self._lock:
            cur = self._cache.get(key)
            if cur is not None:
                return cur
            # an evicted-but-unwritten state in the queue is NEWER than
            # any warm record (disk pressure kept it from landing);
            # promoting the stale record instead would let fresh pushes
            # advance the horizon over the queued samples — a hole the
            # serve path would then vouch for. Latest queued wins.
            for i in range(len(self._spill_pending) - 1, -1, -1):
                k, e = self._spill_pending[i]
                if k == key:
                    del self._spill_pending[i]
                    self._cache[key] = e
                    self._cache.move_to_end(key)
                    self.warm_promotes += 1
                    self._evict_overflow_locked()
                    return e
        state = self.store.load(key)
        if state is None:
            return None
        from .winstore import WindowStore

        entry = _Entry(WindowStore.state_window(state), state["qstart"],
                       state["qend"], state["url_step"],
                       np.asarray(state["nan_ts"], np.float64),
                       state["full_bytes"], state["full_points"])
        entry.pushed_until = state["pushed_until"]
        entry.push_blocked = bool(state["push_blocked"])
        entry.dirty = False  # it IS the segment's state
        with self._lock:
            cur = self._cache.get(key)
            if cur is not None:
                return cur
            if key in self._dropped_spill_keys:
                # a NEWER state for this key was dropped on the way to
                # the segment: the warm record's pushed horizon may miss
                # acked samples, so it comes back latched until a poll
                # heals it (the latch consumes the drop marker)
                self._dropped_spill_keys.discard(key)
                entry.pushed_until = 0.0
                entry.push_blocked = True
                entry.dirty = True
            self._cache[key] = entry
            self._cache.move_to_end(key)
            self.warm_promotes += 1
            self._evict_overflow_locked()
        self._flush_spills()
        return entry

    def spill_dirty(self) -> int:
        """Checkpoint half: write every dirty hot entry AND every queued
        evictee to the warm segment (winstore.checkpoint drives this
        after rotating the WAL — evictees sitting in ``_spill_pending``
        belong to the checkpoint too, because the WAL generation about
        to be dropped may hold their acked pushes). Snapshot under the
        lock, write outside it; a failed write re-marks/requeues its
        entry and RAISES so the checkpoint keeps ``wal.old`` — the
        record-or-effect invariant."""
        if self.store is None:
            return 0
        with self._lock:
            pending, self._spill_pending = self._spill_pending, []
            states_p = [self._entry_state(k, e) for k, e in pending]
        spilled = 0
        for i, st in enumerate(states_p):
            try:
                self.store.spill(st)
            except OSError:
                self._requeue_spills(pending[i:])
                raise
            spilled += 1
            with self._lock:
                self._dropped_spill_keys.discard(pending[i][0])
        with self._lock:
            batch = [(k, e) for k, e in self._cache.items() if e.dirty]
            states = []
            for k, e in batch:
                states.append(self._entry_state(k, e))
                e.dirty = False
        for i, ((k, e), st) in enumerate(zip(batch, states)):
            try:
                self.store.spill(st)
            except OSError:
                # the WHOLE batch was marked clean at snapshot time: re-
                # dirty every entry whose spill never ran, or the next
                # (successful) checkpoint would retire the WAL generation
                # holding their acked pushes with no durable effect. An
                # entry EVICTED while clean mid-checkpoint re-dirties an
                # orphan the dirty sweep can never see again — those go
                # back through the pending queue instead.
                requeue = []
                with self._lock:
                    queued = {id(e2) for _k2, e2 in self._spill_pending}
                    for k2, e2 in batch[i:]:
                        if self._cache.get(k2) is e2:
                            e2.dirty = True
                        elif id(e2) not in queued:
                            # (re-dirtied-then-evicted entries already
                            # queued themselves — don't double-book the
                            # bounded queue's slots)
                            requeue.append((k2, e2))
                if requeue:
                    self._requeue_spills(requeue)
                raise
            spilled += 1
            with self._lock:
                self._dropped_spill_keys.discard(k)
        with self._lock:
            self.warm_spills += spilled
        return spilled

    def force_resync(self) -> None:
        """Latch EVERY cached entry into resync mode (WAL corruption on
        recovery: pushed horizons can no longer be trusted store-wide;
        the poll path heals each entry and lifts its latch)."""
        with self._lock:
            for entry in self._cache.values():
                entry.pushed_until = 0.0
                entry.push_blocked = True
                entry.dirty = True

    # ------------------------------------------------------------- ingest
    def ingest_append(self, url: str, ts, vals) -> dict:
        """Splice PUSHED samples into the cached window for this query —
        the same frozen-copy + resample geometry as the delta splice, so
        the grown window is byte-identical to a full refetch of a backend
        holding the same samples (the interleaved push+poll property test
        in tests/test_delta.py).

        Returns an outcome dict the receiver turns into counters:
        ``{"spliced": n, "advanced": bool, "reason": str|None}`` —
        ``reason`` (when nothing spliced) is ``no_range`` (URL not
        delta-capable), ``no_entry`` (nothing cached yet: the caller
        buffers until a poll primes the entry), ``off_grid`` (push
        timestamps not on the step grid), ``stale`` (nothing newer
        than the cache — duplicate delivery, dropped), or ``late``
        (below).

        Only samples STRICTLY newer than the newest cached sample are
        accepted: the frozen region stays immutable (the delta coherence
        contract), and a pushed rewrite of history is exactly the
        divergence the poll path's splice-mismatch canary exists to
        catch, not something to honor. Older timestamps are safe to drop
        only when the cache already HOLDS them (duplicate delivery —
        remote-write retries after a lost ack). An older timestamp the
        cache does NOT hold is a LATE arrival: batch k landing after
        k+1 was spliced. Dropping it silently would leave a hole the
        backend doesn't have inside the pushed horizon, so the entry
        latches into resync instead (``reason="late"``) and the poll
        path heals it — the byte-identical-or-resync contract pinned by
        the push-chaos property tests."""
        rng = parse_range_params(url)
        if rng is None:
            self._count_ingest_reject("no_range")
            return {"spliced": 0, "advanced": False, "reason": "no_range"}
        step = self.step
        key = self._cache_key(url, rng)
        with self._lock:
            entry = self._cache.get(key)
        if entry is None:
            # warm tier: a spilled (or crash-recovered) entry serves the
            # splice as if it never left RAM — this is also how boot-time
            # WAL replay finds its entries
            entry = self._promote(key)
        if entry is None:
            return {"spliced": 0, "advanced": False, "reason": "no_entry"}
        if entry.push_blocked:
            # the push stream for this query has a known hole (the
            # receiver dropped spliceable samples): no splice is sound
            # until the poll path re-syncs the entry from the backend
            self._count_ingest_reject("resync")
            return {"spliced": 0, "advanced": False, "reason": "resync"}
        ts_f, vals_f, nan_new = _split_finite(ts, vals)
        if not _exact(ts_f, step) or nan_new.size > _MAX_NAN_TS \
                or ts_f.size == 0:
            self._count_ingest_reject("off_grid")
            return {"spliced": 0, "advanced": False, "reason": "off_grid"}
        with self._cpu_lock:
            w = entry.win
            valid_ts = (w.start
                        + np.nonzero(w.mask)[0].astype(np.float64) * w.step)
            sample_ts = np.concatenate([valid_ts, entry.nan_ts])
            last = float(np.max(sample_ts)) if sample_ts.size else -np.inf
            fresh = ts_f > last
            ts_new, vals_new = ts_f[fresh], vals_f[fresh]
            # late-arrival canary: a non-fresh timestamp the cache does
            # not hold means the push stream reordered ACROSS batches —
            # dropping it would punch a hole inside the pushed horizon
            # that the backend doesn't have. Latch resync; the poll path
            # heals the entry and lifts the latch. (Timestamps the cache
            # DOES hold are plain duplicate delivery and drop free.)
            # Only timestamps inside the RETAINED span [w.start, last]
            # are evidence: below it, a missing ts is indistinguishable
            # from a clipped-out duplicate (remote-write retries of
            # long-queued data), and pre-span history is outside the
            # module's coherence contract anyway — the serve path never
            # vouches for slots below w.start.
            old_ts = np.concatenate([ts_f[~fresh], nan_new[nan_new <= last]])
            old_ts = old_ts[old_ts >= float(w.start)]
            if old_ts.size and not np.isin(old_ts, sample_ts).all():
                with self._lock:
                    if self._cache.get(key) is entry:
                        entry.pushed_until = 0.0
                        entry.push_blocked = True
                        entry.dirty = True
                self._count_ingest_reject("late")
                return {"spliced": 0, "advanced": False, "reason": "late"}
            nan_new = nan_new[nan_new > last]
            if ts_new.size == 0:
                return {"spliced": 0, "advanced": False, "reason": "stale"}
            first_new = float(np.min(ts_new))
            all_min = min(float(np.min(sample_ts)) if sample_ts.size
                          else np.inf, first_new)
            all_max = float(np.max(ts_new))
            cap = TS_SPAN_CAP
            end = align_step(float(np.clip(all_max, -cap, cap)), step) + step
            start = max(align_step(float(np.clip(all_min, -cap, cap)), step),
                        end - MAX_WINDOW_STEPS * step)
            out = resample_to_grid(ts_new, vals_new, start, end, step)
            boundary = int(max(first_new - start, 0) // step)
            # frozen region: the cached grid's slots in [start, boundary)
            _copy_frozen(out, w, boundary)

            frozen_nan = entry.nan_ts[entry.nan_ts >= start]
            nan_ts = np.unique(np.concatenate([frozen_nan, nan_new]))
            if nan_ts.size > _MAX_NAN_TS:
                self._count_ingest_reject("off_grid")
                return {"spliced": 0, "advanced": False,
                        "reason": "off_grid"}
            total_points = int(out.mask.sum() + nan_ts.size)
            with self._lock:
                if self._cache.get(key) is not entry:
                    # evicted while we were splicing: drop the work (a
                    # later poll rebuilds the entry from the backend)
                    return {"spliced": 0, "advanced": False,
                            "reason": "evicted"}
                grow = max(total_points - entry.full_points, 0)
                if entry.full_points and entry.full_bytes:
                    entry.full_bytes += int(
                        grow * entry.full_bytes / entry.full_points)
                entry.full_points = total_points
                entry.win = out
                entry.nan_ts = nan_ts
                entry.pushed_until = max(entry.pushed_until, all_max)
                entry.dirty = True
                self.ingest_spliced_points += int(ts_new.size)
                self._cache.move_to_end(key)
        return {"spliced": int(ts_new.size), "advanced": True,
                "reason": None}

    def ingest_block(self, url: str) -> None:
        """Latch a query into resync mode: the caller dropped pushed
        samples the backend still has, so the cached entry's pushed
        horizon is no longer trustworthy — stop serving from it and
        refuse further splices until a poll-driven refresh clears the
        latch. No-op for queries with no cached state ANYWHERE — then
        there is no pushed horizon to poison and the first prime comes
        from a poll."""
        rng = parse_range_params(url)
        if rng is None:
            return
        key = self._cache_key(url, rng)
        with self._lock:
            entry = self._cache.get(key)
        if entry is None:
            # the hole hazard applies to SPILLED entries too: a warm
            # state with a pushed horizon must come back latched, or a
            # later promote would serve around the dropped samples
            entry = self._promote(key)
        if entry is not None:
            with self._lock:
                entry.pushed_until = 0.0
                entry.push_blocked = True
                entry.dirty = True  # the latch must survive a restart

    def _try_ingest_serve(self, key, entry, rng, url_s: float):
        """Serve a requested range entirely from the push-fed cache, or
        None to fall through to the delta/full path, with the URL seconds
        `url_s` still to note (0 once noted with the lock's). Safe only
        while the pushed horizon covers every on-grid slot the query's end
        could hold (``qend < pushed_until + step``) and the cache provably
        retains every sample at/after the requested start."""
        qstart, qend, url_step = rng
        if url_step != entry.url_step or qstart < entry.qstart:
            return None, url_s
        t0 = time.perf_counter()
        with self._cpu_lock:
            t1 = time.perf_counter()
            out = self._serve_pushed(key, entry, qstart, qend)
            t2 = time.perf_counter()
        _note_fetch_seconds(t1 - t0, t2 - t1, 0.0, url_s)
        return out, 0.0

    def _serve_pushed(self, key, entry, qstart, qend):
        """`_try_ingest_serve` under the cpu lock."""
        step = self.step
        if entry.pushed_until <= 0:
            return None
        # coverage proof: every on-grid sample the backend could
        # return at/below the EFFECTIVE end is already in the cache.
        # A future query end clamps to the wall clock — the backend
        # cannot hold samples from the future either.
        eff_end = min(qend, float(self.clock()))
        if eff_end >= entry.pushed_until + step:
            return None
        w = entry.win
        if w.values.shape[0] >= MAX_WINDOW_STEPS:
            # span-clipped cache: samples may have been dropped at
            # the head, so full-refetch geometry is no longer
            # provable from the cache alone
            return None
        valid_ts = (w.start
                    + np.nonzero(w.mask)[0].astype(np.float64) * w.step)
        all_ts = np.concatenate([valid_ts, entry.nan_ts])
        sel = (all_ts >= qstart) & (all_ts <= qend)
        if not np.any(sel):
            return None
        mn = float(np.min(all_ts[sel]))
        mx = float(np.max(all_ts[sel]))
        end = align_step(mx, step) + step
        start = max(align_step(mn, step), end - MAX_WINDOW_STEPS * step)
        off = int((start - w.start) // step)
        n = int((end - start) // step)
        if off < 0 or off + n > w.values.shape[0]:
            return None
        out = Window(w.values[off:off + n].copy(),
                     w.mask[off:off + n].copy(), int(start), step)
        with self._lock:
            if self._cache.get(key) is entry:  # evicted mid-serve?
                self._cache.move_to_end(key)
        return out

    # ------------------------------------------------------------- fetch
    def fetch_window(self, url: str) -> Window:
        # the URL's seconds (the range's parse, the cache key) are noted
        # once a fetch, with the rest of what its path notes
        t0 = time.perf_counter()
        rng = parse_range_params(url)
        if rng is None:
            url_s = time.perf_counter() - t0
            # no parseable range: never delta-capable, so keep the inner
            # source's fused byte->Window fast path when it has one
            with self._lock:
                self.full_fetches += 1
            tracing.tracer.add_note("fetch_full")
            fw = getattr(self.inner, "fetch_window", None)
            if fw is not None:
                win = fw(url)
                if win is not None:
                    tracing.tracer.add_note("url_thread_seconds", url_s)
                    return win
            return self._full(url, None, None, url_s)
        # key = URL minus start/end values, PLUS the log2 bucket of the
        # range span: a job's current and historical windows often share
        # the same underlying query and differ only in their range
        # (continuous jobs re-materialize both from one query each
        # cycle), so the bare stripped URL would collapse the two roles
        # into one entry that they thrash — each historical fetch a
        # range_extended full refetch of the 7-day body, forever. The
        # span's power-of-two bucket separates the roles (30-min vs
        # 7-day spans land 9 buckets apart) while staying stable for
        # trailing windows (constant span) and for fixed-start/growing-
        # end windows (one extra miss per span doubling).
        key = self._cache_key(url, rng)
        url_s = time.perf_counter() - t0
        # read before the lock: the clock is the caller's own function
        closed_before = float(self.clock()) - self.overlap_steps * self.step
        win = None
        with self._lock:
            entry = self._cache.get(key)
            if entry is not None:
                self._cache.move_to_end(key)
                if self._unmoved(entry, rng, closed_before):
                    # the stored object itself, as the splice path returns
                    # the object it stores: no caller writes into a Window
                    win = entry.win
                    self.unmoved_hits += 1
                    self.points_saved += entry.full_points
                    self.bytes_saved += entry.full_bytes
        if win is not None:
            tracing.tracer.add_note("fetch_unmoved")
            tracing.tracer.add_note("url_thread_seconds", url_s)
            return win
        if entry is None:
            # warm tier first: a spilled/recovered entry promotes back to
            # the hot LRU and serves through the normal pushed/delta
            # paths — a restart costs a segment read, not a refetch storm
            entry = self._promote(key)
        if entry is None:
            with self._lock:
                self.full_fetches += 1
            tracing.tracer.add_note("fetch_full")
            return self._full(url, key, rng, url_s)
        if entry.pushed_until > 0:
            # streamed path: pushed samples already cover the requested
            # range end — serve the window without touching the backend
            win, url_s = self._try_ingest_serve(key, entry, rng, url_s)
            if win is not None:
                with self._lock:
                    self.ingest_hits += 1
                tracing.tracer.add_note("fetch_ingest")
                return win
        win, url_s = self._try_delta(url, key, rng, entry, url_s)
        with self._lock:
            if win is not None:
                self.delta_hits += 1
            else:
                self.full_fetches += 1
        # per-job fetch provenance (thread-local note, read by the engine's
        # preprocess bracket): delta splice vs full refetch
        tracing.tracer.add_note("fetch_delta" if win is not None
                                else "fetch_full")
        if win is not None:
            return win
        return self._full(url, key, rng, url_s)

    def _unmoved(self, entry, rng, closed_before: float) -> bool:
        """The closed-range rule (caller holds ``_lock``, under which every
        refresh of an entry is written): True when the cached window IS
        what a splice of this request would rebuild, so it can be returned
        as it stands, with no backend query and no splice. All four hold:

          1. the entry is poll-fed, trusted and whole: no pushed horizon
             (that path keeps ``_try_ingest_serve``), no resync latch, no
             span clip at the head;
          2. the range has not moved since the entry was last refreshed;
          3. the tail is full: the next on-grid slot after the newest
             sample (valid or NaN-valued: the window's last slot, since
             entries are exact-grid) lies beyond the range's end, so a
             backend that only appends has nothing to add;
          4. the range is closed: its end is at least ``overlap_steps``
             old by the source's clock, the age below which a splice
             re-reads samples and above which it never does.
        """
        qstart, qend, url_step = rng
        w = entry.win
        n = w.values.shape[0]
        if (entry.pushed_until != 0 or entry.push_blocked
                or n >= MAX_WINDOW_STEPS):
            return False
        if (url_step != entry.url_step or qstart != entry.qstart
                or qend != entry.qend):
            return False
        last_end = w.start + (n - 1) * w.step
        return qstart <= last_end and last_end + w.step > qend \
            and qend <= closed_before

    def _full(self, url: str, key, rng, url_s: float) -> Window:
        """Full refetch; (re)prime the cache entry when the response is
        exact-grid (spliceable next cycle). `url_s`: the URL seconds still
        to note."""
        t0 = time.perf_counter()
        ts, vals, nbytes = self._series(url)
        t1 = time.perf_counter()
        with self._cpu_lock:
            t2 = time.perf_counter()
            win = self._full_grid(ts, vals, nbytes, key, rng)
            t3 = time.perf_counter()
        _note_fetch_seconds(t2 - t1, t3 - t2, t1 - t0, url_s)
        self._flush_spills()
        return win

    def _full_grid(self, ts, vals, nbytes, key, rng) -> Window:
        win = grid_from_series(ts, vals, self.step)
        if key is None:
            return win
        ts_f, _, nan_ts = _split_finite(ts, vals)
        qstart, qend, url_step = rng
        if (not _exact(ts_f, self.step) or nan_ts.size > _MAX_NAN_TS
                or ts_f.size == 0):
            # off-grid or pathological body: drop the entry so we never
            # splice against it (and re-check on every later full fetch)
            with self._lock:
                self._cache.pop(key, None)
            if ts_f.size:
                self._count_fallback("off_grid")
            return win
        with self._lock:
            # a fresh poll prime starts push-clean (pushed_until=0), so a
            # pending dropped-spill latch for the key is now satisfied
            self._dropped_spill_keys.discard(key)
            self._cache[key] = _Entry(win, qstart, qend, url_step,
                                      nan_ts, nbytes, int(ts_f.size))
            self._cache.move_to_end(key)
            self._evict_overflow_locked()
        return win

    def _try_delta(self, url, key, rng, entry, url_s: float):
        """Splice path. Returns the spliced Window, or None to signal a
        full refetch (the caller counts it; reasons counted here), with
        the URL seconds `url_s` still to note (0 once noted)."""
        qstart, qend, url_step = rng
        step = self.step
        if entry.push_blocked:
            # resync latch: the entry's frozen region may hide holes the
            # backend does not have (late pushes dropped, WAL corruption)
            # DEEPER than the overlap window, where the tail query and
            # its splice-mismatch canary never look. Only a full refetch
            # re-establishes trust (and re-primes a clean entry).
            self._count_fallback("resync")
            return None, url_s
        if url_step != entry.url_step:
            self._count_fallback("step_change")
            return None, url_s
        if qstart < entry.qstart:
            # range extends backwards past what the cache ever covered
            self._count_fallback("range_extended")
            return None, url_s
        # t0..t6: where this fetch's seconds go (_note_fetch_seconds); a
        # fallback that returns from under the first lock notes nothing
        t0 = time.perf_counter()
        with self._cpu_lock:
            t1 = time.perf_counter()
            w, nan_ts = entry.win, entry.nan_ts
            n = w.values.shape[0]
            # the append rule's half that is known before the query. An
            # exact grid spans its own first and last sample, and every
            # sample lies on a slot: when the first slot the range keeps
            # (`j0`) and the last slot both hold a valid sample, no
            # NaN-valued sample anchors the span, the newest sample is
            # the last slot, and no timestamp is rebuilt to learn it
            j0 = 0 if qstart <= w.start else -int((w.start - qstart) // step)
            appendable = (w.step == step and j0 < n
                          and w.mask[j0] and w.mask[n - 1])
            if appendable:
                valid_ts = sample_ts = None
                last_end = float(w.start + (n - 1) * step)
            else:
                valid_ts, sample_ts = _cached_sample_ts(w, nan_ts, qstart)
                if sample_ts.size == 0:
                    self._count_fallback("empty_cache_range")
                    return None, url_s
                last_end = float(np.max(sample_ts))
            delta_start = max(qstart, last_end - self.overlap_steps * step)
            if delta_start > qend:
                self._count_fallback("range_regressed")
                return None, url_s
            t2 = time.perf_counter()

        # a delta-query failure propagates like a full-fetch failure would:
        # same backend, same URL shape — the resilience layer already ran.
        # The fetch itself stays OUTSIDE the cpu lock: network I/O is the
        # part that genuinely overlaps across the engine's fetch pool. The
        # query's URL is the URL's work, not the source's.
        delta_url = _set_range(url, delta_start, qend)
        t3 = time.perf_counter()
        ts_d, vals_d, nbytes = self._series(delta_url)
        t4 = time.perf_counter()
        with self._cpu_lock:
            t5 = time.perf_counter()
            out = self._append_tail(
                key, entry, w, nan_ts, j0, delta_start, qstart, qend, ts_d,
                vals_d, nbytes) if appendable else None
            appended = out is not None
            if not appended:
                # the general path, on the response already in hand: the
                # backend is asked once a request
                if appendable:
                    valid_ts, sample_ts = _cached_sample_ts(w, nan_ts,
                                                            qstart)
                out = self._splice(key, entry, w, valid_ts, sample_ts,
                                   delta_start, qstart, qend, ts_d, vals_d,
                                   nbytes)
            t6 = time.perf_counter()
        _note_fetch_seconds((t1 - t0) + (t5 - t4), (t2 - t1) + (t6 - t5),
                            t4 - t3, url_s + (t3 - t2))
        if appended:
            tracing.tracer.add_note("fetch_append")
        return out, 0.0

    def _append_tail(self, key, entry, w, nan_ts, j0, delta_start, qstart,
                     qend, ts_d, vals_d, nbytes) -> Window | None:
        """The append rule's half that reads the response (caller holds
        the cpu lock, and found the entry appendable: `w` is an exact grid
        at the source's step, slot `j0` is the first the range keeps, it
        and the last slot hold valid samples; `nan_ts` are the entry's
        NaN-valued samples' timestamps, all inside `w`). Returns the grown
        Window, or None for the general splice.

        The tail must be what a backend that only appends returns for
        `[delta_start, qend]`: one finite (as float32) sample on every
        grid slot from the first slot it re-read to its newest, which is
        no older than the cached newest. Then a full refetch grids to the
        cached slots from `j0` up to the tail plus the tail, provided the
        overlap (bar the one most recent cached point, as the canary in
        `_splice` has it) is all valid samples and equals the tail there."""
        step = self.step
        ts_d = np.asarray(ts_d, np.float64)
        vals_d = np.asarray(vals_d, np.float64)
        k = ts_d.size
        if k == 0 or ts_d.ndim != 1 or vals_d.shape != ts_d.shape:
            return None
        n = w.values.shape[0]
        start = w.start + j0 * step
        # the first slot the query re-read: past the range's first slot,
        # `delta_start` is a slot of the grid
        first = max(int(delta_start), start)
        i0 = (first - w.start) // step
        if (ts_d[0] != first  # a hole, a backfilled head, off the grid
                or i0 + k < n  # the backend lost the newest sample(s)
                or i0 - j0 + k > MAX_WINDOW_STEPS  # the head would be cut
                or first + (k - 1) * step >= min(TS_SPAN_CAP, 2.0**53)):
            return None
        with np.errstate(over="ignore", invalid="ignore"):
            # a gap, a repeat, an out-of-order or a non-finite timestamp
            ragged = np.count_nonzero(ts_d[1:] - ts_d[:-1] != step)
            v32 = vals_d.astype(np.float32)  # the cast IS the finiteness check
        m = n - 1 - i0  # overlap slots the canary compares
        if (ragged or np.count_nonzero(np.isfinite(v32)) != k
                or np.count_nonzero(w.mask[i0:n - 1]) != m
                or np.count_nonzero(w.values[i0:n - 1] != v32[:m])):
            return None
        kept = i0 - j0  # cached slots below the tail
        values = np.empty(kept + k, np.float32)
        values[:kept] = w.values[j0:i0]
        values[kept:] = v32
        mask = np.empty(kept + k, bool)
        mask[:kept] = w.mask[j0:i0]
        mask[kept:] = True
        out = Window(values, mask, start, step)
        if nan_ts.size:
            # NaN-valued samples the range keeps and the tail did not
            # re-read (the tail holds none)
            nan_ts = nan_ts[(nan_ts >= start) & (nan_ts < first)]
        self._refresh(key, entry, out, nan_ts, qstart, qend, k,
                      int(np.count_nonzero(mask)) + nan_ts.size, nbytes,
                      appended=True)
        return out

    def _splice(self, key, entry, w, valid_ts, sample_ts, delta_start,
                qstart, qend, ts_d, vals_d, nbytes) -> Window | None:
        step = self.step
        ts_d, vals_d, nan_d = _split_finite(ts_d, vals_d)
        if not _exact(ts_d, step) or nan_d.size > _MAX_NAN_TS:
            self._count_fallback("off_grid")
            return None
        # a real backend only returns in-range samples; anything below the
        # delta range start belongs to the frozen region (served from cache)
        in_range = ts_d >= delta_start
        ts_d, vals_d = ts_d[in_range], vals_d[in_range]
        nan_d = nan_d[nan_d >= delta_start]
        if ts_d.size == 0:
            # the overlap sample(s) vanished: retention gap / series reset
            self._count_fallback("retention_gap")
            return None

        # full-fetch grid geometry from the union of frozen + delta samples
        frozen_sel = sample_ts < delta_start
        all_min = min(float(np.min(sample_ts[frozen_sel]))
                      if frozen_sel.any() else np.inf, float(np.min(ts_d)))
        all_max = max(float(np.max(sample_ts[frozen_sel]))
                      if frozen_sel.any() else -np.inf, float(np.max(ts_d)))
        cap = TS_SPAN_CAP
        end = align_step(float(np.clip(all_max, -cap, cap)), step) + step
        start = max(align_step(float(np.clip(all_min, -cap, cap)), step),
                    end - MAX_WINDOW_STEPS * step)
        out = resample_to_grid(ts_d, vals_d, start, end, step)
        boundary = int(max((delta_start - start), 0) // step)
        n = out.values.shape[0]
        # frozen region: the cached grid's slots in [start, boundary)
        # (both starts are aligned)
        _copy_frozen(out, w, boundary)

        # splice-mismatch canary: the delta's overlap region (everything it
        # re-fetched below the previous last sample, bar the one most
        # recent point — in-flight rate windows legitimately rewrite it)
        # must agree with the cached grid; disagreement means history
        # moved under us.
        prev_last_valid = float(np.max(valid_ts)) if valid_ts.size else -np.inf
        chk_lo = int(max(delta_start - start, 0) // step)
        chk_hi = int(max(prev_last_valid - step - start + step, 0) // step)
        chk_hi = min(chk_hi, n)
        if chk_hi > chk_lo:
            c_lo = int((start - w.start) // w.step) + chk_lo
            c_hi = c_lo + (chk_hi - chk_lo)
            if c_lo < 0 or c_hi > w.values.shape[0]:
                self._count_fallback("splice_mismatch")
                return None
            cm = w.mask[c_lo:c_hi]
            if (not np.array_equal(out.mask[chk_lo:chk_hi], cm)
                    or not np.array_equal(out.values[chk_lo:chk_hi][cm],
                                          w.values[c_lo:c_hi][cm])):
                self._count_fallback("splice_mismatch")
                return None

        # accounting + entry refresh
        frozen_nan = entry.nan_ts[(entry.nan_ts >= start)
                                  & (entry.nan_ts < delta_start)]
        nan_ts = np.unique(np.concatenate([frozen_nan, nan_d]))
        if nan_ts.size > _MAX_NAN_TS:
            self._count_fallback("off_grid")
            return None
        self._refresh(key, entry, out, nan_ts, qstart, qend, int(ts_d.size),
                      int(out.mask.sum() + nan_ts.size), nbytes)
        return out

    def _refresh(self, key, entry, out, nan_ts, qstart, qend, points,
                 total_points, nbytes, appended=False) -> None:
        """Accounting and entry refresh of one delta hit: `points` samples
        in `nbytes` came from the backend, and the entry now holds `out`,
        `total_points` samples in all."""
        with self._lock:
            if appended:
                self.append_hits += 1
            self.bytes_delta += nbytes
            self.points_saved += max(entry.full_points - points, 0)
            if nbytes and entry.full_bytes:
                self.bytes_saved += max(entry.full_bytes - nbytes, 0)
            elif entry.full_bytes and entry.full_points:
                per_pt = entry.full_bytes / max(entry.full_points, 1)
                self.bytes_saved += int(
                    per_pt * max(entry.full_points - points, 0))
            # full_bytes/full_points track what a full refetch WOULD cost
            # now: the window only grows by the delta's fresh points
            grow = max(total_points - entry.full_points, 0)
            if entry.full_points:
                entry.full_bytes += int(
                    grow * entry.full_bytes / entry.full_points)
            entry.full_points = total_points
            entry.win = out
            entry.qstart, entry.qend = qstart, qend
            entry.nan_ts = nan_ts
            entry.dirty = True
            # a poll-driven splice re-established the backend as the
            # source of truth; the pushed horizon re-arms on the next
            # push, and any resync latch is satisfied
            entry.pushed_until = 0.0
            entry.push_blocked = False
            # the entry may have been EVICTED by a concurrent fetch while
            # this splice held only the cpu lock (a hot cache smaller
            # than the in-flight fetch set): the spliced window is still
            # correct to return, but a bare move_to_end would KeyError
            if self._cache.get(key) is entry:
                self._cache.move_to_end(key)
