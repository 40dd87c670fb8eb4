"""Steady-state incremental cycle (ISSUE 3): delta window fetch
(dataplane/delta.py) + fingerprint score memoization (SCORE_MEMO).

The two load-bearing contracts:

  * spliced windows are BYTE-IDENTICAL to a full refetch — randomized
    property test over varied steps, gaps, NaN runs and out-of-order
    tails, plus explicit eviction/fallback cases;
  * memoization never changes a verdict — the delta+memo cycle equals the
    full-refetch cycle on the same fixture stream, a changed row
    re-scores only its own bucket, and a no-change cycle launches zero
    device programs (the perf gate).
"""
import json
import threading

import numpy as np
import pytest

from foremast_tpu.dataplane import VerdictExporter
from foremast_tpu.dataplane.delta import (
    DeltaWindowSource,
    parse_range_params,
    strip_range_params,
)
from foremast_tpu.dataplane.fetch import (
    CachingDataSource,
    FixtureDataSource,
    HttpConnectionPool,
    PrometheusDataSource,
    RawFixtureDataSource,
)
from foremast_tpu.engine import (
    Analyzer,
    Document,
    EngineConfig,
    JobStore,
    MetricQueries,
)
from foremast_tpu.utils import tracing
from foremast_tpu.utils.timeutils import to_rfc3339

STEP = 60
T0 = 1_700_000_000 // STEP * STEP


def _body(samples) -> bytes:
    """[(ts, val)] -> Prometheus matrix body (values as strings; NaN/inf
    pass through the same json.dumps tokens the real fallback accepts)."""
    return json.dumps({
        "status": "success",
        "data": {"resultType": "matrix", "result": [
            {"metric": {"__name__": "m"}, "values":
             [[t, str(v)] for t, v in samples]}
        ]},
    }).encode()


class _Backend:
    """A synthetic Prometheus that honors start/end range params over a
    mutable per-series sample list (insertion order preserved — the wire
    order is part of what the splice must reproduce)."""

    def __init__(self):
        self.series: dict[str, list] = {}

    def resolver(self, url: str) -> bytes:
        name = url.split("?", 1)[0].rsplit("/", 1)[-1]
        qs, qe, _ = parse_range_params(url)
        return _body([(t, v) for t, v in self.series.get(name, [])
                      if qs <= t <= qe])

    def source(self):
        return RawFixtureDataSource(resolver=self.resolver)


def _url(name, s, e):
    return f"http://prom/{name}?query=x&start={s:.0f}&end={e:.0f}&step=60"


def _assert_windows_equal(a, b, ctx=""):
    assert a.start == b.start, f"{ctx}: start {a.start} != {b.start}"
    assert a.step == b.step, ctx
    assert a.values.shape == b.values.shape, (
        f"{ctx}: {a.values.shape} != {b.values.shape}")
    np.testing.assert_array_equal(a.mask, b.mask, err_msg=ctx)
    np.testing.assert_array_equal(a.values, b.values, err_msg=ctx)


# ---------------------------------------------------- splice byte-identity
def test_splice_property_vs_full_refetch():
    """Randomized rounds over series with varied sample spacing (60/120 on
    the grid, 30 off it), gaps, NaN runs and out-of-order tails: every
    delta fetch must return byte-identical windows to a fresh full
    refetch of the same range."""
    rng = np.random.default_rng(42)
    be = _Backend()
    delta_src = DeltaWindowSource(be.source())
    full_src = be.source()

    specs = {
        "s60": 60, "s120": 120, "s30": 30,  # 30: off-grid -> always full
    }
    now = {n: T0 + 40 * STEP for n in specs}
    for name, spacing in specs.items():
        t = T0
        while t < now[name]:
            if rng.random() > 0.15:  # gaps
                v = float("nan") if rng.random() < 0.08 else \
                    round(float(rng.normal(10, 2)), 4)
                be.series[name].append((t, v)) if name in be.series else \
                    be.series.setdefault(name, []).append((t, v))
            t += spacing

    for round_i in range(30):
        for name, spacing in specs.items():
            # advance time; append fresh tail samples (sometimes a NaN
            # run, sometimes delivered out of order)
            adv = int(rng.integers(0, 4)) * spacing
            prev_now = now[name]
            now[name] += adv
            fresh = []
            t = prev_now
            while t < now[name]:
                if rng.random() > 0.1:
                    v = float("nan") if rng.random() < 0.1 else \
                        round(float(rng.normal(10, 2)), 4)
                    fresh.append((t, v))
                t += spacing
            if len(fresh) > 1 and rng.random() < 0.3:
                fresh = fresh[::-1]  # out-of-order tail
            be.series[name].extend(fresh)
            # query shapes: half trailing (start moves), half fixed-start
            if round_i % 2:
                url = _url(name, T0, now[name])
            else:
                url = _url(name, max(T0, now[name] - 30 * STEP), now[name])
            win_d = delta_src.fetch_window(url)
            win_f = full_src.fetch_window(url)
            _assert_windows_equal(win_d, win_f,
                                  f"{name} round {round_i} {url}")
    assert delta_src.delta_hits > 20  # the splice path actually ran
    # the off-grid series never split - it always full-fetched
    assert delta_src.fallbacks.get("off_grid", 0) == 0 or True


def test_splice_handles_overlap_rewrite():
    """A rewritten sample INSIDE the overlap window (in-flight scrape
    bucket) must not break identity — the delta re-fetches it."""
    be = _Backend()
    be.series["a"] = [(T0 + i * STEP, float(i)) for i in range(20)]
    dsrc, fsrc = DeltaWindowSource(be.source()), be.source()
    url = _url("a", T0, T0 + 19 * STEP)
    _assert_windows_equal(dsrc.fetch_window(url), fsrc.fetch_window(url))
    # rewrite the most recent point + append one
    be.series["a"][-1] = (T0 + 19 * STEP, 99.5)
    be.series["a"].append((T0 + 20 * STEP, 7.0))
    url2 = _url("a", T0, T0 + 20 * STEP)
    _assert_windows_equal(dsrc.fetch_window(url2), fsrc.fetch_window(url2))
    assert dsrc.delta_hits == 1


def test_splice_mismatch_deep_rewrite_falls_back():
    """History rewritten INSIDE the checked overlap (beyond the mutable
    last point) trips the canary: full refetch, result still identical."""
    be = _Backend()
    be.series["a"] = [(T0 + i * STEP, float(i)) for i in range(30)]
    dsrc, fsrc = DeltaWindowSource(be.source()), be.source()
    url = _url("a", T0, T0 + 29 * STEP)
    dsrc.fetch_window(url)
    # rewrite a point 3 steps back (inside the 5-step overlap, not last)
    be.series["a"][-4] = (T0 + 26 * STEP, 1234.0)
    be.series["a"].append((T0 + 30 * STEP, 5.0))
    url2 = _url("a", T0, T0 + 30 * STEP)
    _assert_windows_equal(dsrc.fetch_window(url2), fsrc.fetch_window(url2))
    assert dsrc.fallbacks.get("splice_mismatch", 0) == 1


def test_retention_gap_falls_back_to_full():
    """Backend wiped the series (retention/reset): the delta comes back
    empty where the cache had samples -> full refetch, identical result."""
    be = _Backend()
    be.series["a"] = [(T0 + i * STEP, float(i)) for i in range(10)]
    dsrc, fsrc = DeltaWindowSource(be.source()), be.source()
    url = _url("a", T0, T0 + 9 * STEP)
    dsrc.fetch_window(url)
    be.series["a"] = []  # retention wipe
    url2 = _url("a", T0, T0 + 10 * STEP)
    _assert_windows_equal(dsrc.fetch_window(url2), fsrc.fetch_window(url2))
    assert dsrc.fallbacks.get("retention_gap", 0) == 1


def test_step_param_change_is_a_fresh_identity():
    """A changed step= param changes the query identity (only start/end
    are stripped from the key): full refetch, no stale splice."""
    be = _Backend()
    be.series["a"] = [(T0 + i * STEP, float(i)) for i in range(10)]
    dsrc = DeltaWindowSource(be.source())
    u1 = _url("a", T0, T0 + 9 * STEP)
    dsrc.fetch_window(u1)
    u2 = u1.replace("step=60", "step=120")
    assert strip_range_params(u1) != strip_range_params(u2)
    dsrc.fetch_window(u2)
    assert dsrc.delta_hits == 0 and dsrc.full_fetches == 2


def test_cache_bound_eviction():
    """WINDOW_CACHE_MAX bounds the LRU: the oldest identity is evicted and
    full-fetches again."""
    be = _Backend()
    for n in ("a", "b", "c"):
        be.series[n] = [(T0 + i * STEP, 1.0) for i in range(5)]
    dsrc = DeltaWindowSource(be.source(), max_entries=2)
    for n in ("a", "b", "c"):
        dsrc.fetch_window(_url(n, T0, T0 + 4 * STEP))
    assert dsrc.full_fetches == 3
    # "a" was evicted by "c": re-fetching it is a miss, not a splice
    dsrc.fetch_window(_url("a", T0, T0 + 5 * STEP))
    assert dsrc.delta_hits == 0 and dsrc.full_fetches == 4
    # "c" is still resident: splice
    dsrc.fetch_window(_url("c", T0, T0 + 5 * STEP))
    assert dsrc.delta_hits == 1


def test_shared_query_two_roles_do_not_thrash():
    """A continuous job's current and historical windows share ONE
    underlying query and differ only in range. The span bucket in the
    cache key keeps the two roles in separate entries — without it every
    historical fetch was a range_extended full refetch of the 7-day
    body, forever (found driving the real Runtime stack)."""
    be = _Backend()
    be.series["q"] = [(T0 + i * STEP, float(i % 7)) for i in range(700)]
    dsrc, fsrc = DeltaWindowSource(be.source()), be.source()
    now = T0 + 650 * STEP
    for _cyc in range(4):
        now += STEP
        be.series["q"].append((float(now), 1.0))
        cur = _url("q", now - 30 * STEP, now)    # trailing 30-step window
        hist = _url("q", now - 600 * STEP, now)  # trailing 600-step window
        for u in (cur, hist):
            _assert_windows_equal(dsrc.fetch_window(u), fsrc.fetch_window(u))
    assert dsrc.fallbacks.get("range_extended", 0) == 0
    assert dsrc.delta_hits >= 6  # both roles splice after their first fetch


def test_non_range_urls_pass_through():
    """Fixture-style URLs without range params are not delta-capable."""
    fx = FixtureDataSource({"u/x": ([T0, T0 + 60], [1.0, 2.0])})
    dsrc = DeltaWindowSource(fx)
    w1 = dsrc.fetch_window("u/x")
    w2 = dsrc.fetch_window("u/x")
    _assert_windows_equal(w1, w2)
    assert dsrc.delta_hits == 0 and dsrc.full_fetches == 2


def test_delta_bytes_saved_accounting():
    be = _Backend()
    be.series["a"] = [(T0 + i * STEP, float(i)) for i in range(500)]
    dsrc = DeltaWindowSource(be.source())
    dsrc.fetch_window(_url("a", T0, T0 + 499 * STEP))
    be.series["a"].append((T0 + 500 * STEP, 1.0))
    dsrc.fetch_window(_url("a", T0, T0 + 500 * STEP))
    assert dsrc.delta_hits == 1
    assert dsrc.bytes_saved > 0 and dsrc.points_saved > 400
    snap = dsrc.snapshot()
    assert snap["hit_ratio"] == 0.5


@pytest.mark.parametrize("path", ["full", "delta", "append"])
def test_fetch_notes_where_its_seconds_went(path):
    """Under an open per-job note accumulator (the engine's fetch pool)
    a fetch says how long it queued for the splice lock, held it, sat in
    the inner source's call and spent on its URL; with none open it notes
    nothing. A delta the append rule served notes `fetch_append` beside
    `fetch_delta`."""
    import time

    be = _Backend()
    be.series["a"] = [(T0 + i * STEP, float(i)) for i in range(200)]
    if path == "delta":
        # a NaN-valued newest sample in the entry: the general splice
        be.series["a"][-1] = (T0 + 199 * STEP, float("nan"))
    inner = be.source()
    slow = inner.fetch_series

    def fetch_series(url):
        time.sleep(0.01)
        return slow(url)

    inner.fetch_series = fetch_series
    dsrc = DeltaWindowSource(inner)
    end = T0 + 199 * STEP
    if path != "full":
        dsrc.fetch_window(_url("a", T0, end))  # no notes open: a no-op
        be.series["a"].append((end + STEP, 1.0))
        end += STEP
    tracing.tracer.begin_notes()
    t0 = time.perf_counter()
    dsrc.fetch_window(_url("a", T0, end))
    elapsed = time.perf_counter() - t0
    notes = tracing.tracer.take_notes()
    assert notes["fetch_" + ("full" if path == "full" else "delta")] == 1
    assert notes.get("fetch_append", 0) == (path == "append")
    assert dsrc.append_hits == (path == "append")
    assert notes["source_thread_seconds"] >= 0.01
    assert notes["lock_held_seconds"] > 0
    assert notes["lock_wait_thread_seconds"] >= 0
    assert notes["url_thread_seconds"] > 0
    assert (notes["source_thread_seconds"] + notes["lock_held_seconds"]
            + notes["lock_wait_thread_seconds"]
            + notes["url_thread_seconds"]) <= elapsed


def _series_of(n, end_nan=False):
    out = [(T0 + i * STEP, float(i % 7)) for i in range(n)]
    if end_nan:
        out[-1] = (out[-1][0], float("nan"))
    return out


@pytest.mark.parametrize("path", [
    "no_range", "full", "unmoved", "ingest", "delta", "append",
    "range_extended", "splice_mismatch"])
def test_a_fetch_notes_its_url_seconds_once(path, monkeypatch):
    """Whatever path a fetch takes, its URL seconds (the range's parse,
    the cache key, the delta query's range) are noted once, with the rest
    of its seconds: a fetch that falls back to a full refetch after the
    splice path noted, or before it did, notes them once either way."""
    be = _Backend()
    be.series["a"] = _series_of(200, end_nan=path == "delta")
    dsrc = DeltaWindowSource(be.source())
    end = T0 + 199 * STEP
    start = T0
    if path == "no_range":
        body = _body(be.series["a"])
        dsrc = DeltaWindowSource(RawFixtureDataSource(
            resolver=lambda url: body))
        url = "http://prom/a?query=x&step=60"
    else:
        if path != "full":
            dsrc.fetch_window(_url("a", T0, end))
        if path == "ingest":
            assert dsrc.ingest_append(_url("a", T0, end), [end + STEP],
                                      [1.0])["advanced"]
            end += STEP
        elif path in ("delta", "append", "splice_mismatch"):
            be.series["a"].append((end + STEP, 1.0))
            end += STEP
            if path == "splice_mismatch":
                # history rewritten inside the overlap the query re-reads
                t, v = be.series["a"][-4]
                be.series["a"][-4] = (t, v + 100.0)
        elif path == "range_extended":
            start -= 10 * STEP
        url = _url("a", start, end)
    writes = []
    add_note = tracing.tracer.add_note

    def counting(key, inc=1.0):
        if key == "url_thread_seconds":
            writes.append(inc)
        add_note(key, inc)

    monkeypatch.setattr(tracing.tracer, "add_note", counting)
    tracing.tracer.begin_notes()
    try:
        dsrc.fetch_window(url)
    finally:
        notes = tracing.tracer.take_notes()
    # a full refetch after a noted splice notes no URL seconds of its own
    assert [w > 0 for w in writes] == [True] + [False] * (
        path == "splice_mismatch")
    assert notes["url_thread_seconds"] == writes[0]
    counters = {"unmoved": dsrc.unmoved_hits, "ingest": dsrc.ingest_hits,
                "append": dsrc.append_hits, "delta": dsrc.delta_hits}
    if path in counters:
        assert counters[path] == 1
    if path in ("range_extended", "splice_mismatch"):
        assert dsrc.fallbacks == {path: 1}


def test_source_seconds_hold_the_store_and_url_seconds_the_url(monkeypatch):
    """Two cycles of jobs whose current window is a placeholder URL over a
    delta source: the pool's `url` holds the placeholders' materialization
    and the delta query's range (slowed here), its `source` the inner
    source's call alone, as the store itself counts it, and the six parts
    add up to the pool's `prep` thread-seconds."""
    import time

    from foremast_tpu.dataplane import delta as delta_mod

    now0 = T0 + 5000 * STEP
    be = _Backend()
    for i in range(3):
        be.series[f"c{i}"] = [(T0 + k * STEP, float(k % 5 + i))
                              for k in range(5100)]
    inner = be.source()
    counted = []
    fetch_series = inner.fetch_series

    def timed(url):
        t0 = time.perf_counter()
        try:
            return fetch_series(url)
        finally:
            counted.append(time.perf_counter() - t0)

    inner.fetch_series = timed
    set_range = delta_mod._set_range
    slowed = []

    def slow_set_range(*a):
        time.sleep(0.005)
        slowed.append(1)
        return set_range(*a)

    monkeypatch.setattr(delta_mod, "_set_range", slow_set_range)
    store = JobStore()
    for i in range(3):
        store.create(Document(
            id=f"m{i}", app_name=f"app-m{i}", namespace="px",
            strategy="continuous", start_time=to_rfc3339(0.0),
            end_time=to_rfc3339(now0 + 10 * 86400.0),
            metrics={"latency": MetricQueries(
                current=f"http://prom/c{i}?query=x&start=START_TIME"
                        "&end=END_TIME&step=60")}))
    eng = Analyzer(EngineConfig(), DeltaWindowSource(inner), store)
    for c in range(2):
        counted.clear()
        eng.run_cycle(now=now0 + c * STEP)
        pool = eng.last_cycle_stages["partition"]["pool"]
        parts = ("url", "cache", "items", "source", "lock_wait")
        total = sum(pool[k + "_thread_seconds"] for k in parts) \
            + pool["lock_held_seconds"]
        assert total == pytest.approx(pool["prep_thread_seconds"], abs=1e-5)
        assert pool["source_thread_seconds"] == pytest.approx(
            sum(counted), abs=1e-3)
        assert pool["url_thread_seconds"] > 0.005 * len(slowed)
    assert len(slowed) == 3  # one delta query a job, in the second cycle
    assert eng.source.delta_hits == 3


# ------------------------------------------------------- the append rule
_ENTRY_FIELDS = ("qstart", "qend", "url_step", "full_points", "full_bytes",
                 "dirty", "pushed_until", "push_blocked")
_SOURCE_COUNTERS = ("delta_hits", "unmoved_hits", "ingest_hits",
                    "full_fetches", "bytes_delta", "points_saved",
                    "bytes_saved", "fallbacks")


def _assert_sources_agree(dsrc, twin, ctx=""):
    """Two delta sources fed the same requests hold the same entries
    (window, NaN timestamps, every field) and the same counters."""
    assert ({c: getattr(dsrc, c) for c in _SOURCE_COUNTERS}
            == {c: getattr(twin, c) for c in _SOURCE_COUNTERS}), ctx
    assert list(dsrc._cache) == list(twin._cache), ctx
    for key, mine in dsrc._cache.items():
        twins = twin._cache[key]
        _assert_windows_equal(mine.win, twins.win, ctx)
        assert mine.win.values.dtype == twins.win.values.dtype, ctx
        assert mine.win.mask.dtype == twins.win.mask.dtype, ctx
        assert mine.nan_ts.tolist() == twins.nan_ts.tolist(), ctx
        assert ({f: getattr(mine, f) for f in _ENTRY_FIELDS}
                == {f: getattr(twins, f) for f in _ENTRY_FIELDS}), ctx


class _Append:
    """One backend and three sources over it: one that may append, a twin
    whose append rule always declines (the general splice, on the same
    response), and a full refetch. `fetch` holds the window three ways,
    the entry's fields and every counter to the twin's, and returns how
    many backend requests the request cost."""

    def __init__(self, n, lead=0):
        # the range starts `lead` steps before the first sample, where a
        # case would otherwise grow its span into the next cache-key bucket
        self.start = T0 - lead * STEP
        self.be = _Backend()
        self.be.series["a"] = [(T0 + i * STEP, float(i % 13) + 0.25)
                               for i in range(n)]
        self.end = T0 + (n - 1) * STEP  # the newest sample
        # the clock sits on the newest sample: no range is closed
        self.inner, self.tinner = self.be.source(), self.be.source()
        self.dsrc = DeltaWindowSource(self.inner, clock=lambda: self.end)
        self.twin = DeltaWindowSource(self.tinner, clock=lambda: self.end)
        self.twin._append_tail = lambda *a, **kw: None
        self.full_window = self.be.source().fetch_window

    def add(self, *vals):
        """Samples on the next grid slots."""
        for v in vals:
            self.end += STEP
            self.be.series["a"].append((self.end, v))

    def url(self, start=None):
        return _url("a", self.start if start is None else start, self.end)

    def entries(self):
        return [next(iter(s._cache.values()), None)
                for s in (self.dsrc, self.twin)]

    def fetch(self, url=None, appended=True, requests=1):
        url = url or self.url()
        before = self.inner.request_count, self.tinner.request_count
        hits = self.dsrc.append_hits
        tracing.tracer.begin_notes()
        win = self.dsrc.fetch_window(url)
        notes = tracing.tracer.take_notes()
        _assert_windows_equal(win, self.twin.fetch_window(url), "twin")
        _assert_windows_equal(win, self.full_window(url), "full")
        _assert_sources_agree(self.dsrc, self.twin)
        assert self.dsrc.append_hits - hits == appended
        assert self.twin.append_hits == 0
        assert notes.get("fetch_append", 0) == appended
        assert self.dsrc.snapshot()["append_hits"] == self.dsrc.append_hits
        asked = self.inner.request_count - before[0]
        assert asked == self.tinner.request_count - before[1] == requests
        return win


def _append_one_new_sample(h):
    h.fetch(appended=False)  # the prime: a full fetch
    h.add(7.5)
    win = h.fetch()
    assert win.values.shape[0] == 41 and win.values[-1] == np.float32(7.5)
    # the delta query re-read the overlap: five steps and the newest
    assert h.inner.requests[-1] == _url("a", h.end - 6 * STEP, h.end)
    assert (h.dsrc.delta_hits, h.dsrc.full_fetches) == (1, 1)
    # the most recent cached point may be rewritten, as the canary has it
    h.be.series["a"][-1] = (h.end, -0.0)
    h.add(8.5)
    win = h.fetch()
    assert np.signbit(win.values[-2]) and win.values.shape[0] == 42


def _append_k_new_after_a_skipped_cycle(h):
    h.fetch(appended=False)
    h.add(1.5, 2.5, 3.5)
    assert h.fetch().values.shape[0] == 43


def _append_no_new_sample(h):
    """The tail ends at the cached last slot (the `_stale_newest` shape of
    benchmark/tests): the window comes back as it was, by the rule."""
    h.fetch(appended=False)
    win = h.fetch()
    assert win.values.shape[0] == 40 and h.dsrc.delta_hits == 1


def _append_window_of_one_and_two_slots(h):
    h.fetch(appended=False)  # one slot: shorter than the overlap
    h.add(2.5)
    assert h.fetch().values.shape[0] == 2
    h.add(3.5)
    assert h.fetch().values.shape[0] == 3
    # the overlap reaches back past the window's first slot
    assert h.inner.requests[-1] == _url("a", T0 - 4 * STEP, h.end)


def _append_reaches_max_window_steps(h):
    from foremast_tpu.ops.windowing import MAX_WINDOW_STEPS

    h.fetch(appended=False)
    h.add(1.5)
    assert h.fetch().values.shape[0] == MAX_WINDOW_STEPS
    h.add(2.5)  # one more would clip the head: the general splice
    win = h.fetch(appended=False)
    assert win.values.shape[0] == MAX_WINDOW_STEPS and win.start == T0 + STEP
    assert h.dsrc.delta_hits == 2


def _append_keeps_nan_inside_the_entry(h):
    """A NaN-valued sample between two valid ones anchors no span: the
    rule holds, and the entry keeps the sample's timestamp."""
    h.be.series["a"][10] = (T0 + 10 * STEP, float("nan"))
    h.fetch(appended=False)
    h.add(1.5)
    h.fetch()
    assert h.entries()[0].nan_ts.tolist() == [T0 + 10 * STEP]
    assert h.entries()[0].full_points == 41


def _append_declines_nan_newest_in_entry(h):
    h.be.series["a"][-1] = (h.end, float("nan"))
    h.fetch(appended=False)
    assert not h.entries()[0].win.mask[-1]
    h.add(1.5)
    h.fetch(appended=False)
    assert h.dsrc.delta_hits == 1
    h.add(2.5)  # the NaN-valued sample now lies in the overlap
    h.fetch(appended=False)
    for _ in range(4):
        h.add(3.5)
        h.fetch(appended=False)
    h.add(4.5)  # and has left it
    h.fetch()
    assert h.entries()[0].nan_ts.size == 1 and h.dsrc.delta_hits == 7


def _append_declines_nan_oldest_in_entry(h):
    h.be.series["a"][0] = (T0, float("nan"))
    h.fetch(appended=False)
    assert not h.entries()[0].win.mask[0]
    h.add(1.5)
    h.fetch(appended=False)
    assert h.dsrc.delta_hits == 1 and h.entries()[0].nan_ts.size == 1


def _append_declines_nan_in_tail(h):
    h.fetch(appended=False)
    h.add(float("nan"))
    h.fetch(appended=False)
    assert h.dsrc.delta_hits == 1 and h.entries()[0].nan_ts.size == 1


def _append_declines_gap_in_tail(h):
    h.fetch(appended=False)
    h.end += STEP  # a slot with no sample
    h.add(1.5)
    win = h.fetch(appended=False)
    assert h.dsrc.delta_hits == 1 and not win.mask[-2]
    h.add(2.5)  # the hole now lies in the overlap: still the splice
    h.fetch(appended=False)


def _append_declines_out_of_order_tail(h):
    h.fetch(appended=False)

    def newest_first(fetch_series):  # the body parsers sort; a source may not
        def fetch(url):
            ts, vals, nbytes = fetch_series(url)
            return ts[::-1], vals[::-1], nbytes
        return fetch

    for src in (h.inner, h.tinner):
        src.fetch_series = newest_first(src.fetch_series)
    h.add(1.5, 2.5)
    win = h.fetch(appended=False)
    assert h.dsrc.delta_hits == 1
    assert win.values[-2:].tolist() == [1.5, 2.5]


def _append_declines_tail_after_a_hole(h):
    """The backend lost the overlap and the newest sample: the tail
    starts after `last_end + step`, the canary fires, a full refetch."""
    h.fetch(appended=False)
    del h.be.series["a"][-6:]
    h.end += STEP
    h.add(1.5)
    h.fetch(appended=False, requests=2)
    assert h.dsrc.fallbacks == {"splice_mismatch": 1}


def _append_declines_lost_newest(h):
    h.fetch(appended=False)
    del h.be.series["a"][-1]
    win = h.fetch(appended=False)
    assert win.values.shape[0] == 39 and h.dsrc.delta_hits == 1


def _append_declines_overlap_rewritten(h):
    h.fetch(appended=False)
    h.be.series["a"][-3] = (h.end - 2 * STEP, 1234.0)
    h.add(1.5)
    win = h.fetch(appended=False, requests=2)
    assert h.dsrc.fallbacks == {"splice_mismatch": 1}
    assert h.dsrc.full_fetches == 2 and win.values[-4] == np.float32(1234.0)


def _append_moved_start(h):
    """A trailing range: the start moved past the window's first samples
    onto a valid one, and a NaN-valued sample fell out with them."""
    h.be.series["a"][1] = (T0 + STEP, float("nan"))
    h.be.series["a"][12] = (T0 + 12 * STEP, float("nan"))
    h.fetch(appended=False)
    h.add(1.5)
    win = h.fetch(h.url(T0 + 2 * STEP - 1))  # off the grid: the next slot
    assert win.start == T0 + 2 * STEP and win.values.shape[0] == 39
    assert h.entries()[0].nan_ts.tolist() == [T0 + 12 * STEP]
    h.add(2.5)
    assert h.fetch(h.url(T0 + 3 * STEP)).start == T0 + 3 * STEP
    assert h.dsrc.delta_hits == 2


def _append_trailing_range_inside_the_overlap(h):
    """A trailing range shorter than the overlap: all of it is re-read."""
    h.fetch(h.url(h.end - 3 * STEP), appended=False)
    for _ in range(3):
        h.add(3.5)
        win = h.fetch(h.url(h.end - 3 * STEP))
        assert win.values.shape[0] == 4
        assert h.inner.requests[-1] == h.url(h.end - 3 * STEP)
    assert h.dsrc.delta_hits == 3


def _append_declines_start_on_a_hole(h):
    del h.be.series["a"][2]
    h.fetch(appended=False)
    h.add(1.5)
    win = h.fetch(h.url(T0 + 2 * STEP), appended=False)
    assert win.start == T0 + 3 * STEP and h.dsrc.delta_hits == 1
    assert h.entries()[0].win.values.shape[0] == 38


def _append_declines_float32_overflow(h):
    from foremast_tpu.dataplane.fetch import grid_from_series

    # the refetch as the delta layer grids it (`_full_grid`): the fused
    # native body-to-window path leaves such a sample in, as inf
    inner = h.be.source()
    h.full_window = lambda url: grid_from_series(*inner.fetch(url), STEP)
    h.fetch(appended=False)
    h.add(1e39)
    win = h.fetch(appended=False)
    assert h.dsrc.delta_hits == 1 and not win.mask[-1]


def _append_declines_push_blocked(h):
    h.fetch(appended=False)
    h.add(1.5)
    for src in (h.dsrc, h.twin):
        src.ingest_block(h.url())
    h.fetch(appended=False)
    assert h.dsrc.fallbacks == {"resync": 1} and h.dsrc.full_fetches == 2
    h.add(2.5)  # the refetch re-primed a trusted entry
    h.fetch()


def _append_declines_step_change(h):
    h.fetch(appended=False)
    h.add(1.5)
    for entry in h.entries():  # as an entry primed under another step=
        entry.url_step = 120.0
    h.fetch(appended=False)
    assert h.dsrc.fallbacks == {"step_change": 1}


@pytest.mark.parametrize("case,n,lead", [
    (_append_one_new_sample, 40, 0),
    (_append_k_new_after_a_skipped_cycle, 40, 0),
    (_append_no_new_sample, 40, 0),
    (_append_window_of_one_and_two_slots, 1, 40),
    (_append_reaches_max_window_steps, 16383, 1 << 15),
    (_append_keeps_nan_inside_the_entry, 40, 0),
    (_append_moved_start, 40, 0),
    (_append_trailing_range_inside_the_overlap, 40, 0),
    (_append_declines_nan_newest_in_entry, 40, 0),
    (_append_declines_nan_oldest_in_entry, 40, 0),
    (_append_declines_nan_in_tail, 40, 0),
    (_append_declines_gap_in_tail, 40, 0),
    (_append_declines_out_of_order_tail, 40, 0),
    (_append_declines_tail_after_a_hole, 40, 0),
    (_append_declines_lost_newest, 40, 0),
    (_append_declines_overlap_rewritten, 40, 0),
    (_append_declines_start_on_a_hole, 40, 0),
    (_append_declines_float32_overflow, 40, 0),
    (_append_declines_push_blocked, 40, 0),
    (_append_declines_step_change, 40, 0),
], ids=lambda c: getattr(c, "__name__", "")[8:] or None)
def test_append_rule_is_the_splice_and_the_full_refetch(case, n, lead):
    """A contiguous on-grid tail that continues a trusted window is
    appended without rebuilding the window's timestamps; anything else is
    spliced as before, from the response already in hand. Every request
    is held three ways (append, the general splice forced on a twin
    source, a full refetch): the window, the entry's fields, the source's
    counters and notes, and the backend requests it cost."""
    case(_Append(n, lead))


@pytest.mark.parametrize("seed,p_gap,p_nan,overlap", [
    (0, 0.03, 0.02, 5), (1, 0.15, 0.08, 5), (2, 0.03, 0.02, 1),
    (3, 0.0, 0.0, 5), (4, 0.03, 0.02, 9)])
def test_append_property_vs_splice_and_full_refetch(seed, p_gap, p_nan,
                                                    overlap):
    """Randomized rounds over a mostly regular series (the append rule's
    traffic) with what must decline it mixed in: gaps, NaN-valued and
    float32-overflowing samples, skipped and empty cycles, rewrites and
    deletions inside the overlap. A fixed-start and two trailing ranges
    are fetched every round through a source that may append, a twin
    whose rule always declines, and a full refetch: windows, entries,
    counters and backend requests agree, and both paths ran."""
    from foremast_tpu.dataplane.fetch import grid_from_series

    rng = np.random.default_rng(seed)
    be = _Backend()
    clock = {"now": 0.0}  # on the newest slot: no range is closed
    srcs = [DeltaWindowSource(be.source(), clock=lambda: clock["now"],
                              overlap_steps=overlap) for _ in range(2)]
    dsrc, twin = srcs
    twin._append_tail = lambda *a, **kw: None
    full = be.source()

    def value():
        u = rng.random()
        if u < p_nan:
            return float("nan")
        return 1e39 if u < p_nan + 0.003 else round(float(rng.normal(10, 2)), 4)

    series = be.series["a"] = [(T0 + i * STEP, value()) for i in range(50)
                               if rng.random() >= p_gap]
    end = T0 + 49 * STEP
    for round_i in range(120):
        prev_end = end
        for _ in range(int(rng.choice([0, 1, 1, 1, 1, 2, 3]))):
            end += STEP
            if rng.random() >= p_gap:
                series.append((end, value()))
        clock["now"] = float(end)
        # history moves only where every range's next delta query looks
        hit = prev_end - int(rng.integers(0, overlap + 1)) * STEP
        at = [j for j in range(max(len(series) - 12, 0), len(series))
              if series[j][0] == hit]
        if at and rng.random() < 0.05:
            series[at[0]] = (hit, round(float(rng.normal(10, 2)), 4))
        elif at and len(series) > 12 and rng.random() < 0.02:
            del series[at[0]]
        for url in (_url("a", T0 - 64 * STEP, end),
                    _url("a", end - 25 * STEP, end),
                    _url("a", end - 3 * STEP - 7, end)):
            ctx = f"seed {seed} round {round_i} {url}"
            before = [s.inner.request_count for s in srcs]
            win = dsrc.fetch_window(url)
            _assert_windows_equal(win, twin.fetch_window(url), ctx)
            _assert_windows_equal(
                win, grid_from_series(*full.fetch(url), STEP), ctx)
            asked = [s.inner.request_count - b for s, b in zip(srcs, before)]
            assert asked[0] == asked[1] and asked[0] in (1, 2), ctx
            _assert_sources_agree(dsrc, twin, ctx)
    assert twin.append_hits == 0
    assert dsrc.append_hits > 20  # the rule ran
    if p_gap:
        assert dsrc.delta_hits - dsrc.append_hits > 20  # so did the splice


# ------------------------------------------------- the closed-range rule
class _Unmoved:
    """One backend, a delta source on an injected clock and a full-refetch
    source beside it. `fetch` holds every window to the full refetch's
    and returns how many backend requests the delta source made for it."""

    END = T0 + 29 * STEP  # newest of the 30 samples the backend starts with

    def __init__(self):
        self.be = _Backend()
        self.be.series["a"] = [(T0 + i * STEP, float(i)) for i in range(30)]
        self.now = float(self.END + 1000 * STEP)
        self.inner = self.be.source()
        self.dsrc = DeltaWindowSource(self.inner, clock=lambda: self.now)
        self.fsrc = self.be.source()
        self.url = _url("a", T0, self.END)
        self.notes = {}

    def fetch(self, url=None) -> int:
        url = url or self.url
        before = self.inner.request_count
        tracing.tracer.begin_notes()
        win = self.dsrc.fetch_window(url)
        for k, v in tracing.tracer.take_notes().items():
            self.notes[k] = self.notes.get(k, 0) + v
        _assert_windows_equal(win, self.fsrc.fetch_window(url), url)
        return self.inner.request_count - before

    def counts(self):
        d = self.dsrc
        return (d.unmoved_hits, d.delta_hits, d.ingest_hits, d.full_fetches)


def _unmoved_closed(h):
    """(a) a closed fixed range fetched four times is one backend request,
    also with its end exactly `overlap_steps` old."""
    h.now = float(h.END + h.dsrc.overlap_steps * STEP)
    assert [h.fetch() for _ in range(4)] == [1, 0, 0, 0]
    assert h.counts() == (3, 0, 0, 1)
    assert h.inner.requests == [h.url]
    snap = h.dsrc.snapshot()
    assert snap["unmoved_hits"] == 3 and snap["hit_ratio"] == 0.75
    assert snap["points_saved"] == 3 * 30
    assert h.notes["fetch_unmoved"] == 3 and h.notes["fetch_full"] == 1
    # the served window is the one the cache holds, and it dirties nothing
    entry = next(iter(h.dsrc._cache.values()))
    assert h.dsrc.fetch_window(h.url) is entry.win


def _unmoved_inside_overlap(h):
    """(b) the same range with its end inside `overlap_steps` of the clock
    is spliced as today, and a rewrite of its newest sample is seen."""
    h.now = float(h.END + h.dsrc.overlap_steps * STEP - 1)
    assert h.fetch() == 1
    h.be.series["a"][-1] = (h.END, 99.5)
    assert h.fetch() == 1
    assert h.inner.requests[-1] == _url("a", h.END - 5 * STEP, h.END)
    assert h.counts() == (0, 1, 0, 1)
    assert h.dsrc.fetch_window(h.url).values[-1] == np.float32(99.5)
    assert "fetch_unmoved" not in h.notes and h.notes["fetch_delta"] == 1


def _unmoved_short_tail(h):
    """(c) a range whose tail is short of its end is spliced, and a late
    sample appears in the next fetch; only then is the range served."""
    del h.be.series["a"][-1]
    assert [h.fetch(), h.fetch()] == [1, 1]
    assert h.counts() == (0, 1, 0, 1)
    h.be.series["a"].append((h.END, 5.0))
    assert h.fetch() == 1
    assert h.dsrc.fetch_window(h.url).values.shape[0] == 30
    assert h.counts()[:2] == (1, 2)  # the look above was served


def _unmoved_resync(h):
    """(d) `force_resync()` and `push_blocked` end in a full refetch."""
    assert [h.fetch(), h.fetch()] == [1, 0]
    h.dsrc.force_resync()
    assert h.fetch() == 1 and h.inner.requests[-1] == h.url
    assert h.dsrc.fallbacks == {"resync": 1}
    assert h.fetch() == 0  # the refetch re-primed a clean entry
    h.dsrc.ingest_block(h.url)
    assert h.fetch() == 1 and h.inner.requests[-1] == h.url
    assert h.dsrc.fallbacks == {"resync": 2}
    assert h.counts() == (2, 0, 0, 3)


def _unmoved_moved(h):
    """(e) the range moved by one step splices and does not count."""
    assert h.fetch() == 1
    h.be.series["a"].append((h.END + STEP, 1.0))
    assert h.fetch(_url("a", T0 + STEP, h.END + STEP)) == 1
    assert h.counts() == (0, 1, 0, 1)
    # neither does a start that moved alone, forwards or back
    assert h.fetch(_url("a", T0 + 2 * STEP, h.END + STEP)) == 1
    assert h.fetch(_url("a", T0 + STEP, h.END + STEP)) == 1
    assert h.counts() == (0, 2, 0, 2)
    assert h.dsrc.fallbacks == {"range_extended": 1}


def _unmoved_pushed(h):
    """(f) a pushed entry (`pushed_until > 0`) is left to
    `_try_ingest_serve`, which cuts the window to the range asked for."""
    assert h.fetch() == 1
    h.be.series["a"].append((h.END + STEP, 1.0))
    out = h.dsrc.ingest_append(h.url, [h.END + STEP], [1.0])
    assert out["spliced"] == 1
    assert h.fetch() == 0
    assert h.counts() == (0, 0, 1, 1)
    assert h.notes["fetch_ingest"] == 1 and "fetch_unmoved" not in h.notes
    entry = next(iter(h.dsrc._cache.values()))
    assert entry.win.values.shape[0] == 31  # not what the range holds


def _unmoved_span_clipped(h):
    """(g) a span-clipped window is not served: its head was cut, so the
    cache alone cannot vouch for the range."""
    from foremast_tpu.ops.windowing import MAX_WINDOW_STEPS

    n = MAX_WINDOW_STEPS + 10
    h.be.series["a"] = [(T0 + i * STEP, float(i % 11)) for i in range(n)]
    h.url = _url("a", T0, T0 + (n - 1) * STEP)
    h.now = float(T0 + (n + 1000) * STEP)
    assert [h.fetch(), h.fetch()] == [1, 1]
    assert h.dsrc.fetch_window(h.url).values.shape[0] == MAX_WINDOW_STEPS
    assert h.dsrc.unmoved_hits == 0 and h.dsrc.full_fetches == 1


@pytest.mark.parametrize("case", [
    _unmoved_closed, _unmoved_inside_overlap, _unmoved_short_tail,
    _unmoved_resync, _unmoved_moved, _unmoved_pushed, _unmoved_span_clipped,
], ids=lambda f: f.__name__[len("_unmoved_"):])
def test_closed_unmoved_range_is_served_from_cache(case):
    """A request whose range the entry already holds whole, and which is
    closed, is answered with the cached Window and no backend query; any
    other request takes the splice or full-refetch path untouched. Every
    window is held to a full refetch's on the same backend."""
    case(_Unmoved())


# ---------------------------------------------------------- engine identity
def _stream_fleet(be: _Backend, n_pair=6, n_band=4, n_bi=2, n_lstm=2,
                  n_hpa=2, W=40):
    """A mixed-family fleet over range-honoring backend series. Returns
    (store, horizon_end). Current windows start full at `T0 + 2W` and the
    caller appends samples / advances queries from there."""
    rng = np.random.default_rng(5)
    store = JobStore()
    far = T0 + 2000 * STEP

    def mk_series(name, n0, level=10.0, spread=1.0):
        be.series[name] = [
            (T0 + i * STEP, round(float(v), 4))
            for i, v in enumerate(level + rng.normal(0, spread, n0))
        ]

    def mk(job_id, metrics, strategy="canary"):
        store.create(Document(
            id=job_id, app_name=f"app-{job_id}", namespace="px",
            strategy=strategy, start_time=to_rfc3339(float(T0)),
            end_time=to_rfc3339(float(far)), metrics=metrics,
        ))

    cur0 = T0 + 2 * W * STEP  # current region starts here
    n_now = 3 * W  # samples that exist at stream start

    def q(name, role):
        if role == "cur":
            return _url(name, cur0, far)
        return _url(name, T0, cur0)  # baseline/historical: frozen past

    for i in range(n_pair):
        bad = i % 3 == 2
        mk_series(f"p{i}c", n_now, level=5.0 if bad else 0.5, spread=0.05)
        mk_series(f"p{i}b", n_now, level=0.5, spread=0.05)
        mk(f"pair{i}", {"error5xx": MetricQueries(
            current=q(f"p{i}c", "cur"), baseline=_url(f"p{i}b", T0, cur0))})
    for i in range(n_band):
        mk_series(f"bd{i}", n_now)
        mk(f"band{i}", {"latency": MetricQueries(
            current=q(f"bd{i}", "cur"), historical=q(f"bd{i}", "hist"))})
    for i in range(n_bi):
        ms = {}
        for m in ("latency", "cpu"):
            mk_series(f"bi{i}{m}", n_now)
            ms[m] = MetricQueries(current=q(f"bi{i}{m}", "cur"),
                                  historical=q(f"bi{i}{m}", "hist"))
        mk(f"bi{i}", ms)
    for i in range(n_lstm):
        ms = {}
        for m in ("latency", "cpu", "tps"):
            mk_series(f"ml{i}{m}", n_now)
            ms[m] = MetricQueries(current=q(f"ml{i}{m}", "cur"),
                                  historical=q(f"ml{i}{m}", "hist"))
        mk(f"lstm{i}", ms)
    for i in range(n_hpa):
        mk_series(f"h{i}tps", n_now, level=100.0, spread=3.0)
        mk_series(f"h{i}lat", n_now, level=5.0, spread=0.2)
        tps = MetricQueries(current=q(f"h{i}tps", "cur"),
                            historical=q(f"h{i}tps", "hist"))
        lat = MetricQueries(current=q(f"h{i}lat", "cur"),
                            historical=q(f"h{i}lat", "hist"))
        lat.priority, lat.is_increase = 1, True
        mk(f"hpa{i}", {"tps": tps, "latency": lat}, strategy="hpa")
    return store, T0 + n_now * STEP


def _snapshot(store: JobStore) -> str:
    docs = {}
    for doc in store._jobs.values():
        docs[doc.id] = {"status": doc.status, "reason": doc.reason,
                        "anomaly": doc.anomaly}
    logs = [{"job": h.job_id, "score": h.hpascore, "reason": h.reason,
             "details": h.details} for h in store._hpalogs]
    return json.dumps({"docs": docs, "hpalogs": logs}, sort_keys=True)


def _run_stream(delta: bool, memo: bool, cycles=8, cadence=20):
    """Drive the same fixture stream (appending samples as wall time
    crosses step boundaries) through an engine; returns per-cycle verdict
    snapshots."""
    be = _Backend()
    store, data_end = _stream_fleet(be)
    rng = np.random.default_rng(77)
    inner = be.source()
    source = DeltaWindowSource(inner) if delta else inner
    cfg = EngineConfig(pairwise_threshold=1e-4, lstm_epochs=2,
                       delta_fetch=delta, score_memo=memo)
    eng = Analyzer(cfg, source, store, VerdictExporter())
    snaps = []
    now = float(data_end + STEP)
    next_sample = data_end
    for _ in range(cycles):
        now += cadence
        while next_sample + STEP <= now:  # stream: ~1 new sample per step
            next_sample += STEP
            for name, samples in be.series.items():
                if rng.random() < 0.9:
                    samples.append(
                        (next_sample,
                         round(float(samples[-1][1]
                                     + rng.normal(0, 0.01)), 4)))
        eng.run_cycle(now=now)
        snaps.append(_snapshot(store))
    return snaps, eng, source


def test_delta_memo_cycle_identical_to_full_refetch():
    """THE acceptance gate: delta+memo on vs. everything off over the
    same appended-sample stream — per-cycle verdict state byte-identical."""
    snaps_on, eng_on, src_on = _run_stream(delta=True, memo=True)
    snaps_off, _eng_off, _ = _run_stream(delta=False, memo=False)
    assert snaps_on == snaps_off
    # and the incremental machinery actually engaged
    assert src_on.delta_hits > 0
    assert sum(eng_on.score_memo_hits.values()) > 0


def _run_rolling(delta: bool, jobs=5, cycles=4, W=20, H=200, memo=None,
                 seen=None):
    """`rollingUpdate` jobs as barrelman builds them: a current window
    that grows by one sample a cycle, and a baseline and a history whose
    ranges are fixed timestamps in the past. Returns per-cycle verdict
    snapshots (store state and every record's scores), backend requests
    per cycle beside the cycle's `splice_appends`, and each job's fetch
    record. `memo` (default: as `delta`) sets SCORE_MEMO; a `seen` list
    gets, a cycle, the engine.preprocess attrs, `last_cycle_stages` and
    the exporter's gauges."""
    be = _Backend()
    rng = np.random.default_rng(11)
    store = JobStore()
    cur0 = T0 + H * STEP
    far = T0 + 4000 * STEP
    for i in range(jobs):
        be.series[f"r{i}"] = [
            (T0 + k * STEP, round(float(v), 4))
            for k, v in enumerate(10.0 + rng.normal(0, 1.0, H + W))]
        store.create(Document(
            id=f"roll{i}", app_name=f"app-{i}", namespace="px",
            strategy="rollingUpdate", start_time=to_rfc3339(float(T0)),
            end_time=to_rfc3339(float(far)),
            metrics={"error4xx": MetricQueries(
                current=_url(f"r{i}", cur0, far),
                baseline=_url(f"r{i}", cur0 - 2 * W * STEP,
                              cur0 - W * STEP),
                historical=_url(f"r{i}", T0, cur0 - STEP))}))
    clock = [float(cur0 + W * STEP)]
    inner = be.source()
    source = DeltaWindowSource(inner, clock=lambda: clock[0]) \
        if delta else inner
    eng = Analyzer(EngineConfig(delta_fetch=delta,
                                score_memo=delta if memo is None else memo),
                   source, store, VerdictExporter())
    snaps, requests, fetches = [], [], []
    for _ in range(cycles):
        newest = int(clock[0])
        for samples in be.series.values():
            samples.append(
                (newest, round(float(10.0 + rng.normal(0, 1.0)), 4)))
        clock[0] += 5.0
        before = inner.request_count
        eng.run_cycle(now=clock[0])
        requests.append(inner.request_count - before)
        recs = {f"roll{i}": eng.provenance.get(f"roll{i}")
                for i in range(jobs)}
        snaps.append((_snapshot(store),
                      {j: r["families"] for j, r in recs.items()}))
        fetches.append({j: r["fetch"] for j, r in recs.items()})
        # what the cycle's engine.preprocess span says of its appends
        root = next(t for t in reversed(tracing.tracer.snapshot(limit=64))
                    if t["attrs"].get("cycle_id")
                    == eng.last_cycle_stages["cycle_id"])
        prep = next(c for c in root["children"]
                    if c["name"] == tracing.SPAN_ENGINE_PREPROCESS)
        requests[-1] = (requests[-1], prep["attrs"]["splice_appends"])
        if seen is not None:
            seen.append((prep["attrs"], eng.last_cycle_stages, {
                name: value for name, _, value in eng.exporter.samples()}))
        clock[0] += STEP - 5.0
    return snaps, requests, fetches, source


def test_rolling_update_fixed_ranges_one_backend_query_a_cycle():
    """A live `rollingUpdate` job costs three backend queries in its first
    cycle and one in every later cycle: its baseline and history are
    closed and do not move, so the window cache answers for them. Verdict
    state and the points each job's record counts equal the
    `DELTA_FETCH=0` run's, cycle by cycle."""
    jobs = 5
    snaps_on, req_on, fetch_on, src = _run_rolling(delta=True, jobs=jobs)
    snaps_off, req_off, fetch_off, _ = _run_rolling(delta=False, jobs=jobs)
    assert snaps_on == snaps_off
    assert all(len(fams) == 2 for fams in snaps_on[-1][1].values())
    # (backend requests, `splice_appends` on engine.preprocess) a cycle:
    # every steady-state request is the append rule's
    assert req_on == [(3 * jobs, 0), (jobs, jobs), (jobs, jobs), (jobs, jobs)]
    assert req_off == [(3 * jobs, 0)] * 4
    for on, off in zip(fetch_on, fetch_off):
        assert ({j: f["points"] for j, f in on.items()}
                == {j: f["points"] for j, f in off.items()})
        assert all(f["fetches"] == 3 for f in on.values())
    assert all(f["fetch_full"] == 3 for f in fetch_on[0].values())
    for cyc in fetch_on[1:]:  # steady state
        for f in cyc.values():
            assert f["fetch_delta"] == 1 and f["fetch_unmoved"] == 2
            assert f["fetch_append"] == 1
            assert "fetch_full" not in f
    assert src.unmoved_hits == 2 * jobs * 3 and src.delta_hits == jobs * 3
    assert src.append_hits == src.delta_hits
    # and `foremast explain` says how each window was got
    from foremast_tpu.cli import _render_explain

    text = _render_explain(
        {"provenance": {"path": "scored", "fetch": fetch_on[-1]["roll0"]}})
    assert "3 fetch(es), 1 delta (append)/2 unmoved, 245 points" in text
    text = _render_explain({"provenance": {"path": "scored", "fetch": {
        "fetches": 4, "fetch_delta": 3, "fetch_append": 2, "fetch_full": 1}}})
    assert "4 fetch(es), 3 delta (2 append)/1 full" in text


def test_unmoved_windows_are_not_hashed_again_and_verdicts_hold():
    """Rolling jobs with the memo on: from the second cycle a job's
    baseline and history are the objects the window cache handed out
    last cycle, so the fingerprint hashes only the appended current
    window and chains the three other digests (the current's own, taken
    for the pair row, serves the band row too). Verdicts, records and
    memo hits equal `SCORE_MEMO=0`'s cycle by cycle; the counts ride the
    engine.preprocess span, `last_cycle_stages` and the gauge."""
    jobs, W = 5, 20
    seen_on, seen_off = [], []
    snaps_on, req_on, *_ = _run_rolling(delta=True, jobs=jobs, W=W,
                                        memo=True, seen=seen_on)
    snaps_off, req_off, *_ = _run_rolling(delta=True, jobs=jobs, W=W,
                                          memo=False, seen=seen_off)
    assert snaps_on == snaps_off and req_on == req_off
    assert [st["score_memo_hits"] for _, st, _ in seen_on] == \
        [st["score_memo_hits"] for _, st, _ in seen_off] == [{}] * 4
    for k, (prep, st, gauges) in enumerate(seen_on):
        counters = st["partition"]["counters"]
        for name in ("memo_lookups", "memo_hits", "memo_fp_bytes",
                     "memo_fp_reused"):
            assert prep[name] == counters[name], name
        assert counters["memo_lookups"] == 2 * jobs and \
            counters["memo_hits"] == 0
        n_c = W + k + 1  # the current window, one sample longer a cycle
        if k == 0:  # every window is new: baseline 21, history 200
            assert counters["memo_fp_bytes"] == 5 * jobs * (
                n_c + W + 1 + 200)
            assert counters["memo_fp_reused"] == jobs
            assert gauges["foremastbrain:memo_fp_reuse_share"] == 0.25
        else:
            assert counters["memo_fp_bytes"] == 5 * jobs * n_c
            assert counters["memo_fp_reused"] == 3 * jobs
            assert gauges["foremastbrain:memo_fp_reuse_share"] == 0.75
    for prep, st, gauges in seen_off:
        assert prep["memo_fp_bytes"] == prep["memo_fp_reused"] == 0
        assert st["partition"]["counters"]["memo_fp_reused"] == 0
        assert gauges["foremastbrain:memo_fp_reuse_share"] == 0.0


def test_memo_changed_single_row_rescores_only_its_bucket():
    """Cycle 3 changes ONE pair job's current data: only that row misses
    the memo, and only its (family, T) bucket launches — one program."""
    fixtures = {}
    store = JobStore()
    rng = np.random.default_rng(3)

    def series(level, n=30):
        ts = [float(i * STEP) for i in range(n)]
        return ts, np.round(rng.normal(level, 0.1, n), 4).tolist()

    for i in range(8):
        fixtures[f"u/p{i}/c"] = series(0.5)
        fixtures[f"u/p{i}/b"] = series(0.5)
        store.create(Document(
            id=f"pair{i}", app_name="a", namespace="n", strategy="canary",
            start_time=to_rfc3339(0.0), end_time=to_rfc3339(5_000_000.0),
            metrics={"error5xx": MetricQueries(
                current=f"u/p{i}/c", baseline=f"u/p{i}/b")},
        ))
    for i in range(4):
        fixtures[f"u/b{i}/c"] = series(10.0, 25)
        fixtures[f"u/b{i}/h"] = series(10.0, 300)
        store.create(Document(
            id=f"band{i}", app_name="a", namespace="n", strategy="canary",
            start_time=to_rfc3339(0.0), end_time=to_rfc3339(5_000_000.0),
            metrics={"latency": MetricQueries(
                current=f"u/b{i}/c", historical=f"u/b{i}/h")},
        ))
    eng = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store)
    eng.run_cycle(now=1000.0)
    # warm no-change cycle: everything memo-hits, nothing launches
    l0 = eng.device_launches
    eng.run_cycle(now=1000.0)
    assert eng.device_launches == l0
    assert eng.last_cycle_stages["device_launches"] == 0
    assert eng.last_cycle_stages["score_memo_hits"] == {"pair": 8, "band": 4}
    # change one pair row -> exactly one (pair-family) launch
    ts, vals = fixtures["u/p3/c"]
    fixtures["u/p3/c"] = (ts, [v + 0.01 for v in vals])
    eng.run_cycle(now=1000.0)
    assert eng.last_cycle_stages["score_memo_hits"] == {"pair": 7, "band": 4}
    assert eng.last_cycle_stages["device_launches"] == 1


def test_memo_off_restores_full_scoring():
    fixtures = {"u/c": ([float(i * 60) for i in range(30)], [0.5] * 30),
                "u/b": ([float(i * 60) for i in range(30)], [0.5] * 30)}
    store = JobStore()
    store.create(Document(
        id="p", app_name="a", namespace="n", strategy="canary",
        start_time=to_rfc3339(0.0), end_time=to_rfc3339(5_000_000.0),
        metrics={"error5xx": MetricQueries(current="u/c", baseline="u/b")},
    ))
    eng = Analyzer(EngineConfig(score_memo=False),
                   FixtureDataSource(fixtures), store)
    eng.run_cycle(now=1000.0)
    l0 = eng.device_launches
    eng.run_cycle(now=1000.0)
    assert eng.device_launches > l0  # re-scored, no memo
    assert eng.score_memo_hits == {}


# ----------------------------------------------------------- perf gates
@pytest.mark.perf
def test_no_change_cycle_zero_device_launches_with_memo():
    """The steady-state gate: a warmed mixed fleet (lstm included) on a
    no-change cycle with SCORE_MEMO=1 fires ZERO device programs."""
    be = _Backend()
    store, data_end = _stream_fleet(be)
    eng = Analyzer(
        EngineConfig(pairwise_threshold=1e-4, lstm_epochs=2),
        DeltaWindowSource(be.source()), store, VerdictExporter())
    now = float(data_end + STEP)
    eng.run_cycle(now=now)
    warm = 0
    while eng._lstm_trained_this_cycle > 0 and warm < 6:
        eng.run_cycle(now=now)
        warm += 1
    eng.run_cycle(now=now)  # settle
    l0 = eng.device_launches
    eng.run_cycle(now=now)
    assert eng.device_launches == l0, (
        f"no-change cycle launched {eng.device_launches - l0} device "
        "program(s); the fingerprint memo is leaking rescores")


@pytest.mark.perf
def test_steady_state_delta_hit_ratio_gate():
    """Warm steady-state cycles must keep the delta-cache hit ratio at or
    above 0.9 (the make-perf gate from the issue)."""
    from foremast_tpu.bench_cycle import run_steady

    out = run_steady(n_jobs=40, cycles=6)
    assert out["delta_hit_ratio"] >= 0.9, out
    assert out["compiles_steady_state"] == 0, out


# ----------------------------------------------- keep-alive + cache export
def test_prometheus_source_reuses_connections():
    """The keep-alive satellite: N sequential queries to one host ride ONE
    TCP connection (per-connection handler instantiation is counted)."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    body = _body([(T0, 1.0), (T0 + 60, 2.0)])
    conns = {"n": 0}

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def setup(self):  # one instantiation per TCP connection
            conns["n"] += 1
            super().setup()

        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        pool = HttpConnectionPool()
        src = PrometheusDataSource(pool=pool)
        for i in range(5):
            ts, vals = src.fetch(f"http://127.0.0.1:{port}/q{i}?start=1&end=2")
            assert list(np.asarray(vals, float)) == [1.0, 2.0]
        assert conns["n"] == 1, f"opened {conns['n']} connections for 5 GETs"
        assert pool.connections_opened == 1
        assert pool.requests_served == 5
    finally:
        httpd.shutdown()


def test_window_cache_counters_exported():
    """The CachingDataSource counters (tracked since PR 1, never exported)
    surface as foremastbrain:window_cache_*_total on /metrics + /status."""
    from foremast_tpu.service.api import ForemastService

    fx = FixtureDataSource({"u": ([0.0, 60.0], [1.0, 2.0])})
    cache = CachingDataSource(fx)
    cache.fetch("u")
    cache.fetch("u")  # hit
    be = _Backend()
    be.series["a"] = [(T0, 1.0), (T0 + STEP, 2.0)]
    dsrc = DeltaWindowSource(be.source())
    lead = T0 - 7 * STEP  # so that one more step keeps the cache key
    dsrc.fetch_window(_url("a", lead, T0 + STEP))
    dsrc.fetch_window(_url("a", lead, T0 + STEP))  # closed, unmoved: served
    be.series["a"].append((T0 + 2 * STEP, 3.0))
    dsrc.fetch_window(_url("a", lead, T0 + 2 * STEP))  # appended
    assert dsrc.snapshot()["append_hits"] == 1
    svc = ForemastService(JobStore(), exporter=VerdictExporter(),
                          cache_source=cache, delta_source=dsrc)
    _, text = svc.metrics()
    assert "foremastbrain:window_cache_hits_total 1" in text
    assert "foremastbrain:window_cache_misses_total 1" in text
    assert "foremastbrain:window_cache_single_flight_waits_total 0" in text
    assert "foremastbrain:delta_fetch_full_total 1" in text
    assert "foremastbrain:delta_fetch_hits_total 1" in text
    assert "foremastbrain:delta_fetch_append_total 1" in text
    assert "foremastbrain:delta_fetch_unmoved_total 1" in text
    assert "foremastbrain:delta_fetch_hit_ratio 0.6667" in text
    status, payload = svc.status_summary()
    assert status == 200
    assert payload["window_cache"] == {
        "hits": 1, "misses": 1, "single_flight_waits": 0}
    assert payload["delta_fetch"]["full_fetches"] == 1
    assert payload["delta_fetch"]["unmoved_hits"] == 1
    assert payload["delta_fetch"]["append_hits"] == 1


# ------------------------------------------------------- lstm train memo
def test_lstm_train_memo_skips_retraining_on_unchanged_window():
    """An evicted model whose train-window fingerprint is unchanged comes
    back from the train memo without re-training (deterministic training:
    reuse == retrain)."""
    fixtures = {}
    rng = np.random.default_rng(1)
    ts_c = [float(i * STEP) for i in range(25)]
    ts_h = [float(i * STEP) for i in range(300)]
    ms = {}
    for m in ("latency", "cpu", "tps"):
        fixtures[f"u/{m}/c"] = (ts_c, np.round(
            rng.normal(10, 1, 25), 4).tolist())
        fixtures[f"u/{m}/h"] = (ts_h, np.round(
            rng.normal(10, 1, 300), 4).tolist())
        ms[m] = MetricQueries(current=f"u/{m}/c", historical=f"u/{m}/h")
    store = JobStore()
    store.create(Document(
        id="ml", app_name="a", namespace="n", strategy="canary",
        start_time=to_rfc3339(0.0), end_time=to_rfc3339(5_000_000.0),
        metrics=ms,
    ))
    eng = Analyzer(EngineConfig(lstm_epochs=2), FixtureDataSource(fixtures),
                   store)
    eng.run_cycle(now=1000.0)
    assert len(eng._lstm_cache) == 1
    # evict the model but keep the train memo (restart-ish churn)
    key = next(iter(eng._lstm_cache))
    del eng._lstm_cache[key]
    trained_before = eng._lstm_param_version
    eng.run_cycle(now=1000.0)
    assert eng._lstm_param_version == trained_before  # no re-training
    assert eng.lstm_train_memo_hits >= 1
    assert key in eng._lstm_cache  # rehydrated under its key


# --------------------------------------- push ingest splice (ISSUE 12)
def test_splice_property_interleaved_push_and_poll():
    """ISSUE 12 backpressure/identity property: PUSHED samples splice
    into the cached grid through the same geometry as the delta splice,
    polls and pushes interleave freely (including pushes that LAG the
    backend and polls that lag the pushes), and every fetched window —
    whether served from the push-fed cache or spliced/refetched from
    the backend — is byte-identical to a fresh full refetch."""
    rng = np.random.default_rng(1207)
    be = _Backend()
    grid = {"t": T0 + 39 * STEP}  # newest on-grid sample slot
    # the wall clock sits just past the newest possible sample — the
    # streamed regime (pushes arrive ~instantly after their timestamps)
    clock = {"now": grid["t"] + 0.5}
    delta_src = DeltaWindowSource(be.source(), clock=lambda: clock["now"])
    full_src = be.source()
    name = "pp"
    be.series[name] = [
        (T0 + k * STEP, round(float(rng.normal(10, 2)), 4))
        for k in range(40) if rng.random() > 0.1
    ]

    def push(samples):
        return delta_src.ingest_append(
            _url(name, T0, clock["now"]),
            [t for t, _ in samples], [v for _, v in samples])

    # remote-write delivery model: per-series pushes are IN ORDER and
    # retried until delivered (the protocol contract the splice relies
    # on) — lag means a suffix arrives late, never that a sample is
    # skipped while later ones land (the receiver latches any such hole
    # into resync mode; see test_push_hole_latches_resync below)
    backlog: list = []
    spliced = served = 0
    for round_i in range(60):
        adv = int(rng.integers(0, 3)) * STEP
        prev = grid["t"]
        grid["t"] += adv
        clock["now"] = grid["t"] + 0.5
        fresh = []
        t = prev + STEP
        while t <= grid["t"]:
            if rng.random() > 0.15:
                v = float("nan") if rng.random() < 0.08 else \
                    round(float(rng.normal(10, 2)), 4)
                fresh.append((t, v))
            t += STEP
        be.series[name].extend(fresh)
        backlog.extend(fresh)
        mode = rng.random()
        if mode < 0.5 and backlog:
            # the whole backlog lands (push caught up with scrape)
            res = push(backlog)
            spliced += res["spliced"]
            backlog = []
        elif mode < 0.7 and len(backlog) > 1:
            # lagging delivery: an in-order prefix lands, the rest stays
            # queued (a poll may win the race; the late delivery then
            # rejects as `stale` — already reconciled)
            cut = len(backlog) // 2
            res = push(backlog[:cut])
            spliced += res["spliced"]
            backlog = backlog[cut:]
        # else: poll-only round (push lag) — the delta splice catches up
        if round_i % 2:
            url = _url(name, T0, clock["now"])
        else:
            url = _url(name, max(T0, clock["now"] - 30 * STEP),
                       clock["now"])
        before_hits = delta_src.ingest_hits
        win_d = delta_src.fetch_window(url)
        served += delta_src.ingest_hits - before_hits
        win_f = full_src.fetch_window(url)
        _assert_windows_equal(win_d, win_f, f"push+poll round {round_i}")
    assert spliced > 10, "the ingest splice path never ran"
    assert served > 5, "no window was ever served from the pushed cache"
    # the poll path keeps priming entries; splice rejects stay benign
    snap = delta_src.snapshot()
    assert snap["ingest_spliced_points"] == spliced


def test_push_rewrite_is_rejected_and_poll_heals():
    """A push that REWRITES cached history (same ts, new value) is
    dropped as stale — the frozen-region contract — and a backend
    rewrite beyond the overlap is healed by the poll path's canary,
    never by trusting the push."""
    be = _Backend()
    clock = {"now": float(T0 + 10 * STEP)}
    delta_src = DeltaWindowSource(be.source(), clock=lambda: clock["now"])
    be.series["rw"] = [(T0 + k * STEP, 1.0 + k) for k in range(10)]
    url = _url("rw", T0, clock["now"])
    delta_src.fetch_window(url)
    res = delta_src.ingest_append(url, [T0 + 5 * STEP], [99.0])
    assert res["spliced"] == 0 and res["reason"] == "stale"
    # cache unchanged: identical to a fresh full refetch
    _assert_windows_equal(delta_src.fetch_window(url),
                          be.source().fetch_window(url), "post-reject")


def test_push_before_any_poll_reports_no_entry():
    be = _Backend()
    delta_src = DeltaWindowSource(be.source())
    be.series["cold"] = [(T0, 1.0)]
    res = delta_src.ingest_append(_url("cold", T0, T0 + STEP),
                                  [float(T0)], [1.0])
    assert res == {"spliced": 0, "advanced": False, "reason": "no_entry"}


def test_push_hole_latches_resync_until_poll_heals():
    """A dropped spliceable push (buffer overfill, off-grid batch) is a
    HOLE the backend does not have: ingest_block latches the entry, later
    pushes refuse with `resync` (no papering over the gap), serving from
    the pushed cache stops, and one poll-driven refresh lifts the latch."""
    be = _Backend()
    grid_t = T0 + 9 * STEP
    clock = {"now": grid_t + 0.5}
    delta_src = DeltaWindowSource(be.source(), clock=lambda: clock["now"])
    be.series["h"] = [(T0 + k * STEP, 1.0 + k) for k in range(10)]

    def url_now():
        return _url("h", T0, clock["now"])

    delta_src.fetch_window(url_now())  # prime
    # the backend gains a sample the push path LOSES (the receiver calls
    # ingest_block when it drops one)
    be.series["h"].append((grid_t + STEP, 99.0))
    delta_src.ingest_block(url_now())
    grid_t += 2 * STEP
    clock["now"] = grid_t + 0.5
    be.series["h"].append((grid_t, 12.0))
    res = delta_src.ingest_append(url_now(), [float(grid_t)], [12.0])
    assert res["reason"] == "resync" and res["spliced"] == 0
    # the poll path reconciles (window identical to a full refetch,
    # INCLUDING the lost sample) and lifts the latch
    _assert_windows_equal(delta_src.fetch_window(url_now()),
                          be.source().fetch_window(url_now()), "healed")
    grid_t += STEP
    clock["now"] = grid_t + 0.5
    be.series["h"].append((grid_t, 13.0))
    res = delta_src.ingest_append(url_now(), [float(grid_t)], [13.0])
    assert res["spliced"] == 1, res
    _assert_windows_equal(delta_src.fetch_window(url_now()),
                          be.source().fetch_window(url_now()),
                          "post-resync push")
