"""The fleet a cell drives: seeded metric series, the simulated metric
store's range-query bodies, and the job documents that point at it.

The general traffic generator. Everything it does is a function of the
configuration file (classes, history and window lengths, trace shape) and
`--seed`; nothing here names a configuration or a cell. The series
arithmetic is a copy of `foremast_tpu/simfleet/trace.py` `SimTrace.series`
and the query layout a copy of `simfleet/backend.py` `make_docs`, kept here
so that a later change to the program cannot move the yardstick
(PERF.md, Open questions, lists the originals).

Grid: slot k is the sample at `t0 + k * step`. A job's windows are laid
out from the current window's first slot c:
  current     slots [c, now]              (`current_points` points at
                                           warm-up, one more per cadence
                                           step)
  historical  slots [c - H, c]            (H + 1 = `history_points`)
  baseline    slots [c - lead, c - lead + W]  (W + 1 = `current_points`:
                                           the current window's start one
                                           diurnal period earlier, same
                                           phase; [c - H, c - H + W] where
                                           the trace has no period)
where lead is one diurnal period in steps and c = lead + H.

A class may lay each role it carries elsewhere, as its request lays it:
  "placement": {"historical": {"start": -10080, "points": 10081},
                "baseline":   {"start": -10080, "points": 10081},
                "current":    {"points": 31}}
A past role (`baseline`, `historical`) names its first slot relative to
c (`start`, whole steps, negative for earlier) and its length in points;
it has to be whole by the warm-up clock. `current` names its greatest
length: its URL's end is fixed at slot c + points - 1, and the run can
drive only the cycles that fill it; its warm-up length stays
`current_points`. A role a class does not state lies where the layout
above lays it, so a configuration that states nothing reads bit for bit
what it read before placement was data. c moves later only as far as the
longest stated look-back needs, and the horizon covers the longest
current window. A role that lies on a class's historical range is queried
by the history's own URL, byte for byte (the source answers that range
from arrays), as foremast-trigger's rollover request copies its
historical query into its baseline. Whatever reads a role's slots reads
them from `Fleet.window_slots`.

A job that watches several metrics (`metrics` of its class) has these
windows of each, on the same slots; metric i is series slot i of the job,
and the anomaly is on slot 0.

Which file judges a scoring family is the configuration's too:
`"references": {"<family>": "<file stem>"}` names `benchmark/families/<stem>.py`
for a family; one it does not list is judged by `families/<family>.py`.
"""
from __future__ import annotations

import json
import math
import os
import re

import numpy as np

T0 = 1_700_000_000 // 60 * 60  # step-aligned epoch anchor of every trace
_SLOT_STRIDE = 7
_GOLDEN = 0.6180339887
FAMILIES_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "families")
_STEM = re.compile(r"[A-Za-z0-9_]+\Z")
ROLES = ("current", "baseline", "historical")
_PAST = ("baseline", "historical")
_TAGS = {"current": "cur", "baseline": "base", "historical": "hist"}


class BenchError(Exception):
    """The run cannot produce a result; exit non-zero without one."""


class Fleet:
    """One seeded fleet: series, query bodies and documents."""

    def __init__(self, config: dict, seed: int, tiny: bool = False):
        cfg = dict(config)
        if tiny:
            cfg.update(config.get("tiny", {}))
        self.config = cfg
        tr = cfg["trace"]
        self.step = int(cfg["step_s"])
        self.t0 = T0 // self.step * self.step
        hist_steps = int(cfg["history_points"]) - 1
        warm_points = int(cfg["current_points"])
        self.max_cycles = int(cfg["max_cycles"])
        self.level = float(tr["level"])
        self.sigma = float(tr["noise_sigma"])
        self.diurnal_amp = float(tr["diurnal_amp"])
        self.period_s = float(tr["diurnal_period_s"])
        self.lead = int(round(self.period_s / self.step)) \
            if self.diurnal_amp else 0
        self.n_shapes = int(tr["n_shapes"])
        self.classes = list(cfg["classes"])
        # the default layout of the past roles: (start, points) from the
        # current window's first slot; a class's `placement` overrides it
        past = {"historical": (-hist_steps, hist_steps + 1),
                "baseline": (-(self.lead or hist_steps), warm_points)}
        stated = [_placement(c, warm_points) for c in self.classes]
        cur_lo = max([self.lead + hist_steps] + [
            -st[r][0] for st in stated for r in _PAST if r in st])
        longest = [st["current"] for st in stated if "current" in st]
        self.horizon = max([cur_lo + warm_points - 1 + self.max_cycles + 16]
                           + [cur_lo + n for n in longest])
        # a fixed-end current window holds one cycle a slot past warm-up
        self.max_cycles = min([self.max_cycles]
                              + [n - warm_points + 1 for n in longest])
        warm_slot = cur_lo + warm_points - 1
        self._slots = [_slots(c, dict(past, **st), cur_lo, warm_slot,
                              self.horizon) for c, st in zip(self.classes,
                                                             stated)]
        # class of each job: classes interleave in proportion, so any
        # contiguous slice of the claim order carries the same mix
        counts = [int(c["jobs"]) for c in self.classes]
        self.jobs = sum(counts)
        self.class_of = _interleave(counts)
        # {family: stem of the file under families/ that judges it}
        self.references = _references(cfg, self.classes)
        rng = np.random.default_rng(int(seed))
        # fixed draw order: noise field first, then the anomalous jobs
        self.base = self.level + self.sigma * rng.standard_normal(
            (self.n_shapes, self.horizon))
        n_anom = int(round(self.jobs * float(tr["anomaly_rate"])))
        self.anomalous = frozenset(
            int(j) for j in rng.choice(self.jobs, size=n_anom, replace=False)
        ) if n_anom else frozenset()
        self.anomaly_shift = float(tr["anomaly_magnitude_sigma"]) * self.sigma
        # the shift starts mid-way through the warm-up current window, so
        # histories and baselines stay clean and the first cycle convicts
        self.active_from = float(self.t0 + (
            cur_lo + (warm_points - 1) // 2) * self.step)
        self.warm_now = float(self.t0 + warm_slot * self.step) + 5.0
        self.now = self.warm_now

    # --------------------------------------------------------------- series
    def series(self, job: int, slot: int, k_lo: int, k_hi: int) -> np.ndarray:
        """float64 values at grid slots [k_lo, k_hi], both inclusive."""
        k = np.arange(k_lo, k_hi + 1)
        out = self.base[(job * _SLOT_STRIDE + slot) % self.n_shapes][k].copy()
        t = self.t0 + k * self.step
        if self.diurnal_amp:
            phase = (job * _GOLDEN) % 1.0
            out += self.diurnal_amp * self.sigma * np.sin(
                2.0 * np.pi * (t / self.period_s + phase))
        if job in self.anomalous and slot == 0:
            out[t >= self.active_from] += self.anomaly_shift
        return out

    def served(self, job: int, slot: int, k_lo: int, k_hi: int) -> np.ndarray:
        """The series as the metric store serves it: four decimals."""
        return np.round(self.series(job, slot, k_lo, k_hi), 4)

    def served_rows(self, jobs: list, slot: int, k_lo: int,
                    k_hi: int) -> np.ndarray:
        """(len(jobs), k_hi - k_lo + 1): one served series a job."""
        return np.stack([self.served(j, slot, k_lo, k_hi) for j in jobs])

    def clip(self, qstart: float, qend: float) -> tuple[int, int]:
        """Grid slots a range query [qstart, qend] returns: whole steps
        inside the range, nothing newer than the simulated clock."""
        qend = min(float(qend), self.now)
        k_lo = max(int(math.ceil((qstart - self.t0) / self.step)), 0)
        k_hi = min(int((qend - self.t0) // self.step), self.horizon - 1)
        return k_lo, k_hi

    def now_slot(self) -> int:
        return min(int((self.now - self.t0) // self.step), self.horizon - 1)

    # --------------------------------------------------------------- bodies
    def body(self, job: int, slot: int, qstart: float, qend: float) -> bytes:
        """A Prometheus `query_range` matrix body for the query."""
        k_lo, k_hi = self.clip(qstart, qend)
        vals = ""
        if k_hi >= k_lo:
            vals = ",".join(
                f'[{self.t0 + (k_lo + i) * self.step},"{v:.4f}"]'
                for i, v in enumerate(
                    self.series(job, slot, k_lo, k_hi).tolist()))
        return ('{"status":"success","data":{"resultType":"matrix",'
                '"result":[{"metric":{"__name__":"bench_metric"},'
                '"values":[' + vals + ']}]}}').encode()

    # ----------------------------------------------------------------- docs
    def url(self, job: int, slot: int, tag: str, k_lo: int, k_hi: int) -> str:
        s = self.t0 + k_lo * self.step
        e = self.t0 + k_hi * self.step
        return (f"http://bench/q?job={job}&m={slot}&w={tag}"
                f"&start={s:.0f}&end={e:.0f}&step={self.step}")

    def cls(self, job: int) -> dict:
        """The configuration's class entry of a job."""
        return self.classes[self.class_of[job]]

    def job_id(self, job: int) -> str:
        return f"bench-{self.cls(job)['name']}-{job}"

    @staticmethod
    def job_index(job_id: str) -> int:
        return int(job_id.rsplit("-", 1)[1])

    def families_of(self, job: int) -> list:
        """The scoring families the job's windows route it to."""
        return self.cls(job)["families"]

    def metrics_of(self, job: int) -> list:
        """The aliases of the metrics a job watches; a metric's series
        slot is its position. A class lists `metrics`, or names its one
        `metric`."""
        cls = self.cls(job)
        return list(cls["metrics"]) if "metrics" in cls else [cls["metric"]]

    def app_name(self, job: int) -> str:
        """The app a job's document names: jobs share `apps` names (256
        unless the class says otherwise). The program exports a job's
        bounds under its app, so a class whose bounds are compared gives
        every job an app of its own."""
        return f"app-{job % int(self.cls(job).get('apps', 256))}"

    def window_slots(self, role: str, c: int) -> tuple[str, int, int]:
        """(URL tag, first slot, last slot) of a window role of class `c`
        (an index of `classes`): the one place a role's slots come from.
        The three roles are the job API's own; a class lists the ones its
        jobs carry beside `current` (its `windows`). A role that lies on
        the class's historical range carries the history's tag."""
        if role not in ROLES:
            raise ValueError(f"unknown window role {role!r}")
        return self._slots[c][role]

    def held(self, role: str, c: int, k_now: int) -> int:
        """Samples a role's window of class `c` holds with the clock at
        slot `k_now`."""
        _, lo, hi = self.window_slots(role, c)
        return min(hi, k_now) - lo + 1

    def role_rows(self, jobs: list, slot: int, role: str,
                  k_now: int) -> np.ndarray:
        """The served series of a role's window of `jobs` (one class)
        with the clock at slot `k_now`: one row a job."""
        _, lo, hi = self.window_slots(role, int(self.class_of[jobs[0]]))
        return self.served_rows(jobs, slot, lo, min(hi, k_now))

    def queries(self, job: int) -> dict:
        """{metric: {role: url}} for one job, by its class's metrics and
        windows."""
        c = int(self.class_of[job])
        roles = ("current", *self.cls(job)["windows"])
        return {metric: {role: self.url(job, slot,
                                        *self.window_slots(role, c))
                         for role in roles}
                for slot, metric in enumerate(self.metrics_of(job))}

    def points_fetched(self, job: int, k_now: int) -> int:
        """Samples the job's windows hold when the clock is at slot
        `k_now`, over all its metrics: what a cycle has to have fetched
        or spliced for it."""
        c = int(self.class_of[job])
        n = sum(self.held(role, c, k_now)
                for role in ("current", *self.cls(job)["windows"]))
        return n * len(self.metrics_of(job))

    def window_span(self) -> tuple[str, str]:
        """(start, end) RFC 3339 of every job's analysis: it outlasts the
        run, so no job completes by reaching its end time."""
        from datetime import datetime, timezone

        def rfc(ts):
            return datetime.fromtimestamp(ts, timezone.utc).strftime(
                "%Y-%m-%dT%H:%M:%SZ")

        return rfc(self.t0), rfc(self.t0 + (self.horizon + 1440) * self.step)


def _placement(cls: dict, warm_points: int) -> dict:
    """The roles a class lays itself: {past role: (start, points),
    "current": greatest length}. A malformed placement, or one of a role
    the class does not carry, ends the run here, before an engine is
    built."""
    stated = cls.get("placement", {})
    name = cls.get("name")
    if not isinstance(stated, dict):
        raise BenchError(f"class {name!r}: placement is an object by role")
    carried = ("current", *cls["windows"])
    out = {}
    for role, where in stated.items():
        if role not in carried:
            raise BenchError(
                f"class {name!r}: placement names {role!r}, and its jobs "
                f"carry {list(carried)}")
        keys = ("points",) if role == "current" else ("start", "points")
        if not isinstance(where, dict) or sorted(where) != sorted(keys) \
                or not all(type(where[k]) is int for k in keys) \
                or where["points"] < 1:
            raise BenchError(
                f"class {name!r}: placement of {role!r} is {where!r}; it "
                f"is {{{', '.join(map(repr, keys))}}}, whole numbers, at "
                f"least one point")
        if role == "current":
            if where["points"] < warm_points:
                raise BenchError(
                    f"class {name!r}: a current window of at most "
                    f"{where['points']} points cannot hold the "
                    f"{warm_points} of its warm-up (current_points)")
            out[role] = where["points"]
        else:
            out[role] = (where["start"], where["points"])
    return out


def _slots(cls: dict, where: dict, cur_lo: int, warm_slot: int,
           horizon: int) -> dict:
    """{role: (URL tag, first slot, last slot)} of one class: a past role
    at `where[role]` = (start, points) from the current window's first
    slot, the current window to its greatest length or the horizon."""
    past = {}
    for role in _PAST:
        start, points = where[role]
        lo, hi = cur_lo + start, cur_lo + start + points - 1
        if hi > warm_slot:
            raise BenchError(
                f"class {cls.get('name')!r}: its {role} window, slots "
                f"[{lo}, {hi}], does not fit the horizon: it has to be "
                f"whole by the warm-up clock, slot {warm_slot}")
        past[role] = (lo, hi)
    # a range is queried by the history's tag, whichever role asks for it
    out = {role: (_TAGS["historical" if span == past["historical"]
                        else role], *span) for role, span in past.items()}
    last = where.get("current")
    out["current"] = (_TAGS["current"], cur_lo,
                      horizon - 1 if last is None else cur_lo + last - 1)
    return out


def family_path(stem: str) -> str:
    return os.path.join(FAMILIES_DIR, stem + ".py")


def _references(cfg: dict, classes: list) -> dict:
    """{family: file stem} for every family a class lists. A stem is
    letters, digits and `_`, and its file exists: anything else ends the
    run here, before an engine is built."""
    named = dict(cfg.get("references", {}))
    out = {}
    for f in dict.fromkeys(f for c in classes for f in c["families"]):
        stem = named.pop(f, f)
        if not isinstance(stem, str) or not _STEM.match(stem):
            raise BenchError(
                f"references: family {f!r} names {stem!r}; a stem is letters, "
                f"digits and _ (a file benchmark/families/<stem>.py)")
        if not os.path.isfile(family_path(stem)):
            raise BenchError(
                f"family {f!r} is judged by benchmark/families/{stem}.py, "
                f"and there is no such file")
        out[f] = stem
    if named:
        raise BenchError(
            f"references names {sorted(named)}: no class lists such a family")
    return out


def _interleave(counts: list) -> np.ndarray:
    """Class index per job: each class's jobs spread evenly through the
    order (largest-remainder), deterministic in the counts alone."""
    keys = np.concatenate([
        (np.arange(n) + 0.5) / n + 1e-9 * c for c, n in enumerate(counts)])
    cls = np.concatenate([np.full(n, c) for c, n in enumerate(counts)])
    return cls[np.argsort(keys, kind="stable")]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
