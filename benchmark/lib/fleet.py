"""The fleet a cell drives: seeded metric series, the simulated metric
store's range-query bodies, and the job documents that point at it.

The general traffic generator. Everything it does is a function of the
configuration file (classes, history and window lengths, trace shape) and
`--seed`; nothing here names a configuration or a cell. The series
arithmetic is a copy of `foremast_tpu/simfleet/trace.py` `SimTrace.series`
and the query layout a copy of `simfleet/backend.py` `make_docs`, kept here
so that a later change to the program cannot move the yardstick
(PERF.md, Open questions, lists the originals).

Grid: slot k is the sample at `t0 + k * step`. A job's windows are
  historical  slots [lead, lead + H]        (H + 1 points, fixed)
  current     slots [lead + H, now]         (W + 1 points at warm-up, one
                                             more per cadence step)
  baseline    slots [H, H + W]              (the current window's start
                                             one diurnal period earlier,
                                             same phase)
where lead is one diurnal period in steps. A job that watches several
metrics (`metrics` of its class) has these windows of each, on the same
slots; metric i is series slot i of the job, and the anomaly is on slot 0.

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
        self.hist_steps = int(cfg["history_points"]) - 1
        self.window_steps = int(cfg["current_points"]) - 1
        self.max_cycles = int(cfg["max_cycles"])
        self.level = float(tr["level"])
        self.sigma = float(tr["noise_sigma"])
        self.diurnal_amp = float(tr["diurnal_amp"])
        self.period_s = float(tr["diurnal_period_s"])
        self.lead = int(round(self.period_s / self.step)) \
            if self.diurnal_amp else 0
        self.n_shapes = int(tr["n_shapes"])
        self.horizon = (self.lead + self.hist_steps + self.window_steps
                        + self.max_cycles + 16)
        # class of each job: classes interleave in proportion, so any
        # contiguous slice of the claim order carries the same mix
        self.classes = list(cfg["classes"])
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
            self.lead + self.hist_steps + self.window_steps // 2) * self.step)
        self.hist_lo = self.lead
        self.hist_hi = self.lead + self.hist_steps
        self.base_lo = self.hist_hi - self.lead if self.lead else self.hist_lo
        self.warm_now = float(
            self.t0 + (self.hist_hi + self.window_steps) * self.step) + 5.0
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

    def window_slots(self, role: str) -> tuple[str, int, int]:
        """(URL tag, first slot, last slot) of a window role. The three
        roles are the job API's own; a class lists the ones its jobs
        carry beside `current` (its `windows`)."""
        if role == "current":
            return "cur", self.hist_hi, self.horizon - 1
        if role == "baseline":
            return "base", self.base_lo, self.base_lo + self.window_steps
        if role == "historical":
            return "hist", self.hist_lo, self.hist_hi
        raise ValueError(f"unknown window role {role!r}")

    def queries(self, job: int) -> dict:
        """{metric: {role: url}} for one job, by its class's metrics and
        windows."""
        roles = ("current", *self.cls(job)["windows"])
        return {metric: {role: self.url(job, slot, *self.window_slots(role))
                         for role in roles}
                for slot, metric in enumerate(self.metrics_of(job))}

    def points_fetched(self, job: int, k_now: int) -> int:
        """Samples the job's windows hold when the clock is at slot
        `k_now`, over all its metrics: what a cycle has to have fetched
        or spliced for it."""
        n = 0
        for role in ("current", *self.cls(job)["windows"]):
            _, lo, hi = self.window_slots(role)
            n += min(hi, k_now) - lo + 1
        return n * len(self.metrics_of(job))

    def window_span(self) -> tuple[str, str]:
        """(start, end) RFC 3339 of every job's analysis: it outlasts the
        run, so no job completes by reaching its end time."""
        from datetime import datetime, timezone

        def rfc(ts):
            return datetime.fromtimestamp(ts, timezone.utc).strftime(
                "%Y-%m-%dT%H:%M:%SZ")

        return rfc(self.t0), rfc(self.t0 + (self.horizon + 1440) * self.step)


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
