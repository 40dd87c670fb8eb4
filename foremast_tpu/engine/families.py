"""The table of scoring families: the one place that knows what one is.

`Analyzer._preprocess` decides which family a job's metrics are routed to
(by strategy and metric count) and hands on {family name: items}.
Everything after it reads this table and names no family: `CyclePipeline`
(accumulate, memo, launch, collect, per-job retry), `TriageGate`, the fold
in `Analyzer._run_cycle`, and `prewarm`.

What must not move (verdicts, reasons, records and launch counts are the
hand-written code's, byte for byte, only while it holds):

  * `FAMILIES` is in the order pair, band, bivariate, hpa, lstm. Those
    that stream are fed, flushed and collected in it (pair, band,
    bivariate, hpa); those the fold loop folds are folded in it (pair,
    band, bivariate, lstm), appending to a job's `unhealthy` list and to
    its record's `families`, so the order shows in the job's `reason`.
  * `launch`, `collect` and the T rules delegate to `Analyzer._launch_*`,
    `_collect_*`, `_pair_T`, `_band_T`, `_bi_prep` (which calls module-level
    `analyzer._joint_grid`), `_hpa_rows`, `_hpa_row_T`, looked up on the
    analyzer at call time: `benchmark/tests` plants its faults by patching
    those names. Their bodies move here, one module a family, once a
    `benchmark` issue plants the faults at this table's seam.
  * `route`, `entry_key` and `fp_parts` run once an item on the stream,
    15,000 to 20,000 times a cycle: no clock read, no allocation beyond
    the entry (PERF.md section 6). Callers bind a family's callables once
    a job or a cycle, never once an item.

What adding a family costs: docs/performance.md, "Adding a family". The
table is a module-level tuple; nothing registers into it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import numpy as np

from .analyzer import (
    _BandItem,
    _BiItem,
    _HpaItem,
    _PairItem,
    _concat_trimmed,
)

__all__ = ["Family", "FAMILIES", "family", "newest_sample_ts"]


def _band_gate(an, shrunk: int, checked: int) -> bool:
    # band, bivariate: count >= max(band_min_points, band_violation_fraction
    # * checked) is unhealthy. A non-positive gate (band_min_points forced
    # to 0 on an empty region) can never clear: 0 < 0 is false.
    return shrunk < an._gate(checked)


@dataclass(frozen=True)
class Family:
    """One family's answers. An `entry` is what an accumulator holds and a
    launch takes; a `row` is what `route` makes an entry from. Unless a
    family says otherwise both are the item. `an` is the Analyzer, `r` a
    result of `collect`."""

    name: str
    count_attr: str  # the engine.score span's attr that counts its items
    key: Callable = None  # item -> its result's key
    # -- the stream. `route` None: the family is scored at finish, over
    # the cycle's items in claim order, by `scorer`
    route: Callable = None  # (an, row) -> (entry, T bucket)
    rows: Callable = lambda an, items: items  # one job's items -> rows
    row_items: Callable = lambda row: [row]  # of a row `route` failed on
    items_of: Callable = list  # entries -> items, for the per-job retry
    entry_key: Callable = None  # entry -> result key (None: `key`)
    # (entry, T) -> everything launch and collect read from the entry:
    # every window's full identity, the policy, and the T bucket (the band
    # kernel gate is a function of T). Config is deliberately absent: it
    # is frozen for the analyzer's lifetime, and the memo dies with it.
    fp_parts: Callable = None
    launch: Callable = None  # (an, entries, T) -> launch state
    collect: Callable = None  # (an, state) -> {result key: r}
    scorer: Callable = None  # (an, items) -> results, in `score`'s place
    score_attrs: Callable = lambda an: {}  # of a scorer's engine.score.* span
    currents: Callable = lambda items: [it.current for it in items]
    # -- the fold: what one result adds to the job's verdict. `provenance`
    # None: the verdict is folded elsewhere and the loop only counts it
    provenance: Callable = None  # (an, item, r) -> the `families` entry
    bounds: Callable = None  # (item, r) -> [(metric, upper, lower)] to export
    unhealthy: Callable = None  # (an, item, r) -> (metric, cause, pairs)
    # (rung, n_h, n_c, win, policy) -> `rung` rows of items in bucket
    # n_h + n_c (`win(n)`: a window of n samples). None: prewarm skips it
    prewarm_items: Callable = None
    # -- triage: may the screen clear its rows under this config
    # (TRIAGE_FAMILIES chooses among those that say yes), and if so
    screens: Callable = lambda cfg: False
    # entry -> one (values, mask, n_h, policy) a channel: the series as
    # the scorer packs it and where its judged region starts
    screen_rows: Callable = None
    # (an, shrunk count, checked) -> is it under the verdict gate
    screen_clears: Callable = _band_gate
    # (entry, screen outputs) -> what `collect` would give a healthy row,
    # in the fields the fold and the exporter read; sub-gate cosmetics the
    # healthy fold never reads (first_ts, anomaly_pairs, p-values) zeroed
    cleared_result: Callable = None

    def __post_init__(self):
        if self.entry_key is None:
            object.__setattr__(self, "entry_key", self.key)

    @property
    def streams(self) -> bool:
        return self.route is not None

    def score(self, an, items: list) -> dict:
        """Score these items now: bucket, launch, collect, through the
        halves the stream uses, so the per-job retry, `prewarm` and the
        stream cannot drift."""
        if self.scorer is not None:
            return self.scorer(an, items)
        by_bucket: dict[int, list] = {}
        for row in self.rows(an, items):
            entry, T = self.route(an, row)
            by_bucket.setdefault(T, []).append(entry)
        results = {}
        for T, entries in by_bucket.items():
            results.update(self.collect(an, self.launch(an, entries, T)))
        return results


def _pair_cause(an, it, r):
    causes = []
    if r["pairwise_unhealthy"]:
        causes.append(f"pairwise rejection p={r['min_p']:.2e}")
    if r["band_unhealthy"]:
        causes.append(f"{r['band_count']} points outside the baseline band")
    return (it.metric, "; ".join(causes), [])


def _bi_route(an, it):
    pre, T = an._bi_prep(it)
    return (it, pre), T


def _bi_parts(entry, T):
    it = entry[0]
    return (b"bi", T, it.metrics, *it.hist, *it.cur, *it.policies)


def _bi_screen_rows(entry):
    it, (x, m, n_h, _n_c) = entry
    return [(x[0], m[0], n_h, it.policies[0]),
            (x[1], m[1], n_h, it.policies[1])]


def _bi_cleared(entry, outs):
    return {"count": 0, "unhealthy": False, "first_ts": -1.0,
            "anomaly_pairs": [], "bounds": {
                m: (float(o["upper_mean"]), float(o["lower_mean"]))
                for m, o in zip(entry[0].metrics, outs)}}


def _hpa_items(rows):
    items = []
    for _job_id, t, s in rows:
        items.append(t)
        if s is not t:
            items.append(s)
    return items


def _hpa_parts(row, T):
    _job_id, t, s = row
    return (b"hpa", T, t.metric, t.historical, t.current, t.is_increase,
            t.priority, t.is_absolute, t.pod_window, s.metric, s.historical,
            s.current, s.is_increase, s.priority, s.is_absolute)


def _hpa_prewarm(rung, n_h, n_c, win, policy):
    items = []
    for i in range(rung):
        items.append(_HpaItem(f"w{i}", "tps", win(n_h), win(n_c), True, 0))
        items.append(_HpaItem(f"w{i}", "latency", win(n_h), win(n_c), True, 1))
    return items


FAMILIES: tuple = (
    Family(
        name="pair", count_attr="pairs",
        key=lambda it: (it.job_id, it.metric, "pair"),
        route=lambda an, it: (it, an._pair_T(it)),
        fp_parts=lambda it, T: (b"pair", T, it.metric, it.baseline,
                                it.current, it.policy),
        launch=lambda an, group, T: an._launch_pairs(group, T),
        collect=lambda an, state: an._collect_pairs(state),
        provenance=lambda an, it, r: {
            "family": "pair", "metric": it.metric,
            "min_p": round(r["min_p"], 8),
            "alpha": an.config.pairwise_threshold,
            "unhealthy": bool(r["unhealthy"])},
        unhealthy=_pair_cause,
        prewarm_items=lambda rung, n_h, n_c, win, policy: [
            _PairItem(f"w{i}", "latency", win(n_h), win(n_c), policy)
            for i in range(rung)],
        screens=lambda cfg: True,
        screen_rows=lambda it: [
            (*_concat_trimmed(it.baseline, it.current), it.policy)],
        # the pair kernel's internal band condemns at a fixed 0.3
        # violation fraction (parallel/fleet.py _pair_verdict)
        screen_clears=lambda an, shrunk, checked:
            shrunk <= 0.3 * max(checked, 1),
        cleared_result=lambda it, outs: {
            "unhealthy": False, "min_p": 1.0, "pairwise_unhealthy": False,
            "band_unhealthy": False, "band_count": int(outs[0]["count"])},
    ),
    Family(
        name="band", count_attr="bands",
        key=lambda it: (it.job_id, it.metric, "band"),
        route=lambda an, it: (it, an._band_T(it)),
        fp_parts=lambda it, T: (b"band", T, it.metric, it.historical,
                                it.current, it.policy),
        launch=lambda an, group, T: an._launch_bands(group, T),
        collect=lambda an, state: an._collect_bands(state),
        provenance=lambda an, it, r: {
            "family": "band", "metric": it.metric,
            "anomalous_points": int(r["count"]),
            "band": [round(r["lower"], 4), round(r["upper"], 4)],
            "unhealthy": bool(r["unhealthy"])},
        bounds=lambda it, r: ((it.metric, r["upper"], r["lower"]),),
        unhealthy=lambda an, it, r: (
            it.metric,
            f"{r['count']} points outside "
            f"[{r['lower']:.4g},{r['upper']:.4g}] from ts {r['first_ts']:.0f}",
            r["anomaly_pairs"]),
        prewarm_items=lambda rung, n_h, n_c, win, policy: [
            _BandItem(f"w{i}", "latency", win(n_h), win(n_c), policy)
            for i in range(rung)],
        # the screen's one-sided replica argument only covers the MA band;
        # other forecasters' bands always take the full path
        screens=lambda cfg: cfg.algorithm.startswith("moving_average"),
        screen_rows=lambda it: [
            (*_concat_trimmed(it.historical, it.current), it.policy)],
        cleared_result=lambda it, outs: {
            "count": int(outs[0]["count"]), "unhealthy": False,
            "first_ts": -1.0, "upper": float(outs[0]["upper_mean"]),
            "lower": float(outs[0]["lower_mean"]), "anomaly_pairs": []},
    ),
    # an entry is (item, joint-grid prep): the prep is made once, on the
    # stream, and the launch packs from it
    Family(
        name="bivariate", count_attr="bis",
        key=lambda it: (it.job_id, "&".join(it.metrics), "bivariate"),
        route=_bi_route,
        items_of=lambda entries: [it for it, _pre in entries],
        entry_key=lambda e: (e[0].job_id, "&".join(e[0].metrics), "bivariate"),
        fp_parts=_bi_parts,
        launch=lambda an, entries, T: an._launch_bivariate(entries, T),
        collect=lambda an, state: an._collect_bivariate(state),
        currents=lambda items: [w for it in items for w in it.cur],
        provenance=lambda an, it, r: {
            "family": "bivariate", "metric": "&".join(it.metrics),
            "anomalous_points": int(r["count"]),
            "unhealthy": bool(r["unhealthy"])},
        bounds=lambda it, r: [(m, upper, lower)
                              for m, (upper, lower) in r["bounds"].items()],
        unhealthy=lambda an, it, r: (
            "&".join(it.metrics),
            f"{r['count']} points outside the joint "
            f"bivariate-normal ellipse from ts {r['first_ts']:.0f}",
            r["anomaly_pairs"]),
        prewarm_items=lambda rung, n_h, n_c, win, policy: [
            _BiItem(f"w{i}", ("latency", "cpu"), (win(n_h), win(n_h)),
                    (win(n_c), win(n_c)), (policy, policy))
            for i in range(rung)],
        screens=lambda cfg: True,
        screen_rows=_bi_screen_rows,
        cleared_result=_bi_cleared,
    ),
    # a row, and an entry, is (job_id, tps_item, sla_item) over the job's
    # items together, and the result is keyed by the job. The verdict (the
    # gated score, the hpalog, the record) is folded by
    # `Analyzer._finish_hpa`. Triage never screens it: its per-cycle score
    # and hpalog ARE the verdict.
    Family(
        name="hpa", count_attr="hpas",
        rows=lambda an, items: an._hpa_rows(items),
        route=lambda an, row: (row, an._hpa_row_T(row)),
        row_items=lambda row: _hpa_items([row]),
        items_of=_hpa_items,
        entry_key=lambda row: row[0],
        fp_parts=_hpa_parts,
        launch=lambda an, rows, T: an._launch_hpa(rows, T),
        collect=lambda an, state: an._collect_hpa(state),
        prewarm_items=_hpa_prewarm,
    ),
    # scored at finish, not on the stream: training mutates the model
    # cache under a per-cycle budget whose order must match claim order,
    # and the scorer batches the cycle's jobs itself. It keeps memo tables
    # of its own, and triage never sees it.
    Family(
        name="lstm", count_attr="multis",
        key=lambda it: (it.job_id, "+".join(it.metrics), "lstm"),
        scorer=lambda an, items: an._score_multi(items),
        score_attrs=lambda an: {
            "budget_skips": len(an._lstm_budget_skipped_ids)},
        currents=lambda items: [w for it in items for w in it.cur],
        provenance=lambda an, it, r: {
            "family": "lstm", "metric": "+".join(it.metrics),
            "z": round(float(r["z"]), 4),
            "threshold": an.config.lstm_threshold,
            "unhealthy": bool(r["unhealthy"])},
        unhealthy=lambda an, it, r: (
            "+".join(it.metrics),
            f"LSTM-AE reconstruction z={r['z']:.2f} exceeds "
            f"{an.config.lstm_threshold:.1f}",
            []),
    ),
)


def family(name: str) -> Family:
    return next(f for f in FAMILIES if f.name == name)


def newest_sample_ts(routed: dict) -> float:
    """Newest VALID sample timestamp across a job's judged current
    windows: the moment the job's window last ADVANCED, on the data's own
    clock. 0.0 when nothing is judgeable."""
    newest = 0.0
    for fam in FAMILIES:
        items = routed.get(fam.name)
        if not items:
            continue
        for w in fam.currents(items):
            if w is None or w.n_valid == 0:
                continue
            idx = int(np.flatnonzero(w.mask)[-1])
            newest = max(newest, float(w.start + idx * w.step))
    return newest
