"""Tier-0 triage gate: clear the boring rows before family scoring.

PR 3's fingerprint memo skips rows whose bytes didn't move; in a live
steady fleet most rows DO move every cycle (one new sample) yet remain
unremarkable, and each still paid a full per-family device launch. The
gate composes directly after `CyclePipeline._memo_check`: memo skips
unchanged rows, this tier skips changed-but-unremarkable ones. Rows are
batched into the fused `ops.triage.screen_rows` program (one launch
shared by every screened family per T bucket, an order of magnitude
coarser than the family fire rungs because the screen is one cheap
pass), classified host-side as CLEAR or SUSPECT, and:

  * CLEAR rows short-circuit to a healthy result through the existing
    verdict machinery — the synthesized result dict is exactly what the
    family's collect would produce for a zero-violation row (count 0,
    first_ts -1, the screen's band means for the exported bounds), so
    folding, stale-state refresh, memoization and `/metrics` all behave
    identically; provenance tags the job `triaged` with the screen
    statistics vs thresholds.
  * SUSPECT rows flow unchanged into the family rung accumulators and
    are scored by the full path — escalation can never change a verdict,
    only cost a launch.

Verdict safety is by construction, not just by test:

  * the CLEAR rule for the band family requires the violation count of
    the policy band SHRUNK by `TRIAGE_MARGIN` sigmas to stay under the
    family's verdict gate, computed with the band scorer's own
    smoother/sigma math (see ops/triage.py for the one-sided dominance
    argument: shrunk count >= real count, so a sub-gate shrunk count
    implies the full scorer's count is sub-gate — healthy) — and the
    band family is screened ONLY under `moving_average*` algorithms,
    where that replica argument holds. Seasonal/HW/SES bands always
    escalate.
  * canary-class jobs (anything not continuous/hpa) always escalate:
    their verdict gates a live rollout.
  * the hpa family always escalates — its per-cycle score and hpalog
    emission ARE the verdict; there is nothing sound to short-circuit.
  * pair and bivariate rows always escalate by default: rank-test
    p-values (pair) and ellipse correlation breaks (bivariate) are not
    bounded by any cheap marginal statistic, so the screen is not
    provably one-sided there. Opting them in via `TRIAGE_FAMILIES`
    TRADES VERDICT FIDELITY FOR LAUNCHES: a sustained sub-band
    distribution shift (e.g. a uniform ~1.5-sigma level drift stays
    inside the band and under TRIAGE_Z, yet a rank test over a full
    window condemns it) will be cleared that the full pair scorer would
    convict. Only for fleets where band-style violations are the signal
    of record — documented in docs/performance.md; hpa opt-in is
    ignored.

The CLEAR/SUSPECT thresholds (`TRIAGE_Z`, `TRIAGE_MARGIN`,
`TRIAGE_MIN_POINTS`) are applied host-side from the kernel's outputs, so
threshold sweeps — including the verdict-safety sweep test — compile
nothing new.
"""
from __future__ import annotations

import time

import numpy as np

from ..dataplane.promql import CONTINUOUS_STRATEGIES
from ..ops import triage as triage_ops
from ..ops.windowing import bucket_length
from . import families

__all__ = ["TriageGate", "screen_cap"]

# memory budget for one screen launch, in row-steps: the row cap scales
# down for long T buckets so a 16k-row screen of 1k-step windows and a
# 1k-row screen of 16k-step windows cost the same peak bytes
_SCREEN_BUDGET_STEPS = 1024


def screen_cap(fire_rows: int, T: int) -> int:
    """Max rows per screen launch for a T bucket (memory-aware)."""
    fire_rows = max(int(fire_rows), 16)
    budget = fire_rows * _SCREEN_BUDGET_STEPS
    return int(min(fire_rows, max(budget // max(int(T), 1), 1024)))


class TriageGate:
    """One cycle's screen state. Single-threaded like CyclePipeline: fed
    from the ordered preprocess stream, so routing stays deterministic."""

    def __init__(self, analyzer):
        cfg = analyzer.config
        self.an = analyzer
        # the table says which families the screen can clear soundly under
        # this configuration (hpa and lstm never: see module docstring)
        self.families = frozenset(
            f.name for f in families.FAMILIES
            if f.name in cfg.triage_families and f.screens(cfg))
        self.z = float(cfg.triage_z)
        self.margin = float(cfg.triage_margin)
        self.min_points = int(cfg.triage_min_points)
        self.fire_rows = max(int(cfg.triage_fire_rows), 16)
        self.acc: dict[int, list] = {}        # screen T bucket -> [unit]
        self._rows_in: dict[int, int] = {}    # screen T bucket -> row count
        self.results: dict[str, dict] = {f: {} for f in self.families}
        self.stats: dict = {}                 # result key -> screen stats
        self.job_hits: dict[str, int] = {}    # job -> cleared results
        self.screened: dict[str, int] = {}    # per-family row counts
        self.cleared: dict[str, int] = {}
        self.escalated: dict[str, int] = {}
        self.launches = 0
        self.seconds = 0.0

    @property
    def active(self) -> bool:
        return bool(self.families)

    def accepts(self, family: str, strategy: str) -> bool:
        """Does this (family, job-class) row enter the screen at all?"""
        return family in self.families and strategy in CONTINUOUS_STRATEGIES

    # --------------------------------------------------------------- feeding
    def add(self, fam, fam_T: int, entry, pipe) -> None:
        """Route one accumulator entry into the screen; fire full buckets.

        Called inside `CyclePipeline.feed`'s per-item guard: a malformed
        entry raises out to the pipeline's per-job retry list, same blast
        radius as every scoring step."""
        # one logical screen unit: 1 row (pair/band) or 2 channel rows
        # (bivariate), in the exact packed layout the family scorer uses
        rows = fam.screen_rows(entry)
        T = bucket_length(rows[0][0].shape[0])
        unit = {"fam": fam, "fam_T": fam_T, "entry": entry,
                "key": fam.entry_key(entry), "rows": rows}
        self.acc.setdefault(T, []).append(unit)
        self._rows_in[T] = self._rows_in.get(T, 0) + len(rows)
        # counters are in ROWS (a bivariate unit is 2 channel rows) so the
        # exported "rows screened/cleared/escalated" totals stay honest
        self.screened[fam.name] = self.screened.get(fam.name, 0) + len(rows)
        if self._rows_in[T] >= screen_cap(self.fire_rows, T):
            units = self.acc[T]
            self.acc[T] = []
            self._rows_in[T] = 0
            self._fire(T, units, pipe)

    def flush(self, pipe) -> None:
        """Screen every remaining partial bucket (pipeline stream end)."""
        buckets, self.acc = self.acc, {}
        self._rows_in = {}
        for T, units in buckets.items():
            if units:
                self._fire(T, units, pipe)

    # --------------------------------------------------------------- firing
    def _fire(self, T: int, units: list, pipe) -> None:
        t0, c0 = time.perf_counter(), time.thread_time()
        rows = [(u, r) for u in units for r in u["rows"]]
        try:
            outs = self._screen(T, rows)
        except Exception:  # noqa: BLE001 - screen failure must never fail a
            # cycle: a wedged/hung screen (WatchdogTimeout included) or a
            # packing surprise escalates the whole bucket to the full
            # path, which carries its own watchdog + per-job isolation
            outs = None
        suspects: list = []
        if outs is None:
            suspects = units
        else:
            i = 0
            for u in units:
                u_outs = outs[i:i + len(u["rows"])]
                i += len(u["rows"])
                if all(self._row_clear(u["fam"], o) for o in u_outs):
                    self._clear(u, u_outs)
                else:
                    suspects.append(u)
        # the triage clock stops BEFORE suspects route into the family
        # accumulators: pipe._add can fire full family rungs, and that
        # dispatch time belongs to the pipeline's dispatch stage — booking
        # it here would double-count it into foremastbrain:triage_seconds
        self.seconds += time.perf_counter() - t0
        pipe.fired_cpu_seconds += time.thread_time() - c0
        for u in suspects:
            self._escalate(u, pipe)

    def _screen(self, T: int, rows: list) -> list[dict]:
        """Pack + launch the fused kernel (rung-chunked), materialize
        under the analyzer's watchdog, return per-row output dicts."""
        cap = screen_cap(self.fire_rows, T)
        window = self.an.config.ma_window
        out_rows: list[dict] = []
        for i in range(0, len(rows), cap):
            chunk = rows[i:i + cap]
            n = len(chunk)
            R = self._rung(n, cap)
            xv = np.zeros((R, T), np.float32)
            xm = np.zeros((R, T), bool)
            reg = np.zeros((R, T), bool)
            thr = np.zeros(R, np.float32)
            bnd = np.ones(R, np.int32)
            mlb = np.zeros(R, np.float32)
            for j, (_, (vals, mask, n_h, pol)) in enumerate(chunk):
                L = vals.shape[0]
                xv[j, :L] = vals
                xm[j, :L] = mask
                reg[j, n_h:L] = True
                thr[j] = pol.threshold
                bnd[j] = pol.bound
                mlb[j] = pol.min_lower_bound
            mg = np.full(R, self.margin, np.float32)
            self.an.device_launches += 1
            self.launches += 1
            st = self.an._call(triage_ops.screen_rows, xv, xm, reg, thr,
                               bnd, mlb, mg, window)
            # materialize straight to Python lists, real rows only: the
            # per-row classification below touches every field of every
            # row, and 10k+ boxed numpy scalar reads per cycle cost more
            # host time than the screen saves in launches
            out = self.an._watchdog_call(
                lambda s=st, m=n: {k: self.an._host(v)[:m].tolist()
                                   for k, v in s.items()})
            out_rows += [{k: out[k][j] for k in out} for j in range(n)]
        return out_rows

    def _rung(self, n: int, cap: int) -> int:
        """Smallest screen batch rung >= n (the family chunker's ladder
        walk, capped at the screen's own memory-aware cap)."""
        return type(self.an)._rung_for(n, cap)

    # ------------------------------------------------------- classification
    def _row_clear(self, fam, o: dict) -> bool:
        """CLEAR iff the full path provably returns healthy for this row.

        The load-bearing check is `shrunk_count` vs the family's verdict
        gate: shrunk_count counts violations of the band NARROWED by
        `margin` sigmas, a superset of the real band's violations AND of
        any float-drift flips (a point the scorer's program could count
        differently sits within ulps of the real boundary, i.e. well
        outside the shrunk band), so shrunk_count below the gate implies
        the scorer's count is below the gate — healthy. Comparing against
        the gate rather than zero is what lets tight-threshold policies
        (a 2-sigma error band over ordinary noise always has a few
        outliers, which the scorer's gate exists to tolerate) still
        clear. The robust-z guard is escalation-only on top."""
        if int(o["n_hist"]) < self.min_points:
            return False  # too thin a floor: let the full path decide
        if not fam.screen_clears(self.an, int(o["shrunk_count"]),
                                 int(o["checked"])):
            return False
        if float(o["robust_z"]) >= self.z:
            # defense-in-depth guard: suspicious, escalate. >= (not >) so
            # TRIAGE_Z=0 really does screen nothing — a constant series'
            # robust_z is exactly 0.0 and must escalate at z=0 too
            return False
        return True

    def _escalate(self, u: dict, pipe) -> None:
        fam = u["fam"]
        self.escalated[fam.name] = (self.escalated.get(fam.name, 0)
                                    + len(u["rows"]))
        pipe._add(fam, u["fam_T"], u["entry"])

    def _clear(self, u: dict, outs: list[dict]) -> None:
        fam, key = u["fam"], u["key"]
        # the healthy result the family's collect would have produced
        self.results[fam.name][key] = fam.cleared_result(u["entry"], outs)
        self.stats[key] = {
            "triaged": True,
            "robust_z": round(max(float(x["robust_z"]) for x in outs), 4),
            "resid_z": round(max(float(x["resid_z"]) for x in outs), 4),
            "z_threshold": self.z,
            "margin": self.margin,
            "checked": sum(int(x["checked"]) for x in outs),
        }
        job_id = key[0]
        self.job_hits[job_id] = self.job_hits.get(job_id, 0) + 1
        self.cleared[fam.name] = (self.cleared.get(fam.name, 0)
                                  + len(u["rows"]))
