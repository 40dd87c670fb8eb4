"""Single-dispatch mega-batching (ISSUE 15): engine/pipeline.py MEGABATCH.

The load-bearing contract: mega-batching changes LAUNCH COUNT, never
verdicts. Scorers are row-wise, so one padded mega launch per (family,
T bucket) must be byte-identical to the rung path's chunked launches —
pinned here across the padding-class boundaries, the degenerate fleets
(empty family, single job), and the zero-row cases (all rows memo-hit /
triage-cleared must launch NOTHING). The perf-marked A/B additionally
gates the measured win and the per-family launch collapse on the
launch-heavy shape (`make perf-smoke`, the CI perf-smoke job).
"""
from __future__ import annotations

import dataclasses
import os

import pytest

from foremast_tpu.dataplane.delta import DeltaWindowSource
from foremast_tpu.engine import jobs as J
from foremast_tpu.engine.analyzer import Analyzer
from foremast_tpu.engine.config import EngineConfig
from foremast_tpu.simfleet import SimBackend, SimTrace, preset


# ----------------------------------------------------------- mini harness
def _mini(jobs: int, megabatch: bool, cycles: int = 2, *, mix=None,
          memo: bool = False, triage: bool = False,
          anomaly_rate: float = 0.0, advance: float = 60.0,
          max_rows: int = 32768):
    """Run a small simulated fleet through the engine and return
    (per-job outcome map, engine, backend). Steady trace (no diurnal),
    tiny windows so compiles stay cheap in tier-1."""
    spec = preset("steady", jobs, seed=3, window_steps=32,
                  hist_windows=2, anomaly_rate=anomaly_rate)
    if mix is not None:
        spec = dataclasses.replace(spec, mix=mix)
    step = spec.step_s
    t0 = 1_700_000_000 // step * step
    hist = spec.hist_windows * spec.window_steps
    horizon = hist + spec.window_steps + int(cycles * advance) // step + 8
    trace = SimTrace(spec, t0, horizon)
    backend = SimBackend(trace)
    source = DeltaWindowSource(backend.source(), max_entries=8 * jobs,
                               clock=lambda: backend.now)
    store = J.JobStore()
    for d in backend.make_docs():
        store.create(d)
    engine = Analyzer(
        EngineConfig(megabatch=megabatch, megabatch_max_rows=max_rows,
                     score_memo=memo, triage=triage,
                     window_cache_max=8 * jobs),
        source, store)
    backend.set_now(float(t0 + (hist + spec.window_steps) * step) + 5.0)
    outcomes = {}
    for c in range(cycles):
        if c:
            backend.set_now(backend.now + advance)
        outcomes = engine.run_cycle(now=backend.now)
    return outcomes, engine, store, backend


def _verdicts(store) -> list:
    every = store.by_status(*J.OPEN_STATUSES, *J.TERMINAL_STATUSES)
    return sorted((d.id, d.status, d.reason, sorted(d.anomaly.items()))
                  for d in every)


CONT = (("continuous", 1.0),)


# ------------------------------------------------------- padding classes
def test_mega_rows_padding_classes():
    mr = Analyzer._mega_rows
    # rung ladder below the mantissa floor
    assert mr(1) == 16
    assert mr(16) == 16
    assert mr(17) == 64  # the classic ladder's next rung
    assert mr(512) == 512
    # mantissa-quantized above it: m * 2^e with m in [16, 32)
    assert mr(513) == 544   # 17 * 32
    assert mr(1024) == 1024
    assert mr(1025) == 1088  # 17 * 64
    assert mr(100_000) == 102_400
    for n in (513, 700, 1500, 5000, 99_999, 1_000_000):
        cls = mr(n)
        assert cls >= n
        # waste bound: <= 1/16 of the class
        assert cls - n <= cls / 16 + 1
        # classes are idempotent (a class pads to itself)
        assert mr(cls) == cls


def test_mega_cap_scales_with_window_length():
    _, engine, _, _ = _mini(4, megabatch=True, cycles=1, mix=CONT)
    assert engine._mega_cap(128) == 32768
    assert engine._mega_cap(1024) == 32768
    assert engine._mega_cap(2048) == 16384
    assert engine._mega_cap(16384) == 2048
    # floor: never below 1024 rows however long the bucket
    assert engine._mega_cap(10 ** 9) == 1024


def test_mega_accumulator_fires_at_per_T_cap():
    """_add's fire threshold is the T-scaled _mega_cap, not the global
    row ceiling: _fire packs its whole bucket into (n, T) host arrays
    before _launch_chunks re-chunks, so a T-blind threshold would let a
    long-window bucket materialize multi-GB packed arrays the
    launch-time cap can no longer bound."""
    from foremast_tpu.engine.families import family
    from foremast_tpu.engine.pipeline import CyclePipeline

    _, engine, _, _ = _mini(4, megabatch=True, cycles=1, mix=CONT)
    pipe = CyclePipeline(engine)
    band = family("band")
    fired = []
    pipe._fire = lambda fam, T, entries: fired.append((T, len(entries)))
    cap = engine._mega_cap(16384)
    assert cap < max(engine.config.megabatch_max_rows, 1024)
    for i in range(cap):
        pipe._add(band, 16384, i)
    assert fired == [(16384, cap)]
    # a short-window bucket still accumulates past the long-window cap
    # (its own ceiling is the unscaled row budget)
    for i in range(cap):
        pipe._add(band, 128, i)
    assert fired == [(16384, cap)]


def test_padding_class_boundary_sweep_byte_identical():
    """Fleet sizes straddling the small padding-class boundaries pin
    verdicts byte-identical mega on/off (the ISSUE 15 satellite)."""
    for n in (1, 15, 16, 17):
        _, _, s_on, _ = _mini(n, megabatch=True, cycles=2, mix=CONT)
        _, _, s_off, _ = _mini(n, megabatch=False, cycles=2, mix=CONT)
        assert _verdicts(s_on) == _verdicts(s_off), f"diverged at n={n}"


@pytest.mark.slow
@pytest.mark.perf
def test_padding_class_mantissa_boundary_byte_identical():
    """The 512 -> mantissa-class transition (513 rows pads to 544, not a
    power-of-4 rung) stays byte-identical too."""
    for n in (512, 513):
        _, _, s_on, _ = _mini(n, megabatch=True, cycles=1, mix=CONT)
        _, _, s_off, _ = _mini(n, megabatch=False, cycles=1, mix=CONT)
        assert _verdicts(s_on) == _verdicts(s_off), f"diverged at n={n}"


# ------------------------------------------------------- degenerate edges
def test_empty_family_no_launch_no_crash():
    """A fleet with no pair/bivariate/hpa jobs launches only the band
    family — absent families never reach _fire."""
    _, engine, _, _ = _mini(8, megabatch=True, cycles=1, mix=CONT)
    fams = engine.last_cycle_stages["family_launches"]
    assert fams.get("band", 0) >= 1
    for absent in ("pair", "bivariate", "hpa"):
        assert fams.get(absent, 0) == 0


def test_single_job_family_pads_to_smallest_class():
    outcomes, engine, store, _ = _mini(1, megabatch=True, cycles=1,
                                       mix=CONT)
    assert len(outcomes) == 1
    mb = engine.last_cycle_stages["megabatch"]
    assert mb["launches"] == 1
    assert mb["real_rows"] == 1
    assert mb["padded_rows"] == 15  # padded to the 16 class
    _, _, s_off, _ = _mini(1, megabatch=False, cycles=1, mix=CONT)
    assert _verdicts(store) == _verdicts(s_off)


def test_all_rows_memo_hit_zero_row_batch_never_launches():
    """Memo on + an unchanged second cycle: every row resolves from the
    fingerprint memo, the mega accumulators stay empty, and a zero-row
    batch must not launch (device_launches flat, zero mega launches)."""
    _, engine, _, backend = _mini(12, megabatch=True, cycles=1, mix=CONT,
                                  memo=True)
    launches0 = engine.device_launches
    mega0 = engine.megabatch_launches_total
    # second cycle at the SAME sim instant: no window advanced, every
    # row resolves from the fingerprint memo before accumulation
    engine.run_cycle(now=backend.now)
    assert engine.device_launches == launches0
    assert engine.megabatch_launches_total == mega0
    assert engine.last_cycle_stages["megabatch"]["launches"] == 0


def test_all_rows_triage_cleared_zero_family_launches():
    """Triage on, quiet continuous fleet whose windows advance every
    cycle: the screen clears every band row, so the band family's mega
    accumulator holds zero rows and launches nothing (the screen's own
    fused launch is not a family launch)."""
    _, engine, _, _ = _mini(24, megabatch=True, cycles=3, mix=CONT,
                            triage=True)
    stats = engine.last_cycle_stages
    assert stats["triage"]["cleared"] > 0
    assert stats["triage"]["escalated"] == 0
    assert stats["family_launches"].get("band", 0) == 0
    assert stats["megabatch"]["launches"] == 0
    assert stats["megabatch"]["real_rows"] == 0


@pytest.mark.slow
@pytest.mark.perf
def test_mega_chunking_at_row_ceiling_identical():
    """A fleet past the mega row ceiling chunks at it — multiple mega
    launches (full chunks + a re-classed tail), still byte-identical to
    the rung path."""
    _, eng_on, s_on, _ = _mini(1100, megabatch=True, cycles=1, mix=CONT,
                               max_rows=1024)  # 1100 rows > 1024 ceiling
    assert eng_on.last_cycle_stages["megabatch"]["launches"] >= 2
    _, _, s_off, _ = _mini(1100, megabatch=False, cycles=1, mix=CONT)
    assert _verdicts(s_on) == _verdicts(s_off)


def test_donated_twins_not_built_on_cpu():
    """CPU XLA does not alias donated buffers: the mega path must take
    the plain call (no jit twins) so it never pays a donation warning
    per program."""
    _, engine, _, _ = _mini(8, megabatch=True, cycles=1)
    assert engine.megabatch_launches_total > 0
    assert engine._donated_twins == {}


def test_fold_tolist_types_roundtrip():
    """The bulk-tolist fold must keep plain-Python result types (the
    reason strings format band counts as ints, not floats)."""
    outcomes, engine, store, _ = _mini(6, megabatch=True, cycles=2,
                                       mix=CONT, anomaly_rate=0.5)
    unhealthy = [d for d in store.by_status(J.COMPLETED_UNHEALTH)]
    assert unhealthy, "anomaly injection should convict"
    for d in unhealthy:
        # "N points outside [lo,hi]" — N must render as an integer
        head = d.reason.split(" points outside")[0].rsplit(" ", 1)[-1]
        assert head.isdigit(), d.reason


# ----------------------------------------------------------- perf A/B gate
@pytest.mark.slow
@pytest.mark.perf
def test_megabatch_ab_identity_and_launch_collapse_gate():
    """The per-PR acceptance gate (CI perf-smoke): on the launch-heavy
    mixed fleet, mega-batching must (a) keep verdicts byte-identical on
    EVERY interleaved round, (b) collapse >= 2 populated families to
    exactly one launch per cycle, and (c) strictly cut total launches.
    The wall-clock win (d) is enforced only under FOREMAST_PERF_STRICT=1
    (`make perf`): the measured margin is ~11% at this fleet size
    (docs/performance.md §6), within scheduler noise on shared CI
    runners, so the per-PR leg gates the deterministic invariants and
    records — rather than asserts — the timing."""
    from foremast_tpu.bench_cycle import run_megabatch_ab

    ab = run_megabatch_ab(n_jobs=4000, cycles=2, rounds=2)
    assert ab["verdicts_identical"]
    fams_on = ab["family_launches_on"]
    single = [f for f, c in fams_on.items() if c == 1]
    assert len(single) >= 2, fams_on
    assert (ab["launches_per_cycle_on"]
            < ab["launches_per_cycle_off"]), ab
    assert ab["padding_waste_ratio"] is not None
    if os.environ.get("FOREMAST_PERF_STRICT"):
        # the measured win: interleaved best-of-round jobs/s, mega >= rung
        assert ab["speedup"] >= 1.0, ab
