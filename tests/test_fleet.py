"""Fleet scoring on the 8-device virtual CPU mesh."""
import jax
import numpy as np
import pytest

from foremast_tpu.parallel import fleet_mesh, make_fleet_scorer, pad_to_multiple
from foremast_tpu.parallel import fleet as fl


def _fleet_batch(B=64, T=32, bad_every=8, seed=0):
    """Healthy pairs except every bad_every-th (shifted current)."""
    rng = np.random.default_rng(seed)
    base = rng.normal(10, 1, (B, T)).astype(np.float32)
    cur = rng.normal(10, 1, (B, T)).astype(np.float32)
    bad = np.arange(B) % bad_every == 0
    cur[bad] += 8.0
    bm = np.ones((B, T), bool)
    cm = np.ones((B, T), bool)
    return base, bm, cur, cm, bad


def _cfg(B):
    return {
        # decisive threshold: with dozens of healthy pairs, a 1-5% per-pair
        # false-positive rate would (correctly) flag some by chance
        "pvalue_threshold": np.full(B, 1e-4, np.float32),
        "test_mask": np.full(B, fl.TEST_MANN_WHITNEY | fl.TEST_KRUSKAL, np.int32),
        "combine": np.full(B, fl.COMBINE_ANY, np.int32),
        "ma_window": np.full(B, 30, np.int32),
        "band_threshold": np.full(B, 2.0, np.float32),
        "bound_mode": np.full(B, 3, np.int32),
        "min_lower_bound": np.full(B, -np.inf, np.float32),
        "min_points": np.tile(np.asarray([20, 20, 5], np.int32), (B, 1)),
    }


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8
    mesh = fleet_mesh()
    assert mesh.shape["fleet"] == 8


def test_score_pairs_flags_bad_pairs():
    B = 32
    base, bm, cur, cm, bad = _fleet_batch(B)
    cfg = _cfg(B)
    out = fl.score_pairs(
        base, bm, cur, cm,
        cfg["pvalue_threshold"], cfg["test_mask"], cfg["combine"],
        cfg["ma_window"], cfg["band_threshold"], cfg["bound_mode"],
        cfg["min_lower_bound"], cfg["min_points"],
    )
    got = np.asarray(out["unhealthy"])
    np.testing.assert_array_equal(got, bad)


def test_fleet_scorer_end_to_end_sharded():
    mesh = fleet_mesh()
    B = 64
    base, bm, cur, cm, bad = _fleet_batch(B)
    run = make_fleet_scorer(mesh, k=8)
    out, total, top_v, top_idx = run(base, bm, cur, cm, _cfg(B))
    assert total == int(bad.sum())
    # every reported top index is a genuinely bad pair
    tv = np.asarray(top_v)
    ti = np.asarray(top_idx)
    real = tv > -np.inf
    assert real.sum() == min(8, bad.sum())
    assert all(bad[i] for i in ti[real])


def test_fleet_scorer_rejects_undivisible_batch():
    mesh = fleet_mesh()
    base, bm, cur, cm, _ = _fleet_batch(60)
    run = make_fleet_scorer(mesh)
    with pytest.raises(ValueError):
        run(base, bm, cur, cm, _cfg(60))


def test_pad_to_multiple_roundtrip():
    base, bm, cur, cm, _ = _fleet_batch(60)
    (pb, pbm), B0 = pad_to_multiple([base, bm], 8)
    assert pb.shape[0] == 64 and B0 == 60
    assert not pbm[60:].any()  # padding is fully masked


def test_fleet_summary_standalone():
    mesh = fleet_mesh()
    B = 64
    unhealthy = np.zeros(B, bool)
    unhealthy[[3, 17, 42]] = True
    sev = np.zeros(B, np.float32)
    sev[[3, 17, 42]] = [5.0, 9.0, 7.0]
    total, tv, ti = fl.fleet_summary(unhealthy, sev, mesh, k=4)
    assert int(total) == 3
    got = [int(i) for i, v in zip(np.asarray(ti), np.asarray(tv)) if v > -np.inf]
    assert got == [17, 42, 3]  # severity-descending


def test_friedman_bit_in_fused_verdict():
    """ML_PAIRWISE_ALGORITHM=friedman drives the verdict through the paired
    Friedman member of the family (design.md:89-92)."""
    import numpy as np

    from foremast_tpu.engine.config import EngineConfig
    from foremast_tpu.parallel import fleet as fl

    assert EngineConfig(pairwise_algorithm="friedman_all").enabled_tests() \
        == fl.TEST_FRIEDMAN
    assert EngineConfig(pairwise_algorithm="all").enabled_tests() & fl.TEST_FRIEDMAN

    rng = np.random.default_rng(0)
    B, T = 4, 64
    baseline = rng.normal(10.0, 1.0, (B, T)).astype(np.float32)
    # rows 0,1: current consistently above baseline; rows 2,3: same dist
    current = baseline + np.array([3.0, 3.0, 0.0, 0.0])[:, None] \
        + rng.normal(0, 0.2, (B, T)).astype(np.float32)
    masks = np.ones((B, T), bool)
    out = fl.score_pairs(
        baseline, masks, current.astype(np.float32), masks,
        np.full(B, 0.01, np.float32),
        np.full(B, fl.TEST_FRIEDMAN, np.int32),
        np.zeros(B, np.int32),
        np.full(B, 10, np.int32),
        np.full(B, 30.0, np.float32),  # very wide band: pairwise decides
        np.zeros(B, np.int32),
        np.zeros(B, np.float32),
        np.tile(np.asarray([20, 20, 5], np.int32), (B, 1)),
    )
    pw = np.asarray(out["pairwise_unhealthy"])
    assert pw.tolist() == [True, True, False, False]
    # too few paired blocks -> friedman gated out, healthy by default
    few = np.zeros((1, T), bool)
    few[:, :3] = True
    out2 = fl.score_pairs(
        baseline[:1], few, current[:1].astype(np.float32), few,
        np.full(1, 0.01, np.float32), np.full(1, fl.TEST_FRIEDMAN, np.int32),
        np.zeros(1, np.int32), np.full(1, 10, np.int32),
        np.full(1, 30.0, np.float32), np.zeros(1, np.int32),
        np.zeros(1, np.float32), np.tile(np.asarray([20, 20, 5], np.int32), (1, 1)),
    )
    assert not bool(np.asarray(out2["pairwise_unhealthy"])[0])


def test_min_friedman_points_config_wired():
    """MIN_FRIEDMAN_DATA_POINTS reaches the kernel: the analyzer passes a
    4-wide min_points vector, and raising the gate above the available block
    count disables the Friedman member (advisor round 1: the fifth test
    silently fell back to the MIN_FRIEDMAN constant)."""
    import numpy as np

    from foremast_tpu.engine.config import from_env
    from foremast_tpu.parallel import fleet as fl

    cfg = from_env({"MIN_FRIEDMAN_DATA_POINTS": "12"})
    assert cfg.min_friedman_points == 12

    # 8 clean paired blocks, strongly shifted: friedman fires at gate<=8,
    # is gated out at gate>8. Baseline must be non-constant (sigma>0) so the
    # huge band_threshold actually disables the band detector.
    B, T = 1, 8
    rng = np.random.default_rng(0)
    base = rng.normal(10.0, 1.0, (B, T)).astype(np.float32)
    cur = base + 5.0
    ones = np.ones((B, T), bool)

    def verdict(gate):
        out = fl.score_pairs(
            base, ones, cur, ones,
            np.full(B, 0.05, np.float32),
            np.full(B, fl.TEST_FRIEDMAN, np.int32),
            np.zeros(B, np.int32),
            np.full(B, 4, np.int32),
            np.full(B, 1e9, np.float32),  # band never fires
            np.zeros(B, np.int32),
            np.zeros(B, np.float32),
            np.tile(np.asarray([20, 20, 5, gate], np.int32), (B, 1)),
        )
        return bool(np.asarray(out["unhealthy"])[0])

    assert verdict(8) is True   # 8/8 wins: exact p = 2*(1/2)^8 ~ 0.0078 < 0.05
    assert verdict(9) is False  # gated: not enough blocks -> cannot judge


def test_verdict_program_lowers_without_scatters():
    """Scatters serialize on TPU; the round-3 sorted-space redesign removed
    every one from the fleet-scoring program (docs/benchmarks.md 'Kernel
    optimization'). Pin it: a reintroduced segment op or .at[].set in any
    sub-kernel shows up as a scatter in the lowered HLO."""
    import jax

    B, T = 8, 32
    rng = np.random.default_rng(0)
    args = (
        rng.normal(10, 2, (B, T)).astype(np.float32),
        rng.random((B, T)) > 0.05,
        rng.normal(10, 2, (B, T)).astype(np.float32),
        rng.random((B, T)) > 0.05,
        np.full(B, 0.01, np.float32), np.full(B, 0b1111, np.int32),
        np.zeros(B, np.int32), np.full(B, 10, np.int32),
        np.full(B, 3.0, np.float32), np.zeros(B, np.int32),
        np.zeros(B, np.float32),
        np.tile(np.asarray([20, 20, 5], np.int32), (B, 1)),
    )
    hlo = jax.jit(jax.vmap(fl._pair_verdict)).lower(*args).as_text()
    assert "scatter" not in hlo, "a scatter crept back into the verdict program"


def test_moving_average_band_lowers_with_one_batched_gather_at_most():
    """The MA band's per-element dynamic lookups (the old csum[lo], ma[t0],
    x[idx] — 3-4 gathers of computed indices) were rewritten as rolls and
    associative hold-last scans. The one remaining gather is the vmapped
    dynamic roll itself: a batched contiguous row-shift (ma_window is
    per-pair), a fundamentally cheaper access pattern. Pin the ceiling so
    a reintroduced per-element index shows up as a count regression."""
    import jax

    from foremast_tpu.ops import forecast as fc

    B, T = 8, 32
    rng = np.random.default_rng(0)
    x = rng.normal(10, 2, (B, T)).astype(np.float32)
    m = rng.random((B, T)) > 0.3
    w = np.full(B, 10, np.int32)
    f = jax.jit(jax.vmap(fc._moving_average_1d))
    hlo = f.lower(x, m, w).as_text()
    assert "scatter" not in hlo
    # quote-insensitive: the StableHLO printer may emit the op in quoted
    # generic or pretty form; counting the bare name survives both, so the
    # pin cannot vacuously pass on printer-format drift
    # upper bound only: the regression this pin guards is gather growth
    # (per-element indexing reintroduced); an XLA improvement lowering the
    # batched roll without any gather should pass, not fail
    n_gather = hlo.count("stablehlo.gather")
    assert n_gather <= 2, n_gather  # the batched roll, possibly quoted+typed
