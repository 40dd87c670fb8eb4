"""Sequence-parallel smoothers: exact equivalence with the sequential
lax.scan kernels, gap handling, and time-axis sharding over the mesh.
"""
from __future__ import annotations

import jax
import numpy as np

from foremast_tpu.ops import forecast as fc
from foremast_tpu.ops import seqscan as sq


def _series(B=4, T=512, gap_frac=0.1, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(10.0, 2.0, (B, T)).astype(np.float32)
    m = rng.random((B, T)) > gap_frac
    m[:, 0] = True  # a defined first point keeps s0 comparable
    return x, m


def test_ses_assoc_matches_sequential():
    x, m = _series()
    alpha = np.full(4, 0.3, np.float32)
    seq = np.asarray(fc.ses_predictions(x, m, alpha))
    par = np.asarray(sq.ses_predictions_assoc(x, m, alpha))
    np.testing.assert_allclose(par, seq, rtol=1e-5, atol=1e-4)


def test_des_assoc_matches_sequential():
    x, m = _series(seed=3)
    alpha = np.full(4, 0.5, np.float32)
    beta = np.full(4, 0.1, np.float32)
    seq = np.asarray(fc.des_predictions(x, m, alpha, beta))
    par = np.asarray(sq.des_predictions_assoc(x, m, alpha, beta))
    np.testing.assert_allclose(par, seq, rtol=1e-4, atol=1e-3)


def test_assoc_handles_all_gap_tail():
    x, m = _series(B=2, T=64, gap_frac=0.0, seed=1)
    m[:, 40:] = False  # forecaster free-runs over the gap
    seq = np.asarray(fc.des_predictions(x, m, np.full(2, 0.5, np.float32),
                                        np.full(2, 0.1, np.float32)))
    par = np.asarray(sq.des_predictions_assoc(x, m, np.full(2, 0.5, np.float32),
                                              np.full(2, 0.1, np.float32)))
    np.testing.assert_allclose(par, seq, rtol=1e-4, atol=1e-3)


def test_time_axis_sharded_execution_matches():
    """One long window's TIME axis spread across all 8 devices: the
    associative combine tree crosses chip boundaries and must still agree
    with the single-device sequential result."""
    from foremast_tpu.parallel.mesh import FLEET_AXIS, fleet_mesh

    mesh = fleet_mesh(jax.devices())
    B, T = 2, 1024  # T divisible by 8
    x, m = _series(B=B, T=T, seed=5)
    alpha = np.full(B, 0.3, np.float32)
    shard = sq.sequence_sharding(mesh, FLEET_AXIS)
    xs = jax.device_put(x, shard)
    ms = jax.device_put(m, shard)
    par = np.asarray(sq.ses_predictions_assoc(xs, ms, jax.device_put(alpha)))
    seq = np.asarray(fc.ses_predictions(x, m, alpha))
    np.testing.assert_allclose(par, seq, rtol=1e-5, atol=1e-4)
    beta = np.full(B, 0.1, np.float32)
    par_des = np.asarray(sq.des_predictions_assoc(
        xs, ms, jax.device_put(alpha), jax.device_put(beta)))
    seq_des = np.asarray(fc.des_predictions(x, m, alpha, beta))
    np.testing.assert_allclose(par_des, seq_des, rtol=1e-4, atol=1e-3)


def test_long_window_engine_dispatch():
    """Above LONG_WINDOW_STEPS the analyzer's forecaster dispatch uses the
    associative kernels (same numbers, parallel depth)."""
    from foremast_tpu.engine.config import EngineConfig

    cfg = EngineConfig(algorithm="exponential_smoothing", long_window_steps=256)
    assert cfg.long_window_steps == 256
    from foremast_tpu.engine.analyzer import Analyzer
    from foremast_tpu.engine.jobs import JobStore

    analyzer = Analyzer(cfg, None, JobStore())
    x, m = _series(B=2, T=512, seed=7)
    region = np.zeros_like(m)
    region[:, -32:] = True
    preds_long = analyzer._predict(x, m & ~region)
    seq = np.asarray(fc.ses_predictions(x, m & ~region,
                                        np.full(2, 0.3, np.float32)))
    np.testing.assert_allclose(preds_long, seq, rtol=1e-5, atol=1e-4)


def test_long_T_error_bounds():
    """At engine-dispatch lengths: SES assoc stays tight (it is what the
    engine auto-switches to); DES assoc drift stays within its documented
    bound on a trending series (it is NOT auto-dispatched)."""
    rng = np.random.default_rng(11)
    B, T = 4, 8192
    t = np.arange(T, dtype=np.float32)
    x = (10.0 + 0.01 * t + rng.normal(0, 1, (B, T))).astype(np.float32)
    m = rng.random((B, T)) > 0.1
    m[:, 0] = True
    alpha = np.full(B, 0.3, np.float32)
    beta = np.full(B, 0.1, np.float32)
    ses_seq = np.asarray(fc.ses_predictions(x, m, alpha))
    ses_par = np.asarray(sq.ses_predictions_assoc(x, m, alpha))
    np.testing.assert_allclose(ses_par, ses_seq, rtol=1e-4, atol=1e-2)
    des_seq = np.asarray(fc.des_predictions(x, m, np.full(B, 0.5, np.float32), beta))
    des_par = np.asarray(sq.des_predictions_assoc(
        x, m, np.full(B, 0.5, np.float32), beta))
    rel = np.max(np.abs(des_par - des_seq) / np.maximum(np.abs(des_seq), 1.0))
    assert rel < 2e-2  # documented f32 drift bound (seqscan.py docstring)


def test_padded_bucket_does_not_flip_kernel(monkeypatch):
    """The long-window gate sees real data length, not the padded bucket:
    a 300-step series padded to a 4096 bucket must use the sequential
    kernel at the default threshold."""
    from foremast_tpu.engine.analyzer import Analyzer
    from foremast_tpu.engine.config import EngineConfig
    from foremast_tpu.engine.jobs import JobStore
    from foremast_tpu.ops import seqscan

    called = {"assoc": 0}
    real = seqscan.ses_predictions_assoc
    monkeypatch.setattr(seqscan, "ses_predictions_assoc",
                        lambda *a: called.__setitem__("assoc", called["assoc"] + 1) or real(*a))
    cfg = EngineConfig(algorithm="exponential_smoothing", long_window_steps=4096)
    analyzer = Analyzer(cfg, None, JobStore())
    x, m = _series(B=2, T=4096, seed=9)  # padded shape AT the threshold
    region = np.zeros_like(m)
    region[:, -32:] = True
    analyzer._predict(x, m & ~region, data_steps=300)  # but only 300 real steps
    assert called["assoc"] == 0
    analyzer._predict(x, m & ~region, data_steps=4500)
    assert called["assoc"] == 1
