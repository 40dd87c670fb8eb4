"""What crosses the host boundary is what the host needs (ISSUE 34).

A band or bivariate launch brings back rows of results, and of its (B, T)
blocks only `flags`, read only for rows that flagged a point. The
reference here is what the collect halves were before: the kernels' old
(B, T) outputs (the band curves, the broadcast marginal bounds), made in
numpy, and the per-row walk over them, kept verbatim.
"""
import numpy as np
import pytest

from foremast_tpu.engine import Analyzer, EngineConfig, JobStore, families
from foremast_tpu.engine.analyzer import (
    _BandItem,
    _BiItem,
    _concat_trimmed,
    _concat_ts,
    _joint_grid,
)
from foremast_tpu.ops import forecast as fc
from foremast_tpu.ops.windowing import Window, bucket_length, pack_windows

STEP = 60
# (history, current) samples of each row: two T buckets (512 and 256), and
# in each a quiet row, a row with more than 50 flagged points and a row
# whose judged region is one sample
_LENS = [(300, 30), (290, 110), (310, 1), (150, 20), (130, 110), (200, 1)]
_LOUD = (1, 4)


def _window(rng, n, start, loud=False, mu=10.0, sd=1.0):
    vals = rng.normal(mu, sd, n).astype(np.float32)
    if loud:
        vals += np.float32(40.0 * sd)
    mask = rng.random(n) > 0.2
    mask[:2] = True
    return Window(vals, mask, start, STEP)


def _watch(eng, monkeypatch):
    """Record every dict `_collect_chunks` hands on and every array
    `_host` is asked for."""
    handed, hosted = [], []
    real_collect, real_host = eng._collect_chunks, eng._host

    def collect(launches):
        out = real_collect(launches)
        handed.append(out)
        return out

    def host(x):
        a = real_host(x)
        hosted.append(a)
        return a

    monkeypatch.setattr(eng, "_collect_chunks", collect)
    monkeypatch.setattr(eng, "_host", host)
    return handed, hosted


def _only_flags_is_a_block(handed, hosted):
    assert handed
    for out in handed:
        assert [k for k, v in out.items() if v.ndim >= 2] == ["flags"]
        assert out["flags"].dtype == bool
    assert sum(a.ndim >= 2 for a in hosted) == len(handed)


def _old_walk(eng, cur, n_h, count, first, checked, flags_row, values):
    """The per-row walk of the old collect halves, over a (T,) row of
    flags: every row's flags were read, flagged or not."""
    anomalous_idx = np.nonzero(flags_row)[0]
    anomaly_pairs = []
    for j in anomalous_idx[:50]:
        anomaly_pairs += [_concat_ts(cur, n_h, int(j)), float(values[j])]
    return {
        "count": count,
        "unhealthy": count >= eng._gate(checked),
        "first_ts": _concat_ts(cur, n_h, first) if first >= 0 else -1.0,
        "anomaly_pairs": anomaly_pairs,
    }


def test_band_collect_equals_the_walk_over_the_old_blocks(monkeypatch):
    rng = np.random.default_rng(34)
    eng = Analyzer(EngineConfig(), None, JobStore())
    policy = eng.config.policy_for("latency")
    items = [_BandItem(f"j{i}", "latency", _window(rng, h, 0),
                       _window(rng, c, 10**6, loud=i in _LOUD), policy)
             for i, (h, c) in enumerate(_LENS)]
    handed, hosted = _watch(eng, monkeypatch)
    got = families.family("band").score(eng, items)
    _only_flags_is_a_block(handed, hosted)
    assert len(handed) == 2 and len(got) == len(items)

    for T in (512, 256):
        group = [it for it in items if eng._band_T(it) == T]
        assert len(group) == 3
        # the old launch: a host (B, T) region, the predictions and sigma
        # on the host, and band_anomalies' (B, T) curves
        concats, n_hs = [], []
        regions = np.zeros((len(group), T), bool)
        for i, it in enumerate(group):
            vals, mask, n_h = _concat_trimmed(it.historical, it.current)
            n_hs.append(n_h)
            concats.append(Window(vals, mask, 0, STEP))
            regions[i, n_h:vals.shape[0]] = True
        xv, xm = pack_windows(concats, pad_to=T)
        hist = xm & ~regions
        preds = np.asarray(fc.moving_average_predictions(
            xv, hist, eng.config.ma_window))
        sigma = np.asarray(fc.residual_sigma(xv, preds, hist, ~regions))
        thr = np.float32(policy.threshold) * sigma[:, None]
        upper = preds + thr
        lower = np.maximum(preds - thr, np.float32(policy.min_lower_bound))
        mode = policy.bound or fc.BOUND_BOTH
        viol = ((xv > upper) & bool(mode & 1)) | ((xv < lower) & bool(mode & 2))
        flags = viol & xm & regions
        for i, it in enumerate(group):
            count = int(flags[i].sum())
            want = _old_walk(
                eng, it.current, n_hs[i], count,
                int(np.argmax(flags[i])) if count else -1,
                int((xm[i] & regions[i]).sum()), flags[i], xv[i])
            r = dict(got[(it.job_id, it.metric, "band")])
            assert r.pop("upper") == pytest.approx(
                float(np.mean(upper[i][regions[i]])), rel=1e-6)
            assert r.pop("lower") == pytest.approx(
                float(np.mean(lower[i][regions[i]])), rel=1e-6)
            assert r == want
    counts = [got[(it.job_id, it.metric, "band")]["count"] for it in items]
    assert [c > 50 for c in counts] == [i in _LOUD for i in range(len(items))]
    assert [c for i, c in enumerate(counts) if i not in _LOUD] == [0] * 4
    assert all(len(got[(items[i].job_id, "latency", "band")]
                   ["anomaly_pairs"]) == 100 for i in _LOUD)


def test_bivariate_collect_equals_the_walk_over_the_old_blocks(monkeypatch):
    rng = np.random.default_rng(35)
    eng = Analyzer(EngineConfig(), None, JobStore())
    policies = (eng.config.policy_for("latency"),
                eng.config.policy_for("cpu"))
    items = []
    for i, (h, c) in enumerate(_LENS):
        items.append(_BiItem(
            f"j{i}", ("latency", "cpu"),
            (_window(rng, h, 0), _window(rng, h, 0, mu=5.0, sd=0.5)),
            (_window(rng, c, 10**6, loud=i in _LOUD),
             _window(rng, c, 10**6, mu=5.0, sd=0.5)), policies))
    handed, hosted = _watch(eng, monkeypatch)
    got = families.family("bivariate").score(eng, items)
    _only_flags_is_a_block(handed, hosted)
    assert len(handed) == 2 and len(got) == len(items)
    assert all("d2" not in out for out in handed)

    thr = min(p.threshold for p in policies)
    for it in items:
        x, m, n_h, n_c = _joint_grid(list(it.hist), list(it.cur))
        T = bucket_length(x.shape[1])
        n = n_h + n_c
        region = np.zeros(n, bool)
        region[n_h:] = True
        joint = m[0] & m[1]
        hist = joint & ~region
        # the old kernel in float64: mean, population covariance with its
        # ridge, the ellipse, and marginal bounds broadcast along T
        h1, h2 = x[0][hist].astype(np.float64), x[1][hist].astype(np.float64)
        mu1, mu2 = h1.mean(), h2.mean()
        var1, var2 = h1.var(), h2.var()
        cov = np.mean((h1 - mu1) * (h2 - mu2))
        ridge = 1e-6 * max(var1, var2, 1.0)
        var1, var2 = var1 + ridge, var2 + ridge
        a, b = x[0] - mu1, x[1] - mu2
        d2 = (var2 * a * a - 2.0 * cov * a * b + var1 * b * b) / max(
            var1 * var2 - cov * cov, 1e-12)

        def on(dev, mode):
            mode = mode or 3
            return ((dev > 0) & bool(mode & 1)) | ((dev < 0) & bool(mode & 2))

        flags = (d2 > thr ** 2) & joint & region & (
            on(a, policies[0].bound) | on(b, policies[1].bound))
        blocks = {}
        for name, mu, var, pol in (("latency", mu1, var1, policies[0]),
                                   ("cpu", mu2, var2, policies[1])):
            up = np.full(T, mu + thr * np.sqrt(var), np.float32)
            lo = np.full(T, max(mu - thr * np.sqrt(var),
                                pol.min_lower_bound), np.float32)
            sel = np.zeros(T, bool)
            sel[n_h:n] = True
            blocks[name] = (float(np.mean(up[sel])), float(np.mean(lo[sel])))
        count = int(flags.sum())
        want = _old_walk(eng, it.cur[0], n_h, count,
                         int(np.argmax(flags)) if count else -1,
                         int((joint & region).sum()), flags, x[0])
        r = dict(got[(it.job_id, "latency&cpu", "bivariate")])
        bounds = r.pop("bounds")
        assert list(bounds) == ["latency", "cpu"]
        for name, (up, lo) in blocks.items():
            assert bounds[name] == pytest.approx((up, lo), rel=1e-6)
        assert r == want
    counts = [got[(it.job_id, "latency&cpu", "bivariate")]["count"]
              for it in items]
    assert [c > 50 for c in counts] == [i in _LOUD for i in range(len(items))]
