"""Native C++ data-plane extension: build, exact parity with the Python
fallbacks, and graceful degradation on malformed input.
"""
import json

import numpy as np
import pytest

from foremast_tpu import native
from foremast_tpu.dataplane.fetch import _avg_series
from foremast_tpu.ops.windowing import resample_to_grid

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native extension unavailable (no toolchain)"
)


def _prom_payload(series):
    return json.dumps(
        {
            "status": "success",
            "data": {
                "resultType": "matrix",
                "result": [
                    {
                        "metric": {"app": f"s{i}", "pod": "x" * 10},
                        "values": [[t, str(v)] for t, v in s],
                    }
                    for i, s in enumerate(series)
                ],
            },
        }
    ).encode()


def _py_prom(raw):
    payload = json.loads(raw)
    result = payload.get("data", {}).get("result", [])
    series = [
        [(float(ts), float(v)) for ts, v in item.get("values", [])]
        for item in result
    ]
    return _avg_series(series)


def test_parse_prometheus_parity_with_python():
    rng = np.random.default_rng(0)
    base = 1_700_000_000
    s1 = [(base + 60 * i + 0.781, float(rng.normal(10, 2))) for i in range(500)]
    s2 = [(base + 60 * i + 0.781, float(rng.normal(5, 1))) for i in range(250)]
    raw = _prom_payload([s1, s2])
    ts_n, v_n = native.parse_series(raw, native.FLAVOR_PROMETHEUS)
    ts_p, v_p = _py_prom(raw)
    np.testing.assert_array_equal(ts_n, np.asarray(ts_p))
    np.testing.assert_array_equal(v_n, np.asarray(v_p))
    # duplicates across series were averaged
    assert len(ts_n) == 500


def test_parse_special_values_and_escapes():
    raw = json.dumps(
        {
            "status": "success",
            "data": {
                "result": [
                    {
                        "metric": {"weird \"key\"": "va\\lue\nnewlineé"},
                        "values": [
                            [1000, "NaN"],
                            [1060, "+Inf"],
                            [1120, "-Inf"],
                            [1180, "42.5"],
                        ],
                    }
                ]
            },
        }
    ).encode()
    ts, v = native.parse_series(raw, native.FLAVOR_PROMETHEUS)
    assert list(ts) == [1000, 1060, 1120, 1180]
    assert np.isnan(v[0]) and np.isposinf(v[1]) and np.isneginf(v[2])
    assert v[3] == 42.5


def test_parse_numeric_values_and_empty():
    # wavefront flavor: plain-number samples under "data"
    raw = json.dumps(
        {"timeseries": [{"label": "x", "data": [[100, 1.5], [160, 2.5]]}]}
    ).encode()
    ts, v = native.parse_series(raw, native.FLAVOR_WAVEFRONT)
    assert list(ts) == [100, 160] and list(v) == [1.5, 2.5]
    ts, v = native.parse_series(
        b'{"status":"success","data":{"result":[]}}', native.FLAVOR_PROMETHEUS
    )
    assert len(ts) == 0 and len(v) == 0


def test_parse_malformed_returns_none():
    assert native.parse_series(b'{"data": {"result": [', 0) is None
    assert native.parse_series(b"", 0) is None
    assert native.parse_series(b"not json at all", 0) is None


def test_resample_parity_with_python():
    rng = np.random.default_rng(1)
    n = 2000
    start, end, step = 0, 1200 * 60, 60
    ts = rng.uniform(-3600, end + 3600, n)
    # exercise half-step boundaries (np.round half-to-even semantics)
    ts[:200] = (np.arange(200) * 60) + 30.0
    vals = rng.normal(0, 1, n)
    vals[::17] = np.nan
    w_native = native.resample(ts, vals, start, end, step)
    # small python reference (forced: size<512 path would not trigger here,
    # so call with the native layer disabled via a length-1 shim)
    T = (end - start) // step
    ref_vals = np.zeros(T, np.float32)
    ref_mask = np.zeros(T, bool)
    finite = np.isfinite(vals) & np.isfinite(ts)
    tsf, vsf = ts[finite], vals[finite]
    keep = (tsf >= start) & (tsf < end)
    tsf, vsf = tsf[keep], vsf[keep]
    idx = np.clip(np.round((tsf - start) / step).astype(np.int64), 0, T - 1)
    ref_vals[idx] = vsf.astype(np.float32)
    ref_mask[idx] = True
    np.testing.assert_array_equal(w_native[0], ref_vals)
    np.testing.assert_array_equal(w_native[1], ref_mask)


def test_resample_to_grid_uses_native_for_long_series():
    rng = np.random.default_rng(2)
    n = 1024
    ts = np.arange(n) * 60.0
    vals = rng.normal(10, 1, n)
    w = resample_to_grid(ts.tolist(), vals.tolist(), 0, n * 60)
    assert w.n_valid == n
    np.testing.assert_allclose(w.values[:n], vals.astype(np.float32))


def test_fetch_prometheus_native_path(monkeypatch):
    """PrometheusDataSource returns identical data through the native path
    and the forced-fallback path."""
    import foremast_tpu.dataplane.fetch as F

    raw = _prom_payload([[(1000 + 60 * i, float(i)) for i in range(50)]])

    monkeypatch.setattr(
        F.HTTP_POOL, "request",
        lambda url, timeout=None, headers=None: raw,
    )
    src = F.PrometheusDataSource()
    ts1, v1 = src.fetch("http://x")
    monkeypatch.setattr(F.native, "parse_series", lambda *a: None)
    ts2, v2 = src.fetch("http://x")
    np.testing.assert_array_equal(np.asarray(ts1), np.asarray(ts2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def test_fetch_prometheus_error_status_raises(monkeypatch):
    import foremast_tpu.dataplane.fetch as F

    raw = json.dumps({"status": "error", "errorType": "bad_data"}).encode()

    monkeypatch.setattr(
        F.HTTP_POOL, "request",
        lambda url, timeout=None, headers=None: raw,
    )
    with pytest.raises(F.FetchError):
        F.PrometheusDataSource().fetch("http://x")


def test_deeply_nested_body_falls_back_not_segfault():
    # 200k unclosed brackets: must return None (depth-limited), not SIGSEGV
    assert native.parse_series(b"[" * 200_000, native.FLAVOR_PROMETHEUS) is None
    deep = b"[" * 200_000 + b"]" * 200_000
    assert native.parse_series(deep, native.FLAVOR_PROMETHEUS) is None


# ---------------------------------------------------- fused parse_grid path
def _grid_ref(raw, step=60, max_steps=16384):
    """Reference: python parse + the engine's span derivation + resampler."""
    from foremast_tpu.dataplane.fetch import grid_from_series

    ts, vals = _py_prom(raw)
    return grid_from_series(ts, vals, step, max_steps)


@pytest.mark.skipif(not native.available(), reason="native lib not built")
def test_parse_grid_parity_with_python_pipeline():
    rng = np.random.default_rng(3)
    t0 = 1_700_000_000 // 60 * 60
    # ragged, duplicated, string-encoded, multi-series
    s1 = [(t0 + 60 * i, float(rng.normal())) for i in range(200)]
    s2 = [(t0 + 60 * i + 17, float(rng.normal())) for i in range(0, 200, 3)]
    s2 += s2[:5]  # duplicates -> averaged
    raw = _prom_payload([s1, s2])
    got = native.parse_grid(raw, native.FLAVOR_PROMETHEUS)
    assert got is not None
    vals, mask, start = got
    want = _grid_ref(raw)
    assert start == want.start
    np.testing.assert_array_equal(mask, want.mask)
    np.testing.assert_allclose(vals, want.values, rtol=0, atol=0)


@pytest.mark.skipif(not native.available(), reason="native lib not built")
def test_parse_grid_clamps_span_to_max_steps():
    t0 = 1_700_000_000 // 60 * 60
    # 2-day span at 60 s, clamped to a 1-day grid keeping the NEWEST samples
    s = [(t0 + 60 * i, float(i)) for i in range(2880)]
    raw = _prom_payload([s])
    vals, mask, start = native.parse_grid(
        raw, native.FLAVOR_PROMETHEUS, 60, 1440
    )
    want = _grid_ref(raw, 60, 1440)
    assert len(vals) == 1440 and start == want.start
    np.testing.assert_array_equal(vals, want.values)
    # the retained slots are the most recent ones
    assert vals[-1] == 2879.0


@pytest.mark.skipif(not native.available(), reason="native lib not built")
def test_parse_grid_empty_and_malformed():
    empty = _prom_payload([])
    vals, mask, start = native.parse_grid(empty, native.FLAVOR_PROMETHEUS)
    assert len(vals) == 1 and not mask.any() and start == 0
    assert native.parse_grid(b"{nope", native.FLAVOR_PROMETHEUS) is None


def test_artifact_is_named_by_source_hash(tmp_path, monkeypatch):
    """The chip tool copies the working tree as it stands, gitignored
    binaries included, so a library built elsewhere (or from an older
    source) could sit beside the source with a NEWER mtime — the old
    loader's only rebuild trigger. Naming the artifact by the source's
    hash makes loading it impossible: another source is another file."""
    import hashlib
    import os

    with open(native._SRC, "rb") as f:
        src = f.read()
    want = f"foremast_native-{hashlib.sha256(src).hexdigest()[:12]}.so"
    assert os.path.basename(native.lib_path()) == want
    if native.available():
        assert native.parser_name() == want
    # a changed source names a different artifact, whatever sits on disk
    edited = tmp_path / "foremast_native.cpp"
    edited.write_bytes(src + b"\n// edited\n")
    monkeypatch.setattr(native, "_SRC", str(edited))
    assert os.path.basename(native.lib_path()) != want
    # the legacy fixed name is never the load target
    assert os.path.basename(native.lib_path()) != "foremast_native.so"
