"""Windowing: ragged -> masked grid invariants."""
import numpy as np
import pytest

from foremast_tpu.ops.windowing import (
    Window,
    align_step,
    bucket_length,
    pack_windows,
    resample_to_grid,
)


def test_align_step():
    assert align_step(125, 60) == 120
    assert align_step(120, 60) == 120


def test_resample_basic():
    start, end = 0, 600  # 10-min canary window, T=10
    ts = [0, 60, 120, 300, 540]
    vals = [1.0, 2.0, 3.0, 4.0, 5.0]
    w = resample_to_grid(ts, vals, start, end)
    assert w.values.shape == (10,)
    assert w.mask.sum() == 5
    np.testing.assert_array_equal(w.values[[0, 1, 2, 5, 9]], [1, 2, 3, 4, 5])
    assert not w.mask[3] and not w.mask[4]


def test_resample_drops_nan_and_out_of_range():
    w = resample_to_grid([0, 60, 7200, 120], [1.0, np.nan, 9.0, 2.0], 0, 300)
    assert w.mask.sum() == 2  # nan and out-of-range dropped
    assert w.values[0] == 1.0 and w.values[2] == 2.0


def test_resample_rounds_to_nearest_slot():
    # scrape lag: samples a few seconds past the boundary still snap to it
    w = resample_to_grid([61.0, 124.0], [7.0, 8.0], 0, 300)
    assert w.mask[1] and w.values[1] == 7.0
    assert w.mask[2] and w.values[2] == 8.0


def test_pack_windows_buckets():
    ws = [
        Window(np.ones(10, np.float32), np.ones(10, bool), 0),
        Window(np.ones(30, np.float32), np.ones(30, bool), 0),
    ]
    vals, mask = pack_windows(ws)
    assert vals.shape == (2, 32)  # bucket of 30 is 32
    assert mask[0].sum() == 10 and mask[1].sum() == 30
    assert not mask[0, 10:].any()


def test_bucket_length_covers_7day_window():
    assert bucket_length(10_080) == 16384
    assert bucket_length(16) == 16


def test_resample_in_range_by_timestamp_not_slot():
    # review finding: ts=-29 must be dropped (before start); ts=575 must land
    # in the last slot instead of being dropped
    w = resample_to_grid([-29.0, 575.0], [5.0, 6.0], 0, 600)
    assert not w.mask[0]
    assert w.mask[9] and w.values[9] == 6.0


def test_pack_windows_refuses_truncation():
    import pytest

    ws = [Window(np.ones(100, np.float32), np.ones(100, bool), 0)]
    with pytest.raises(ValueError):
        pack_windows(ws, pad_to=64)


@pytest.mark.parametrize("rows", [None, 3, 4, 16, 2],
                         ids=["none", "exact", "one-more", "rung", "short"])
def test_pack_windows_rows_edge_pads_in_place(rows):
    """`rows` allocates the block at that many rows; the rows past the
    windows repeat the last window's row, values and mask; `rows` equal
    to the window count, or None, is the plain pack; fewer rows raise."""
    rng = np.random.default_rng(41)
    ws = [Window(rng.normal(size=n).astype(np.float32), rng.random(n) > 0.3,
                 0) for n in (20, 7, 11)]
    plain_v, plain_m = pack_windows(ws, pad_to=32)
    if rows is not None and rows < len(ws):
        with pytest.raises(ValueError):
            pack_windows(ws, pad_to=32, rows=rows)
        return
    vals, mask = pack_windows(ws, pad_to=32, rows=rows)
    R = len(ws) if rows is None else rows
    assert vals.shape == mask.shape == (R, 32)
    np.testing.assert_array_equal(vals[:3], plain_v)
    np.testing.assert_array_equal(mask[:3], plain_m)
    for r in range(3, R):
        np.testing.assert_array_equal(vals[r], plain_v[2])
        np.testing.assert_array_equal(mask[r], plain_m[2])


def test_resample_masks_values_beyond_f32_range():
    """A 1e39 sample is f64-finite but f32-inf: it must be MASKED, not
    stored as inf with mask=True (the mask contract is what lets every
    downstream kernel skip finiteness checks). Exercised on both the
    python path and (when built) the native >=512-point path."""
    import numpy as np

    from foremast_tpu.ops.windowing import resample_to_grid

    for n in (10, 600):  # python path; native path when available
        ts = [60.0 * i for i in range(n)]
        vals = [10.0] * n
        vals[n // 2] = 1e39
        w = resample_to_grid(ts, vals, 0, 60 * n)
        assert np.all(np.isfinite(w.values[w.mask]))
        assert w.mask.sum() == n - 1  # the monster sample is masked out
