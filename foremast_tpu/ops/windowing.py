"""Ragged time-series -> fixed, masked (B, T) device tensors.

Real Prometheus `query_range` responses are ragged: gaps, unequal lengths,
unaligned starts (reference query semantics: foremast-barrelman
pkg/client/metrics/metricsquery.go:63-65 — 60 s step, boundary-aligned;
+1-step start shift for scrape lag at :72-84). TPU kernels need static shapes,
so this module is the masking boundary of the system: everything downstream of
`resample_to_grid` is dense tensors + bool masks, and nothing downstream ever
filters.

Host-side (numpy) on purpose — it runs in the data plane where series arrive
as Python lists; the packed output is what gets shipped to the device once per
micro-batch.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Sequence

import numpy as np

__all__ = [
    "Window",
    "resample_to_grid",
    "pack_windows",
    "align_step",
    "bucket_length",
    "MAX_WINDOW_STEPS",
]

DEFAULT_STEP = 60  # seconds; metricsquery.go:63 "step = 60"


def align_step(t: float, step: int = DEFAULT_STEP) -> int:
    """Floor-align a unix timestamp to the step boundary (metricsquery.go:64-65)."""
    return int(t) // step * step


@dataclass
class Window:
    """One metric window on the fixed grid."""

    values: np.ndarray  # (T,) float32
    mask: np.ndarray  # (T,) bool
    start: int  # aligned unix seconds
    step: int = DEFAULT_STEP
    # blake2b-128 of (start, step, length, values, mask), taken once by
    # `digest()`: only the object keeps it, the warm tier spills the arrays
    _digest: bytes | None = field(default=None, init=False, repr=False,
                                  compare=False)

    @property
    def n_valid(self) -> int:
        return int(self.mask.sum())

    @property
    def digested(self) -> bool:
        return self._digest is not None

    def digest(self) -> bytes:
        """The window's identity as the score memo fingerprints it, hashed
        on first use and kept. Taking it makes both arrays read-only: no
        producer writes into a Window it has handed out (the fetch layer
        hands the same object back while its range has not moved), and a
        write after the digest would raise instead of serving a stale
        memo hit."""
        d = self._digest
        if d is None:
            self.values.setflags(write=False)
            self.mask.setflags(write=False)
            h = hashlib.blake2b(digest_size=16)
            h.update(np.float64(
                (self.start, self.step, self.values.shape[0])).tobytes())
            h.update(self.values.tobytes())
            h.update(self.mask.tobytes())
            d = self._digest = h.digest()
        return d


def resample_to_grid(
    timestamps: Sequence[float],
    values: Sequence[float],
    start: float,
    end: float,
    step: int = DEFAULT_STEP,
) -> Window:
    """Snap (ts, value) samples onto the [start, end) grid at `step` resolution.

    Samples round to the nearest slot; out-of-range samples and NaNs are
    dropped (masked), later samples win a slot. Returns a Window whose length
    is fully determined by (start, end, step) — never by the data.
    """
    start = align_step(start, step)
    end = align_step(end + step - 1, step)
    ts = np.asarray(timestamps, dtype=np.float64)
    vs = np.asarray(values, dtype=np.float64)
    if ts.shape != vs.shape:
        # a buggy/custom source returning mismatched series must degrade
        # to the overlapping prefix, not crash the whole fleet's cycle
        # (preprocess converts only FetchError; a ValueError here would
        # escape per-job isolation). The Prometheus wire can't produce
        # this — its samples are [ts, val] pairs — so trimming loses
        # nothing real.
        n = min(ts.size, vs.size)
        ts, vs = ts[:n], vs[:n]
    if vs.size:
        # finiteness must be judged at the STORAGE dtype: a 1e39 sample is
        # f64-finite but casts to f32 inf, which would land with mask=True
        # and poison every downstream reduction the mask contract promises
        # to protect. Masking here (NaN is dropped by both the python and
        # native filters) keeps the two resample paths consistent.
        with np.errstate(over="ignore"):  # the cast is the check
            vs = np.where(np.isfinite(vs.astype(np.float32)), vs, np.nan)
    if ts.size >= 512:
        # large (historical) windows: single-pass C resampler when built
        from .. import native

        res = native.resample(ts, vs, start, end, step)
        if res is not None:
            return Window(values=res[0], mask=res[1], start=start, step=step)
    T = max(1, (end - start) // step)
    vals = np.zeros(T, dtype=np.float32)
    mask = np.zeros(T, dtype=bool)
    if ts.size:
        finite = np.isfinite(vs) & np.isfinite(ts)
        ts, vs = ts[finite], vs[finite]
        keep = (ts >= start) & (ts < end)  # in-range by timestamp, not slot
        ts, vs = ts[keep], vs[keep]
        idx = np.clip(np.round((ts - start) / step).astype(np.int64), 0, T - 1)
        vals[idx] = vs.astype(np.float32)
        mask[idx] = True
    return Window(values=vals, mask=mask, start=start, step=step)


_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384)

MAX_WINDOW_STEPS = _BUCKETS[-1]


def bucket_length(T: int) -> int:
    """Smallest padded length bucket >= T.

    Bucketing bounds the number of distinct compiled programs: every jitted
    kernel specializes on T, so free-form lengths would recompile per job.
    16384 covers the 7-day / 60 s historical window (10,080 points,
    metricsquery.go:95).
    """
    for b in _BUCKETS:
        if T <= b:
            return b
    raise ValueError(f"window length {T} exceeds max bucket {_BUCKETS[-1]}")


def pack_windows(windows: Sequence[Window], pad_to: int | None = None,
                 rows: int | None = None):
    """Pack windows into dense (B, T) value/mask arrays, right-padded.

    Returns (values (B,T) float32, mask (B,T) bool). T is the common bucket
    for the longest member unless `pad_to` pins it (e.g. to batch canary and
    baseline windows together).

    `rows` (at least B) allocates the arrays at that many rows, and the
    rows past B are the edge padding, written here: each repeats the last
    window's row. A launch half asks for the row count its chunks are
    launched at, so the launch slices the block and copies nothing. Only
    the last window's samples are written into a pad row; the columns
    past them stay the zeros the allocation gave, which is that row
    already, and a page nothing writes is never touched on the host.

    Numpy on purpose, even at mega-batch sizes: a native batched pack was
    measured (PR 15) and LOST — extracting per-row data pointers for the
    C call costs ~1.4 us/row of GIL-held Python, more than the ~0.8 us
    numpy spends on the whole slice assignment, so the numpy loop is both
    the faster and the simpler path (docs/performance.md §6).
    """
    if not windows:
        raise ValueError("no windows to pack")
    longest = max(w.values.shape[0] for w in windows)
    T = pad_to or bucket_length(longest)
    if longest > T:
        raise ValueError(
            f"window of length {longest} does not fit pad_to={T}; "
            "truncating would silently drop the most recent samples"
        )
    B = len(windows)
    R = B if rows is None else rows
    if R < B:
        raise ValueError(f"rows={R} cannot hold {B} windows")
    vals = np.zeros((R, T), dtype=np.float32)
    mask = np.zeros((R, T), dtype=bool)
    for i, w in enumerate(windows):
        n = w.values.shape[0]
        vals[i, :n] = w.values
        mask[i, :n] = w.mask
    n = windows[-1].values.shape[0]
    vals[B:, :n] = vals[B - 1, :n]
    mask[B:, :n] = mask[B - 1, :n]
    return vals, mask
