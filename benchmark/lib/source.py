"""The metric source the engine polls: the fleet's simulated store.

It has the three calls of the program's `RawFixtureDataSource` (`fetch`,
`fetch_series`, `fetch_window`). A query of a class's historical range
(`w=hist`: `Fleet.window_slots` gives that tag to whichever role lies on
the range, so a baseline laid on the history is one) is answered from the
fleet's arrays: the same numbers the byte body would carry, at the same
four decimals, without rendering and parsing 226 KB of text per job in
every run's set-up. Every other range (`w=cur`, `w=base`: the current
window's tails and a baseline that lies elsewhere) is rendered as a
Prometheus body and parsed by the program's own parser, so the parse
stays in the timed path. `benchmark/tests/test_benchmark.py` pins the two
paths equal.
"""
from __future__ import annotations

import re
import threading

import numpy as np

_URL_RE = re.compile(
    r"[?&]job=(\d+)&m=(\d+)&w=(\w+)&start=([0-9.]+)&end=([0-9.]+)")
# text bytes one sample takes in a matrix body: [1700000000,"10.1234"],
_BYTES_PER_SAMPLE = 23


class FleetSource:
    def __init__(self, fleet, arrays_for=("hist",)):
        self.fleet = fleet
        self.arrays_for = frozenset(arrays_for)
        self.request_count = 0
        self._lock = threading.Lock()  # the engine fetches from a pool

    def _query(self, url: str):
        m = _URL_RE.search(url)
        if m is None:
            raise ValueError(f"not a fleet range URL: {url}")
        with self._lock:
            self.request_count += 1
        return (int(m.group(1)), int(m.group(2)), m.group(3),
                float(m.group(4)), float(m.group(5)))

    def fetch_series(self, url: str):
        """(timestamps, values, body bytes) for a range query."""
        from foremast_tpu.dataplane.fetch import parse_prometheus_body

        job, slot, tag, qstart, qend = self._query(url)
        fl = self.fleet
        if tag in self.arrays_for:
            k_lo, k_hi = fl.clip(qstart, qend)
            if k_hi < k_lo:
                return np.zeros(0), np.zeros(0), 0
            ts = (fl.t0 + np.arange(k_lo, k_hi + 1) * fl.step).astype(
                np.float64)
            return (ts, fl.served(job, slot, k_lo, k_hi),
                    _BYTES_PER_SAMPLE * ts.shape[0])
        raw = fl.body(job, slot, qstart, qend)
        ts, vals = parse_prometheus_body(raw)
        return ts, vals, len(raw)

    def fetch(self, url: str):
        ts, vals, _ = self.fetch_series(url)
        return ts, vals

    def fetch_window(self, url: str):
        from foremast_tpu.dataplane.fetch import grid_from_series

        ts, vals, _ = self.fetch_series(url)
        return grid_from_series(ts, vals, self.fleet.step)
