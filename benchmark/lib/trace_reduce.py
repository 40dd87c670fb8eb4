"""From a profiler trace to device busy time, program times, the
operations that took most time, and the idle gaps named by what the host
was doing. Reads `.xplane.pb` with `jax.profiler.ProfileData`, nothing
but JAX.

What it takes from a trace (checked by hand on a v5e trace, PR 25):
  device planes   `/device:TPU:<n>`; line `XLA Ops` holds one event per
                  executed operation, line `XLA Modules` one per program
                  run, named `jit_<function>(<fingerprint>)`
  host plane      `/host:CPU`; one line per thread, holding the
                  `TraceAnnotation` spans the engine and the harness open
The traced window is the span from the first `bench.cycle` annotation's
start to the last one's end.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench.cycle"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_RE = re.compile(r"^/device:TPU:\d+$")
_HOST_SPAN_RE = re.compile(r"^(bench|engine|dataplane|ingest)\.[\w.]+$")
_FINGERPRINT_RE = re.compile(r"\(\d+\)$")
MIN_GAP_S = 1e-7  # shorter gaps are the seams between back-to-back ops


def _op_name(printed: str) -> str:
    """An operation's own name: the trace prints the whole HLO
    instruction, `%name = shape op(operands)`."""
    return printed.split(" = ", 1)[0][:64]


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def load(path: str) -> dict:
    """{plane name: {line name: [(event name, start_s, duration_s)]}}."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.name, ev.start_ns * 1e-9,
                            ev.duration_ns * 1e-9))
    return planes


def union_seconds(intervals: list) -> tuple[float, list]:
    """Total covered seconds and the merged [start, end] intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def reduce(planes: dict) -> dict:
    """The numbers the per-layer readers and `breakdown` use."""
    spans = []  # host spans: (name, start, end)
    for line in planes.get("/host:CPU", {}).values():
        spans += [(n, s, s + d) for n, s, d in line if _HOST_SPAN_RE.match(n)]
    cycles = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    devices = sorted(p for p in planes if _DEVICE_RE.match(p))
    out = {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
           "idle_gaps": [], "programs": {}}
    if not cycles:
        return out
    w0, w1 = min(s for s, _ in cycles), max(e for _, e in cycles)
    out["window_s"] = w1 - w0
    if not devices:
        return out

    def clipped(line):
        return [(n, max(s, w0), min(s + d, w1)) for n, s, d in line
                if s + d > w0 and s < w1]

    ops_time: dict = {}
    busy = 0.0
    merged_first = []
    for i, dev in enumerate(devices):
        ops = clipped(planes[dev].get(OPS_LINE)
                      or planes[dev].get(MODULES_LINE) or [])
        total, merged = union_seconds([(s, e) for _, s, e in ops])
        busy += total
        if i == 0:
            merged_first = merged
        for n, s, e in ops:
            n = _op_name(n)
            ops_time[n] = ops_time.get(n, 0.0) + (e - s)
        for n, s, e in clipped(planes[dev].get(MODULES_LINE, [])):
            name = _FINGERPRINT_RE.sub("", n)
            prog = out["programs"].setdefault(name, [0.0, 0])
            prog[0] += e - s
            prog[1] += 1
    out["busy_s"] = busy / len(devices)
    out["device_ops"] = [[n, t] for n, t in sorted(
        ops_time.items(), key=lambda kv: -kv[1])]

    # idle gaps of the first device, named by the innermost host span
    # that covers the gap's middle
    edges = [w0] + [t for iv in merged_first for t in iv] + [w1]
    gaps = []
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 - g0 < MIN_GAP_S:
            continue
        mid = (g0 + g1) / 2
        cover = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        gaps.append([min(cover)[1] if cover else "outside any span",
                     g1 - g0])
    out["idle_gaps"] = sorted(gaps, key=lambda g: -g[1])
    return out


def reduce_dir(trace_dir: str) -> dict:
    path = find_xplane(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(load(path))
