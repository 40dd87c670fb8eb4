"""The window's cycles as the program's own tracer recorded them.

Every `Analyzer.run_cycle` finishes one `engine.cycle` root span into the
tracer's ring (`foremast_tpu.utils.tracing.tracer.snapshot()`, what
`/debug/traces` serves), stamped with the cycle's id; `run.py` keeps that
id for each cycle of the window. A span here is the plain dict the ring
holds: `name`, `duration_ms`, `attrs`, `children`.

A reader built on this returns None, so that the metric is left out of the
line and never wrong, when a window's root is not in the ring, when a root
dropped children at the tracer's fan-out cap, or when the program has no
such span or attribute (a parent commit from before they existed).
"""
from __future__ import annotations

ROOT = "engine.cycle"
PREPROCESS = "engine.preprocess"
SCORE = "engine.score"
# the pieces that interleave per job inside engine.preprocess: no span of
# their own, totals on that span's attrs
PIECES = ("wait_s", "route_s", "memo_fp_s", "triage_s")


def _whole(span: dict) -> bool:
    return not span.get("children_dropped") and all(
        _whole(c) for c in span.get("children", ()))


def roots(ctx) -> list | None:
    """The `engine.cycle` root of each cycle of the window, in order."""
    try:
        from foremast_tpu.utils.tracing import tracer
    except ImportError:
        return None
    ring = {t.get("attrs", {}).get("cycle_id"): t
            for t in tracer.snapshot(limit=tracer.max_traces)
            if t.get("name") == ROOT}
    out = [ring.get(c["cycle_id"]) for c in ctx["cycles"]]
    if not out or any(r is None or not _whole(r) for r in out):
        return None
    return out


def find(span: dict, name: str) -> list:
    """Every span called `name` in the tree under (and including) `span`."""
    hits = [span] if span.get("name") == name else []
    for c in span.get("children", ()):
        hits += find(c, name)
    return hits


def seconds(span: dict) -> float:
    return span["duration_ms"] * 1e-3


def self_seconds(span: dict) -> float:
    """A span's duration less what its children cover."""
    return seconds(span) - sum(seconds(c) for c in span.get("children", ()))


def per_cycle(ctx, of_root):
    """Mean of `of_root(root)` over the window's cycles; None when a root is
    missing or `of_root` finds nothing to read in one."""
    rs = roots(ctx)
    if rs is None:
        return None
    values = [of_root(r) for r in rs]
    if any(v is None for v in values):
        return None
    return sum(values) / len(values)


def span_seconds(ctx, name: str, of=seconds):
    """Summed seconds (`of=self_seconds`: self time) of the spans called
    `name`, per cycle."""
    def of_root(root):
        hits = find(root, name)
        return sum(of(s) for s in hits) if hits else None
    return per_cycle(ctx, of_root)


def attr(ctx, span_name: str, key: str):
    """An attribute of the cycle's one `span_name` span, per cycle."""
    def of_root(root):
        hits = find(root, span_name)
        return hits[0].get("attrs", {}).get(key) if hits else None
    return per_cycle(ctx, of_root)


def uncovered_seconds(root: dict):
    """What no name covers: the root's self time, and what is left of
    `engine.preprocess` after its children and its pieces."""
    prep = find(root, PREPROCESS)
    if not prep or "route_s" not in prep[0].get("attrs", {}):
        return None
    attrs = prep[0]["attrs"]
    return (self_seconds(root) + self_seconds(prep[0])
            - sum(attrs.get(k, 0.0) for k in PIECES))
