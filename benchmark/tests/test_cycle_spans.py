"""The readers of the program's own cycle spans (`lib/cycle_spans.py` and
the metrics built on it): on a tiny traced CPU run every one of them
reports, the named seconds fill the hole `unstaged_s_per_cycle` measures,
and a window whose root is not in the ring reads as nothing."""
import os
import types

import pytest

import run as harness
from lib import cycle_spans, fleet as fleet_mod

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
# a tiny cycle takes about 80 ms: a few calls between two clocks are the
# whole difference between two ways of measuring the same seconds
TINY_TOLERANCE_S = 0.01


NEW = (
    "prep_thread_s_per_cycle", "source_s_per_cycle",
    "splice_lock_wait_s_per_cycle", "splice_s_per_cycle",
    "claim_s_per_cycle", "route_s_per_cycle", "route_cpu_s_per_cycle",
    "memo_fp_s_per_cycle", "advance_s_per_cycle", "fold_s_per_cycle",
    "publish_s_per_cycle", "uncovered_s_per_cycle", "pack_s_per_cycle",
    "launch_s_per_cycle", "h2d_bytes_per_cycle", "d2h_bytes_per_cycle",
    "pack_fill_share", "materialize_s_per_cycle",
    "collect_rows_s_per_cycle")


@pytest.fixture(scope="module")
def line():
    return harness.run(types.SimpleNamespace(
        workload="rollout7d_polled", seed=2147483659, seconds=0.2, trace=1,
        tiny=True))


def test_every_new_metric_is_reported_on_a_tiny_traced_run(line):
    manifest = fleet_mod.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert all(by_name[n]["source"] in ("program_span", "program_counter")
               and "workloads" not in by_name[n] for n in NEW)
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(values)
    assert line["correct"] is True and line["failed"] == 0
    assert all(values[n] >= 0 for n in NEW if n != "uncovered_s_per_cycle")
    assert values["h2d_bytes_per_cycle"] > values["d2h_bytes_per_cycle"] > 0
    assert 0 < values["pack_fill_share"] <= 100
    # the cycle thread's CPU over the stream cannot pass the stream's wall
    assert values["route_cpu_s_per_cycle"] <= (
        values["route_s_per_cycle"] + values["memo_fp_s_per_cycle"]
        + values["preprocess_s_per_cycle"] + TINY_TOLERANCE_S)
    assert values["splice_s_per_cycle"] <= \
        values["preprocess_s_per_cycle"] + values["unstaged_s_per_cycle"]


def test_the_named_seconds_fill_the_unstaged_hole(line):
    v = {k: m["value"] for k, m in line["metrics"].items()}
    named = sum(v[k + "_s_per_cycle"] for k in (
        "claim", "route", "memo_fp", "advance", "publish", "uncovered"))
    assert named == pytest.approx(v["unstaged_s_per_cycle"],
                                  abs=TINY_TOLERANCE_S)
    assert abs(v["uncovered_s_per_cycle"]) < TINY_TOLERANCE_S
    assert v["pack_s_per_cycle"] + v["launch_s_per_cycle"] == pytest.approx(
        v["dispatch_s_per_cycle"], abs=TINY_TOLERANCE_S)
    assert v["fold_s_per_cycle"] == pytest.approx(
        sum(c["stage_seconds"]["fold"] for c in line["cycles"])
        / len(line["cycles"]), abs=TINY_TOLERANCE_S)
    assert v["materialize_s_per_cycle"] + v["collect_rows_s_per_cycle"] \
        == pytest.approx(v["collect_s_per_cycle"], abs=TINY_TOLERANCE_S)


def test_a_window_whose_root_left_the_ring_reads_as_nothing(line):
    from foremast_tpu.utils.tracing import tracer

    held = [t["attrs"]["cycle_id"] for t in tracer.snapshot(limit=256)
            if t["name"] == cycle_spans.ROOT]
    assert held
    ctx = {"cycles": [{"cycle_id": held[-1]}]}
    assert cycle_spans.roots(ctx) is not None
    assert cycle_spans.span_seconds(ctx, "engine.fold") > 0
    assert cycle_spans.span_seconds(ctx, "engine.no_such_span") is None
    assert cycle_spans.attr(ctx, cycle_spans.SCORE, "no_such_attr") is None
    missing = {"cycles": [{"cycle_id": held[-1]},
                          {"cycle_id": "bench-c999999"}]}
    assert cycle_spans.roots(missing) is None
    for name in NEW:
        assert harness.load_reader(name)(missing) is None, name


def test_a_root_that_dropped_children_reads_as_nothing(monkeypatch):
    from foremast_tpu.utils import tracing

    root = {"name": cycle_spans.ROOT, "duration_ms": 10.0,
            "attrs": {"cycle_id": "x-c1"},
            "children": [{"name": "engine.fold", "duration_ms": 4.0},
                         {"name": cycle_spans.SCORE, "duration_ms": 5.0,
                          "children_dropped": 3}]}
    monkeypatch.setattr(tracing.tracer, "snapshot", lambda **kw: [root])
    ctx = {"cycles": [{"cycle_id": "x-c1"}]}
    assert cycle_spans.span_seconds(ctx, "engine.fold") is None
    del root["children"][1]["children_dropped"]
    assert cycle_spans.span_seconds(ctx, "engine.fold") == pytest.approx(4e-3)
    assert cycle_spans.self_seconds(root) == pytest.approx(1e-3)
    # a program from before the pieces existed: no `route_s`, no reading
    assert cycle_spans.per_cycle(ctx, cycle_spans.uncovered_seconds) is None


def test_collect_rows_is_the_collect_spans_self_time(monkeypatch):
    from foremast_tpu.utils import tracing

    def collect(ms, wait_ms):
        return {"name": "engine.collect", "duration_ms": ms, "children": [
            {"name": "engine.materialize", "duration_ms": wait_ms}]}

    root = {"name": cycle_spans.ROOT, "duration_ms": 40.0,
            "attrs": {"cycle_id": "x-c1"},
            "children": [{"name": cycle_spans.SCORE, "duration_ms": 30.0,
                          "children": [collect(10.0, 6.0),
                                       collect(5.0, 4.5)]}]}
    monkeypatch.setattr(tracing.tracer, "snapshot", lambda **kw: [root])
    ctx = {"cycles": [{"cycle_id": "x-c1"}]}
    read = harness.load_reader("collect_rows_s_per_cycle")
    assert read(ctx) == pytest.approx(4.5e-3)
    assert harness.load_reader("materialize_s_per_cycle")(ctx) \
        == pytest.approx(10.5e-3)
    # a cycle that collected nothing has no such span: nothing to read
    root["children"][0]["children"] = []
    assert read(ctx) is None
