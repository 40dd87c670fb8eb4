"""Tracing spans + multi-host helpers — the aux subsystems the reference
lacks (SURVEY.md §5: no tracing implemented; distribution = shared-nothing
workers). Covers span nesting/aggregation, the /debug/traces and /metrics
surfaces, engine-cycle instrumentation, and process-slice math.
"""
from __future__ import annotations

import threading

import numpy as np
import pytest

from foremast_tpu.utils.tracing import (
    Tracer,
    W3CContext,
    parse_traceparent,
)


def test_span_nesting_builds_one_trace_tree():
    tr = Tracer()
    with tr.span("cycle", worker="w0"):
        with tr.span("claim"):
            pass
        with tr.span("score", pairs=3):
            with tr.span("batch"):
                pass
    [trace] = tr.snapshot()
    assert trace["name"] == "cycle"
    assert trace["attrs"] == {"worker": "w0"}
    names = [c["name"] for c in trace["children"]]
    assert names == ["claim", "score"]
    score = trace["children"][1]
    assert [c["name"] for c in score["children"]] == ["batch"]
    assert trace["duration_ms"] >= score["duration_ms"] >= 0


def test_stats_aggregate_and_render():
    tr = Tracer()
    for _ in range(3):
        with tr.span("fetch"):
            pass
    st = tr.stats()["fetch"]
    assert st["count"] == 3
    assert st["max_seconds"] <= st["total_seconds"] + 1e-9
    text = tr.render_metrics()
    assert 'foremast_trace_count{span="fetch"} 3' in text


def test_span_records_even_when_body_raises():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    [trace] = tr.snapshot()
    assert trace["name"] == "boom" and trace["duration_ms"] >= 0
    assert tr.stats()["boom"]["count"] == 1


def test_ring_buffer_bounded():
    tr = Tracer(max_traces=5)
    for i in range(12):
        with tr.span(f"t{i}"):
            pass
    snap = tr.snapshot()
    assert len(snap) == 5
    assert snap[-1]["name"] == "t11"


def test_threads_get_independent_span_stacks():
    tr = Tracer()
    errs = []

    def work(i):
        try:
            with tr.span(f"root{i}"):
                with tr.span("child"):
                    pass
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    roots = {t["name"] for t in tr.snapshot()}
    assert roots == {f"root{i}" for i in range(8)}
    # every root got exactly its own child, none were cross-adopted
    assert all(len(t.get("children", [])) == 1 for t in tr.snapshot())


def test_engine_cycle_emits_spans_and_service_exposes_them():
    from foremast_tpu.dataplane import FixtureDataSource, VerdictExporter
    from foremast_tpu.engine import Analyzer, Document, EngineConfig, JobStore, MetricQueries
    from foremast_tpu.service.api import ForemastService
    from foremast_tpu.utils.tracing import tracer

    tracer.reset()
    rng = np.random.default_rng(0)
    ts = list(np.arange(30) * 60.0)
    fixtures = {
        "u-cur": (ts, list(rng.normal(5.0, 0.3, 30))),
        "u-base": (ts, list(rng.normal(0.5, 0.05, 30))),
    }
    store = JobStore()
    store.create(Document(id="j", app_name="a", namespace="d", strategy="canary",
                          start_time="1970-01-01T00:00:00Z",
                          end_time="1970-01-01T00:30:00Z",
                          metrics={"error5xx": MetricQueries(current="u-cur",
                                                             baseline="u-base")}))
    analyzer = Analyzer(EngineConfig(), FixtureDataSource(fixtures), store,
                        VerdictExporter())
    analyzer.run_cycle(now=10_000.0)
    [trace] = [t for t in tracer.snapshot() if t["name"] == "engine.cycle"]
    child_names = {c["name"] for c in trace["children"]}
    assert {"engine.claim", "engine.preprocess", "engine.score"} <= child_names

    svc = ForemastService(store, exporter=VerdictExporter())
    status, payload = svc.debug_traces()
    assert status == 200
    assert any(t["name"] == "engine.cycle" for t in payload["traces"])
    status, text = svc.metrics()
    assert 'foremast_trace_count{span="engine.cycle"}' in text


# ----------------------------------------------------- cross-thread context

def test_monotonic_durations_survive_wall_clock_steps(monkeypatch):
    """Span durations come from time.monotonic(): a wall-clock step mid
    span (NTP slew, the bench_cycle.py clock-domain caveat this PR
    retired) cannot produce negative or inflated durations."""
    from foremast_tpu.utils import tracing as tmod

    tr = Tracer()
    real_time = tmod.time.time
    # wall clock jumps BACKWARD one hour between span start and end
    seq = iter([real_time(), real_time() - 3600.0])
    monkeypatch.setattr(tmod.time, "time", lambda: next(seq, real_time()))
    with tr.span("stepped"):
        pass
    [trace] = tr.snapshot()
    assert 0.0 <= trace["duration_ms"] < 1000.0
    st = tr.stats()["stepped"]
    assert 0.0 <= st["max_seconds"] < 1.0


def test_worker_thread_span_parents_under_cycle_trace():
    """attach(): a span opened on a pool thread lands as a CHILD of the
    originating trace (PR 2's fetch-pool spans no longer orphan), and the
    bound correlation ids propagate into its attrs."""
    tr = Tracer()
    done = threading.Event()

    with tr.bind(cycle_id="w0-c7"):
        with tr.span("cycle"):
            ctx = tr.context()

            def work():
                with tr.attach(ctx):
                    assert tr.current_ids() == {"cycle_id": "w0-c7"}
                    with tr.span("fetch", job="j1"):
                        pass
                done.set()

            t = threading.Thread(target=work, daemon=True)
            t.start()
            assert done.wait(5.0)
            t.join(5.0)
    assert tr.current_ids() == {}  # bind restored
    [trace] = tr.snapshot()
    assert trace["name"] == "cycle"
    assert trace["attrs"]["cycle_id"] == "w0-c7"
    [child] = trace["children"]
    assert child["name"] == "fetch"
    assert child["attrs"]["cycle_id"] == "w0-c7"  # ids crossed the thread


def test_abandoned_thread_never_corrupts_other_stacks():
    """A watchdog-style abandoned thread (attached, span open, never
    finishes before the root does) must not corrupt the main thread's
    stack or the finished trace; its late span is dropped silently."""
    tr = Tracer()
    release = threading.Event()
    started = threading.Event()
    finished = threading.Event()

    with tr.span("cycle"):
        ctx = tr.context()

        def hung():
            with tr.attach(ctx):
                with tr.span("hung-collect"):
                    started.set()
                    release.wait(10.0)
            finished.set()

        t = threading.Thread(target=hung, daemon=True)
        t.start()
        assert started.wait(5.0)
        # main thread abandons the worker and finishes the root
    [trace] = tr.snapshot()
    assert trace["name"] == "cycle"
    assert not trace.get("children")  # late child not yet recorded
    # the abandoned thread eventually returns: nothing raises, the late
    # child is DROPPED (finished parents are never retroactively mutated),
    # and the main thread can keep tracing fresh roots
    release.set()
    assert finished.wait(5.0)
    assert ctx.parent.children == []
    assert ctx.parent.dropped == 1
    with tr.span("next-cycle"):
        pass
    names = [t["name"] for t in tr.snapshot()]
    assert names == ["cycle", "next-cycle"]


def test_child_cap_bounds_trace_allocation():
    from foremast_tpu.utils import tracing as tmod

    tr = Tracer()
    with tr.span("root"):
        for i in range(tmod._MAX_CHILDREN + 10):
            with tr.span("child"):
                pass
    [trace] = tr.snapshot()
    assert len(trace["children"]) == tmod._MAX_CHILDREN
    assert trace["children_dropped"] == 10


def test_notes_accumulate_per_thread_unit_of_work():
    tr = Tracer()
    tr.add_note("ignored")  # no accumulator open: no-op
    tr.begin_notes()
    tr.add_note("fetches")
    tr.add_note("fetches")
    tr.add_note("fetch_seconds", 0.25)
    assert tr.take_notes() == {"fetches": 2, "fetch_seconds": 0.25}
    assert tr.take_notes() == {}  # closed


def test_annotate_names_a_stretch_and_records_nothing(monkeypatch):
    """`annotate` opens a profiler annotation under its name and closes it,
    even when the body raises, and leaves no span in the ring and no stats."""
    from foremast_tpu.utils import tracing

    seen = []

    class Recording:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(tracing, "_TraceAnnotation", Recording)
    tracing.tracer.reset()
    with tracing.annotate(tracing.SPAN_ENGINE_FETCH):
        pass
    with pytest.raises(KeyError):
        with tracing.annotate(tracing.SPAN_ENGINE_FETCH):
            raise KeyError("body")
    assert seen == [("enter", "engine.fetch"), ("exit", "engine.fetch")] * 2
    assert tracing.tracer.snapshot() == [] and tracing.tracer.stats() == {}
    # no profiler to annotate for: a plain block
    monkeypatch.setattr(tracing, "_TraceAnnotation", None)
    with tracing.annotate(tracing.SPAN_ENGINE_FETCH):
        pass
    assert tracing.tracer.stats() == {}


@pytest.mark.parametrize("name, registered", [
    ("tracing.SPAN_ENGINE_FETCH", True),
    ('"engine.fetch"', True),
    ('"engine.pack.pad"', True),
    ('"engine.fetch.nowhere"', False),
])
def test_annotate_names_pass_the_trace_registry_rule(name, registered):
    import os
    import textwrap

    import foremast_tpu
    from foremast_tpu.devtools.checks import TraceNameRegistry
    from foremast_tpu.devtools.linter import (
        Baseline,
        ModuleInfo,
        load_module,
        run_lint,
    )

    root = os.path.dirname(os.path.dirname(foremast_tpu.__file__))
    checker = TraceNameRegistry()
    rel = "foremast_tpu/utils/tracing.py"
    checker.check(load_module(os.path.join(root, rel), rel))
    mod = ModuleInfo("<fixture>", "foremast_tpu/engine/fixture.py",
                     textwrap.dedent(f"""
        from foremast_tpu.utils import tracing

        def f():
            with tracing.annotate({name}):
                pass
    """))
    findings = run_lint([checker], [mod], Baseline()).findings
    assert (not findings) == registered, [f.render() for f in findings]


# --------------------------------------------------- W3C trace context
def test_parse_traceparent_valid_and_flags():
    tid, sid = "a" * 32, "b" * 16
    ctx = parse_traceparent(f"00-{tid}-{sid}-01")
    assert ctx is not None
    assert (ctx.trace_id, ctx.span_id, ctx.sampled) == (tid, sid, True)
    assert parse_traceparent(f"00-{tid}-{sid}-00").sampled is False
    # round trip through the header formatter
    assert parse_traceparent(ctx.traceparent()).trace_id == tid
    # future versions may carry extra fields; version 00 may not
    assert parse_traceparent(f"cc-{tid}-{sid}-01-extra") is not None
    assert parse_traceparent(f"00-{tid}-{sid}-01-extra") is None
    # surrounding whitespace tolerated (header transport artifacts)
    assert parse_traceparent(f"  00-{tid}-{sid}-01 ") is not None


@pytest.mark.parametrize("header", [
    "",                                   # empty
    "00",                                 # truncated
    "00-" + "a" * 32,                     # missing span id
    "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",   # forbidden version
    "00-" + "0" * 32 + "-" + "b" * 16 + "-01",   # all-zero trace id
    "00-" + "a" * 32 + "-" + "0" * 16 + "-01",   # all-zero span id
    "00-" + "A" * 32 + "-" + "b" * 16 + "-01",   # uppercase hex
    "00-" + "g" * 32 + "-" + "b" * 16 + "-01",   # non-hex
    "00-" + "a" * 31 + "-" + "b" * 16 + "-01",   # short trace id
    "00-" + "a" * 32 + "-" + "b" * 15 + "-01",   # short span id
    "0-" + "a" * 32 + "-" + "b" * 16 + "-01",    # short version
    "00-" + "a" * 32 + "-" + "b" * 16 + "-1",    # short flags
    "00_" + "a" * 32 + "_" + "b" * 16 + "_01",   # wrong separators
    "x" * 10_000,                         # oversized
    None,                                 # not a string at all
    42,
])
def test_parse_traceparent_rejects_malformed(header):
    assert parse_traceparent(header) is None


def test_span_ids_mint_and_inherit():
    tr = Tracer()
    with tr.span("cycle") as root:
        assert len(root.trace_id) == 32 and len(root.span_id) == 16
        with tr.span("claim") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_span_id == root.span_id
            assert child.span_id != root.span_id
    trace = tr.snapshot()[-1]
    assert trace["trace_id"] == root.trace_id
    assert trace["children"][0]["parent_span_id"] == root.span_id


def test_adopt_remote_continues_the_senders_trace():
    tr = Tracer()
    remote = W3CContext("c" * 32, "d" * 16, sampled=True)
    with tr.adopt_remote(remote):
        with tr.span("ingest.receive") as sp:
            assert sp.trace_id == remote.trace_id
            assert sp.parent_span_id == remote.span_id
            # header injection for the next hop names THIS span
            assert tr.current_traceparent() == \
                f"00-{'c' * 32}-{sp.span_id}-01"
    # adoption is scoped: outside the block fresh roots mint their own
    with tr.span("next") as sp2:
        assert sp2.trace_id != remote.trace_id
    trace = tr.snapshot(trace_id=remote.trace_id)
    assert len(trace) == 1 and trace[0]["name"] == "ingest.receive"


def test_remote_forced_root_span_inside_open_stack():
    """`_remote=` closes a distributed trace from INSIDE another open
    span (the engine's verdict span inside the cycle span): it parents
    under the remote context, finishes as its own root tree, and never
    lands as a child of the enclosing local span."""
    tr = Tracer()
    remote = W3CContext("e" * 32, "f" * 16)
    with tr.span("engine.cycle") as cyc:
        with tr.span("engine.verdict", _remote=remote, job_id="j1") as v:
            assert v.trace_id == remote.trace_id
            assert v.parent_span_id == remote.span_id
    assert not cyc.children  # not attached locally
    roots = {t["name"]: t for t in tr.snapshot()}
    assert roots["engine.verdict"]["trace_id"] == remote.trace_id
    assert roots["engine.cycle"]["trace_id"] == cyc.trace_id


def test_unsampled_roots_measured_but_not_ringed_or_exported():
    tr = Tracer()
    exported = []
    tr.add_sink(exported.append)
    tr.set_sample_rate(0.0)
    with tr.span("quiet"):
        pass
    # an adopted sampled=False context is honored the same way
    with tr.adopt_remote(W3CContext("a" * 32, "b" * 16, sampled=False)):
        with tr.span("quiet-remote") as sp:
            assert sp.sampled is False
    tr.set_sample_rate(1.0)
    with tr.span("loud"):
        pass
    names = [t["name"] for t in tr.snapshot()]
    assert names == ["loud"]
    assert [t["name"] for t in exported] == ["loud"]
    # stats saw everything — sampling bounds storage, not measurement
    assert tr.stats()["quiet"]["count"] == 1
    assert tr.stats()["quiet-remote"]["count"] == 1


def test_resource_stamped_on_finished_roots():
    tr = Tracer()
    tr.resource = {"replica": "rep-a"}
    with tr.span("cycle"):
        pass
    assert tr.snapshot()[-1]["resource"] == {"replica": "rep-a"}


def test_attach_carries_remote_context_across_threads():
    tr = Tracer()
    remote = W3CContext("9" * 32, "8" * 16)
    seen = {}
    with tr.adopt_remote(remote):
        ctx = tr.context()

    def work():
        with tr.attach(ctx):
            with tr.span("worker-root") as sp:
                seen["tid"] = sp.trace_id

    t = threading.Thread(target=work)
    t.start()
    t.join(5.0)
    assert seen["tid"] == remote.trace_id


def test_log_filter_stamps_trace_ids(caplog):
    import logging

    from foremast_tpu.utils.tracing import TraceContextFilter

    tr = Tracer()
    logger = logging.getLogger("foremast_tpu.test_tracing")
    handler = logging.Handler()
    records = []
    handler.emit = records.append
    handler.addFilter(TraceContextFilter(tr))
    logger.addHandler(handler)
    try:
        with tr.bind(cycle_id="w0-c3", job_id="jobA"):
            logger.warning("inside")
        logger.warning("outside")
    finally:
        logger.removeHandler(handler)
    inside, outside = records
    assert inside.trace_ctx == " cycle_id=w0-c3 job_id=jobA"
    assert outside.trace_ctx == ""
    # the runtime's format string appends %(trace_ctx)s: grep-able
    line = f"{inside.getMessage()}{inside.trace_ctx}"
    assert "cycle_id=w0-c3" in line


# ---------------------------------------------------------------- distributed
def test_process_batch_slice_partitions_evenly():
    from foremast_tpu.parallel.distributed import HostInfo, process_batch_slice

    slices = [
        process_batch_slice(32, HostInfo(process_id=i, num_processes=4,
                                         local_devices=2, global_devices=8))
        for i in range(4)
    ]
    covered = []
    for s in slices:
        covered += list(range(32))[s]
    assert covered == list(range(32))
    with pytest.raises(ValueError):
        process_batch_slice(33, HostInfo(0, 4, 2, 8))


def test_initialize_single_host_is_noop():
    from foremast_tpu.parallel import distributed

    assert distributed.initialize(env={}) is False  # no coordinator config


def test_initialize_single_host_tpu_vm_needs_no_handshake(monkeypatch):
    """A single-host TPU VM sets TPU_WORKER_HOSTNAMES=localhost; there the
    argument-less jax.distributed.initialize() asks a metadata server for
    the cluster and raises where there is none (seen on the v5e check
    machine). One listed worker is a world of one: no call. Two or more
    still auto-initialize."""
    from foremast_tpu.parallel import distributed

    calls = []
    monkeypatch.setattr(distributed.jax.distributed, "initialize",
                        lambda **kw: calls.append(kw))
    monkeypatch.setattr(distributed, "_initialized", False)
    assert distributed.initialize(
        env={"TPU_WORKER_HOSTNAMES": "localhost"}) is False
    assert calls == []
    assert distributed.initialize(
        env={"TPU_WORKER_HOSTNAMES": "t1v-0,t1v-1"}) is True
    assert calls == [{}]
    monkeypatch.setattr(distributed, "_initialized", False)


def test_initialize_partial_config_degrades_to_single_host(caplog):
    """A templated NUM_PROCESSES=1 or a lone COORDINATOR_ADDRESS must not
    crash the runtime at boot — warn (through logging, the lint suite's
    thread-hygiene rule bans bare print) and continue local."""
    import logging

    from foremast_tpu.parallel import distributed

    with caplog.at_level(logging.WARNING, logger="foremast_tpu.parallel"):
        assert distributed.initialize(env={"NUM_PROCESSES": "1"}) is False
        assert distributed.initialize(
            env={"COORDINATOR_ADDRESS": "10.0.0.2:8476"}) is False
    assert "incomplete multi-host config" in caplog.text


def test_initialize_passes_explicit_world(monkeypatch):
    from foremast_tpu.parallel import distributed

    calls = {}

    def fake_init(**kw):
        calls.update(kw)

    monkeypatch.setattr(distributed.jax.distributed, "initialize", fake_init)
    monkeypatch.setattr(distributed, "_initialized", False)
    ok = distributed.initialize(env={
        "COORDINATOR_ADDRESS": "10.0.0.2:8476",
        "NUM_PROCESSES": "4",
        "PROCESS_ID": "1",
        "LOCAL_DEVICE_IDS": "0,1",
    })
    assert ok is True
    assert calls == {
        "coordinator_address": "10.0.0.2:8476",
        "num_processes": 4,
        "process_id": 1,
        "local_device_ids": [0, 1],
    }
    # second call is a no-op
    assert distributed.initialize(env={}) is False
    monkeypatch.setattr(distributed, "_initialized", False)


def test_global_fleet_mesh_spans_all_devices():
    import jax

    from foremast_tpu.parallel.distributed import global_fleet_mesh

    mesh = global_fleet_mesh()
    assert mesh.devices.size == len(jax.devices())
