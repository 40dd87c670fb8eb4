"""One run of one benchmark cell: `Analyzer.run_cycle` in a closed loop.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It holds the chip, builds the cell's fleet from `--seed`,
warms the engine up until a whole cycle compiles nothing (that is
`setup_s`), drives whole cycles for `--seconds`, reads the peak device
memory, frees the engine, checks the last cycle's verdicts against the
plain reference, and prints one JSON line. `--trace 1` wraps the window
(two cycles) in the profiler and prints the per-layer metrics instead.

A cell is an entry of `BENCHMARK.json`: its configuration is the file
that entry's `configs` row names, its traffic `benchmark/traffic/<traffic>.json`,
a per-layer metric `benchmark/metrics/<name>.py`, a scoring family's
reference and comparison `benchmark/families/<stem>.py` (the stem the
configuration's `references` names for the family, else the family's own
name). No name is listed here.

Without a TPU it exits non-zero and prints no result, unless `--tiny` is
given: the CPU rehearsal (job counts and history cut by the
configuration's `tiny` block; no device metric is reported).
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, for setup_s

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import check, fleet as fleet_mod, peaks, trace_reduce  # noqa: E402
from lib.source import FleetSource  # noqa: E402

MAX_WARM_CYCLES = 6
TRACE_CYCLES = 2
BAD_STATUSES = ("preprocess_failed", "abort", "completed_unknown")
CYCLE_COUNTERS = ("jobs_shed", "watchdog_fires", "quarantined_jobs",
                  "stale_verdicts_served")


BenchError = fleet_mod.BenchError  # the run ends non-zero with no result


def load_cell(name: str) -> dict:
    """The cell's manifest entries and data files, found by name."""
    manifest = fleet_mod.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_row = next(c for c in manifest["configs"]
                   if c["name"] == cell["config"])
    names = {}
    for group, trace in (("end_to_end", 0), ("per_layer", 1)):
        names[trace] = [
            m for m in manifest[group]
            if "workloads" not in m or name in m["workloads"]]
    return {
        "name": name, "chips": int(cell["chips"]),
        "config": fleet_mod.load_json(os.path.join(ROOT, cfg_row["file"])),
        "traffic": fleet_mod.load_json(
            os.path.join(HERE, "traffic", cell["traffic"] + ".json")),
        "metrics": names,
    }


def find_devices(chips: int, tiny: bool):
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if not tiny and (devices[0].platform != "tpu" or len(devices) < chips):
        raise BenchError(
            f"needs {chips} TPU chip(s); JAX found {len(devices)} x "
            f"{devices[0].platform}")
    return devices


class Engine:
    """The system under test and the fleet it watches, built as
    `simfleet/driver.run_fleet` builds them."""

    def __init__(self, cell: dict, seed: int, tiny: bool):
        from foremast_tpu.dataplane.delta import DeltaWindowSource
        from foremast_tpu.engine import jobs as J
        from foremast_tpu.engine.analyzer import Analyzer
        from foremast_tpu.engine.config import EngineConfig
        from foremast_tpu.engine.pipeline import CompileCounter

        self.fleet = fl = fleet_mod.Fleet(cell["config"], seed, tiny)
        self.cadence_s = float(cell["traffic"]["cadence_s"])
        self.inner = FleetSource(fl)
        eng = dict(fl.config["engine"])
        cache = int(eng.pop("window_cache_per_job")) * fl.jobs
        self.delta = DeltaWindowSource(self.inner, max_entries=cache,
                                       clock=lambda: fl.now)
        self.store = J.JobStore()
        start, end = fl.window_span()
        for job in range(fl.jobs):
            self.store.create(J.Document(
                id=fl.job_id(job), app_name=fl.app_name(job),
                namespace="bench", strategy=fl.cls(job)["strategy"],
                start_time=start, end_time=end,
                metrics={m: J.MetricQueries(**q)
                         for m, q in fl.queries(job).items()}))
        self.analyzer = Analyzer(
            EngineConfig(window_cache_max=cache, **eng), self.delta,
            self.store)
        # the latest-record table is sized for the fleet, as the window
        # cache is: the check reads the last cycle's records from it
        self.analyzer.provenance.max_jobs = 2 * fl.jobs
        # rows a job of each class gives each scoring family: one a result
        first = fl.class_of.tolist().index
        self.rows_of = [
            collections.Counter(f for f, _, _ in check.expected(fl, first(c)))
            for c in range(len(fl.classes))]
        self.compiles = CompileCounter().start()
        self.cycles_run = 0

    def cycle(self) -> dict:
        """Step the clock one cadence and run one whole cycle."""
        an, fl = self.analyzer, self.fleet
        if self.cycles_run:
            fl.now += self.cadence_s
        if self.cycles_run >= fl.max_cycles:
            raise BenchError(
                f"the fleet's horizon holds {fl.max_cycles} cycles")
        self.cycles_run += 1
        fetches0, launches0 = self.inner.request_count, an.device_launches
        records0, compiles0 = an.provenance.records_total, \
            self.compiles.compiles
        t0 = time.perf_counter()
        outcomes = an.run_cycle(worker="bench", now=fl.now)
        seconds = time.perf_counter() - t0
        st = an.last_cycle_stages
        offered = int(st["jobs"])
        bad = sum(1 for s in outcomes.values() if s in BAD_STATUSES)
        unjudged = max(
            offered - (an.provenance.records_total - records0), 0)
        failed = bad + unjudged + sum(int(st[k]) for k in CYCLE_COUNTERS)
        # rows each scoring family was given, by class and in all
        jobs_of = collections.Counter(
            int(fl.class_of[fl.job_index(jid)]) for jid in outcomes)
        class_rows = {c: {fam: n * k for fam, k in self.rows_of[c].items()}
                      for c, n in jobs_of.items()}
        rows = {}
        for of_class in class_rows.values():
            for fam, n in of_class.items():
                rows[fam] = rows.get(fam, 0) + n
        k_now = fl.now_slot()
        return {
            "seconds": seconds, "offered": offered, "failed": failed,
            "judged": max(offered - failed, 0),
            "cycle_id": st["cycle_id"], "now_slot": k_now,
            "lag_s": fl.now - (fl.t0 + k_now * fl.step),
            "stage_seconds": dict(st["stage_seconds"]),
            "family_launches": dict(st["family_launches"]),
            "launches": an.device_launches - launches0,
            "fetches": self.inner.request_count - fetches0,
            "compiles": self.compiles.compiles - compiles0,
            "rows": rows, "class_rows": class_rows, "outcomes": outcomes,
        }

    def warm_up(self) -> list:
        """Cycles until one whole cycle has compiled nothing."""
        warm = []
        for _ in range(MAX_WARM_CYCLES):
            warm.append(self.cycle())
            if warm[-1]["compiles"] == 0:
                return warm
        raise BenchError(
            f"still compiling after {MAX_WARM_CYCLES} warm-up cycles")

    def close(self):
        self.compiles.stop()


def measure(engine: Engine, seconds: float, trace_dir: str | None) -> dict:
    """Whole cycles, at least two, until `seconds` have elapsed; with a
    trace directory, exactly TRACE_CYCLES cycles under the profiler."""
    import jax

    cycles = []
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    try:
        while True:
            with jax.profiler.TraceAnnotation("bench.cycle"):
                cycles.append(engine.cycle())
            elapsed = time.perf_counter() - t0
            if trace_dir is not None:
                if len(cycles) >= TRACE_CYCLES:
                    break
            elif len(cycles) >= 2 and elapsed >= seconds:
                break
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return {"cycles": cycles, "elapsed": elapsed}


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run(args) -> dict:
    cell = load_cell(args.workload)
    devices = find_devices(cell["chips"], args.tiny)
    engine = Engine(cell, args.seed, args.tiny)
    try:
        warm = engine.warm_up()
        setup_s = time.perf_counter() - _T0
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") \
            if args.trace else None
        try:
            window = measure(engine, args.seconds, trace_dir)
            cycles = window["cycles"]
            peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devices[:cell["chips"]])
            trace = trace_reduce.reduce_dir(trace_dir) \
                if trace_dir is not None else None
        finally:
            if trace_dir is not None:
                shutil.rmtree(trace_dir, ignore_errors=True)
        fl = engine.fleet
        answers = check.program_answers(
            engine.analyzer, engine.store, fl, cycles[-1])
    finally:
        engine.close()
    in_window_compiles = sum(c["compiles"] for c in cycles)
    # the reference runs with the program's state freed
    del engine
    gc.collect()
    numbers = check.compare(fl, answers)
    numbers.append(("compiles_in_window", in_window_compiles, 0))
    correct = all(v <= lim for _, v, lim in numbers)

    attempted = sum(c["offered"] for c in cycles)
    failed = sum(c["failed"] for c in cycles)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed}
    if not args.trace:
        values = {
            "jobs_per_s": sum(c["judged"] for c in cycles)
            / window["elapsed"],
            "setup_s": setup_s,
        }
    else:
        ctx = {"cycles": cycles, "trace": trace, "fleet": fl,
               "peaks": None if args.tiny
               else peaks.for_kind(devices[0].device_kind),
               "notes": {}}
        values = {}
        for m in cell["metrics"][1]:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                values[m["name"]] = v
        if trace is not None and trace["busy_s"] > 0:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            out["breakdown"] = {"device_ops": trace["device_ops"][:10],
                                "idle_gaps": trace["idle_gaps"][:10]}
        out["notes"] = ctx["notes"]
    units = {m["name"]: m["unit"] for m in cell["metrics"][int(args.trace)]}
    out["metrics"] = {k: {"value": v, "unit": units[k]}
                      for k, v in values.items() if k in units}
    out["device"] = device
    out["cycles"] = [{k: c[k] for k in ("seconds", "offered", "failed",
                                        "stage_seconds", "launches", "fetches",
                                        "rows")}
                     for c in cycles]
    out["warm_cycles"] = [{"seconds": c["seconds"], "compiles": c["compiles"]}
                          for c in warm]
    out["compared"] = {n: {"value": v, "limit": lim}
                       for n, v, lim in numbers}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal: cut job counts and history")
    args = ap.parse_args(argv)
    try:
        out = run(args)
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
