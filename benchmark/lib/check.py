"""What decides `correct`: the last cycle's verdicts, as the timed path
folded them, against the plain reference.

The program's side is read once the window has closed: for every job of
the last cycle, the job's status in the store and what that cycle
recorded for it (the record `/jobs/<id>/explain` serves): each scoring
family's result, the samples its windows held, and how far behind the
cycle's clock its newest judged sample lay. The reference's side is
computed from the fleet's own series with the engine freed. Each number
compared has a limit (PERF.md gives the readings they were set from).

Which file judges a family is the configuration's to say. A class lists
its families by the names the program records (`"band"`, `"pair"`), and
those names stay the keys of every result here; the family's reference
and comparison (`reference_rows`, `answer`, `judge`, `NUMBERS`, `JOINS`)
are `benchmark/families/<stem>.py`, where the stem is the configuration's
`"references": {"<family>": "<stem>"}` entry, or the family's own name
where it has none (`Fleet.references`). So a forecaster or a rank test
other than the default comes as a configuration and a family file.

A family file is handed the `fleet`, and reads its algorithm's settings
from `fleet.config["engine"]`. The rule: a reference file states which
engine setting it is the reference of, as `REFERENCE_OF = {"<engine
key>": "<prefix of the values it judges>"}` (`{"algorithm":
"moving_average"}`; a tuple of prefixes where it judges several), and a
configuration whose engine block states another value, or none, is
refused with `BenchError` the first time the family is looked up, which
is in set-up. A file that judges whatever the engine block says states
nothing.

A job's results are (family, metric) entries, as the program records
them: a family gives one a metric of the job, or, where its file sets
`JOINS`, one for the job's metrics together, named by joining their
aliases with that string (`"error4xx&latency"`). The bounds the program
exports for a job's app (`foremastbrain:<metric>_upper`, `_lower`) ride
on every entry under `exported`; only a class whose jobs have an app
each (`apps`) can be held to them.

Numbers of every cell, beside the families' own:
  verdict_miss  jobs with no verdict from the last cycle, or whose stored
                verdict the reference contradicts
  stale_jobs    jobs whose windows did not hold every sample up to the
                cycle's clock, or whose newest judged sample is not the
                newest scrape
"""
from __future__ import annotations

import importlib.util

from lib.fleet import BenchError, family_path

UNHEALTHY = "completed_unhealth"
HEALTHY = ("initial", "completed_health")
_BLOCK = 256  # reference rows at a time: blocks that stay in cache
_FAMILIES: dict = {}


def family(fleet, name: str):
    """The file that judges family `name` in this fleet's configuration,
    loaded once a stem, and only where the configuration's engine block
    states what the file is the reference of."""
    stem = fleet.references[name]
    if stem not in _FAMILIES:
        spec = importlib.util.spec_from_file_location(
            "bench_family_" + stem, family_path(stem))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _FAMILIES[stem] = mod
    mod = _FAMILIES[stem]
    for key, prefix in getattr(mod, "REFERENCE_OF", {}).items():
        stated = fleet.config["engine"].get(key)
        if not isinstance(stated, str) or not stated.startswith(prefix):
            raise BenchError(
                f"benchmark/families/{stem}.py is the reference of engine."
                f"{key} {prefix!r}*, and the configuration states "
                f"{stated!r}: name the file that judges family {name!r} "
                f"under \"references\"")
    return mod


def expected(fleet, job: int) -> list:
    """[(family, metric key, series slots)]: the results a cycle owes the
    job, one a metric or one for the metrics together, as each family's
    file says."""
    metrics = fleet.metrics_of(job)
    out = []
    for f in fleet.families_of(job):
        joins = getattr(family(fleet, f), "JOINS", None)
        if joins is None:
            out += [(f, m, (slot,)) for slot, m in enumerate(metrics)]
        else:
            out.append((f, joins.join(metrics), tuple(range(len(metrics)))))
    return out


def _exported(analyzer) -> dict:
    """{(app, metric): [lower, upper]} the program's exporter holds."""
    out: dict = {}
    for name, labels, value in analyzer.exporter.samples():
        for i, side in enumerate(("_lower", "_upper")):
            if name.startswith("foremastbrain:") and name.endswith(side):
                metric = name[len("foremastbrain:"):-len(side)]
                out.setdefault((labels.get("app"), metric),
                               [None, None])[i] = value
    return out


def program_answers(analyzer, store, fleet, last_cycle: dict) -> dict:
    """{job index: answer} for every job of the last cycle. An answer is
    None where the cycle left no verdict for the job, or not every
    result it owes it."""
    answers = {}
    exported = _exported(analyzer)
    for jid in last_cycle["outcomes"]:
        j = fleet.job_index(jid)
        rec = analyzer.provenance.get(jid)
        doc = store.get(jid)
        bounds = {m: exported.get((fleet.app_name(j), m))
                  for m in fleet.metrics_of(j)}
        entries = (rec or {}).get("families") or []
        fams = {(f["family"], f["metric"]): dict(f, exported=bounds)
                for f in entries}
        if (rec is None or doc is None or len(fams) != len(entries)
                or sorted(fams) != sorted(
                    (f, key) for f, key, _ in expected(fleet, j))
                or rec["cycle"].get("cycle_id") != last_cycle["cycle_id"]):
            answers[j] = None
            continue
        answers[j] = {
            "status": doc.status, "families": fams,
            "points": (rec.get("fetch") or {}).get("points"),
            "lag_s": (rec.get("detection_stages") or {}).get(
                "schedule_wait")}
    return {"jobs": answers, "now_slot": last_cycle["now_slot"],
            "lag_s": last_cycle["lag_s"]}


def _blocks(fleet, jobs) -> list:
    """Blocks of jobs of one class, so a block has one list of metrics
    and one of families."""
    by_class: dict = {}
    for j in sorted(jobs):
        by_class.setdefault(int(fleet.class_of[j]), []).append(j)
    return [js[i:i + _BLOCK] for js in by_class.values()
            for i in range(0, len(js), _BLOCK)]


def reference_answers(fleet, jobs: list, now_slot: int, lag_s: float,
                      precision: str) -> dict:
    """The reference put in the program's place: answers in the shape
    `program_answers` gives, computed at `precision`. With "bfloat16" it
    is the control, which `compare` has to find not correct."""
    limits = fleet.config["check"]
    out = {}
    for block in _blocks(fleet, jobs):
        refs = _reference(fleet, block, now_slot, limits, precision)
        for i, j in enumerate(block):
            fams = {e: family(fleet, e[0]).answer(ref, i)
                    for e, ref in refs.items()}
            bad = any(e["unhealthy"] for e in fams.values())
            out[j] = {"status": UNHEALTHY if bad else HEALTHY[0],
                      "families": fams, "lag_s": lag_s,
                      "points": fleet.points_fetched(j, now_slot)}
    return {"jobs": out, "now_slot": now_slot, "lag_s": lag_s}


def _reference(fleet, block: list, k_now: int, limits: dict,
               precision: str = "float64") -> dict:
    """{(family, metric key): reference rows} of one block of jobs."""
    return {(f, key): family(fleet, f).reference_rows(
        fleet, block, slots, k_now, limits, precision)
        for f, key, slots in expected(fleet, block[0])}


def compare(fleet, answers: dict) -> list:
    """[(name, value, limit)] for the cell; `correct` is every value
    within its limit."""
    got, k_now = answers["jobs"], answers["now_slot"]
    limits = fleet.config["check"]
    spec = {}  # number -> (how readings merge, limit), in listed order
    for cls in fleet.classes:
        for f in cls["families"]:
            for name, how, lim in family(fleet, f).NUMBERS:
                spec[name] = (how, float(limits[lim])
                              if isinstance(lim, str) else lim)
    value = {name: 0 for name in spec}
    miss = sum(1 for a in got.values() if a is None)
    stale = 0
    for block in _blocks(fleet, [j for j, a in got.items() if a is not None]):
        refs = _reference(fleet, block, k_now, limits)
        for i, j in enumerate(block):
            a = got[j]
            must_bad, must_good = False, True
            for e, ref in refs.items():
                readings, bad, good = family(fleet, e[0]).judge(
                    a["families"][e], ref, i, limits)
                must_bad, must_good = must_bad or bad, must_good and good
                for name, v in readings.items():
                    value[name] = max(value[name], v) \
                        if spec[name][0] == "max" else value[name] + v
            miss += _contradicts(a, must_bad, must_good)
            stale += int(a["points"] != fleet.points_fetched(j, k_now)
                         or a["lag_s"] is None
                         or abs(a["lag_s"] - answers["lag_s"]) > 1e-3)
    return [(name, value[name], lim) for name, (_, lim) in spec.items()] \
        + [("verdict_miss", miss, 0), ("stale_jobs", stale, 0)]


def _contradicts(a: dict, must_bad: bool, must_good: bool) -> int:
    bad = a["status"] == UNHEALTHY
    recorded = any(e["unhealthy"] for e in a["families"].values())
    if bad != recorded or (not bad and a["status"] not in HEALTHY):
        return 1
    return int((must_bad and not bad) or (must_good and bad))
