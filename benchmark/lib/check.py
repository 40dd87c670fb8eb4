"""What decides `correct`: the last cycle's verdicts, as the timed path
folded them, against the plain reference.

The program's side is read once the window has closed: for every job of
the last cycle, the job's status in the store and what that cycle
recorded for it (the record `/jobs/<id>/explain` serves): each scoring
family's result, the samples its windows held, and how far behind the
cycle's clock its newest judged sample lay. The reference's side is
computed from the fleet's own series with the engine freed. A family's
reference and comparison are `benchmark/families/<family>.py`, found by
the name the configuration's class lists; each number compared has a
limit (PERF.md gives the readings they were set from).

Numbers of every cell, beside the families' own:
  verdict_miss  jobs with no verdict from the last cycle, or whose stored
                verdict the reference contradicts
  stale_jobs    jobs whose windows did not hold every sample up to the
                cycle's clock, or whose newest judged sample is not the
                newest scrape
"""
from __future__ import annotations

import importlib.util
import os

UNHEALTHY = "completed_unhealth"
HEALTHY = ("initial", "completed_health")
_BLOCK = 256  # reference rows at a time: blocks that stay in cache
_FAMILIES: dict = {}


def family(name: str):
    """`benchmark/families/<name>.py`, loaded once."""
    if name not in _FAMILIES:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "families", name + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_family_" + name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _FAMILIES[name] = mod
    return _FAMILIES[name]


def program_answers(analyzer, store, fleet, last_cycle: dict) -> dict:
    """{job index: answer} for every job of the last cycle. An answer is
    None where the cycle left no verdict for the job."""
    answers = {}
    for jid in last_cycle["outcomes"]:
        j = fleet.job_index(jid)
        rec = analyzer.provenance.get(jid)
        doc = store.get(jid)
        fams = {f["family"]: f for f in (rec or {}).get("families") or []}
        if (rec is None or doc is None
                or sorted(fams) != sorted(fleet.families_of(j))
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
    """Blocks of jobs of one class, so a block has one metric and one
    list of families."""
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
        refs = {f: family(f).reference_rows(fleet, block, now_slot, limits,
                                            precision)
                for f in fleet.families_of(block[0])}
        for i, j in enumerate(block):
            fams = {f: family(f).answer(ref, i) for f, ref in refs.items()}
            bad = any(e["unhealthy"] for e in fams.values())
            out[j] = {"status": UNHEALTHY if bad else HEALTHY[0],
                      "families": fams, "lag_s": lag_s,
                      "points": fleet.points_fetched(j, now_slot)}
    return {"jobs": out, "now_slot": now_slot, "lag_s": lag_s}


def compare(fleet, answers: dict) -> list:
    """[(name, value, limit)] for the cell; `correct` is every value
    within its limit."""
    got, k_now = answers["jobs"], answers["now_slot"]
    limits = fleet.config["check"]
    spec = {}  # number -> (how readings merge, limit), in listed order
    for cls in fleet.classes:
        for f in cls["families"]:
            for name, how, lim in family(f).NUMBERS:
                spec[name] = (how, float(limits[lim])
                              if isinstance(lim, str) else lim)
    value = {name: 0 for name in spec}
    miss = sum(1 for a in got.values() if a is None)
    stale = 0
    for block in _blocks(fleet, [j for j, a in got.items() if a is not None]):
        refs = {f: family(f).reference_rows(fleet, block, k_now, limits)
                for f in fleet.families_of(block[0])}
        for i, j in enumerate(block):
            a = got[j]
            must_bad, must_good = False, True
            for f, ref in refs.items():
                readings, bad, good = family(f).judge(
                    a["families"][f], ref, i, limits)
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
