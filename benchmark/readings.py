"""The readings that the limits in a configuration's `check` block are
set from (PERF.md records them). Nothing here is timed.

    python3 benchmark/readings.py --workload <cell> [--seeds 1,2,3] [--control-seeds 4,5,6]

`--seeds`: for each, build the cell, run the first cycle (every window
fetched whole) and a second (every current window spliced, as in every
cycle of a measured window), and compare the second's answers with the
reference: the lower reading of each number (every benchmark run prints the same numbers under
`compared`, so its seeds count too). `--control-seeds`: for each, put
the reference computed from bfloat16 samples in the program's place, at
the cell's own size: the upper reading. One JSON line per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run as harness
from lib import check, fleet as fleet_mod


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def program_reading(cell: dict, seed: int, tiny: bool) -> dict:
    engine = harness.Engine(cell, seed, tiny)
    try:
        engine.cycle()
        last = engine.cycle()
        fl = engine.fleet
        answers = check.program_answers(
            engine.analyzer, engine.store, fl, last)
    finally:
        engine.close()
    del engine
    gc.collect()
    return {k: v for k, v, _ in check.compare(fl, answers)}


def control_reading(cell: dict, seed: int, tiny: bool) -> dict:
    fl = fleet_mod.Fleet(cell["config"], seed, tiny)
    jobs = [j for j in range(fl.jobs) if j not in fl.anomalous]
    control = check.reference_answers(fl, jobs, fl.now_slot() + 3, 5.0,
                                      "bfloat16")
    return {k: v for k, v, _ in check.compare(fl, control)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if args.seeds:
        harness.find_devices(cell["chips"], args.tiny)
    for seed in _seeds(args.seeds):
        print(json.dumps({"seed": seed, "program": program_reading(
            cell, seed, args.tiny)}), flush=True)
    for seed in _seeds(args.control_seeds):
        print(json.dumps({"seed": seed, "control": control_reading(
            cell, seed, args.tiny)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
