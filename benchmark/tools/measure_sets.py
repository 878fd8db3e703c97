#!/usr/bin/env python3
"""Run the sets of runs a bound is set from, as the contract describes:
for one cell, ``--sets`` sets of ``--runs`` runs, the same seeds in every
set, each run a new process; then one traced run.  Prints each run's
result line, and per metric each set's median and spread (distance between
the quartiles as a share of the median).  Writes the lot to
``chiprun_out/sets/<cell>.json``.  This process never touches JAX.

    python3 benchmark/tools/measure_sets.py --workload <cell> [--seconds S]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))

from lib import stats  # noqa: E402

SEEDS = [2147483659, 2147483693, 2147483713, 2147483743, 2147483777,
         2147483783, 2147483813, 2147483851]


def one_run(cell, seed, seconds, trace):
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", cell, "--seed", str(seed), "--seconds",
           str(seconds), "--trace", str(trace)]
    t0 = time.time()
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.time() - t0
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    result = None
    if proc.returncode == 0 and lines:
        result = json.loads(lines[-1])
    return {"seed": seed, "rc": proc.returncode, "wall_s": wall,
            "result": result, "notes": lines[:-1][-8:],
            "stderr_tail": proc.stderr[-1500:] if proc.returncode else ""}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-runs", type=int, default=1)
    ap.add_argument("--cold-first", type=int, default=1,
                    help="one unrecorded run first, so that no set holds "
                         "the run that compiles")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    out = {"cell": args.workload, "seconds": seconds, "sets": [],
           "traced": [], "cold": None}
    if args.cold_first:
        out["cold"] = one_run(args.workload, SEEDS[-1], seconds, 0)
        print(json.dumps({"cold": out["cold"]}), flush=True)
        if out["cold"]["rc"] != 0 or not out["cold"]["result"]["correct"]:
            return 1
    for s in range(args.sets):
        runs = []
        for seed in SEEDS[:args.runs]:
            r = one_run(args.workload, seed, seconds, 0)
            print(json.dumps({"set": s, "seed": seed, "rc": r["rc"],
                              "wall_s": r["wall_s"], "result": r["result"],
                              "stderr": r["stderr_tail"][-400:]}),
                  flush=True)
            runs.append(r)
        out["sets"].append(runs)
    for k in range(args.trace_runs):
        r = one_run(args.workload, SEEDS[k], seconds, 1)
        print(json.dumps({"traced": r}), flush=True)
        out["traced"].append(r)
    summary = {}
    names = sorted({n for runs in out["sets"] for r in runs
                    if r["result"] for n in r["result"]["metrics"]})
    for name in names:
        per_set = []
        for runs in out["sets"]:
            vals = [r["result"]["metrics"][name]["value"] for r in runs
                    if r["result"] and name in r["result"]["metrics"]]
            per_set.append({"n": len(vals), "median": stats.median(vals),
                            "spread": stats.iqr_share(vals), "values": vals})
        widest = max(p["spread"] for p in per_set)
        summary[name] = {"sets": per_set, "widest_spread": widest,
                         "five_times": 5 * widest}
    out["summary"] = summary
    out["all_correct"] = all(r["result"] and r["result"]["correct"]
                             and r["result"]["failed"] == 0
                             for runs in out["sets"] for r in runs)
    os.makedirs(os.path.join(ROOT, "chiprun_out", "sets"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "sets",
                           args.workload + ".json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"summary": {k: {"medians": [p["median"]
                                                   for p in v["sets"]],
                                      "spreads": [p["spread"]
                                                  for p in v["sets"]]}
                                  for k, v in summary.items()},
                      "all_correct": out["all_correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
