#!/usr/bin/env python3
"""The knee sweep of a ``serve_open_loop`` cell: ONE process sets the
server up once and offers each rate for one window.  Writes the table to
``chiprun_out/sweeps/<cell>.json`` (the builder copies it to
``benchmark/sweeps/`` beside the rate it chose).

    python3 benchmark/tools/sweep_rate.py --workload <cell> --rates 2,3,4,5,6 --seconds 30
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import run as harness  # noqa: E402
from lib import manifest as _manifest, openloop  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2147483659)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    manifest, read = _manifest.load(ROOT)
    entry, config_entry, workload_file = _manifest.cell_files(
        manifest, args.workload)
    config, workload = read(config_entry["file"]), read(workload_file)
    devices, device = harness.require_chips(entry["chips"])
    traffic = harness.load_module(
        os.path.join(HERE, "..", "traffic", workload["kind"] + ".py"),
        "traffic_" + workload["kind"])
    harness.enable_cache()
    schedules = [openloop.build_schedule(args.seconds,
                                         {**workload, "rate_per_s": r})
                 for r in rates]
    ctx = harness.make_context(args.workload, args.seed, args.seconds, config,
                               workload, devices[:entry["chips"]],
                               harness.DeviceTracer(False, None))
    ctx["schedules"] = schedules
    state = traffic.setup(ctx)
    rows = []
    try:
        for rate, schedule in zip(rates, schedules):
            raw = traffic.window(state, ctx, schedule=schedule)
            verdict = traffic.verify(state, ctx, raw)
            row = {"rate_per_s": rate, "seconds": args.seconds,
                   **raw["end_to_end"], **raw["observed"],
                   "failed": raw["failed"], "correct": verdict["correct"]}
            rows.append(row)
            print(json.dumps({"sweep": row}), flush=True)
            time.sleep(2.0)
    finally:
        traffic.close(state)
    out = {"cell": args.workload, "device": device, "seed": args.seed,
           "rows": rows}
    os.makedirs(os.path.join(ROOT, "chiprun_out", "sweeps"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "sweeps",
                           args.workload + ".json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
