#!/usr/bin/env python3
"""The control's side of a serving cell's ``logits_tol``: what the set-up
comparison (``lib/serving_rig._reference_check``: the logits after a prompt
and after one more token, against the plain reference, as a share of the
logits' range) would read if the adapter's CONTROLS stood in the program's
place, over seeded weights and the cell's own ``reference_prompts``.  The
program's side of the limit is on every run's ``reference`` note line.
Writes ``chiprun_out/logits/<cell>.json``.

    python3 benchmark/tools/logits_readings.py --workload <cell> --seeds 12
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import run as harness  # noqa: E402
from lib import manifest as _manifest, models, serving_rig as rig  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2147487001)
    ap.add_argument("--controls", default="fp8,bf16")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    manifest, read = _manifest.load(ROOT)
    _, config_entry, workload_file = _manifest.cell_files(manifest,
                                                          args.workload)
    cfg, wl = read(config_entry["file"]), read(workload_file)
    adapter = models.adapter_of(cfg)
    harness.enable_cache()
    controls = args.controls.split(",")

    def two(ids):
        return jnp.asarray([ids.shape[0] - 2, ids.shape[0] - 1])

    ref = jax.jit(lambda p, ids: adapter.reference_logits(p, cfg, ids,
                                                          two(ids)))
    ctl = {k: jax.jit(lambda p, ids, k=k: adapter.control_logits(
        p, cfg, ids, two(ids), k).astype(jnp.float32)) for k in controls}
    rows = []
    for s in range(args.seeds):
        seed = args.first_seed + 37 * s
        seed31 = harness.mixed_seed(seed)
        weights = adapter.seeded_weights(cfg, seed31)
        row = {"seed": seed, **{k: 0.0 for k in controls}}
        for j, n in enumerate(wl["reference_prompts"]):
            prompt = rig._prompt(cfg, seed31, 2 * 10 ** 6 + j, n)
            first = np.asarray(ref(weights, jnp.asarray(prompt + [0],
                                                        jnp.int32)))[0]
            ids = jnp.asarray(prompt + [int(np.argmax(first))], jnp.int32)
            want = np.asarray(ref(weights, ids))
            spread = want.max(-1) - want.min(-1)
            for k in controls:
                got = np.asarray(ctl[k](weights, ids))
                row[k] = max(row[k], float(
                    (np.abs(got - want).max(-1) / spread).max()))
        rows.append(row)
        print(json.dumps({"logits_reading": row}), flush=True)
        del weights
    out = {"cell": args.workload, "rows": rows,
           **{f"control_{k}_smallest": min(r[k] for r in rows)
              for k in controls},
           **{f"control_{k}_largest": max(r[k] for r in rows)
              for k in controls}}
    print(json.dumps({k: v for k, v in out.items() if k != "rows"}),
          flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out", "logits"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "logits",
                           args.workload + ".json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
