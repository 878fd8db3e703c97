#!/usr/bin/env python3
"""How often bfloat16 flips a SELECTED row: for seeded weights and prompts
of a configuration with learned sparse attention whose adapter's reference
can hand out its selections (``forward_logits(..., selections=[])``), the
share of (query row, layer) selections that agree position for position
between the float32 "highest" reference and the reference run in bfloat16
(the precision the configuration states), over the query rows past
``index_topk`` (before it the selection is the identity).  Rows near the
``index_topk``-th score swap; what a swap costs the logits is the cell's
``logits_tol_why``.  Writes ``chiprun_out/select_flips/<config>.json``.

    python3 benchmark/tools/select_flips.py --config glm_5.2 --seeds 3
    (``--config`` may also be the path of a configuration file)
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


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147486911)
    ap.add_argument("--prompts", default="3000,6000")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import run as harness
    from lib import models
    path = args.config if args.config.endswith(".json") else os.path.join(
        HERE, "..", "configs", args.config + ".json")
    with open(path) as f:
        cfg = json.load(f)
    adapter = models.adapter_of(cfg)
    ref = adapter.glm_dsa_ref.forward_logits
    k = int(cfg["index_topk"])

    def agreeing(weights, ids):
        """[full layers, rows past top_k]: positions both select, of k."""
        found = []
        for dtype in (jnp.float32, jnp.bfloat16):
            got = []
            ref(weights, cfg, ids, jnp.asarray([ids.shape[0] - 1]),
                dtype=dtype, selections=got)
            found.append(jnp.stack(got)[:, k:])
        return jnp.sum(found[0] & found[1], axis=-1)

    agreeing = jax.jit(agreeing)
    rows = []
    for s in range(args.seeds):
        seed31 = harness.mixed_seed(args.first_seed + 37 * s)
        weights = adapter.seeded_weights(cfg, seed31)
        for j, n in enumerate(int(x) for x in args.prompts.split(",")):
            rng = np.random.RandomState((seed31 * 1000003 + j) % 2 ** 32)
            ids = jnp.asarray(rng.randint(1, cfg["vocab_size"], size=n),
                              jnp.int32)
            same = np.asarray(agreeing(weights, ids))
            rows.append({"seed31": seed31, "tokens": n,
                         "selections": int(same.size),
                         "positions_that_agree": float(same.mean() / k),
                         "least_agreeing_row": float(same.min() / k),
                         "rows_that_agree_whole": float((same == k).mean()),
                         "by_layer": [float(x.mean() / k) for x in same]})
            print(json.dumps({"select_flips": rows[-1]}), flush=True)
        del weights
    out = {"config": cfg["name"], "rows": rows,
           "positions_that_agree": float(np.mean(
               [r["positions_that_agree"] for r in rows])),
           "least_agreeing_row": min(r["least_agreeing_row"] for r in rows)}
    print(json.dumps({k_: v for k_, v in out.items() if k_ != "rows"}),
          flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out", "select_flips"),
                exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "select_flips",
                           cfg["name"] + ".json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
