#!/usr/bin/env python3
"""How often bfloat16 activations flip an expert: for seeded weights and
prompts of a configuration whose adapter's reference can hand out its
routes (``forward_logits(..., routes=[])``), the share of (token, layer)
pairs whose chosen expert SET differs between the float32 "highest"
reference and the reference run in bfloat16 (the precision the
configuration states), and the share of single assignments that differ.
The serving rig's ``reference`` note line is fixed, so this reading has a
tool of its own.  Writes ``chiprun_out/route_flips/<config>.json``.

    python3 benchmark/tools/route_flips.py --config nemotron3_super_ep8 --seeds 3
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
    ap.add_argument("--first-seed", type=int, default=2147486011)
    ap.add_argument("--prompts", default="40,200,700")
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
    ref = adapter.hybrid_moe_ref.forward_logits

    def routes_of(dtype):
        def fn(weights, ids):
            routes = []
            ref(weights, cfg, ids, jnp.asarray([ids.shape[0] - 1]),
                dtype=dtype, routes=routes)
            return jnp.stack(routes)            # [layers, T, k]
        return jax.jit(fn)

    exact, stated = routes_of(jnp.float32), routes_of(jnp.bfloat16)
    rows = []
    for k in range(args.seeds):
        seed31 = harness.mixed_seed(args.first_seed + 37 * k)
        weights = adapter.seeded_weights(cfg, seed31)
        for j, n in enumerate(int(x) for x in args.prompts.split(",")):
            rng = np.random.RandomState((seed31 * 1000003 + j) % 2 ** 32)
            ids = jnp.asarray(rng.randint(1, cfg["vocab_size"], size=n),
                              jnp.int32)
            a = np.sort(np.asarray(exact(weights, ids)), -1)
            b = np.sort(np.asarray(stated(weights, ids)), -1)
            moved = [len(set(x) - set(y)) for x, y in
                     zip(a.reshape(-1, a.shape[-1]),
                         b.reshape(-1, b.shape[-1]))]
            rows.append({"seed31": seed31, "tokens": n,
                         "pairs": len(moved),
                         "sets_that_differ": sum(m > 0 for m in moved)
                         / len(moved),
                         "assignments_that_differ": sum(moved)
                         / (len(moved) * a.shape[-1]),
                         "by_layer": [float(np.mean([
                             len(set(x) - set(y)) > 0
                             for x, y in zip(a[l], b[l])]))
                             for l in range(a.shape[0])]})
            print(json.dumps({"route_flips": rows[-1]}), flush=True)
        del weights
    out = {"config": cfg["name"], "rows": rows,
           "sets_that_differ": float(np.mean(
               [r["sets_that_differ"] for r in rows])),
           "assignments_that_differ": float(np.mean(
               [r["assignments_that_differ"] for r in rows]))}
    print(json.dumps({k: v for k, v in out.items() if k != "rows"}),
          flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out", "route_flips"),
                exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "route_flips",
                           cfg["name"] + ".json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
