#!/usr/bin/env python3
"""``draft_readings.py`` for a LATENT bundle that drafts (parameters
``lat*``, the reference ``benchmark/reference/xing4_ref.py``: streams,
its own ``hidden`` / ``draft_logits``): the draft head held to the plain
reference at the published widths over seeded weights and the cell's own
``reference_prompts``, and what the seeded construction predicts for the
acceptance rate.  Writes ``chiprun_out/draft/<cell>.json``.

    python3 benchmark/tools/latent_draft_readings.py --workload <cell> --seeds 3

A seed's row holds ``program`` (``draft_readings.program_readings``: the
loaded bundle's draft logits after a prefill and ONE cached turn against
the reference's teacher-forced ones, and whether the seeded draft is the
reference's pick) and ``reference`` (on ``--rows`` random tokens,
teacher-forced: the cosine of the last SUMMED residual with its token's
embedding, which the head's leaning columns were divided by at draw
time; the main model's and the module's logit of the seeded successor and
of the token after it and the share of rows at which each puts either
first; the share of rows at which the module's pick IS the main model's
pick one row later: what a stream's acceptance rate comes to on such
rows; the assignments a HELD expert takes per 64 rows in each sparse
block: 4 where the routers have no favourites)."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import draft_readings  # noqa: E402
import run as harness  # noqa: E402
from lib import manifest as _manifest, models, serving_rig as rig  # noqa: E402


def reference_readings(adapter, cfg, weights, prompt, seed31):
    import jax
    import jax.numpy as jnp
    import numpy as np
    ref = adapter.ref
    ids = jnp.asarray(prompt, jnp.int32)
    every = jnp.arange(len(prompt))
    succ = jnp.asarray(adapter.successor(cfg, seed31))

    @jax.jit
    def read(p):
        routes = []
        x = ref.hidden(p, cfg, ids, routes=routes)
        own = p["lat_emb"][ids].astype(jnp.float32).at[:, 0].set(0.0)
        cos = jnp.sum(x * own, -1) / (
            jnp.linalg.norm(x, axis=-1) * jnp.linalg.norm(own, axis=-1))
        main = ref.forward_logits(p, cfg, ids, every)
        draft = ref.draft_logits(p, cfg, ids, every[:-1])
        first = cfg.get("expert_offset", 0)
        held = cfg.get("experts_held") or cfg["n_routed_experts"]
        loads = [64.0 * idx.shape[-1] * jnp.mean(
            (idx >= first) & (idx < first + held)) / held for idx in routes]

        def of(logits, tokens):
            want, after = succ[tokens], succ[succ[tokens]]
            rows = jnp.arange(logits.shape[0])
            spike, second = logits[rows, want], logits[rows, after]
            rest = jnp.max(logits.at[rows, want].set(-1e30)
                           .at[rows, after].set(-1e30), -1)
            return {"successor_logit_mean": jnp.mean(spike),
                    "after_successor_logit_mean": jnp.mean(second),
                    "difference_std": jnp.std(spike - second),
                    "others_largest_mean": jnp.mean(rest),
                    "successor_first_share": jnp.mean(
                        jnp.argmax(logits, -1) == want),
                    "after_successor_first_share": jnp.mean(
                        jnp.argmax(logits, -1) == after)}

        return {"cos_last_residual_own_embedding": jnp.mean(cos),
                "held_assignments_per_64_rows_by_sparse_layer": loads,
                "main": of(main, ids), "mtp": of(draft, ids[1:]),
                "mtp_pick_is_mains_next_pick_share": jnp.mean(
                    jnp.argmax(draft, -1) == jnp.argmax(main[1:], -1)),
                "logits_range_mean": jnp.mean(main.max(-1) - main.min(-1))}

    return jax.tree_util.tree_map(lambda v: float(np.asarray(v)),
                                  read(weights))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147487001)
    ap.add_argument("--program", type=int, default=1)
    ap.add_argument("--rows", type=int, default=2048)
    args = ap.parse_args(argv)
    manifest, read = _manifest.load(ROOT)
    _, config_entry, workload_file = _manifest.cell_files(manifest,
                                                          args.workload)
    cfg, wl = read(config_entry["file"]), read(workload_file)
    adapter = models.adapter_of(cfg)
    harness.enable_cache()
    predictor = None
    if args.program:
        from paddle_tpu.gen import GenPredictor
        ctx = {"config": cfg, "cache_root": harness.CACHE_ROOT}
        predictor = GenPredictor(rig.ensure_bundle(ctx, adapter)[0])
    rows = []
    for s in range(args.seeds):
        seed = args.first_seed + 37 * s
        seed31 = harness.mixed_seed(seed)
        weights = adapter.seeded_weights(cfg, seed31)
        row = {"seed": seed}
        if predictor is not None:
            rig.install_weights(predictor, weights)
            row["program"] = draft_readings.program_readings(
                adapter, cfg, wl, weights, seed31, predictor)
        row["reference"] = reference_readings(
            adapter, cfg, weights,
            rig._prompt(cfg, seed31, 3 * 10 ** 6, args.rows), seed31)
        rows.append(row)
        print(json.dumps({"draft_reading": row}), flush=True)
        del weights
    os.makedirs(os.path.join(ROOT, "chiprun_out", "draft"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "draft",
                           args.workload + ".json"), "w") as f:
        json.dump({"cell": args.workload, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
