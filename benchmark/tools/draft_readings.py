#!/usr/bin/env python3
"""The DRAFT HEAD of a cell whose model drafts for itself, held to the
plain reference at the published widths, over seeded weights and the
cell's own ``reference_prompts`` (the set-up check and ``lib/served.py``
judge the MAIN model's logits alone: under greedy verification a draft
never changes which token is served).  Writes
``chiprun_out/draft/<cell>.json``.

    python3 benchmark/tools/draft_readings.py --workload <cell> --seeds 3

A seed's row holds

* ``program`` (with ``--program 1``): the loaded bundle's draft logits
  after a prefill and ONE cached turn (the MTP module's row behind the
  committed token, through its own page pool) against the reference's
  teacher-forced ``draft_logits`` there, as a share of those logits'
  range, the largest over the prompts; and whether the draft the
  prefill's last chunk seeded is the reference's pick.
* ``reference``: what the seeded construction reads on the longest
  prompt (random tokens, teacher-forced): the residual's rms behind each
  layer and in the MTP block, the assignments a HELD expert takes per 64
  rows in each sparse block (4 where the routers have no favourites), the
  cosine of the last residual with its token's embedding, the main
  model's and the module's logit of the seeded successor and of the token
  after it, the largest of the other logits, the share of rows at which
  each puts either first, and the share of rows at which the module's
  pick IS the main model's pick one row later (what a stream's
  acceptance rate comes to on such rows).
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


def reference_readings(adapter, cfg, weights, prompt, seed31):
    import jax
    import jax.numpy as jnp
    import numpy as np
    ref = adapter.ref
    ids = jnp.asarray(prompt, jnp.int32)
    T = len(prompt)
    every = jnp.arange(T)
    succ = jnp.asarray(adapter.successor(cfg, seed31))

    @jax.jit
    def read(p):
        with jax.default_matmul_precision("highest"):
            rms = lambda x: jnp.sqrt(jnp.mean(jnp.square(
                x.astype(jnp.float32))))
            after = [rms(ref.hidden(p, {**cfg, "num_hidden_layers": k}, ids))
                     for k in range(1, cfg["num_hidden_layers"] + 1)]
            x = ref.hidden(p, cfg, ids)
            own = p["win_emb"][ids].astype(jnp.float32).at[:, 0].set(0.0)
            cos = jnp.sum(x * own, -1) / (
                jnp.linalg.norm(x, axis=-1) * jnp.linalg.norm(own, axis=-1))
            main = ref.forward_logits(p, cfg, ids, every)
            draft = ref.draft_logits(p, cfg, ids, every[:-1])
            # the MTP block's own residual, in and out
            value, eps = ref._values(p, jnp.float32, None), \
                cfg["rms_norm_eps"]

            def held_load(before, i):
                # assignments a HELD expert takes per 64 rows in block i
                # (``before``: the residual in front of it): 4 where every
                # expert is as likely as the next
                bp = lambda name, cast=True: value(f"win{i}_{name}", cast)
                seen = before + ref.attention(
                    ref._rms(before, bp("norm1.scale"), eps), bp, cfg, i,
                    jnp.float32)
                idx, _ = ref.route(ref._rms(seen, bp("norm2.scale"), eps),
                                   bp, cfg)
                first = cfg.get("expert_offset", 0)
                held = cfg.get("experts_held") or cfg["num_experts"]
                mine = (idx >= first) & (idx < first + held)
                return 64.0 * idx.shape[-1] * jnp.mean(mine) / held

            loads = [held_load(
                ref.hidden(p, {**cfg, "num_hidden_layers": i}, ids), i)
                for i in range(cfg["num_hidden_layers"])
                if ref.is_moe(cfg, i)]
            mtp_in = jnp.concatenate(
                [ref._rms(x[:-1], value("win_mtp_hnorm.scale"), eps),
                 ref._rms(ref._embed(p, ids[1:], jnp.float32, None),
                          value("win_mtp_enorm.scale"), eps)],
                axis=-1) @ value("win_mtp_proj.w")
            mtp_out = ref._block(mtp_in, value, cfg, ref.MTP, jnp.float32)

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
                    "others_largest_std": jnp.std(rest),
                    "successor_first_share": jnp.mean(
                        jnp.argmax(logits, -1) == want),
                    "after_successor_first_share": jnp.mean(
                        jnp.argmax(logits, -1) == after)}

        return {"residual_rms_behind_layer": after,
                "held_assignments_per_64_rows_by_sparse_block":
                    loads + [held_load(mtp_in, ref.MTP)],
                "mtp_residual_rms_in_out": [rms(mtp_in), rms(mtp_out)],
                "cos_last_residual_own_embedding": jnp.mean(cos),
                "main": of(main, ids), "mtp": of(draft, ids[1:]),
                "mtp_pick_is_mains_next_pick_share": jnp.mean(
                    jnp.argmax(draft, -1) == jnp.argmax(main[1:], -1)),
                "logits_range_mean": jnp.mean(main.max(-1) - main.min(-1))}

    return jax.tree_util.tree_map(lambda v: float(np.asarray(v)),
                                  read(weights))


def program_readings(adapter, cfg, wl, weights, seed31, predictor):
    """Prefill, then the decode program once with its draft logits
    fetched: the MTP module's cached row against the reference's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    block = predictor._dec_prog.global_block()
    spec = next(op for op in block.ops if op.type == "spec_draft")
    fetch = [predictor._dec_fetch[0], block.var(spec.input("Logits")[0])]
    ref_draft = jax.jit(lambda p, ids, at: adapter.draft_logits(
        p, cfg, ids, at[None]))
    worst, seeded_ok = 0.0, True
    S = predictor.num_slots
    for j, n in enumerate(wl["reference_prompts"]):
        prompt = rig._prompt(cfg, seed31, 2 * 10 ** 6 + j, n)
        logits, kv = predictor.prefill(prompt)
        tok = int(np.argmax(logits))
        predictor.alloc_slot_pages(0, predictor.pages_needed(n, 1))
        try:
            predictor.write_slot(0, kv, n)
            seeded = int(np.asarray(predictor._scope.find_var(
                predictor.speculative["draft_var"]))[0, 0])
            want = np.asarray(ref_draft(
                weights, jnp.asarray(prompt + [tok], jnp.int32),
                jnp.asarray(n - 1)))[0]
            seeded_ok &= seeded == int(np.argmax(want))
            feed = {"gen_token": np.zeros((S, 1), np.int32),
                    "gen_pos": np.zeros((S, 1), np.int32),
                    "gen_lens": np.zeros((S, 1), np.int32),
                    "gen_spec": np.zeros((S, 1), np.int32)}
            feed["gen_token"][0], feed["gen_pos"][0] = tok, n
            feed["gen_lens"][0] = n + 1
            pages = predictor._page_bucket(np.asarray([n + 2], np.int32))
            with predictor._lock, \
                    predictor._fluid.scope_guard(predictor._scope):
                feed["gen_page_table"] = predictor._page_table[:, :pages]
                first, draft = predictor._exe.run(
                    predictor._dec_prog, feed=feed, fetch_list=fetch)
        finally:
            predictor.free_slot_pages(0)
        nxt = int(np.argmax(np.asarray(first)[0]))
        want = np.asarray(ref_draft(
            weights, jnp.asarray(prompt + [tok, nxt], jnp.int32),
            jnp.asarray(n)))[0]
        worst = max(worst, float(np.abs(np.asarray(draft)[0] - want).max())
                    / float(want.max() - want.min()))
    return {"draft_err_of_range": worst, "seeded_draft_is_references": bool(
        seeded_ok)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147487001)
    ap.add_argument("--program", type=int, default=1)
    ap.add_argument("--rows", type=int, default=0,
                    help="rows of the reference's readings (0: the "
                         "longest reference prompt)")
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
            row["program"] = program_readings(adapter, cfg, wl, weights,
                                              seed31, predictor)
        n = args.rows or max(wl["reference_prompts"])
        row["reference"] = reference_readings(
            adapter, cfg, weights, rig._prompt(cfg, seed31, 3 * 10 ** 6, n),
            seed31)
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
