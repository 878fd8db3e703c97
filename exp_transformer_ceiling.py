"""Transformer-base MFU ceiling study (r5): the flagship bench model
against a pure-JAX bound of the same step.

Two measurements, both scope-attributed DEVICE time (xplane named
scopes; wall clocks carry the host's dispatch/sync latency — see
profiler.measure_device_seconds):

  part A (``ours``):    per-IR-op decomposition of the framework's
                        Transformer-base training step (B=256, S=256,
                        bf16 AMP, Adam) via the executor's ptop_ scopes,
                        async-DMA excluded — replacing the discredited
                        r3 accounting.
  part B (``purejax``): a hand-written pure-JAX training step of the
                        SAME model (same shapes, post-LN, composed
                        attention, dropout 0.1, bf16 casts at matmul
                        inputs with f32 master params, f32 Adam) — the
                        toolchain bound: no Program IR, no executor, no
                        framework overhead.  What XLA gives this step is
                        the ceiling for ours.

Run:  python exp_transformer_ceiling.py ours|purejax|both

Reference workload: /root/reference/benchmark/fluid/machine_translation.py:1
(Transformer/NMT flagship); model config mirrors
test_parallel_executor.py:308 ModelHyperParams.
"""

import os
import sys
import tempfile
from functools import partial

os.environ["PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION"] = "python"

import numpy as np

BATCH = int(os.environ.get("CEIL_BATCH", "256"))
SEQ = int(os.environ.get("CEIL_SEQ", "256"))
STEPS = int(os.environ.get("CEIL_STEPS", "16"))


# --------------------------------------------------------------------------
# part A: the framework step, per-op attributed
# --------------------------------------------------------------------------

def run_ours():
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import profiler
    from paddle_tpu.models import transformer as T

    hp = T.ModelHyperParams()
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        avg_cost, _ = T.transformer(BATCH, SEQ, SEQ, hp)
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
    main_prog.amp = True

    batches = [T.fake_batch(BATCH, SEQ, SEQ, hp, seed=s)
               for s in range(STEPS)]
    stacked = {k: jax.device_put(np.stack([b[k] for b in batches]))
               for k in batches[0]}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        for _ in range(2):  # compile + settle
            exe.run_steps(main_prog, feed=stacked,
                          fetch_list=[avg_cost.name], steps=STEPS)
        td = tempfile.mkdtemp(prefix="ptceil_")
        jax.profiler.start_trace(td)
        exe.run_steps(main_prog, feed=stacked,
                      fetch_list=[avg_cost.name], steps=STEPS)
        jax.profiler.stop_trace()

    # scope-attributed total: every event inside one of OUR ptop_ scopes
    total_s = profiler.scope_device_seconds(td, "ptop_")
    _, rows = profiler.compiled_op_table(td)
    import shutil
    shutil.rmtree(td, ignore_errors=True)
    print(f"OURS device: {total_s * 1e3 / STEPS:.2f} ms/step "
          f"(scope-attributed, async-excluded, {STEPS} steps)")
    for op, calls, sec in rows:
        if sec * 1e3 / STEPS >= 0.05:
            print(f"  {op:34s} {calls:6d} {sec * 1e3 / STEPS:9.3f} ms/step")
    return total_s / STEPS


# --------------------------------------------------------------------------
# part B: pure-JAX same-model training step (the toolchain bound)
# --------------------------------------------------------------------------

def run_purejax():
    import jax
    import jax.numpy as jnp
    from paddle_tpu import profiler
    from paddle_tpu.models.transformer import (ModelHyperParams,
                                               position_encoding_init)

    hp = ModelHyperParams()
    D, DFF, H, DK = hp.d_model, hp.d_inner_hid, hp.n_head, hp.d_key
    V, NL, DROP = hp.src_vocab_size, hp.n_layer, hp.dropout
    if os.environ.get("CEIL_DROP") is not None:
        DROP = float(os.environ["CEIL_DROP"])
    bf16 = jnp.bfloat16

    rng = np.random.RandomState(0)

    def w(*shape):
        return jnp.asarray(rng.normal(0, 0.02, shape), jnp.float32)

    def layer_params(cross):
        p = {"q": w(D, D), "k": w(D, D), "v": w(D, D), "o": w(D, D),
             "ln1_g": jnp.ones(D), "ln1_b": jnp.zeros(D),
             "f1": w(D, DFF), "f1b": jnp.zeros(DFF),
             "f2": w(DFF, D), "f2b": jnp.zeros(D),
             "ln2_g": jnp.ones(D), "ln2_b": jnp.zeros(D)}
        if cross:
            p.update({"cq": w(D, D), "ck": w(D, D), "cv": w(D, D),
                      "co": w(D, D),
                      "ln3_g": jnp.ones(D), "ln3_b": jnp.zeros(D)})
        return p

    params = {
        "src_emb": w(V, D), "trg_emb": w(V, D), "proj": w(D, V),
        "enc": [layer_params(False) for _ in range(NL)],
        "dec": [layer_params(True) for _ in range(NL)],
    }
    pos_tab = jnp.asarray(position_encoding_init(hp.max_length, D))
    causal = jnp.triu(jnp.full((1, 1, SEQ, SEQ), -1e9, jnp.float32), 1)

    def scoped(name):
        def deco(fn):
            def wrapped(*a, **kw):
                with jax.named_scope(name):
                    return fn(*a, **kw)
            return wrapped
        return deco

    @scoped("pjx_ln")
    def ln(x, g, b):
        x = x.astype(jnp.float32)
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b

    @scoped("pjx_drop")
    def drop(x, key, i):
        if not DROP:
            return x
        keep = jax.random.bernoulli(jax.random.fold_in(key, i),
                                    1.0 - DROP, x.shape)
        return jnp.where(keep, x, jnp.zeros((), x.dtype))

    def mm(x, wmat):  # AMP discipline: bf16 at every matmul input
        return x.astype(bf16) @ wmat.astype(bf16)

    @scoped("pjx_attn")
    def attention(x, kv, p, bias, pre):
        B, S = x.shape[0], x.shape[1]
        q = mm(x, p[pre + "q"]).reshape(B, S, H, DK).transpose(0, 2, 1, 3)
        k = mm(kv, p[pre + "k"]).reshape(B, -1, H, DK).transpose(0, 2, 1, 3)
        v = mm(kv, p[pre + "v"]).reshape(B, -1, H, DK).transpose(0, 2, 1, 3)
        # bf16 scores end-to-end: the f32 [B,H,S,S] temporaries otherwise
        # push the step past HBM (the framework's f32-score path relies on
        # XLA remat; the bound should be the lean formulation)
        with jax.named_scope("pjx_sdpa"):
            s = (q @ k.transpose(0, 1, 3, 2)) * (DK ** -0.5) \
                + bias.astype(bf16)
            wts = jax.nn.softmax(s, axis=-1)
            ctx = (wts @ v).transpose(0, 2, 1, 3).reshape(B, S, D)
        return mm(ctx, p[pre + "o"])

    @scoped("pjx_ffn")
    def ffn(x, p):
        h = jax.nn.relu(mm(x, p["f1"]) + p["f1b"])
        return mm(h, p["f2"]) + p["f2b"]

    def loss_fn(ps, batch, key):
        src, trg = batch["src_word"], batch["trg_word"]
        lbl, lw = batch["lbl_word"], batch["lbl_weight"]
        pad_bias = ((batch["src_mask"] * 1e9) - 1e9) \
            .reshape(BATCH, 1, 1, SEQ)
        ki = iter(range(100))

        def embed(ids, tab):
            e = tab[ids] * (D ** 0.5) + pos_tab[:SEQ][None]
            return drop(e, key, next(ki))

        def enc_layer(x, p, k0):
            a = attention(x, x, p, pad_bias, "")
            x = ln(x + drop(a, key, k0), p["ln1_g"], p["ln1_b"])
            return ln(x + drop(ffn(x, p), key, k0 + 1),
                      p["ln2_g"], p["ln2_b"])

        def dec_layer(y, enc_out, p, k0):
            a = attention(y, y, p, causal, "")
            y = ln(y + drop(a, key, k0), p["ln1_g"], p["ln1_b"])
            c = attention(y, enc_out, p, pad_bias, "c")
            y = ln(y + drop(c, key, k0 + 1), p["ln3_g"], p["ln3_b"])
            return ln(y + drop(ffn(y, p), key, k0 + 2),
                      p["ln2_g"], p["ln2_b"])

        if os.environ.get("CEIL_REMAT"):
            # per-layer remat, matmul outputs saved — the standard
            # pure-JAX memory/FLOPs trade (jax.checkpoint docs)
            pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            enc_layer = jax.checkpoint(enc_layer, policy=pol,
                                       static_argnums=(2,))
            dec_layer = jax.checkpoint(dec_layer, policy=pol,
                                       static_argnums=(3,))

        x = embed(src, ps["src_emb"])
        for li, p in enumerate(ps["enc"]):
            x = enc_layer(x, p, 2 + 2 * li)
        enc_out = x
        y = embed(trg, ps["trg_emb"])
        for li, p in enumerate(ps["dec"]):
            y = dec_layer(y, enc_out, p, 20 + 3 * li)
        with jax.named_scope("pjx_ce"):
            logits16 = mm(y, ps["proj"])  # bf16 residual (1.3G, not 2.6G)
            logits = logits16.astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            nll = lse - jnp.take_along_axis(logits, lbl[..., None],
                                            -1).squeeze(-1)
            return (nll * lw).sum() / lw.sum()

    # f32 Adam on the f32 master params
    def adam_update(g, p, m, v, t):
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mh = m / (1 - 0.9 ** t)
        vh = v / (1 - 0.999 ** t)
        return p - 1e-4 * mh / (jnp.sqrt(vh) + 1e-8), m, v

    opt = {"m": jax.tree.map(jnp.zeros_like, params),
           "v": jax.tree.map(jnp.zeros_like, params),
           "t": jnp.zeros((), jnp.int32)}

    batches = {
        "src_word": rng.randint(1, V, (STEPS, BATCH, SEQ)).astype("int32"),
        "trg_word": rng.randint(1, V, (STEPS, BATCH, SEQ)).astype("int32"),
        "lbl_word": rng.randint(1, V, (STEPS, BATCH, SEQ)).astype("int32"),
        "src_mask": np.ones((STEPS, BATCH, SEQ), "float32"),
        "lbl_weight": np.ones((STEPS, BATCH, SEQ), "float32"),
    }
    batches = {k: jax.device_put(v) for k, v in batches.items()}

    def body(carry, batch):
        ps, op = carry
        with jax.named_scope("pjxstep"):
            t = op["t"] + 1
            key = jax.random.fold_in(jax.random.PRNGKey(0), t)
            loss, grads = jax.value_and_grad(loss_fn)(ps, batch, key)
            with jax.named_scope("pjx_adam"):
                flat_g, treedef = jax.tree.flatten(grads)
                flat = [adam_update(g.astype(jnp.float32), p, m, v, t)
                        for g, p, m, v in zip(
                            flat_g, treedef.flatten_up_to(ps),
                            treedef.flatten_up_to(op["m"]),
                            treedef.flatten_up_to(op["v"]))]
                ps = jax.tree.unflatten(treedef, [f[0] for f in flat])
                new_m = jax.tree.unflatten(treedef, [f[1] for f in flat])
                new_v = jax.tree.unflatten(treedef, [f[2] for f in flat])
        return (ps, {"m": new_m, "v": new_v, "t": t}), loss

    # donate the master params + Adam state, as the executor's run_steps
    # does — without donation both generations live and the step OOMs
    @partial(jax.jit, donate_argnums=(0, 1))
    def run(ps, op, bs):
        (ps, op), losses = jax.lax.scan(body, (ps, op), bs)
        return ps, op, losses

    state = (params, opt)
    state = run(*state, batches)[:2]  # compile + settle
    state = run(*state, batches)[:2]

    holder = [state]

    def once():
        ps, op, losses = run(*holder[0], batches)
        jax.block_until_ready(losses)
        holder[0] = (ps, op)
        return losses

    import collections
    import shutil
    td = tempfile.mkdtemp(prefix="pjxceil_")
    jax.profiler.start_trace(td)
    once()
    jax.profiler.stop_trace()
    total_ps = 0
    by_label = collections.Counter()
    for cands, dur in profiler.iter_trace_events(td, device_only=True,
                                                 exclude_async=True):
        hit = next((c for c in cands if "pjxstep" in c), None)
        if hit is None:
            continue
        total_ps += dur
        label = "other"
        for part in str(hit).split("/"):
            if part.startswith("pjx_"):
                label = part          # deepest pjx_ component wins
        by_label[label] += dur
    shutil.rmtree(td, ignore_errors=True)
    dev_s = total_ps / 1e12
    per_step = dev_s / STEPS
    for label, ps in by_label.most_common():
        print(f"  {label:12s} {ps / 1e12 * 1e3 / STEPS:8.3f} ms/step")
    from paddle_tpu.models.transformer import matmul_param_count
    import bench
    flops_per_token = 6 * matmul_param_count(hp) + 12 * SEQ * D * (3 * NL)
    toks = BATCH * SEQ / per_step
    mfu = toks * flops_per_token / bench.peak_flops_per_chip()
    print(f"PUREJAX device: {per_step * 1e3:.2f} ms/step "
          f"-> {toks:,.0f} tok/s, MFU {mfu:.3f}")
    return per_step


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "both"
    ours = run_ours() if which in ("ours", "both") else None
    pjx = run_purejax() if which in ("purejax", "both") else None
    if ours and pjx:
        print(f"RATIO ours/purejax = {ours / pjx:.3f}")

