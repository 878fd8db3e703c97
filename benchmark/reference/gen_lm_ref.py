"""Plain float32 reference of the decoder-only LM that
``paddle_tpu.models.gen_lm`` builds, as one forward pass over a whole
sequence: no cache, no pages, no buckets, matmul precision "highest".
It takes parameter VALUES by name and the configuration's numbers.

The block (post-layer-norm, the program's; the departures from OPT are
listed in ``benchmark/configs/genlm_opt6.7b.json``):

    x   = E[ids] * sqrt(d) + P[0:T]
    a   = causal multi-head attention(x Wq, x Wk, x Wv) Wo
    x   = LN1(x + a)
    x   = LN2(x + relu(x W1 + b1) W2 + b2)
    out = x Wlogits
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

EPS = 1e-5


def position_table(n_position, d_model):
    """Sinusoid table (Vaswani et al. 2017, section 3.5), float64 then
    rounded, as the program initialises its frozen ``genlm_pos_emb``."""
    position = np.arange(n_position)[:, None].astype("float64")
    div = np.exp(np.arange(0, d_model, 2).astype("float64")
                 * -(np.log(10000.0) / d_model))
    table = np.zeros((n_position, d_model))
    table[:, 0::2] = np.sin(position * div)
    table[:, 1::2] = np.cos(position * div[: d_model // 2])
    return table.astype("float32")


def _ln(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * scale + bias


def forward_logits(params, cfg, ids, positions):
    """Logits ``[len(positions), V]`` at ``positions`` of the sequence
    ``ids`` (1-D int array), every position attending to itself and
    everything before it."""
    with jax.default_matmul_precision("highest"):
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        dh = d // h
        t = ids.shape[0]
        p = params
        x = p["genlm_word_emb"][ids] * d ** 0.5 \
            + jnp.asarray(position_table(t, d))
        causal = jnp.triu(jnp.full((t, t), -1e9, jnp.float32), 1)
        for i in range(cfg["num_hidden_layers"]):
            def heads(w):
                return (x @ p[f"genlm{i}_{w}.w"]).reshape(t, h, dh) \
                    .transpose(1, 0, 2)
            q, k, v = heads("q"), heads("k"), heads("v")
            scores = jnp.einsum("hqd,hkd->hqk", q, k) * dh ** -0.5 + causal
            ctx = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, -1), v)
            attn = ctx.transpose(1, 0, 2).reshape(t, d) \
                @ p[f"genlm{i}_attnout.w"]
            x = _ln(x + attn, p[f"genlm{i}_ln1.scale"],
                    p[f"genlm{i}_ln1.bias"])
            ffn = jax.nn.relu(x @ p[f"genlm{i}_ffn1.w"]
                              + p[f"genlm{i}_ffn1.b"]) \
                @ p[f"genlm{i}_ffn2.w"] + p[f"genlm{i}_ffn2.b"]
            x = _ln(x + ffn, p[f"genlm{i}_ln2.scale"],
                    p[f"genlm{i}_ln2.bias"])
        return x[jnp.asarray(positions)] @ p["genlm_logits.w"]
