"""Plain float32 reference of the encoder-decoder Transformer (Vaswani et
al. 2017, post-layer-norm), forward loss only, in straightforward
``jax.numpy``: no kernels, no mixed precision, matmul precision
"highest".  Independent of the program: it takes the parameter VALUES by
name and nothing else.

Departures from the paper, all the program's own and listed in
``benchmark/configs/transformer_base.json``: separate source and target
embeddings with an untied output projection; Q/K/V/output projections
without biases; at test time every residual/embedding dropout site
multiplies by ``1 - dropout`` (the reference framework's
"downgrade in inference" convention) instead of training with inverted
dropout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-5


def _ln(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * scale + bias


def _attention(p, prefix, q_in, kv_in, bias, n_head):
    b, sq, d = q_in.shape
    sk = kv_in.shape[1]
    dh = p[f"{prefix}_q.w"].shape[1] // n_head

    def heads(x, s):
        return x.reshape(b, s, n_head, dh).transpose(0, 2, 1, 3)

    q = heads(q_in @ p[f"{prefix}_q.w"], sq)
    k = heads(kv_in @ p[f"{prefix}_k.w"], sk)
    v = heads(kv_in @ p[f"{prefix}_v.w"], sk)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * dh ** -0.5
    if bias is not None:
        scores = scores + bias
    ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, sq, n_head * dh)
    return ctx @ p[f"{prefix}_attnout.w"]


def _ffn(p, prefix, x):
    h = jax.nn.relu(x @ p[f"{prefix}_ffn1.w"] + p[f"{prefix}_ffn1.b"])
    return h @ p[f"{prefix}_ffn2.w"] + p[f"{prefix}_ffn2.b"]


def forward_loss(params, layer_norms, cfg, batch):
    """Per-sequence mean token loss ``[B]`` and the batch's weighted mean.

    ``params``: name -> array.  ``layer_norms``: the ``(scale, bias)``
    parameter names of the layer norms in the order they are applied
    (two per encoder layer, then three per decoder layer).  ``cfg``: the
    configuration file's fields.  ``batch``: ``src_word``, ``trg_word``,
    ``lbl_word`` int ``[B, S]``; ``src_mask``, ``lbl_weight`` float."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        keep = 1.0 - float(cfg["dropout"])
        n_head, d = cfg["n_head"], cfg["d_model"]
        lns = iter(layer_norms)

        def post(prev, out):
            scale, bias = next(lns)
            return _ln(out * keep + prev, p[scale], p[bias])

        def embed(ids, side):
            s = ids.shape[1]
            x = p[f"{side}_word_emb"][ids] * d ** 0.5
            return (x + p[f"{side}_pos_emb"][:s][None]) * keep

        src_mask = jnp.asarray(batch["src_mask"], jnp.float32)
        pad_bias = ((src_mask - 1.0) * 1e9)[:, None, None, :]
        st = batch["trg_word"].shape[1]
        causal = jnp.triu(jnp.full((st, st), -1e9, jnp.float32), 1)[None, None]

        x = embed(jnp.asarray(batch["src_word"]), "src")
        for i in range(cfg["n_layer"]):
            x = post(x, _attention(p, f"enc{i}_attn", x, x, pad_bias, n_head))
            x = post(x, _ffn(p, f"enc{i}", x))
        enc = x
        y = embed(jnp.asarray(batch["trg_word"]), "trg")
        for i in range(cfg["n_layer"]):
            y = post(y, _attention(p, f"dec{i}_self", y, y, causal, n_head))
            y = post(y, _attention(p, f"dec{i}_cross", y, enc, pad_bias,
                                   n_head))
            y = post(y, _ffn(p, f"dec{i}", y))
        logits = y @ p["proj_logits.w"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        labels = jnp.asarray(batch["lbl_word"])
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        w = jnp.asarray(batch["lbl_weight"], jnp.float32)
        per_seq = jnp.sum(nll * w, axis=1) / jnp.sum(w, axis=1)
        return per_seq, jnp.sum(nll * w) / jnp.sum(w)
