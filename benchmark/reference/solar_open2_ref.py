"""Plain float32 reference of the linear-attention / softmax-attention
mixture-of-experts LM that ``paddle_tpu.models.hybrid_moe`` builds for
``solar_open2`` (Solar-Open2-250B), as one forward pass over a whole
sequence: no kernels, no cache, no pages, no chunks, no batching, the
recurrence one token at a time (``lax.scan``), the softmax layers' scores
a block of query rows at a time, matmul precision "highest".  It takes
parameter VALUES by the program's names (the seeded bfloat16 matrices,
cast up where they are used) and the configuration's numbers;
``paddle_tpu`` is not imported.

Published layer ``l = layer_offset + j`` is two pre-norm sublayers, ``x
<- x + Mix(RMSNorm(x))`` then ``x <- x + FFN(RMSNorm(x))`` (eps
``rms_norm_eps``), which the program names ``hyb{2j}`` and ``hyb{2j +
1}``; a final RMSNorm precedes the untied head.

Mix   ``l`` in ``gqa_layers``: softmax attention, ``q = a W_q`` (H heads
      of D), ``k, v = a W_k, a W_v`` (Hkv heads), NO position applied
      (``use_rope`` false), query head ``h`` reads K/V head ``h // (H /
      Hkv)``, causal softmax of ``q . k D^-1/2``, out ``= (o * sigmoid(a
      W_gate)) W_o`` (``use_gqa_gate``: element-wise, 4096 x 8192).
      Else KDA (Kimi Linear, arXiv:2510.26692), per head of
      ``linear_attn_config``: ``[q~ | k~ | v~] = a W_qkv``, each channel
      through a causal depthwise conv of ``short_conv_kernel_size`` taps
      (tap K-1 on the current row) and SiLU; ``q = l2norm(q~) D^-1/2``,
      ``k = l2norm(k~)``; ``g = -exp(A_log_h) softplus(a W_fa W_fb +
      dt_bias)`` a key channel (rank ``head_dim``: ``kda_use_full_proj``
      false); ``beta = 2 sigmoid(a W_b)`` (``kda_allow_neg_eigval``);
      ``S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T``, ``o_t =
      S_t^T q_t`` (S [D, D] float32, zeros at the start); out ``=
      (RMSNorm_head(o) * scale * sigmoid(a W_ga W_gb)) W_o``.
FFN   router in float32, ``r = sigmoid(u W_r)``, the
      ``num_experts_per_tok`` largest of ``r + b``, weights ``r_i /
      sum_chosen r`` (``norm_topk_prob``) times ``routed_scaling_factor``;
      gated experts ``W_d (silu(W_g u) * W_u u)`` of width
      ``moe_intermediate_size`` over the experts HELD (``expert_offset ..
      + experts_held - 1``; what the absent experts would add is left
      out, as in the program), plus ``n_shared_experts`` shared ones on
      every row.

Departures from the published model are listed in
``benchmark/configs/solar_open2_250b.json``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256


def _matrix(name):
    """Names of the parameters a lower-precision CONTROL stores narrow:
    the matrices (vectors, norms, the conv's taps and the router's bias
    stay)."""
    return (name.endswith(".w") and not name.endswith("conv.w")) \
        or name.endswith(("_wg", "_wu", "_wd")) or name == "hyb_emb"


def _stored_as(w, stored, by_row=False):
    """``w`` as it reads back from storage in the type ``stored``, one
    scale per output channel (per row of the embedding, which is read by
    row) so that the largest entry sits at the type's largest value."""
    if stored is None:
        return w
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=-1 if by_row else -2, keepdims=True) \
        / float(jnp.finfo(stored).max)
    scale = jnp.where(scale > 0, scale, 1.0)        # a channel of zeros
    return (w / scale).astype(stored).astype(jnp.float32) * scale


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def layer_kinds(cfg):
    """``"G"`` (softmax) or ``"K"`` (KDA) for each published layer held,
    in order."""
    first = int(cfg.get("layer_offset", 0))
    return ["G" if l in cfg["gqa_layers"] else "K"
            for l in range(first, first + cfg["num_hidden_layers"])]


def attention(h, p, cfg, dtype):
    """``h`` [T, d] -> [T, d]: gated NoPE grouped-query attention."""
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    T, G = h.shape[0], H // Hkv
    q = (h @ p("q.w")).reshape(T, Hkv, G, D)
    k = (h @ p("k.w")).reshape(T, Hkv, D)
    v = (h @ p("v.w")).reshape(T, Hkv, D)
    # query rows a block at a time; the last block is filled up with rows
    # of zeros, whose results are dropped (the keys are not padded)
    block = min(T, QUERY_BLOCK)
    n_blocks = -(-T // block)
    qg = jnp.pad(q, ((0, n_blocks * block - T), (0, 0), (0, 0), (0, 0)))

    def rows(j):
        row = j * block + jnp.arange(block)[:, None]
        seen = jnp.arange(T)[None, :] <= row
        qb = jax.lax.dynamic_slice_in_dim(qg, j * block, block, 0)
        sc = jnp.einsum("qkgd,tkd->kgqt", qb, k).astype(jnp.float32) \
            * D ** -0.5
        sc = jnp.where(seen[None, None], sc, -1e30)
        e = jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True))
        probs = e / jnp.sum(e, axis=-1, keepdims=True)
        return jnp.einsum("kgqt,tkd->qkgd", probs.astype(dtype), v)

    ctx = jax.lax.map(rows, jnp.arange(n_blocks)).reshape(-1, H * D)[:T]
    if cfg.get("use_gqa_gate"):
        ctx = ctx * jax.nn.sigmoid((h @ p("gate.w")).astype(jnp.float32)) \
            .astype(dtype)
    return ctx @ p("o.w")


def _conv(x, w):
    """``silu`` of the causal depthwise conv of ``x`` [T, C] with taps
    ``w`` [K, C], tap K-1 on the current row; float32."""
    K, T = w.shape[0], x.shape[0]
    xf = jnp.pad(x.astype(jnp.float32), ((K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xf[j:j + T] * w[j].astype(jnp.float32)
                           for j in range(K)))


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda(h, p, cfg, dtype, decay=True):
    """``h`` [T, d] -> [T, d]: the KDA mixer, its recurrence one token at
    a time in float32.  ``decay`` false (``alpha = 1``: the gate a
    program could ignore) is a CONTROL."""
    lin = cfg["linear_attn_config"]
    H, D, T = lin["num_heads"], lin["head_dim"], h.shape[0]
    qkv = _conv(h @ p("qkv.w"), p("conv.w", cast=False)).astype(dtype) \
        .astype(jnp.float32)
    q, k, v = (qkv[:, j * H * D:(j + 1) * H * D].reshape(T, H, D)
               for j in range(3))
    q, k = _unit(q) * D ** -0.5, _unit(k)
    f = ((h @ p("f_a.w")) @ p("f_b.w")).astype(jnp.float32)
    g = -jnp.exp(p("a_log", cast=False))[:, None] * jax.nn.softplus(
        f.reshape(T, H, D) + p("dt_bias", cast=False).reshape(H, D))
    if not decay:
        g = jnp.zeros_like(g)
    beta = (2.0 if cfg.get("kda_allow_neg_eigval") else 1.0) \
        * jax.nn.sigmoid((h @ p("b.w")).astype(jnp.float32))

    def token(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp               # [H, D] (b_t [H])
        S = S * jnp.exp(g_t)[:, :, None]
        u = v_t - jnp.sum(S * k_t[:, :, None], axis=1)
        S = S + (b_t[:, None] * k_t)[:, :, None] * u[:, None, :]
        return S, jnp.sum(S * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((H, D, D), jnp.float32),
                        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg["rms_norm_eps"]) \
        * p("onorm.scale", cast=False)
    gate = jax.nn.sigmoid(((h @ p("g_a.w")) @ p("g_b.w"))
                          .astype(jnp.float32))
    return (o.reshape(T, H * D) * gate).astype(dtype) @ p("o.w")


def _gated(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def route(h, p, cfg):
    """Expert indices [T, k] and weights [T, k], float32."""
    scores = jax.nn.sigmoid(h.astype(jnp.float32)
                            @ p("gate.w").astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + p("gate.bias", cast=False),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * float(cfg.get("routed_scaling_factor") or 1.0)


def moe(h, p, cfg, dtype, routes=None, shared=True):
    """The routed experts HELD (``p("wg")`` .. hold ``experts_held`` of
    them, the experts ``expert_offset ..``) and, with ``shared``, the
    shared expert."""
    idx, w = route(h, p, cfg)
    if routes is not None:
        routes.append(idx)
    held = cfg.get("experts_held") or cfg["n_routed_experts"]
    first = cfg.get("expert_offset", 0)

    def expert(acc, inp):
        wg, wu, wd, e = inp
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)     # [T]
        out = _gated(h, wg.astype(dtype), wu.astype(dtype),
                     wd.astype(dtype))
        return acc + mine[:, None].astype(dtype) * out, None

    # the stacked experts are cast up one at a time, inside the loop
    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (p("wg", cast=False), p("wu", cast=False), p("wd", cast=False),
         first + jnp.arange(held)))
    if shared and cfg.get("n_shared_experts"):
        out = out + _gated(h, p("sh_gate.w"), p("sh_up.w"), p("sh_down.w"))
    return out


def forward_logits(params, cfg, ids, positions, dtype=jnp.float32,
                   stored=None, routes=None, decay=True):
    """Logits ``[len(positions), V]`` (float32) at ``positions`` of the
    sequence ``ids`` (1-D int array).  ``dtype`` other than float32,
    ``stored`` (a narrower type the matrices are kept in) or ``decay``
    false is a CONTROL of the comparison that decides ``correct``, never
    the reference.  ``routes`` (a list) receives each expert layer's
    chosen indices [T, k]."""
    with jax.default_matmul_precision("highest"):
        def value(name, cast=True):
            w = params[name]
            if stored is not None and _matrix(name):
                w = _stored_as(w, stored)
            return w.astype(dtype) if cast else w.astype(jnp.float32) \
                if w.ndim < 3 else w

        rows = params["hyb_emb"][ids]
        if stored is not None:
            rows = _stored_as(rows, stored, by_row=True)
        x = rows.astype(dtype)
        eps = cfg["rms_norm_eps"]
        for j, kind in enumerate(layer_kinds(cfg)):
            for i in (2 * j, 2 * j + 1):
                p = lambda name, cast=True, i=i: value(f"hyb{i}_{name}",
                                                       cast)
                h = _rms(x, p("norm.scale"), eps)
                if i % 2:
                    x = x + moe(h, p, cfg, dtype, routes)
                elif kind == "G":
                    x = x + attention(h, p, cfg, dtype)
                else:
                    x = x + kda(h, p, cfg, dtype, decay)
        x = _rms(x[jnp.asarray(positions)], value("hyb_norm.scale"), eps)
        return (x @ value("hyb_head.w")).astype(jnp.float32)
