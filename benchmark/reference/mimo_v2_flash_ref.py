"""Plain float32 reference of the sliding-window / full attention
mixture-of-experts LM that ``paddle_tpu.models.window_moe`` builds for
``mimo_v2_flash`` (MiMo-V2-Flash), as one forward pass over a whole
sequence: no kernels, no cache, no pages, no ring, no buckets, a ``[rows,
T]`` mask a block of query rows at a time, matmul precision "highest".
It takes parameter VALUES by the program's names (the seeded bfloat16
matrices, cast up where they are used) and the configuration's numbers;
``paddle_tpu`` is not imported.

Layer ``i`` (published layer ``l = layer_offset + i``) is pre-norm with
two sublayers, ``x <- x + Attn(RMSNorm(x))``, ``x <- x + FFN(RMSNorm(x))``,
eps ``layernorm_epsilon``; a final RMSNorm precedes the untied head.

Attn  kind by ``hybrid_layer_pattern[l]``: 0 = FULL (``num_attention_heads``
      over ``num_key_value_heads``, ``head_dim`` / ``v_head_dim``, theta
      ``rope_theta``, no window, a sink only with
      ``add_full_attention_sink_bias``); 1 = WINDOW (the ``swa_`` keys,
      theta ``swa_rope_theta``, ``sliding_window`` rows, a sink with
      ``add_swa_attention_sink_bias``).  ``q = a W_q`` (H heads of Dk),
      ``k = a W_k`` (Hkv heads of Dk), ``v = attention_value_scale * a W_v``
      (Hkv heads of Dv).  The first ``R = floor(Dk *
      partial_rotary_factor)`` lanes (made even) of every q and k head
      turn by ``p_t * theta^(-2i/R)``, pair ``i`` = lanes ``(i, i + R/2)``;
      the rest pass.  Query head ``h`` reads K/V head ``h // (H / Hkv)``.
      ``s_h(t, u) = q_h(t) . k(u) * Dk^-1/2`` for ``u <= t`` and, in a
      window layer, ``t - u < sliding_window``.  With the head's sink
      logit ``b_h``: ``P_h(t, u) = exp(s_h(t, u)) / (exp(b_h) + sum_u'
      exp(s_h(t, u')))`` (the sink takes weight and adds no value); else a
      plain softmax.  ``o_h(t) = sum_u P_h(t, u) v(u)``; out ``=
      concat_h(o) W_o``.
FFN   ``moe_layer_freq[l]`` 0: ``W_d (silu(W_g u) * W_u u)``, width
      ``intermediate_size``.  1: router in float32, ``r = sigmoid(u W_r)``,
      the ``num_experts_per_tok`` largest of ``r + b``, weights ``r_i /
      sum_chosen r`` (``norm_topk_prob``) times ``routed_scaling_factor``
      (null = 1); routed experts of width ``moe_intermediate_size`` over
      the experts HELD (``expert_offset .. + experts_held - 1``; what the
      absent experts would add is left out, as in the program); no shared
      expert.

Departures from the published model are listed in
``benchmark/configs/mimo_v2_flash.json``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256


def _matrix(name):
    """Names of the parameters a lower-precision CONTROL stores narrow:
    the matrices (vectors, norms, sinks and the router's bias stay)."""
    return name.endswith(".w") or name.endswith(("_wg", "_wu", "_wd")) \
        or name == "win_emb"


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


def is_window(cfg, i):
    return bool(cfg["hybrid_layer_pattern"][
        int(cfg.get("layer_offset", 0)) + i])


def is_moe(cfg, i):
    return bool(cfg["moe_layer_freq"][int(cfg.get("layer_offset", 0)) + i])


def attention_shape(cfg, i):
    """Layer ``i``'s ``(H, Hkv, Dk, Dv, theta, window or 0, sink)``."""
    if is_window(cfg, i):
        return (cfg["swa_num_attention_heads"],
                cfg["swa_num_key_value_heads"], cfg["swa_head_dim"],
                cfg["swa_v_head_dim"], float(cfg["swa_rope_theta"]),
                int(cfg["sliding_window"]),
                bool(cfg.get("add_swa_attention_sink_bias")))
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["v_head_dim"], float(cfg["rope_theta"]), 0,
            bool(cfg.get("add_full_attention_sink_bias")))


def rotary_lanes(cfg, dk):
    return int(dk * float(cfg["partial_rotary_factor"])) // 2 * 2


def _rope(x, positions, rot, theta):
    """``x`` [T, heads, D]: the first ``rot`` lanes of every head turned,
    pairs ``(i, i + rot/2)`` by ``positions * theta^(-2i/rot)``."""
    half = rot // 2
    freqs = theta ** (-2.0 * np.arange(half, dtype=np.float64) / rot)
    ang = positions.astype(jnp.float32)[:, None, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half].astype(jnp.float32), x[..., half:rot].astype(
        jnp.float32)
    return jnp.concatenate(
        [(a * cos - b * sin).astype(x.dtype),
         (b * cos + a * sin).astype(x.dtype), x[..., rot:]], axis=-1)


def attention(h, p, cfg, i, dtype, window=True, sink=True, rotary=True):
    """``h`` [T, d] -> [T, d].  ``window`` false (a window layer attends
    every row before it), ``sink`` false (a plain softmax) and ``rotary``
    false are CONTROLS."""
    H, Hkv, Dk, Dv, theta, W, has_sink = attention_shape(cfg, i)
    T, G = h.shape[0], H // Hkv
    positions = jnp.arange(T)
    q = (h @ p("q.w")).reshape(T, H, Dk)
    k = (h @ p("k.w")).reshape(T, Hkv, Dk)
    v = ((h @ p("v.w")) * jnp.asarray(cfg["attention_value_scale"], dtype)) \
        .reshape(T, Hkv, Dv)
    if rotary:
        rot = rotary_lanes(cfg, Dk)
        q, k = _rope(q, positions, rot, theta), _rope(k, positions, rot,
                                                     theta)
    b = p("sink").astype(jnp.float32).reshape(Hkv, G, 1, 1) \
        if has_sink and sink else None
    # query rows a block at a time; the last block is filled up with rows
    # of zeros, whose results are dropped (the keys are not padded)
    block = min(T, QUERY_BLOCK)
    n_blocks = -(-T // block)
    qg = jnp.pad(q.reshape(T, Hkv, G, Dk),
                 ((0, n_blocks * block - T), (0, 0), (0, 0), (0, 0)))

    def rows(j):
        row = j * block + jnp.arange(block)[:, None]
        col = jnp.arange(T)[None, :]
        seen = col <= row
        if W and window:
            seen &= row - col < W
        qb = jax.lax.dynamic_slice_in_dim(qg, j * block, block, 0)
        sc = jnp.einsum("qkgd,tkd->kgqt", qb, k).astype(jnp.float32) \
            * Dk ** -0.5
        sc = jnp.where(seen[None, None], sc, -1e30)
        m = jnp.max(sc, axis=-1, keepdims=True)
        if b is not None:
            m = jnp.maximum(m, b)
        e = jnp.exp(sc - m)
        denom = jnp.sum(e, axis=-1, keepdims=True)
        if b is not None:
            denom = denom + jnp.exp(b - m)
        return jnp.einsum("kgqt,tkd->qkgd", (e / denom).astype(dtype), v)

    ctx = jax.lax.map(rows, jnp.arange(n_blocks))
    return ctx.reshape(-1, H * Dv)[:T] @ p("o.w")


def _gated(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def route(h, p, cfg, bias=True):
    """Expert indices [T, k] and weights [T, k], float32."""
    scores = jax.nn.sigmoid(h.astype(jnp.float32)
                            @ p("gate.w").astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + (p("gate.bias") if bias else 0.0),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * float(cfg.get("routed_scaling_factor") or 1.0)


def moe(h, p, cfg, dtype, routes=None, bias=True):
    idx, w = route(h, p, cfg, bias)
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
    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (p("wg", cast=False), p("wu", cast=False), p("wd", cast=False),
         first + jnp.arange(held)))
    return routed


def forward_logits(params, cfg, ids, positions, dtype=jnp.float32,
                   stored=None, routes=None, window=True, sink=True,
                   rotary=True, bias=True):
    """Logits ``[len(positions), V]`` (float32) at ``positions`` of the
    sequence ``ids`` (1-D int array).  ``dtype`` other than float32,
    ``stored`` (a narrower type the matrices are kept in), ``window``
    false, ``sink`` false, ``rotary`` false or ``bias`` false (the
    router's correction bias dropped) is a CONTROL of the comparison that
    decides ``correct``, never the reference.  ``routes`` (a list)
    receives each expert layer's chosen indices [T, k]."""
    with jax.default_matmul_precision("highest"):
        def value(name, cast=True):
            w = params[name]
            if stored is not None and _matrix(name):
                w = _stored_as(w, stored)
            return w.astype(dtype) if cast else w

        rows = params["win_emb"][ids]
        if stored is not None:
            rows = _stored_as(rows, stored, by_row=True)
        x = rows.astype(dtype)
        eps = cfg["layernorm_epsilon"]
        for i in range(cfg["num_hidden_layers"]):
            p = lambda name, cast=True, i=i: value(f"win{i}_{name}", cast)
            x = x + attention(_rms(x, p("norm1.scale"), eps), p, cfg, i,
                              dtype, window, sink, rotary)
            h = _rms(x, p("norm2.scale"), eps)
            if is_moe(cfg, i):
                x = x + moe(h, p, cfg, dtype, routes, bias)
            else:
                x = x + _gated(h, p("ffn_gate.w"), p("ffn_up.w"),
                               p("ffn_down.w"))
        x = _rms(x[jnp.asarray(positions)], value("win_norm.scale"), eps)
        return (x @ value("win_head.w")).astype(jnp.float32)
