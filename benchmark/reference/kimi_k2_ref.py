"""Plain float32 reference of the latent-attention / shared-expert
mixture-of-experts LM that ``paddle_tpu.models.latent_moe`` builds
(``kimi_k2``, the DeepSeek-V3 family's block), as one forward pass over a
whole sequence: no kernels, no cache, no pages, no buckets, NON-absorbed
attention (K and V of every head are expanded from the latent, as the
published modelling code has it: the program's absorbed decode is thereby
checked against the published form), matmul precision "highest".  It
takes parameter VALUES by the program's names (the seeded bfloat16
matrices, cast up where they are used) and the configuration's numbers;
``paddle_tpu`` is not imported.

Layer ``i`` is pre-norm with two sublayers, ``x <- x + MLA(RMSNorm(x))``,
``x <- x + FFN(RMSNorm(x))``, eps ``rms_norm_eps``; a final RMSNorm
precedes the untied head.

MLA   ``c_q = RMSNorm(h W_qa)``; ``[q_nope | q_rope] = c_q W_qb`` a head
      (``qk_nope_head_dim`` | ``qk_rope_head_dim``); ``[c_kv | k_r] = h
      W_kva`` (``kv_lora_rank`` | rope); ``c_kv <- RMSNorm(c_kv)``; ``k_r
      <- RoPE(k_r)``, ONE rotary key for all heads; ``q_rope <-
      RoPE(q_rope)``; ``[k_nope | v] = c_kv W_kvb`` a head.  ``score =
      (q_nope . k_nope + q_rope . k_r) s``, causal softmax, ``o = P v``,
      out ``= concat_h(o) W_o``.  RoPE with YaRN: frequency ``i`` of the
      ``rope / 2`` is ``theta^(-2i/rope)`` blended with that over
      ``factor`` by the linear ramp between the pairs whose wavelengths
      make ``beta_fast`` and ``beta_slow`` turns in
      ``original_max_position_embeddings``; pair ``i`` is lanes ``(i, i +
      rope / 2)`` of the slice (configuration file, ``assumed``); cos and
      sin carry ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``;
      ``s = (nope + rope)^-1/2 m^2``, ``m = 0.1 mscale_all_dim ln(factor) +
      1``.  Attention is computed a block of query rows at a time, so that
      4096 rows fit beside the program on the chip.
FFN   layers before ``first_k_dense_replace``: ``W_d (silu(W_g h) * W_u
      h)``, width ``intermediate_size``.  The others: router in float32 on
      the full hidden state, ``s = sigmoid(h W_r)``, the
      ``num_experts_per_tok`` largest of ``s + b``, weights
      ``routed_scaling_factor * s_i / sum_chosen s``; routed experts ``W_d^e
      (silu(W_g^e h) * W_u^e h)`` of width ``moe_intermediate_size`` over
      the experts HELD (``expert_offset .. expert_offset + experts_held -
      1``: a loop over them; what the absent experts would add is left out,
      as in the program); plus the shared expert on every token.

Departures from the published model are listed in
``benchmark/configs/kimi_k2.6_text.json``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256


def _matrix(name):
    """Names of the parameters a lower-precision CONTROL stores narrow:
    the matrices (vectors, norms and the router's bias stay)."""
    return name.endswith(".w") or name.endswith(("_wg", "_wu", "_wd")) \
        or name == "lat_emb"


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


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(cfg):
    """The ``qk_rope_head_dim / 2`` frequencies, radians a position."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg.get("rope_scaling") or {}
    factor = float(rs.get("factor", 1.0))
    i = np.arange(dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / dim)
    if factor <= 1:
        return plain
    turns_at = lambda turns: dim * math.log(
        rs["original_max_position_embeddings"] / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(turns_at(rs["beta_fast"])), 0)
    high = min(math.ceil(turns_at(rs["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / factor * ramp


def _rope(x, positions, cfg):
    """``x`` [T, ..., rope]: pairs ``(i, i + rope/2)`` turned by
    ``positions * f_i``."""
    rs = cfg.get("rope_scaling") or {}
    factor = float(rs.get("factor", 1.0))
    m = yarn_mscale(factor, rs.get("mscale", 1.0)) \
        / yarn_mscale(factor, rs.get("mscale_all_dim", 0.0))
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(yarn_frequencies(cfg), jnp.float32)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    half = x.shape[-1] // 2
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def softmax_scale(cfg):
    rs = cfg.get("rope_scaling") or {}
    m = yarn_mscale(float(rs.get("factor", 1.0)),
                    rs.get("mscale_all_dim", 0.0))
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def attention(h, p, cfg, dtype):
    """``h`` [T, d] -> [T, d]; the published, expanded form."""
    H, L = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, R, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                   cfg["v_head_dim"])
    T, eps = h.shape[0], cfg["rms_norm_eps"]
    positions = jnp.arange(T)
    c_q = _rms(h @ p("qa.w"), p("qnorm.scale"), eps)
    q = (c_q @ p("qb.w")).reshape(T, H, nope + R)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], positions, cfg)
    kva = h @ p("kva.w")
    c_kv = _rms(kva[:, :L], p("kvnorm.scale"), eps)
    k_r = _rope(kva[:, L:], positions, cfg)                      # [T, R]
    kv = (c_kv @ p("kvb.w")).reshape(T, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = softmax_scale(cfg)
    block = math.gcd(T, QUERY_BLOCK)

    def rows(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * block, block, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, i * block, block, 0)
        sc = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
              + jnp.einsum("qhd,kd->hqk", qr, k_r)).astype(jnp.float32)
        row = i * block + jnp.arange(block)[:, None]
        sc = jnp.where(jnp.arange(T)[None, :] <= row, sc * scale, -1e30)
        return jnp.einsum("hqk,khd->qhd",
                          jax.nn.softmax(sc, -1).astype(dtype), v)

    ctx = jax.lax.map(rows, jnp.arange(T // block))
    return ctx.reshape(T, H * vd) @ p("o.w")


def _gated(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def route(h, p, cfg):
    """Expert indices [T, k] and weights [T, k], float32."""
    scores = jax.nn.sigmoid(h.astype(jnp.float32)
                            @ p("gate.w").astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + p("gate.bias"),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


def moe(h, p, cfg, dtype, routes=None, shared=True):
    idx, w = route(h, p, cfg)
    if routes is not None:
        routes.append(idx)
    held = cfg.get("experts_held", cfg["n_routed_experts"])
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
    if not shared:
        return routed
    return routed + _gated(h, p("sh_gate.w"), p("sh_up.w"), p("sh_down.w"))


def forward_logits(params, cfg, ids, positions, dtype=jnp.float32,
                   stored=None, routes=None):
    """Logits ``[len(positions), V]`` (float32) at ``positions`` of the
    sequence ``ids`` (1-D int array), every position seeing itself and
    everything before it.  ``dtype`` other than float32, or ``stored`` (a
    narrower type the matrices are kept in), is a CONTROL of the
    comparison that decides ``correct``, never the reference.  ``routes``
    (a list) receives each expert layer's chosen indices [T, k]."""
    with jax.default_matmul_precision("highest"):
        def value(name, cast=True):
            w = params[name]
            if stored is not None and _matrix(name):
                w = _stored_as(w, stored)
            return w.astype(dtype) if cast else w

        rows = params["lat_emb"][ids]
        if stored is not None:
            rows = _stored_as(rows, stored, by_row=True)
        x = rows.astype(dtype)
        eps = cfg["rms_norm_eps"]
        for i in range(cfg["num_hidden_layers"]):
            p = lambda name, cast=True, i=i: value(f"lat{i}_{name}", cast)
            x = x + attention(_rms(x, p("norm1.scale"), eps), p, cfg, dtype)
            h = _rms(x, p("norm2.scale"), eps)
            if i < cfg["first_k_dense_replace"]:
                x = x + _gated(h, p("ffn_gate.w"), p("ffn_up.w"),
                               p("ffn_down.w"))
            else:
                x = x + moe(h, p, cfg, dtype, routes)
        x = _rms(x[jnp.asarray(positions)], value("lat_norm.scale"), eps)
        return (x @ value("lat_head.w")).astype(jnp.float32)
