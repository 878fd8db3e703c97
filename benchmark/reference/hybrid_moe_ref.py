"""Plain float32 reference of the hybrid state-space / attention /
mixture-of-experts LM that ``paddle_tpu.models.hybrid_moe`` builds, as one
forward pass over a whole sequence: no kernels, no cache, no pages, no
buckets, no chunks, matmul precision "highest".  It takes parameter VALUES
by the program's names (the seeded bfloat16 matrices, cast up where they
are used) and the configuration's numbers; ``paddle_tpu`` is not imported.

Every layer ``i`` of ``hybrid_override_pattern`` holds one sublayer and is
pre-norm, ``x <- x + f(RMSNorm(x))``, eps ``layer_norm_epsilon``; a final
RMSNorm precedes the untied head:

``M``  Mamba-2 mixer.  ``[z | xBC | dt] = h W_in``; ``xBC <-
       silu(causal depthwise conv_K(xBC) + b)``; ``xBC = [x | B | C]``
       (heads x head_dim | groups x state | groups x state, head h reads
       group h // (heads / groups)); ``dt = softplus(dt + dt_bias)``,
       ``A = -exp(A_log)``; a ``lax.scan`` over the tokens of
       ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``,
       ``y_t = h_t . C_t + D x_t``; out ``= W_out
       GroupRMSNorm(y * silu(z))`` (norm over each of the groups' parts).
``*``  causal attention, ``num_attention_heads`` query heads over
       ``num_key_value_heads`` K/V heads (query head i reads K/V head
       i // group), softmax(QK^T / sqrt(head_dim)) V, NO positional
       embedding (configuration file, ``assumed``).
``E``  LatentMoE.  Router on the full hidden state in float32: ``s =
       sigmoid(h W_g)``, the ``num_experts_per_tok`` largest of ``s + b``,
       weights ``routed_scaling_factor * s_i / sum_chosen s``.  Routed
       path in the latent ``u = h W_down``: ``sum_i w_i W2_i
       relu(W1_i u)^2`` over the experts HELD (``expert_offset ..
       expert_offset + experts_held - 1``: a dense loop over them; what
       the absent experts would add is left out, as in the program), then
       ``W_up``.  Plus the shared expert ``V2 relu(V1 h)^2`` on the full
       hidden state.

Departures from the published model are listed in
``benchmark/configs/nemotron3_super_ep8.json``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _matrix(name):
    """Names of the parameters a lower-precision CONTROL stores narrow:
    the matrices (the conv's taps, the vectors and the norms stay)."""
    return (name.endswith(".w") and not name.endswith("conv.w")) \
        or name.endswith(("_w1", "_w2")) or name == "hyb_emb"


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


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0))


def mixer(h, p, cfg, dtype):
    """``h`` [T, d] -> [T, d]."""
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    inner, gn, T = H * P, G * N, h.shape[0]
    zxbcdt = h @ p("in.w")
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * gn],
                  zxbcdt[:, 2 * inner + 2 * gn:])
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    taps = p("conv.w")
    conv = sum(padded[k:k + T] * taps[k] for k in range(K)) + p("conv.b")
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(T, H, P)
    B = jnp.repeat(xbc[:, inner:inner + gn].reshape(T, G, N), H // G, axis=1)
    C = jnp.repeat(xbc[:, inner + gn:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + p("dt_bias"))                     # [T, H]
    A = -jnp.exp(p("a_log"))

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = jnp.exp(dt_t * A)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), dtype), (x, B, C, dt))
    y = (y + p("d")[:, None] * x).reshape(T, inner)
    v = (y * jax.nn.silu(z)).astype(jnp.float32).reshape(T, G, inner // G)
    v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    v = (v.reshape(T, inner) * p("gnorm.scale")).astype(dtype)
    return v @ p("out.w")


def attention(h, p, cfg, dtype):
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    T = h.shape[0]
    q = (h @ p("q.w")).reshape(T, H, D)
    k = jnp.repeat((h @ p("k.w")).reshape(T, Hkv, D), H // Hkv, axis=1)
    v = jnp.repeat((h @ p("v.w")).reshape(T, Hkv, D), H // Hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * D ** -0.5 \
        + jnp.triu(jnp.full((T, T), -1e9, dtype), 1)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return ctx.reshape(T, H * D) @ p("o.w")


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


def moe(h, p, cfg, dtype, routes=None):
    idx, w = route(h, p, cfg)
    if routes is not None:
        routes.append(idx)
    u = h @ p("down.w")
    held = cfg.get("experts_held", cfg["n_routed_experts"])
    first = cfg.get("expert_offset", 0)

    def expert(acc, inp):
        w1, w2, e = inp
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)     # [T]
        out = _relu2(u @ w1.astype(dtype)) @ w2.astype(dtype)
        return acc + mine[:, None].astype(dtype) * out, None

    # the stacked experts are cast up one at a time, inside the loop
    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(u),
        (p("w1", cast=False), p("w2", cast=False),
         first + jnp.arange(held)))
    shared = _relu2(h @ p("sh1.w")) @ p("sh2.w")
    return routed @ p("up.w") + shared


def forward_logits(params, cfg, ids, positions, dtype=jnp.float32,
                   stored=None, routes=None):
    """Logits ``[len(positions), V]`` (float32) at ``positions`` of the
    sequence ``ids`` (1-D int array), every position seeing itself and
    everything before it.  ``dtype`` other than float32, or ``stored`` (a
    narrower type the matrices are kept in), is a CONTROL of the
    comparison that decides ``correct``, never the reference: every
    parameter and every activation in ``dtype``.  ``routes`` (a list)
    receives each ``E`` layer's chosen expert indices [T, k]."""
    with jax.default_matmul_precision("highest"):
        def value(name, cast=True):
            w = params[name]
            if stored is not None and _matrix(name):
                w = _stored_as(w, stored)
            return w.astype(dtype) if cast else w

        rows = params["hyb_emb"][ids]
        if stored is not None:
            rows = _stored_as(rows, stored, by_row=True)
        x = rows.astype(dtype)
        eps = cfg["layer_norm_epsilon"]
        for i, kind in enumerate(cfg["hybrid_override_pattern"]):
            p = lambda name, cast=True, i=i: value(f"hyb{i}_{name}", cast)
            h = _rms(x, p("norm.scale"), eps)
            if kind == "M":
                x = x + mixer(h, p, cfg, dtype)
            elif kind == "*":
                x = x + attention(h, p, cfg, dtype)
            else:
                x = x + moe(h, p, cfg, dtype, routes)
        x = _rms(x[jnp.asarray(positions)], value("hyb_norm.scale"), eps)
        return (x @ value("hyb_head.w")).astype(jnp.float32)
