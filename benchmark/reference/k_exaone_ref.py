"""Plain float32 reference of K-EXAONE-236B-A23B (``model_type``
``exaone_moe``) as ``paddle_tpu.models.window_moe`` builds it with its
multi-token-prediction module loaded: the main model's forward over a
whole sequence, and SEPARATELY the MTP module's teacher-forced draft
logits.  No kernels, no cache, no pages, no ring, no buckets, no
drafting: a ``[rows, T]`` mask a block of query rows at a time, matmul
precision "highest".  It takes parameter VALUES by the program's names
(the seeded bfloat16 matrices, cast up where they are used) and the
configuration's published keys; ``paddle_tpu`` is not imported.

Layer ``i`` (published layer ``l = layer_offset + i``) is pre-norm:
``x <- x + Attn(RMS(x))``, ``x <- x + FFN(RMS(x))``, eps ``rms_norm_eps``;
a final RMSNorm precedes the untied head.

Attn  ``q, k, v = a W_q, a W_k, a W_v`` (``num_attention_heads`` over
      ``num_key_value_heads`` heads of ``head_dim``); an RMSNorm over each
      q and k head's lanes (``qk_norm``: scales ``qnorm.scale`` /
      ``knorm.scale``); on a ``sliding_attention`` layer only
      (``full_attention_rotary`` false) the whole head turns, pair ``i`` =
      lanes ``(i, i + D/2)`` by ``p_t * theta^(-2i/D)``; ``s_h(t, u) =
      q_h(t) . k(u) * D^-1/2`` for ``u <= t`` and, on a sliding layer,
      ``t - u < sliding_window``; a plain softmax; query head ``h`` reads
      K/V head ``h // (H / Hkv)``; ``W_o``.
FFN   ``mlp_layer_types[l]`` ``dense``: ``W_d (silu(W_g u) * W_u u)``,
      width ``intermediate_size``.  ``sparse``: ``r = sigmoid(u W_r)`` in
      float32, the ``num_experts_per_tok`` largest of ``r + b``, weights
      ``r_i / sum_chosen r`` (``norm_topk_prob``) x ``routed_scaling_
      factor``, experts of ``moe_intermediate_size`` over the experts HELD
      (``expert_offset .. + experts_held - 1``; what the absent ones would
      add is left out, as in the program), PLUS ``num_shared_experts``
      shared experts of that width on every token (``sh_*``).
MTP   (``num_nextn_predict_layers`` 1; DeepSeek-V3, arXiv:2412.19437
      section 2.2) ``h'_i = W_p [RMS_h(h_i) ; RMS_e(E[t_{i+1}])]`` with
      ``h_i`` the main model's last residual before its final norm,
      ``g_i = Block(h'_i)`` (one ``mtp_layer_types[0]`` block, sparse FFN,
      parameters ``win_mtp_*``), draft logits for ``t_{i+2}`` =
      ``Head(RMS_mtp(g_i))`` with the main model's head.

Departures from the published model are listed in
``benchmark/configs/k_exaone_236b_a23b.json``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
MTP = "_mtp"


def _matrix(name):
    """Names of the parameters a lower-precision CONTROL stores narrow."""
    return name.endswith(".w") or name.endswith(("_wg", "_wu", "_wd")) \
        or name == "win_emb"


def _stored_as(w, stored, by_row=False):
    """``w`` as it reads back from storage in the type ``stored``, one
    scale per output channel (per row of the embedding)."""
    if stored is None:
        return w
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=-1 if by_row else -2, keepdims=True) \
        / float(jnp.finfo(stored).max)
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(stored).astype(jnp.float32) * scale


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _kind(cfg, i, key, mtp_key):
    if i == MTP:
        return cfg[mtp_key][0] if mtp_key in cfg else None
    return cfg[key][int(cfg.get("layer_offset", 0)) + i]


def is_window(cfg, i):
    return _kind(cfg, i, "layer_types", "mtp_layer_types") \
        == "sliding_attention"


def is_moe(cfg, i):
    # the MTP block's feed-forward is the sparse one (assumed)
    return i == MTP or _kind(cfg, i, "mlp_layer_types", "") == "sparse"


def theta_of(cfg):
    return float((cfg.get("rope_parameters") or {}).get("rope_theta", 1e4))


def _rope(x, positions, theta):
    """``x`` [T, heads, D]: every head turned, pairs ``(i, i + D/2)``."""
    D = x.shape[-1]
    half = D // 2
    freqs = theta ** (-2.0 * np.arange(half, dtype=np.float64) / D)
    ang = positions.astype(jnp.float32)[:, None, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([(a * cos - b * sin).astype(x.dtype),
                            (b * cos + a * sin).astype(x.dtype)], axis=-1)


def attention(h, p, cfg, i, dtype, window=True, qk_norm=True,
              rotary_by_kind=True):
    """``h`` [T, d] -> [T, d].  CONTROLS: ``window`` false (a sliding
    layer attends every row before it), ``qk_norm`` false (heads not
    normed), ``rotary_by_kind`` false (full layers rotate too)."""
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    T, G = h.shape[0], H // Hkv
    W = int(cfg["sliding_window"]) if is_window(cfg, i) else 0
    positions = jnp.arange(T)
    q = (h @ p("q.w")).reshape(T, H, D)
    k = (h @ p("k.w")).reshape(T, Hkv, D)
    v = (h @ p("v.w")).reshape(T, Hkv, D)
    if cfg.get("qk_norm", True) and qk_norm:
        q = _rms(q, p("qnorm.scale"), cfg["rms_norm_eps"])
        k = _rms(k, p("knorm.scale"), cfg["rms_norm_eps"])
    if W or cfg.get("full_attention_rotary", False) or not rotary_by_kind:
        q = _rope(q, positions, theta_of(cfg))
        k = _rope(k, positions, theta_of(cfg))
    block = min(T, QUERY_BLOCK)
    n_blocks = -(-T // block)
    qg = jnp.pad(q.reshape(T, Hkv, G, D),
                 ((0, n_blocks * block - T), (0, 0), (0, 0), (0, 0)))

    def rows(j):
        row = j * block + jnp.arange(block)[:, None]
        col = jnp.arange(T)[None, :]
        seen = col <= row
        if W and window:
            seen &= row - col < W
        qb = jax.lax.dynamic_slice_in_dim(qg, j * block, block, 0)
        sc = jnp.einsum("qkgd,tkd->kgqt", qb, k).astype(jnp.float32) \
            * D ** -0.5
        probs = jax.nn.softmax(jnp.where(seen[None, None], sc, -1e30),
                               axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", probs.astype(dtype), v)

    ctx = jax.lax.map(rows, jnp.arange(n_blocks))
    return ctx.reshape(-1, H * D)[:T] @ p("o.w")


def _gated(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def route(h, p, cfg, scaling=True):
    """Expert indices [T, k] and weights [T, k], float32."""
    scores = jax.nn.sigmoid(h.astype(jnp.float32)
                            @ p("gate.w").astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + p("gate.bias"),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * (float(cfg.get("routed_scaling_factor") or 1.0)
                     if scaling else 1.0)


def moe(h, p, cfg, dtype, shared=True, scaling=True, held=None, first=None):
    """The routed experts HELD (``held`` of them from ``first``: the
    configuration's share by default) and the shared experts."""
    idx, w = route(h, p, cfg, scaling)
    held = held or cfg.get("experts_held") or cfg["num_experts"]
    first = cfg.get("expert_offset", 0) if first is None else first

    def expert(acc, inp):
        wg, wu, wd, e = inp
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)     # [T]
        out = _gated(h, wg.astype(dtype), wu.astype(dtype),
                     wd.astype(dtype))
        return acc + mine[:, None].astype(dtype) * out, None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (p("wg", cast=False), p("wu", cast=False), p("wd", cast=False),
         first + jnp.arange(held)))
    if shared and cfg.get("num_shared_experts"):
        out = out + _gated(h, p("sh_gate.w"), p("sh_up.w"), p("sh_down.w"))
    return out


def _block(x, value, cfg, i, dtype, **controls):
    p = lambda name, cast=True: value(f"win{i}_{name}", cast)
    eps = cfg["rms_norm_eps"]
    attn = {k: controls[k] for k in ("window", "qk_norm", "rotary_by_kind")
            if k in controls}
    x = x + attention(_rms(x, p("norm1.scale"), eps), p, cfg, i, dtype,
                      **attn)
    h = _rms(x, p("norm2.scale"), eps)
    if is_moe(cfg, i):
        return x + moe(h, p, cfg, dtype, controls.get("shared", True),
                       controls.get("scaling", True))
    return x + _gated(h, p("ffn_gate.w"), p("ffn_up.w"), p("ffn_down.w"))


def _values(params, dtype, stored):
    def value(name, cast=True):
        w = params[name]
        if stored is not None and _matrix(name):
            w = _stored_as(w, stored)
        return w.astype(dtype) if cast else w
    return value


def _embed(params, ids, dtype, stored):
    rows = params["win_emb"][ids]
    if stored is not None:
        rows = _stored_as(rows, stored, by_row=True)
    return rows.astype(dtype)


def hidden(params, cfg, ids, dtype=jnp.float32, stored=None, **controls):
    """The main model's last residual [T, d], before its final norm."""
    value = _values(params, dtype, stored)
    x = _embed(params, ids, dtype, stored)
    for i in range(cfg["num_hidden_layers"]):
        x = _block(x, value, cfg, i, dtype, **controls)
    return x


def forward_logits(params, cfg, ids, positions, dtype=jnp.float32,
                   stored=None, **controls):
    """The MAIN model's logits ``[len(positions), V]`` (float32) at
    ``positions`` of the sequence ``ids`` (1-D int array).  ``dtype``
    other than float32, ``stored`` (a narrower type the matrices are kept
    in) or a control of :func:`attention` / :func:`moe` (``window``,
    ``qk_norm``, ``rotary_by_kind``, ``shared``, ``scaling`` false) is a
    CONTROL of the comparison that decides ``correct``, never the
    reference."""
    with jax.default_matmul_precision("highest"):
        value = _values(params, dtype, stored)
        x = hidden(params, cfg, ids, dtype, stored, **controls)
        x = _rms(x[jnp.asarray(positions)], value("win_norm.scale"),
                 cfg["rms_norm_eps"])
        return (x @ value("win_head.w")).astype(jnp.float32)


def draft_logits(params, cfg, ids, positions, dtype=jnp.float32,
                 stored=None):
    """The MTP module's teacher-forced logits ``[len(positions), V]``:
    row ``i`` (``i + 1 < len(ids)``) takes ``(h_i, E[ids[i + 1]])`` and
    predicts the token at ``i + 2``."""
    with jax.default_matmul_precision("highest"):
        value = _values(params, dtype, stored)
        eps = cfg["rms_norm_eps"]
        h = hidden(params, cfg, ids, dtype, stored)[:-1]
        e = _embed(params, ids[1:], dtype, stored)
        both = jnp.concatenate([_rms(h, value("win_mtp_hnorm.scale"), eps),
                                _rms(e, value("win_mtp_enorm.scale"), eps)],
                               axis=-1)
        g = _block(both @ value("win_mtp_proj.w"), value, cfg, MTP, dtype)
        g = _rms(g[jnp.asarray(positions)], value("win_mtp_norm.scale"), eps)
        return (g @ value("win_head.w")).astype(jnp.float32)
