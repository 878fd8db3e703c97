"""Plain float32 reference of ``dots3-note-prev``'s LANGUAGE MODEL
(``model_type`` ``dots3_note``) as ``paddle_tpu.models.latent_moe`` builds
it: window layers of latent attention beside full layers of latent
attention under a lightning indexer each, a head-wise output gate on both,
a shared-expert mixture of experts.  One forward pass over a whole
sequence: no kernels, no cache, no pages, no ring, no chunks, NON-absorbed
attention (K and V of every head expanded from the latent), the band as a
mask, the selection by ``jax.lax.top_k`` on its own float32 index scores
(``glm_dsa_ref``'s tie rule: the lower position), matmul precision
"highest".  It takes parameter VALUES by the program's names (the seeded
bfloat16 matrices, cast up where they are used) and the configuration's
published keys; ``paddle_tpu`` is not imported.

Layer ``i`` (``layer_types[i]``) is pre-norm with two sublayers, ``x <- x
+ Attn(RMSNorm(x))``, ``x <- x + FFN(RMSNorm(x))``, eps ``rms_norm_eps``; a
final RMSNorm precedes the untied head.  ``h`` is the normed residual row
of token ``t``; a ``sliding_attention`` layer reads the ``swa_*`` sizes.

Attn   ``c_q = RMSNorm(h W_qa) (hidden / q_rank)^1/2``; ``[q_nope |
       q_rope] = c_q W_qb`` a head; ``[c_kv | k_r] = h W_kva``; ``c_kv <-
       RMSNorm(c_kv) (hidden / kv_rank)^1/2`` (the two factors:
       ``apply_mla_qkv_lora_rescale``); ``q_rope`` and the ONE ``k_r``
       rotated (plain frequencies ``theta^(-2i/rope)``, ``theta`` =
       ``rope_theta`` on a full layer, ``swa_rope_theta`` on a sliding
       one; pair ``i`` = lanes ``(i, i + rope / 2)`` of the slice);
       ``[k_nope | v] = c_kv W_kvb`` a head; ``score = (q_nope . k_nope +
       q_rope . k_r) (nope + rope)^-1/2``; softmax over the rows ``s`` in
       ``S_t`` and nowhere else; ``o_j = P_j v_j``; the gate ``g =
       sigmoid(h W_g)`` [heads], ``o_j <- g_j o_j``; out ``= concat_j(o_j)
       W_o``.
S_t    sliding layer: ``s <= t`` with ``t - s < sliding_window_size``.
       Full layer: its OWN indexer, ``q^I_j = (c_q W_qb^I)_j`` for
       ``index_n_heads`` heads of ``index_head_dim`` lanes, the first
       ``rope`` lanes rotated (at ``rope_theta``); ``k^I = LayerNorm(h
       W_k^I)`` (scale and bias, eps 1e-6), the first ``rope`` lanes
       rotated; ``w = (h W_w^I) heads^-1/2 head_dim^-1/2``; ``I(t, s) =
       sum_j w_j(t) ReLU(q^I_j(t) . k^I(s))``; ``S_t`` = the positions of
       the ``index_topk`` largest ``I(t, s)`` over ``s <= t`` (all of them
       while ``t + 1 <= index_topk``; ties to the lower position).
FFN    layer ``i < first_k_dense_replace``: ``W_d (silu(W_g h) * W_u
       h)``, width ``intermediate_size``.  The others: router in float32,
       ``s = sigmoid(h W_r)``, the ``num_experts_per_tok`` largest of ``s +
       b``, weights ``routed_scaling_factor * s_i / sum_chosen s``; routed
       experts of width ``moe_intermediate_size`` over the experts HELD
       (``expert_offset .. + experts_held - 1``; what the absent experts
       would add is left out, as in the program); plus the shared expert.

Everything that is quadratic in the rows is computed a block of query rows
at a time, so that 18432 rows fit beside the program on the chip.

Departures from the published description: the vision tower, the audio
encoder and the MTP module are not here (text in, text out); the rescale's
formula, the gate's input and place, the window's inequality and the
rotary pair layout are ASSUMED as ``benchmark/configs/dots3_note_prev.json``
lists them; the published indexer scores in FP8, here float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128
INDEX_NORM_EPS = 1e-6
#: what a CONTROL may drop (``forward_logits``'s ``drop``): the selection
#: (full layers attend every row), the band (sliding layers attend every
#: row), the gate (g = 1), the rescale (both factors 1), and ``theta``
#: SWAPS the two rotary bases
DROPS = ("select", "window", "gate", "rescale", "theta")


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


def _rope(x, positions, theta):
    """``x`` [T, ..., rope]: pairs ``(i, i + rope/2)`` turned by
    ``positions * theta^(-2i/rope)``."""
    dim = x.shape[-1]
    freqs = float(theta) ** (-2.0 * np.arange(dim // 2,
                                              dtype=np.float64) / dim)
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    half = dim // 2
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def is_sliding(cfg, i):
    return cfg["layer_types"][i] == "sliding_attention"


def is_sparse_ffn(cfg, i):
    return i >= cfg["first_k_dense_replace"]


def attention_shape(cfg, i):
    """Layer ``i``'s ``(heads, q rank, kv rank, nope, rope, v, theta)``
    by its kind."""
    key = "swa_" if is_sliding(cfg, i) else ""
    return tuple(cfg[key + k] for k in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta"))


def index_parts(h, c_q, p, cfg, dtype, theta):
    """The indexer's queries [T, H, D], keys [T, D] and head weights [T,
    H] of one full layer."""
    Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
    R, T = cfg["qk_rope_head_dim"], h.shape[0]
    positions = jnp.arange(T)
    q = (c_q @ p("idx_qb.w")).reshape(T, Hi, Di)
    q = jnp.concatenate([_rope(q[..., :R], positions, theta), q[..., R:]], -1)
    k = (h @ p("idx_k.w")).astype(jnp.float32)
    mean = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
    k = ((k - mean) * jax.lax.rsqrt(var + INDEX_NORM_EPS)
         * p("idx_knorm.scale") + p("idx_knorm.bias")).astype(dtype)
    k = jnp.concatenate([_rope(k[..., :R], positions, theta), k[..., R:]], -1)
    w = (h @ p("idx_w.w")).astype(jnp.float32) * (Hi ** -0.5 * Di ** -0.5)
    return q, k, w


def select_rows(scores, seen, k):
    """``scores`` [Q, T] float32, ``seen`` [Q, T] bool -> bool [Q, T]: the
    ``k`` largest seen scores a row (``jax.lax.top_k``: ties to the lower
    position), every seen one where there are no more than ``k``."""
    Q, T = scores.shape
    if T <= k:
        return seen
    sc = jnp.where(scores == 0, 0.0, scores)    # -0.0 is 0.0
    _, idx = jax.lax.top_k(jnp.where(seen, sc, -jnp.inf), k)
    chosen = jnp.zeros((Q, T), bool).at[jnp.arange(Q)[:, None], idx] \
        .set(True)
    return chosen & seen


def attention(h, p, cfg, i, dtype, drop=()):
    """``h`` [T, d] -> [T, d]: layer ``i``'s attention in the published,
    expanded form."""
    H, q_rank, L, nope, R, vd, theta = attention_shape(cfg, i)
    sliding = is_sliding(cfg, i)
    if "theta" in drop:
        theta = cfg["rope_theta" if sliding else "swa_rope_theta"]
    d, T, eps = cfg["hidden_size"], h.shape[0], cfg["rms_norm_eps"]
    rescale = bool(cfg.get("apply_mla_qkv_lora_rescale")) \
        and "rescale" not in drop
    positions = jnp.arange(T)
    c_q = _rms(h @ p("qa.w"), p("qnorm.scale"), eps)
    kva = h @ p("kva.w")
    c_kv = _rms(kva[:, :L], p("kvnorm.scale"), eps)
    if rescale:
        c_q = c_q * jnp.asarray((d / q_rank) ** 0.5, dtype)
        c_kv = c_kv * jnp.asarray((d / L) ** 0.5, dtype)
    q = (c_q @ p("qb.w")).reshape(T, H, nope + R)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], positions, theta)
    k_r = _rope(kva[:, L:], positions, theta)                    # [T, R]
    kv = (c_kv @ p("kvb.w")).reshape(T, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = (nope + R) ** -0.5
    window = 0 if "window" in drop or not sliding \
        else int(cfg["sliding_window_size"])
    topk = int(cfg.get("index_topk") or 0)
    selecting = not sliding and "select" not in drop and 0 < topk < T
    # query rows a block at a time; the last block is filled up with
    # rows of zeros, whose results are dropped (the keys are not padded)
    block = min(T, QUERY_BLOCK)
    n_blocks = -(-T // block)
    filled = lambda a: jnp.pad(a, ((0, n_blocks * block - T),)
                               + ((0, 0),) * (a.ndim - 1))
    q_nope, q_rope = filled(q_nope), filled(q_rope)
    if selecting:
        qi, ki, wi = index_parts(h, c_q, p, cfg, dtype, theta)
        qi, wi = filled(qi), filled(wi)
    part = lambda a, j: jax.lax.dynamic_slice_in_dim(a, j * block, block, 0)

    def rows(j):
        row = j * block + jnp.arange(block)[:, None]
        col = jnp.arange(T)[None, :]
        seen = col <= row
        if window:
            seen &= row - col < window
        if selecting:
            s = jnp.einsum("qhd,td->qht", part(qi, j), ki).astype(
                jnp.float32)
            index = jnp.sum(jax.nn.relu(s) * part(wi, j)[:, :, None], axis=1)
            seen = select_rows(index, seen, topk)
        sc = (jnp.einsum("qhd,khd->hqk", part(q_nope, j), k_nope)
              + jnp.einsum("qhd,kd->hqk", part(q_rope, j), k_r)).astype(
                  jnp.float32)
        sc = jnp.where(seen[None], sc * scale, -1e30)
        return jnp.einsum("hqk,khd->qhd",
                          jax.nn.softmax(sc, -1).astype(dtype), v)

    ctx = jax.lax.map(rows, jnp.arange(n_blocks)).reshape(-1, H, vd)[:T]
    if cfg.get(("swa_" if sliding else "") + "attention_gate_type") \
            and "gate" not in drop:
        g = jax.nn.sigmoid((h @ p("og.w")).astype(jnp.float32))
        ctx = (ctx.astype(jnp.float32) * g[:, :, None]).astype(dtype)
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
    if not shared:
        return routed
    return routed + _gated(h, p("sh_gate.w"), p("sh_up.w"), p("sh_down.w"))


def forward_logits(params, cfg, ids, positions, dtype=jnp.float32,
                   stored=None, routes=None, drop=(), residual=None):
    """Logits ``[len(positions), V]`` (float32) at ``positions`` of the
    sequence ``ids`` (1-D int array).  ``dtype`` other than float32,
    ``stored`` (a narrower type the matrices are kept in) or a ``drop``
    (names of :data:`DROPS`) is a CONTROL of the comparison that decides
    ``correct``, never the reference.  ``routes`` (a list) receives each
    expert layer's chosen indices [T, k]; ``residual`` (a list) the rms
    of the residual stream at every layer's second norm (what the seeded
    router's offset is sized by)."""
    unknown = set(drop) - set(DROPS)
    if unknown:
        raise ValueError(f"drop {sorted(unknown)}: one of {DROPS}")
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
            x = x + attention(_rms(x, p("norm1.scale"), eps), p, cfg, i,
                              dtype, drop)
            if residual is not None:
                residual.append(jnp.sqrt(jnp.mean(jnp.square(
                    x[:, 1:].astype(jnp.float32)))))
            h = _rms(x, p("norm2.scale"), eps)
            if is_sparse_ffn(cfg, i):
                x = x + moe(h, p, cfg, dtype, routes)
            else:
                x = x + _gated(h, p("ffn_gate.w"), p("ffn_up.w"),
                               p("ffn_down.w"))
        x = _rms(x[jnp.asarray(positions)], value("lat_norm.scale"), eps)
        return (x @ value("lat_head.w")).astype(jnp.float32)
