"""Plain float32 reference of the latent-attention / shared-expert
mixture-of-experts LM with LEARNED SPARSE ATTENTION that
``paddle_tpu.models.latent_moe`` builds for ``glm_moe_dsa`` (GLM-5.2: the
DeepSeek-V3 family's block with the DeepSeek-V3.2 "lightning indexer"), as
one forward pass over a whole sequence: no kernels, no cache, no pages, no
buckets, NON-absorbed attention, the selection by ``jax.lax.top_k`` on the
float32 index scores, matmul precision "highest".  It takes parameter
VALUES by the program's names (the seeded bfloat16 matrices, cast up
where they are used) and the configuration's numbers; ``paddle_tpu`` is
not imported.

Layer ``i`` (published layer ``layer_offset + i``) is pre-norm with two
sublayers, ``x <- x + MLA(RMSNorm(x))``, ``x <- x + FFN(RMSNorm(x))``, eps
``rms_norm_eps``; a final RMSNorm precedes the untied head.  ``h`` is the
normed residual row of token ``t``.

MLA   ``c_q = RMSNorm(h W_qa)``; ``[q_nope | q_rope] = c_q W_qb`` a head;
      ``[c_kv | k_r] = h W_kva``; ``c_kv <- RMSNorm(c_kv)``; ``q_rope``
      and the ONE ``k_r`` rotated (plain frequencies ``theta^(-2i/rope)``,
      pair ``i`` = lanes ``(i, i + rope / 2)`` of the slice); ``[k_nope |
      v] = c_kv W_kvb`` a head; ``score = (q_nope . k_nope + q_rope . k_r)
      (nope + rope)^-1/2``; softmax over the rows ``s`` in ``S_t`` and
      nowhere else; ``o = P v``; out ``= concat_h(o) W_o``.
DSA   a layer whose ``indexer_types`` entry is ``"full"``: ``q_j = (c_q
      W_qb^I)_j`` for ``index_n_heads`` heads of ``index_head_dim`` lanes,
      the first ``rope`` lanes rotated; ``k = LayerNorm(h W_k^I)`` (scale
      and bias, eps 1e-6), the first ``rope`` lanes rotated; ``w = (h
      W_w^I) heads^-1/2 head_dim^-1/2``; ``I(t, s) = sum_j w_j(t) ReLU(q_j(t)
      . k(s))``; ``S_t`` = the positions of the ``index_topk`` largest ``I(t,
      s)`` over ``s <= t`` (all of them while ``t + 1 <= index_topk``; ties
      to the lower position, as ``jax.lax.top_k``).  A ``"shared"`` layer
      holds no indexer and uses the ``S_t`` of the nearest full layer
      before it.
FFN   ``mlp_layer_types`` ``"dense"``: ``W_d (silu(W_g h) * W_u h)``,
      width ``intermediate_size``.  ``"sparse"``: router in float32, ``s =
      sigmoid(h W_r)``, the ``num_experts_per_tok`` largest of ``s + b``,
      weights ``routed_scaling_factor * s_i / sum_chosen s``; routed experts
      of width ``moe_intermediate_size`` over the experts HELD
      (``expert_offset .. + experts_held - 1``; what the absent experts
      would add is left out, as in the program); plus the shared expert.

Everything that is quadratic in the rows (the index scores, the
selection, the attention) is computed a block of query rows at a time,
so that 18432 rows fit beside the program on the chip.  Departures from
the published model are listed in ``benchmark/configs/glm_5.2.json``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
INDEX_NORM_EPS = 1e-6


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


def rope_theta(cfg):
    return float((cfg.get("rope_parameters") or {}).get(
        "rope_theta", cfg.get("rope_theta", 10000.0)))


def _rope(x, positions, cfg):
    """``x`` [T, ..., rope]: pairs ``(i, i + rope/2)`` turned by
    ``positions * theta^(-2i/rope)``."""
    dim = x.shape[-1]
    freqs = rope_theta(cfg) ** (-2.0 * np.arange(dim // 2,
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


def indexer_kind(cfg, i):
    """``"full"``, ``"shared"`` or None for layer ``i`` of the layers
    held (a shared layer with no full layer before it attends every
    row)."""
    if not cfg.get("index_topk"):
        return None
    kinds, at = cfg.get("indexer_types"), int(cfg.get("layer_offset", 0))
    kind = kinds[at + i] if kinds else "full"
    if kind == "full" or any(not kinds or kinds[at + j] == "full"
                             for j in range(i)):
        return kind
    return None


def is_sparse_ffn(cfg, i):
    kinds = cfg.get("mlp_layer_types")
    if kinds:
        return kinds[int(cfg.get("layer_offset", 0)) + i] == "sparse"
    return i >= cfg["first_k_dense_replace"]


def index_parts(h, c_q, p, cfg, dtype):
    """The indexer's queries [T, H, D], keys [T, D] and head weights [T,
    H] of one layer."""
    Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
    R, T = cfg["qk_rope_head_dim"], h.shape[0]
    positions = jnp.arange(T)
    q = (c_q @ p("idx_qb.w")).reshape(T, Hi, Di)
    q = jnp.concatenate([_rope(q[..., :R], positions, cfg), q[..., R:]], -1)
    k = (h @ p("idx_k.w")).astype(jnp.float32)
    mean = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
    k = ((k - mean) * jax.lax.rsqrt(var + INDEX_NORM_EPS)
         * p("idx_knorm.scale") + p("idx_knorm.bias")).astype(dtype)
    k = jnp.concatenate([_rope(k[..., :R], positions, cfg), k[..., R:]], -1)
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


def attention(h, p, cfg, dtype, kind=None, selection=None, select=True):
    """``h`` [T, d] -> ``([T, d], selection)``; the published, expanded
    form.  ``kind`` ``"full"``: the layer's own indexer makes the
    selection (bool [T, T], returned); ``"shared"``: ``selection`` is the
    one it attends under; None (or ``select`` false, a CONTROL): every
    row before it."""
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
    scale = (nope + R) ** -0.5
    # query rows a block at a time; the last block is filled up with
    # rows of zeros, whose results are dropped (the keys are not padded)
    block = min(T, QUERY_BLOCK)
    n_blocks = -(-T // block)
    filled = lambda a: jnp.pad(a, ((0, n_blocks * block - T),)
                               + ((0, 0),) * (a.ndim - 1))
    topk = int(cfg.get("index_topk") or 0)
    selecting = bool(select and kind and T > topk)
    making = selecting and kind == "full"
    q_nope, q_rope = filled(q_nope), filled(q_rope)
    if making:
        qi, ki, wi = index_parts(h, c_q, p, cfg, dtype)
        qi, wi = filled(qi), filled(wi)
    elif selecting:
        selection = filled(selection)
    part = lambda a, i: jax.lax.dynamic_slice_in_dim(a, i * block, block, 0)

    def rows(i):
        row = i * block + jnp.arange(block)[:, None]
        seen = jnp.arange(T)[None, :] <= row
        if making:
            s = jnp.einsum("qhd,td->qht", part(qi, i), ki).astype(
                jnp.float32)
            index = jnp.sum(jax.nn.relu(s) * part(wi, i)[:, :, None], axis=1)
            seen = select_rows(index, seen, topk)
        elif selecting:
            seen = part(selection, i)
        sc = (jnp.einsum("qhd,khd->hqk", part(q_nope, i), k_nope)
              + jnp.einsum("qhd,kd->hqk", part(q_rope, i), k_r)).astype(
                  jnp.float32)
        sc = jnp.where(seen[None], sc * scale, -1e30)
        out = jnp.einsum("hqk,khd->qhd",
                         jax.nn.softmax(sc, -1).astype(dtype), v)
        return (out, seen) if making else out

    ctx = jax.lax.map(rows, jnp.arange(n_blocks))
    if making:
        ctx, selection = ctx[0], ctx[1].reshape(-1, T)[:T]
    elif selecting:
        selection = selection[:T]
    return ctx.reshape(-1, H * vd)[:T] @ p("o.w"), selection


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
                   stored=None, routes=None, select=True, selections=None):
    """Logits ``[len(positions), V]`` (float32) at ``positions`` of the
    sequence ``ids`` (1-D int array), every position seeing itself and,
    of everything before it, what its layer's selection keeps.  ``dtype``
    other than float32, ``stored`` (a narrower type the matrices are kept
    in) or ``select`` false (dense attention over every row: the
    selection switched off) is a CONTROL of the comparison that decides
    ``correct``, never the reference.  ``routes`` (a list) receives each
    expert layer's chosen indices [T, k]; ``selections`` (a list) each
    full layer's selection, bool [T, T] (None where the rows do not pass
    ``index_topk``)."""
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
        selection = None
        for i in range(cfg["num_hidden_layers"]):
            p = lambda name, cast=True, i=i: value(f"lat{i}_{name}", cast)
            kind = indexer_kind(cfg, i)
            out, selection = attention(_rms(x, p("norm1.scale"), eps), p,
                                       cfg, dtype, kind, selection, select)
            if selections is not None and kind == "full":
                selections.append(selection)
            x = x + out
            h = _rms(x, p("norm2.scale"), eps)
            if is_sparse_ffn(cfg, i):
                x = x + moe(h, p, cfg, dtype, routes)
            else:
                x = x + _gated(h, p("ffn_gate.w"), p("ffn_up.w"),
                               p("ffn_down.w"))
        x = _rms(x[jnp.asarray(positions)], value("lat_norm.scale"), eps)
        return (x @ value("lat_head.w")).astype(jnp.float32)
