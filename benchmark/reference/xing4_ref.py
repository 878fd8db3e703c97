"""Plain float32 reference of Xing4.0 (``model_type`` ``xing4_0``) as
``paddle_tpu.models.latent_moe`` builds it with ``hc_mult`` > 1 and its
multi-token-prediction module loaded: ONE forward pass over a whole
sequence, no kernels, no cache, no pages, no chunks, no drafting,
NON-absorbed attention (K and V of every head expanded from the latent),
matmul precision "highest".  It takes parameter VALUES by the program's
names and the configuration's numbers; ``paddle_tpu`` is not imported.

The residual is ``n = hc_mult`` STREAMS of ``C = hidden_size`` (mHC,
arXiv:2512.24880, on Hyper-Connections, arXiv:2409.19606): the
embedding's row is copied into the n streams, the streams are summed
before the final RMSNorm and the untied head.  EVERY sublayer ``F``
(attention, FFN; each with its own ``phi``, ``alpha``, ``b``) is wrapped::

    x'      = vec(x) / rms(vec(x))             over the n C values, no scale
    H~_pre  = a_pre  (x' phi_pre)  + b_pre     [n]
    H~_post = a_post (x' phi_post) + b_post    [n]
    H~_res  = a_res  mat(x' phi_res) + b_res   [n, n]  (row-major)
    H_pre = sigmoid(H~_pre)   H_post = 2 sigmoid(H~_post)   H_res = SK(H~_res)
    u = H_pre x     y = F(RMSNorm(u))     x <- H_res x + H_post^T y

``SK``: ``M = exp(clamp(H~_res, mhc_h_res_clamp_min, _max))``, then
``hc_sinkhorn_iters`` times: every COLUMN divided by (its sum +
``hc_eps``), then every ROW by (its sum + ``hc_eps``); written here as a
plain loop over matrices (the program runs it on n x n separate arrays).
``phi`` is stored ``[n C, n (n + 2)]``: columns ``0 .. n - 1`` pre, ``n ..
2n - 1`` post, the rest res.

F, attention (MLA): ``c_q = RMSNorm(h W_qa)``; ``[q_nope | q_rope] = c_q
W_qb`` a head; ``[c_kv | k_r] = h W_kva``; ``c_kv <- RMSNorm(c_kv)``;
``q_rope`` and the ONE shared ``k_r`` rotated with YaRN frequencies (pair
``i`` = lanes ``(i, i + rope / 2)``); ``[k_nope | v] = c_kv W_kvb`` a
head; ``score = (q_nope . k_nope + q_rope . k_r) (nope + rope)^-1/2 m^2``,
``m = 0.1 mscale_all_dim ln(factor) + 1``; causal softmax; ``W_o``.
F, FFN: the first ``first_k_dense_replace`` layers held ``W_d (silu(W_g
h) * W_u h)``; the others ``s = sigmoid(h W_r)`` in float32, the
``num_experts_per_tok`` largest of ``s + b``, weights
``routed_scaling_factor s_i / sum_chosen s``, gated experts over the
experts HELD (what the absent ones would add is left out, as in the
program) plus the shared expert.

The MTP module (DeepSeek-V3, arXiv:2412.19437 section 2.2), teacher-forced
(:func:`draft_logits`): row ``i`` takes ``h'_i = W_p [RMSNorm(h_i) ;
RMSNorm(E[t_{i+1}])]``, ``h_i`` the SUMMED streams the main model's final
norm takes; one MoE block of the model's own kind under its own two
wrappers (``h'`` copied into the n streams, the block's output summed),
the module's own final norm, the main model's head: the logits of the
token at ``i + 2``.

Everything quadratic in the rows is computed a block of query rows at a
time (18,432 rows fit beside the serving program on the chip).

Departures from the published model are listed in
``benchmark/configs/xing4.0_29b_a4b.json``; the controls of the
comparison that decides ``correct`` are :func:`forward_logits`'s
``dtype`` / ``stored`` (precision) and ``drop`` (``"mhc_static"``:
alpha = 0, the mappings do not move with the token; ``"sinkhorn_1"``: one
Sinkhorn round for ``hc_sinkhorn_iters``; ``"streams_mean"``: H_res = 1 /
n everywhere).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
#: the layer key of the MTP module's block (parameters ``lat_mtp_*``)
MTP = "_mtp"


def _matrix(name):
    """Names of the parameters a lower-precision CONTROL stores narrow:
    the matrices (vectors, norms, the router's bias and a wrapper's
    float32 parameters stay)."""
    return (name.endswith(".w") or name.endswith(("_wg", "_wu", "_wd"))
            or name == "lat_emb")


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


def _values(params, dtype, stored):
    def value(name, cast=True):
        w = params[name]
        if stored is not None and _matrix(name):
            w = _stored_as(w, stored)
        return w.astype(dtype) if cast and _matrix(name) else w
    return value


def _embed(params, ids, dtype, stored):
    rows = params["lat_emb"][ids]
    if stored is not None:
        rows = _stored_as(rows, stored, by_row=True)
    return rows.astype(dtype)


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


# -- rotary, YaRN -----------------------------------------------------------

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


# -- the sublayers ----------------------------------------------------------

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
    # query rows a block at a time; the last block is filled up with rows
    # of zeros, whose results are dropped (the keys are not padded)
    block = min(T, QUERY_BLOCK)
    n_blocks = -(-T // block)
    filled = lambda a: jnp.pad(
        a, ((0, n_blocks * block - T),) + ((0, 0),) * (a.ndim - 1))
    q_nope, q_rope = filled(q_nope), filled(q_rope)

    def rows(j):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, j * block, block, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, j * block, block, 0)
        sc = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
              + jnp.einsum("qhd,kd->hqk", qr, k_r)).astype(jnp.float32)
        row = j * block + jnp.arange(block)[:, None]
        sc = jnp.where(jnp.arange(T)[None, :] <= row, sc * scale, -1e30)
        return jnp.einsum("hqk,khd->qhd",
                          jax.nn.softmax(sc, -1).astype(dtype), v)

    ctx = jax.lax.map(rows, jnp.arange(n_blocks)).reshape(-1, H * vd)[:T]
    return ctx @ p("o.w")


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


def is_moe(cfg, i):
    return i == MTP or i >= cfg["first_k_dense_replace"]


def ffn(h, p, cfg, i, dtype, routes=None):
    if is_moe(cfg, i):
        return moe(h, p, cfg, dtype, routes)
    return _gated(h, p("ffn_gate.w"), p("ffn_up.w"), p("ffn_down.w"))


# -- hyper-connections ------------------------------------------------------

def sinkhorn(h_res, cfg, iters=None):
    """``SK`` of ``h_res`` [T, n, n] float32, a plain loop: columns over
    their sums, then rows over theirs, ``hc_eps`` added to each sum."""
    m = jnp.exp(jnp.clip(h_res, cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"]))
    eps = cfg["hc_eps"]
    for _ in range(cfg["hc_sinkhorn_iters"] if iters is None else iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def mappings(x, phi, alpha, bias, cfg, drop=()):
    """``(H_pre [T, n], H_post [T, n], H_res [T, n, n])`` of the streams
    ``x`` [T, n, C]; float32."""
    T, n, c = x.shape
    flat = x.reshape(T, n * c).astype(jnp.float32)
    normed = flat * jax.lax.rsqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
        + cfg["rms_norm_eps"])
    h = normed @ phi.astype(jnp.float32)                 # [T, n (n + 2)]
    if "mhc_static" in drop:
        h = jnp.zeros_like(h)
    pre = alpha[0] * h[:, :n] + bias[:n]
    post = alpha[1] * h[:, n:2 * n] + bias[n:2 * n]
    res = (alpha[2] * h[:, 2 * n:] + bias[2 * n:]).reshape(T, n, n)
    h_res = sinkhorn(res, cfg, 1 if "sinkhorn_1" in drop else None)
    if "streams_mean" in drop:
        h_res = jnp.full_like(h_res, 1.0 / n)
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), h_res


def wrapped(x, sublayer, p, which, cfg, drop=()):
    """One sublayer under its wrapper ``which`` (``"hc1"`` | ``"hc2"``):
    ``x`` [T, n, C] -> [T, n, C]."""
    pre, post, res = mappings(x, p(which + ".phi"), p(which + ".alpha"),
                              p(which + ".bias"), cfg, drop)
    xf = x.astype(jnp.float32)
    u = jnp.einsum("tn,tnc->tc", pre, xf).astype(x.dtype)
    y = sublayer(u).astype(jnp.float32)
    out = jnp.einsum("tij,tjc->tic", res, xf) + post[:, :, None] * y[:, None]
    return out.astype(x.dtype)


def _block(x, value, cfg, i, dtype, routes=None, drop=()):
    """Block ``i`` over the streams ``x`` [T, n, C]."""
    eps = cfg["rms_norm_eps"]
    p = lambda name, cast=True: value(f"lat{i}_{name}", cast)
    x = wrapped(x, lambda u: attention(_rms(u, p("norm1.scale"), eps), p,
                                       cfg, dtype), p, "hc1", cfg, drop)
    return wrapped(x, lambda u: ffn(_rms(u, p("norm2.scale"), eps), p, cfg,
                                    i, dtype, routes), p, "hc2", cfg, drop)


def _copy_in(h, cfg):
    return jnp.repeat(h[:, None, :], cfg["hc_mult"], axis=1)


def hidden(params, cfg, ids, dtype=jnp.float32, stored=None, routes=None,
           drop=()):
    """The SUMMED streams behind the last layer, [T, C]: what the final
    norm, and the MTP module, take."""
    value = _values(params, dtype, stored)
    x = _copy_in(_embed(params, ids, dtype, stored), cfg)
    for i in range(cfg["num_hidden_layers"]):
        x = _block(x, value, cfg, i, dtype, routes, drop)
    return jnp.sum(x.astype(jnp.float32), axis=1).astype(dtype)


def forward_logits(params, cfg, ids, positions, dtype=jnp.float32,
                   stored=None, routes=None, drop=()):
    """The MAIN model's logits ``[len(positions), V]`` (float32) at
    ``positions`` of the sequence ``ids`` (1-D int array), every position
    seeing itself and everything before it.  ``dtype`` other than
    float32, ``stored`` or ``drop`` make a CONTROL of the comparison that
    decides ``correct``, never the reference."""
    with jax.default_matmul_precision("highest"):
        value = _values(params, dtype, stored)
        x = hidden(params, cfg, ids, dtype, stored, routes, drop)
        x = _rms(x[jnp.asarray(positions)], value("lat_norm.scale"),
                 cfg["rms_norm_eps"])
        return (x @ value("lat_head.w")).astype(jnp.float32)


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
        both = jnp.concatenate([_rms(h, value("lat_mtp_hnorm.scale"), eps),
                                _rms(e, value("lat_mtp_enorm.scale"), eps)],
                               axis=-1)
        g = _block(_copy_in(both @ value("lat_mtp_proj.w"), cfg), value,
                   cfg, MTP, dtype)
        g = jnp.sum(g.astype(jnp.float32), axis=1).astype(dtype)
        g = _rms(g[jnp.asarray(positions)], value("lat_mtp_norm.scale"), eps)
        return (g @ value("lat_head.w")).astype(jnp.float32)
