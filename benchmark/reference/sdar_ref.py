"""Plain float32 reference of the block-diffusion mixture-of-experts LM
that ``paddle_tpu.models.block_moe`` builds (``sdar_moe``: the Qwen3-MoE
block, generated by diffusion over blocks): the forward pass over rows
that are handed to it whole, and the family's generation loop.  No
kernels, no pages, no buckets, no batching, matmul precision "highest".
It takes parameter VALUES by the program's names (the seeded bfloat16
matrices, cast up where they are used) and the configuration's numbers;
``paddle_tpu`` is not imported.

Layer ``i`` (pre-norm, no biases, eps ``rms_norm_eps``; ``h`` a row):
``a = RMSNorm(h; norm1)``; ``q = a W_q`` (H heads of D), ``k = a W_k``,
``v = a W_v`` (Hkv heads); per head ``q <- RMSNorm_D(q; qnorm)``, ``k <-
RMSNorm_D(k; knorm)``, then rotary on all D lanes, pairs ``(i, i + D/2)``
turned by ``pos * theta^(-2i/D)``; query head ``n`` reads K/V head ``n //
(H / Hkv)``; scores ``q.k / sqrt(D)``, float32 softmax over the columns
the mask allows; ``h <- h + ctx W_o``.  ``u = RMSNorm(h; norm2)``; ``p =
softmax_f32(u W_r)`` over all ``num_experts``; ``T`` = the
``num_experts_per_tok`` largest; ``w_e = p_e / sum_T p``; ``h <- h +
sum_{e in T} w_e W_d^e (silu(W_g^e u) * W_u^e u)`` (a loop over ALL the
experts, each over every row with the weight 0 where the router did not
choose it: no capacity, nothing dropped).  After the last layer
``RMSNorm(h; norm) W_head``.

The mask.  Positions are cut into blocks of ``block_length`` from 0.  A
row carries a position and a COPY number: copy 0 is the clean sequence
(every token committed), copy ``g > 0`` one block with some of its rows
masked (their input is the mask token's embedding).  A row sees a clean
row iff that row's block lies before its own, and every row of its own
block and copy: the family's training mask (the clean sequence joined to
its noised copy), of which the block-causal mask is the case of copy 0
alone.  The logits of a row predict that row's OWN token.

Generation (``generate``), as published for ``remasking_strategy
sequential`` with ``block_length / denoising_steps`` = 1 token a pass:
the prompt's full blocks are run once and their K/V stored; for each
block, denoising passes over its ``block_length`` rows against the stored
rows, each committing the leftmost masked position's greedy token, then
one more pass with every token committed whose K/V are stored for the
blocks behind it (the store pass).

Departures from the published model are listed in
``benchmark/configs/sdar_30b_a3b_chat.json``.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128


def _matrix(name):
    """Names of the parameters a lower-precision CONTROL stores narrow:
    the matrices (norm scales stay)."""
    return name.endswith(".w") or name.endswith(("_wg", "_wu", "_wd")) \
        or name == "blk_emb"


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
    """``x`` [R, n, D]: pairs ``(i, i + D/2)`` turned by ``positions *
    theta^(-2i/D)``."""
    D = x.shape[-1]
    half = D // 2
    freqs = float(theta) ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / D)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _sees(blocks, copies, key_blocks, key_copies):
    """[R, C]: a clean key of an earlier block, or a key of the row's own
    block and copy."""
    rb, rc = blocks[:, None], copies[:, None]
    kb, kc = key_blocks[None, :], key_copies[None, :]
    return ((kc == 0) & (kb < rb)) | ((kc == rc) & (kb == rb))


def attention(h, p, cfg, positions, copies, dtype, past=None):
    """``h`` [R, d] -> ([R, d], (k, v, blocks) of these rows).  ``past``
    (``generate``'s store): ``(k, v, blocks)`` of clean rows stored by
    earlier passes."""
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    R, eps = h.shape[0], cfg["rms_norm_eps"]
    q = _rms((h @ p("q.w")).reshape(R, H, D), p("qnorm.scale"), eps)
    k = _rms((h @ p("k.w")).reshape(R, Hkv, D), p("knorm.scale"), eps)
    v = (h @ p("v.w")).reshape(R, Hkv, D)
    q = _rope(q, positions, cfg["rope_theta"])
    k = _rope(k, positions, cfg["rope_theta"])
    blocks = positions // cfg["block_length"]
    mine = (k, v, blocks)
    key_copies = copies
    if past is not None:
        k, v = (jnp.concatenate([a, b]) for a, b in zip(past[:2], (k, v)))
        key_copies = jnp.concatenate([jnp.zeros_like(past[2]), copies])
        blocks_k = jnp.concatenate([past[2], blocks])
    else:
        blocks_k = blocks
    g = H // Hkv
    qg = q.reshape(R, Hkv, g, D)
    step = QUERY_BLOCK if R % QUERY_BLOCK == 0 else R

    def rows(i):
        at = i * step
        qb = jax.lax.dynamic_slice_in_dim(qg, at, step, 0)
        seen = _sees(jax.lax.dynamic_slice_in_dim(blocks, at, step),
                     jax.lax.dynamic_slice_in_dim(copies, at, step),
                     blocks_k, key_copies)
        sc = jnp.einsum("qkgd,ckd->kgqc", qb, k).astype(jnp.float32) \
            * D ** -0.5
        sc = jnp.where(seen[None, None], sc, -1e30)
        return jnp.einsum("kgqc,ckd->qkgd",
                          jax.nn.softmax(sc, -1).astype(dtype), v)

    ctx = jax.lax.map(rows, jnp.arange(R // step))
    return ctx.reshape(R, H * D) @ p("o.w"), mine


def route(u, p, cfg):
    """Expert indices [R, k] and weights [R, k], float32."""
    probs = jax.nn.softmax(u.astype(jnp.float32)
                           @ p("gate.w").astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w


def moe(u, p, cfg, dtype, routes=None):
    idx, w = route(u, p, cfg)
    if routes is not None:
        routes.append(idx)

    def expert(acc, inp):
        wg, wu, wd, e = inp
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)     # [R]
        out = (jax.nn.silu(u @ wg.astype(dtype)) * (u @ wu.astype(dtype))) \
            @ wd.astype(dtype)
        return acc + mine[:, None].astype(dtype) * out, None

    # the stacked experts are cast up one at a time, inside the loop
    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(u),
        (p("wg", cast=False), p("wu", cast=False), p("wd", cast=False),
         jnp.arange(cfg["num_experts"])))
    return out


def forward_rows(params, cfg, ids, positions, copies, rows,
                 dtype=jnp.float32, stored=None, routes=None, past=None):
    """Logits ``[len(rows), V]`` (float32) of the rows ``rows`` among
    those handed over (``ids``, ``positions``, ``copies``: 1-D int
    arrays, one entry a row; the module's head says what a row sees), and
    each layer's ``(k, v, blocks)`` of the rows.  ``past``: one such
    triple a layer, of clean rows stored earlier."""
    with jax.default_matmul_precision("highest"):
        def value(name, cast=True):
            w = params[name]
            if stored is not None and _matrix(name):
                w = _stored_as(w, stored)
            return w.astype(dtype) if cast else w

        x = params["blk_emb"][ids]
        if stored is not None:
            x = _stored_as(x, stored, by_row=True)
        x = x.astype(dtype)
        eps, kv = cfg["rms_norm_eps"], []
        for i in range(cfg["num_hidden_layers"]):
            p = lambda name, cast=True, i=i: value(f"blk{i}_{name}", cast)
            out, mine = attention(_rms(x, p("norm1.scale"), eps), p, cfg,
                                  positions, copies, dtype,
                                  None if past is None else past[i])
            kv.append(mine)
            x = x + out
            x = x + moe(_rms(x, p("norm2.scale"), eps), p, cfg, dtype,
                        routes)
        x = _rms(x[jnp.asarray(rows)], value("blk_norm.scale"), eps)
        return (x @ value("blk_head.w")).astype(jnp.float32), kv


def forward_logits(params, cfg, ids, positions, dtype=jnp.float32,
                   stored=None, routes=None):
    """For each ``t`` of ``positions``: the logits ``[V]`` of row ``t +
    1`` of the sequence ``ids`` (1-D int array) with the rows ``<= t``
    holding ``ids``, the rest of ``t + 1``'s block masked and the blocks
    before it final: what the generation loop computes before it commits
    position ``t + 1``.  One pass: the clean sequence (copy 0), joined to
    one copy of ``t + 1``'s block for each ``t``.  ``dtype`` other than
    float32, or ``stored`` (a narrower type the matrices are kept in), is
    a CONTROL of the comparison that decides ``correct``, never the
    reference.  ``routes`` (a list) receives each layer's chosen indices
    [rows, k]."""
    ids = jnp.asarray(ids, jnp.int32)
    T, L = ids.shape[0], cfg["block_length"]
    q = jnp.asarray(positions, jnp.int32) + 1                     # [P]
    n = q.shape[0]
    at = (q // L * L)[:, None] + jnp.arange(L, dtype=jnp.int32)   # [P, L]
    noised = jnp.where(at < q[:, None], ids[jnp.clip(at, 0, T - 1)],
                       jnp.int32(cfg["mask_token_id"]))
    copies = jnp.broadcast_to(1 + jnp.arange(n, dtype=jnp.int32)[:, None],
                              (n, L))
    logits, _ = forward_rows(
        params, cfg, jnp.concatenate([ids, noised.reshape(-1)]),
        jnp.concatenate([jnp.arange(T, dtype=jnp.int32), at.reshape(-1)]),
        jnp.concatenate([jnp.zeros(T, jnp.int32), copies.reshape(-1)]),
        T + jnp.arange(n) * L + q % L, dtype, stored, routes)
    return logits


_PASSES = {}     # one compiled pass per configuration (and row count)


def _pass_of(cfg):
    """``forward_rows`` over clean rows, compiled: the loop below makes
    the same few calls over and over."""
    key = json.dumps(cfg, sort_keys=True, default=str)
    if key not in _PASSES:
        _PASSES[key] = jax.jit(
            lambda params, ids, at, row, past: forward_rows(
                params, cfg, ids, at, jnp.zeros_like(ids), row, past=past))
    return _PASSES[key]


def generate(params, cfg, prompt, max_new_tokens):
    """The published block loop, greedy: the ``max_new_tokens`` tokens
    behind ``prompt`` (a list of ids).  The last block is run whole; what
    lies past ``max_new_tokens`` is dropped."""
    L, mask = cfg["block_length"], cfg["mask_token_id"]
    assert L == cfg["denoising_steps"], "one token a pass"
    forward = _pass_of(cfg)
    start, past = len(prompt) // L * L, None
    ints = lambda xs: jnp.asarray(xs, jnp.int32)
    if start:
        # the prompt's full blocks, once, under the block-causal mask
        _, past = forward(params, ints(prompt[:start]),
                          jnp.arange(start, dtype=jnp.int32), ints([0]),
                          None)
    block, out = list(prompt[start:]), []
    while len(out) < max_new_tokens:
        at = start + jnp.arange(L, dtype=jnp.int32)
        while len(block) < L:
            # a denoising pass: commit the leftmost masked position
            logits, _ = forward(
                params, ints(block + [mask] * (L - len(block))), at,
                ints([len(block)]), past)
            block.append(int(jnp.argmax(logits[0])))
            out.append(block[-1])
        # the store pass: every token committed; later blocks read its K/V
        _, kv = forward(params, ints(block), at, ints([0]), past)
        past = kv if past is None else [
            tuple(jnp.concatenate([a, b]) for a, b in zip(old, new))
            for old, new in zip(past, kv)]
        start, block = start + L, []
    return out[:max_new_tokens]
