"""Block-diffusion mixture-of-experts LM (the ``sdar_moe`` family: a
``qwen3_moe`` block generated by diffusion over blocks) on the generative
serving path: the same prefill + paged-decode program pair and bundle
layout as ``models/gen_lm.py``, ``hybrid_moe.py`` and ``latent_moe.py``.

Every layer is pre-norm, no biases: ``x <- x + Attn(RMSNorm(x))`` then
``x <- x + MoE(RMSNorm(x))``; a final RMSNorm precedes the untied head.

* **Attention.**  ``q = a W_q`` (H heads of D), ``k = a W_k``, ``v = a
  W_v`` (Hkv heads); per head ``q <- RMSNorm_D(q)``, ``k <- RMSNorm_D(k)``
  (QK-norm, one scale of D lanes a layer each) and then rotary on all D
  lanes (``ops/mla_ops.rope``, pairs ``(i, i + D/2)``, no scaling); query
  head ``n`` reads K/V head ``n // (H / Hkv)``.  The mask is BLOCK-causal:
  positions are cut into blocks of ``block_length`` from 0 and a row sees
  every row of its own block and of the blocks before it
  (``gqa_attention`` with a block width in the prefill; in the decode
  step ``paged_attention`` with a limit a row: the rows of a block read
  the live rows through their own block's end).
* **MoE.**  A float32 softmax router over all ``num_experts`` experts,
  the ``num_experts_per_tok`` largest, weights renormalised over the
  chosen (``moe_route`` with ``scoring="softmax"``, no bias, no
  scaling); gated experts ``W_d (silu(W_g u) * W_u u)`` through the one
  routed product (``moe_experts_gated``); no shared expert.

**Generation** (the family's block loop, ``remasking_strategy
sequential``, one token a pass).  A masked row's input is the mask
token's embedding and the logits of a row predict that row's OWN token.
The prefill is one forward over the prompt followed by mask rows to the
end of the block that position ``n`` lies in; its logits are row ``n``'s.
A decode step forwards ``2L`` rows a slot (``ops/block_ops.py``): half A,
the ``L`` rows of the block the slot is generating (its committed tokens
live on the device, the bundle's ``state_vars``), and half B, the block
behind it.  It writes A's K/V over the block's rows of the slot's pages
and returns the logits of the leftmost masked row, ONE row a slot through
the head.  While the token fed does not complete block A, half B is dead:
no K/V written, no routed expert, its output read by nothing.  The token
that completes A makes A the forward with every token committed, whose
K/V are what later blocks read, and B the next block with every row
masked, whose first row is the one picked: the published loop's store
pass rides with the opening pass of the next block, A's rows reading the
rows through A and B's the ``L`` more (the same block-causal mask the two
passes had).  A block of ``L`` takes ``L`` forwards, each yielding a token.

Matrices and activations are ``dtype`` (bfloat16) with float32
accumulation; router scores, norm statistics, rotary angles, softmax and
logits are float32; the page pool is ``dtype``.

``export_block_model`` writes ``prefill/``, ``decode/`` and
``gen_meta.json`` (with ``block_length`` and ``mask_token_id``, which
tell ``GenPredictor`` and ``GenScheduler`` that a step carries a block);
``block_moe_train_program`` is the teacher-forced graph over the same
parameter names under the block-causal mask (the model-zoo lint gate's
view of this model).
"""

from __future__ import annotations

import paddle_tpu.layers as layers
from paddle_tpu.models.decoder import (DECODE_STATS, PAGE_LEN_DEFAULT,
                                       DecoderConfig, decode_fetches,
                                       decode_inputs, decoder_layer, embed,
                                       export_bundle, group, head_norm,
                                       last_row, live_rows, logits, matrix, op,
                                       persistable, prefill_inputs,
                                       program_role, routed_experts,
                                       train_inputs, train_loss)

__all__ = ["BlockMoEConfig", "build_prefill_program",
           "build_paged_decode_program", "block_moe_train_program",
           "export_block_model", "paged_cache_var_names", "STATE_VAR"]

#: the per-slot state: the tokens committed to the block a slot generates
STATE_VAR = "blk_block_tokens"


class BlockMoEConfig(DecoderConfig):
    """Toy-scale defaults; ``from_dict`` takes the published keys of an
    ``sdar_moe`` ``config.json`` and the generation settings its
    ``generation`` code takes (``block_length``, ``mask_token_id``)."""
    vocab_size = 64
    hidden_size = 64
    num_hidden_layers = 2
    eps = 1e-6                       # rms_norm_eps
    num_attention_heads = 4
    num_key_value_heads = 2
    head_dim = 16
    rope_theta = 1000000.0
    num_experts = 8
    num_experts_per_tok = 2
    moe_intermediate_size = 32
    norm_topk_prob = True
    block_length = 4
    mask_token_id = 63
    dtype = "bfloat16"
    max_len = 64
    eos_id = -1

    _KEYS = {"rms_norm_eps": "eps"}


def paged_cache_var_names(hp):
    """Page-pool tensors, (k, v) a layer, in layer order."""
    return [f"blk{i}_paged_{r}" for i in range(int(hp.num_hidden_layers))
            for r in "kv"]


def _attention(h, hp, i, pos, mask=None, paged=None):
    """Prefill (block-causal; returns the masked K/V that seed the pool)
    or the paged decode of a slot's two blocks of rows (``paged`` =
    pools, page table, the rows' end, a limit a row)."""
    d = int(hp.hidden_size)
    H, Hkv, D = (int(hp.num_attention_heads), int(hp.num_key_value_heads),
                 int(hp.head_dim))
    rope = {"rope_dim": D, "theta": float(hp.rope_theta)}
    q = layers.matmul(h, matrix(hp, f"blk{i}_q.w", [d, H * D]))
    k = layers.matmul(h, matrix(hp, f"blk{i}_k.w", [d, Hkv * D]))
    v = layers.matmul(h, matrix(hp, f"blk{i}_v.w", [d, Hkv * D]))
    q = op("rope", {"X": head_norm(q, f"blk{i}_qnorm.scale", hp, H, D),
                    "Pos": pos}, {"Out": hp.dtype},
           {"n_head": H, **rope})["Out"]
    k = op("rope", {"X": head_norm(k, f"blk{i}_knorm.scale", hp, Hkv, D),
                    "Pos": pos}, {"Out": hp.dtype},
           {"n_head": Hkv, **rope})["Out"]
    attrs = {"n_head": H, "n_kv_head": Hkv, "scale": float(D) ** -0.5}
    kv = []
    if paged is None:
        mask_t = layers.cast(mask, hp.dtype)
        k = layers.elementwise_mul(k, mask_t, axis=0)
        v = layers.elementwise_mul(v, mask_t, axis=0)
        kv = [k, v]
        ctx = op("gqa_attention", {"Q": q, "K": k, "V": v, "Mask": mask},
                 {"Out": hp.dtype},
                 {**attrs, "block": int(hp.block_length)})["Out"]
    else:
        pk, pv, page_table, end, row_lens = paged
        ctx = op("paged_attention",
                 {"Q": q, "K": k, "V": v, "KCache": pk, "VCache": pv,
                  "PageTable": page_table, "Lens": end,
                  "RowLens": row_lens},
                 {"Out": hp.dtype, "KCacheOut": pk, "VCacheOut": pv},
                 attrs)["Out"]
    return layers.matmul(ctx, matrix(hp, f"blk{i}_o.w", [H * D, d])), kv


def _layer(x, hp, i, pos, lens, mask=None, paged=None):
    """One layer; returns ``(x, [k, v] or [], stats)``.  ``lens`` [rows,
    1] int32: a row with 0 (a free slot's, a pad row) takes no routed
    expert."""
    E = int(hp.num_experts)
    return decoder_layer(
        x, hp, f"blk{i}",
        lambda h: _attention(h, hp, i, pos, mask=mask, paged=paged),
        # a softmax router, no bias, no scaling, no shared expert.  Every
        # expert is held, so every assignment lands: the sorted rows go
        # through the grouped product in one (chunk_rows 0)
        lambda h: routed_experts(
            h, hp, f"blk{i}", lens, experts=E, held=E, expert_offset=0,
            scaling=1.0, scoring="softmax", bias=False, chunk_rows=0),
        routed=True)


@program_role("gen_prefill")
def build_prefill_program(hp):
    """The prefill forward in the CURRENT program guard.

    Feeds (length-dynamic; the predictor pads to a bucket +
    ``block_length`` rows): ``gen_ids`` [1, T] int32 (the prompt, then
    mask tokens to the end of the block that position ``n`` = the
    prompt's length lies in), ``gen_pos`` [1, T] int32 (0 .. T-1),
    ``gen_mask`` [1, T] f32 (1 on those rows, first), ``gen_last``
    [1, T] f32 (one-hot of row ``n``, whose logits predict its own
    token).  Fetches ``[logits [1, V], k, v a layer [1, T, Hkv*D] ...,
    block tokens [1, block_length] int32]``: the K/V zeroed on pad
    rows; the full blocks' are final, the open block's are written over
    by the decode steps that follow."""
    ids, pos, mask, last = prefill_inputs()
    lens = live_rows(mask)
    x = embed(ids, hp, "blk")
    kv = []
    for i in range(int(hp.num_hidden_layers)):
        x, new, _ = _layer(x, hp, i, pos, lens, mask=mask)
        kv += new
    lasth = last_row(x, last, hp)
    with group("head"):
        tail = op("block_tail", {"Ids": ids, "Last": last},
                  {"Out": "int32"},
                  {"block_length": int(hp.block_length)})["Out"]
    return (["gen_ids", "gen_pos", "gen_mask", "gen_last"],
            [logits(lasth, hp, "blk")] + kv + [tail])


def block_moe_train_program(seq_len, hp: BlockMoEConfig = None):
    """Teacher-forced forward over ONE clean sequence under the
    block-causal mask, in the current program guard, over the serving
    programs' parameter names; also the model-zoo lint gate's view of
    this model (the family's training objective, the clean sequence
    joined to a noised copy, is a training matter the serving path has
    no part in).  Returns ``(avg_cost, feed_names)``; feeds ``gen_ids``
    / ``gen_labels`` [1, T] int32."""
    hp = hp or BlockMoEConfig()
    ids, labels, rows = train_inputs(seq_len, "pos", "mask", "lens")
    x = embed(ids, hp, "blk")
    for i in range(int(hp.num_hidden_layers)):
        x, _, _ = _layer(x, hp, i, rows["pos"], rows["lens"],
                         mask=rows["mask"])
    return train_loss(x, labels, hp, "blk")


@program_role("gen_decode")
def build_paged_decode_program(hp, num_slots, page_len, num_pages):
    """The decode step over ``2L`` rows a slot (``L = block_length``:
    the block a slot is generating and the block behind it), in the
    CURRENT program guard.

    Feeds: ``gen_token`` [S, 1] int32 (the newest committed token: the
    device's own pick, or the host's after an admission), ``gen_pos``
    [S, 1] int32 (its position), ``gen_page_table`` [S, P] int32,
    ``gen_lens`` [S, 1] int32 (rows through the END of the block that
    ``gen_pos`` lies in; 0 = free slot: nothing is written).  The token
    is committed to its block; where it completes the block, the block
    is stored and the next one opened in the same forward
    (``ops/block_ops.py``), and the page table must cover ``gen_lens +
    L`` rows.  Persistable state, updated in place: the page pools
    ``[num_pages, page_len, Hkv*D]`` in ``hp.dtype`` and the slots'
    block tokens ``[S, L]`` int32.  Fetches ``[logits [S, V] of each
    slot's leftmost masked row (ONE row a slot goes through the head),
    stats [n_layer, 3]]``."""
    S, L = int(num_slots), int(hp.block_length)
    token, pos, page_table, lens = decode_inputs(S)
    row = int(hp.num_key_value_heads) * int(hp.head_dim)
    pools = {n: persistable(n, [int(num_pages), int(page_len), row],
                            hp.dtype) for n in paged_cache_var_names(hp)}
    state = persistable(STATE_VAR, [S, L], "int32")
    with group("embed"):
        rows = op("block_rows",
                  {"Token": token, "Pos": pos, "Lens": lens, "Block": state},
                  {"Ids": "int32", "RowPos": "int32", "RowLens": "int32",
                   "Pick": "float32", "End": "int32", "BlockOut": state},
                  {"mask_id": int(hp.mask_token_id)})
    x = embed(rows["Ids"], hp, "blk", lead=[S, 2 * L])
    stats = []
    for i in range(int(hp.num_hidden_layers)):
        x, _, st = _layer(x, hp, i, rows["RowPos"], rows["RowLens"],
                          paged=(pools[f"blk{i}_paged_k"],
                                 pools[f"blk{i}_paged_v"], page_table,
                                 rows["End"], rows["RowLens"]))
        stats.append(st)
    with group("head"):
        picked = layers.matmul(layers.cast(rows["Pick"], hp.dtype), x)
    return (["gen_token", "gen_pos", "gen_page_table", "gen_lens"],
            decode_fetches(picked, stats, S, hp, "blk"))


def export_block_model(dirname, hp: BlockMoEConfig = None, num_slots=8,
                       prompt_buckets=None, page_len=PAGE_LEN_DEFAULT,
                       num_pages=None, page_buckets=None):
    """Export a generation bundle (``decoder.export_bundle``).
    ``gen_meta.json`` names the block tokens as the bundle's
    ``state_vars`` and carries ``block_length`` and ``mask_token_id``.
    ``page_len`` and ``max_len`` must be multiples of ``block_length``
    (a block never straddles a page).  Returns ``dirname``."""
    hp = hp or BlockMoEConfig()
    L = int(hp.block_length)
    page_len = max(1, min(int(page_len), int(hp.max_len)))
    if page_len % L or int(hp.max_len) % L:
        raise ValueError(
            f"page_len {page_len} and max_len {hp.max_len} must be "
            f"multiples of block_length {L}")
    return export_bundle(
        dirname, hp, "block_moe.export_block_model",
        lambda *pool: build_prefill_program(hp),
        lambda *pool: build_paged_decode_program(hp, *pool),
        paged_cache_var_names(hp), hp.num_hidden_layers,
        num_slots=num_slots, prompt_buckets=prompt_buckets,
        page_len=page_len, num_pages=num_pages, page_buckets=page_buckets,
        state_vars=[STATE_VAR],
        sections={"decode_stats": DECODE_STATS, "block_length": L,
                  "mask_token_id": int(hp.mask_token_id)})
