"""Latent-attention / shared-expert mixture-of-experts causal LM (the
``deepseek_v3`` / ``kimi_k2`` families' block) on the generative serving
path: the same prefill + paged-decode program pair and bundle layout as
``models/gen_lm.py`` and ``models/hybrid_moe.py``, its prefill ONE CHUNK
of a prompt over the slot's own pools as ``models/window_moe.py``'s.

Every layer is pre-norm and holds two sublayers, ``x <- x +
MLA(RMSNorm(x))`` then ``x <- x + FFN(RMSNorm(x))``; a final RMSNorm
precedes the untied head.

* **MLA** (``ops/mla_ops.py``).  ``c_q = RMSNorm(h W_qa)``, ``[q_nope |
  q_rope] = c_q W_qb`` a head; ``[c_kv | k_r] = h W_kva``, ``c_kv <-
  RMSNorm(c_kv)``; ``q_rope`` and the ONE shared ``k_r`` are rotated
  (YaRN frequencies).  What is cached a token a layer is the row ``[c_kv
  | k_r]`` and nothing else, stored ``latent_row`` wide (the next
  multiple of 128 lanes, zeros behind: the chip's DMA takes whole vregs,
  and a 576-wide array is laid out 640 wide in its memory anyway).  TWO
  attention forms over the same weights: a whole sequence (the training
  forward) EXPANDS K and V of every head from the latent
  (``mla_attention``); the serving programs ABSORB ``W_kvb`` into the
  query and out of the context and attend over the cached rows as they
  are, reading each once for scores and values: a chunk of a prompt
  writes its rows into the slot's pages and attends the pages' rows
  through its own (``mla_attention_chunk``), the decode step does with
  its one row (``mla_absorb``, ``paged_attention_latent``).
* **Learned sparse attention** (``ops/dsa_ops.py``; a configuration
  with ``index_topk``, the ``glm_moe_dsa`` family).  A layer whose
  ``indexer_types`` entry is ``"full"`` holds an indexer: it scores
  every cached row for the query row (``dsa_index`` /
  ``dsa_index_chunk`` / ``dsa_index_paged``; its 128-lane key a token is
  cached in a SECOND page pool under the same page table) and keeps the
  ``index_topk`` best (``dsa_select``); the layer's attention, and that
  of the ``"shared"`` layers after it, runs over the selected rows and
  nowhere else.  A row's selection depends on its own query and the keys
  at or before it alone, so nothing but the two pools crosses a chunk's
  edge.  Up to ``index_topk`` rows the selection is the identity.  A
  configuration without ``index_topk`` builds none of this.
* **FFN**.  The first ``first_k_dense_replace`` layers: ``W_d (silu(W_g
  h) * W_u h)``, width ``intermediate_size`` (or the layers that
  ``mlp_layer_types`` calls ``"dense"``).  The others: a sigmoid
  top-k router over ALL the model's experts on the full hidden state
  (``moe_route``; the correction bias moves the choice only), gated
  routed experts of width ``moe_intermediate_size`` over the experts
  HELD (``experts_held`` from ``expert_offset``: one chip's share of an
  expert-parallel deployment; ``moe_experts_gated``, a routed product),
  plus one shared expert on every token.

Matrices and activations are ``dtype`` (bfloat16) with float32
accumulation; router scores, norm statistics, rotary angles, softmax and
logits are float32; the latent pool is ``dtype``.

``export_latent_model`` writes ``prefill/`` (the chunk program: a prompt
runs as chunks of ``prefill_chunks`` rows, ``gen_meta.json``), ``decode/``
and ``gen_meta.json``; ``latent_moe_train_program`` is the teacher-forced
training graph over the same parameter names (the model-zoo lint gate's
view of this model).  The chunk program feeds ``gen_ids``, ``gen_pos``,
``gen_mask``, ``gen_last``, ``gen_page_table`` and fetches ``[logits]``;
the decode step fetches ``[logits, stats]`` with ``stats``
``[n_moe_layers, 3]`` int32 (``decode_stats``, as ``hybrid_moe``).
"""

from __future__ import annotations

import paddle_tpu.layers as layers
from paddle_tpu.models.decoder import (DECODE_STATS, PAGE_LEN_DEFAULT,
                                       DecoderConfig, chunk_rows, data,
                                       decode_fetches, decode_inputs,
                                       decoder_layer, embed,
                                       export_bundle, gated_ffn,
                                       last_row, live_rows, logits, matrix, op,
                                       persistable, prefill_inputs,
                                       program_role, rms, routed_experts,
                                       train_inputs, train_loss, vector)
from paddle_tpu.ops.mla_ops import yarn_mscale

__all__ = ["LatentMoEConfig", "build_chunk_program",
           "build_paged_decode_program", "latent_moe_train_program",
           "export_latent_model", "paged_cache_var_names"]


class LatentMoEConfig(DecoderConfig):
    """Toy-scale defaults; ``from_dict`` takes the published keys of a
    ``kimi_k2`` / ``deepseek_v3`` ``config.json``."""
    vocab_size = 64
    hidden_size = 64
    num_hidden_layers = 3
    first_k_dense_replace = 1
    eps = 1e-5                       # rms_norm_eps
    # MLA
    num_attention_heads = 4
    q_lora_rank = 48
    kv_lora_rank = 32
    qk_nope_head_dim = 16
    qk_rope_head_dim = 8
    v_head_dim = 16
    rope_theta = 10000.0
    rope_scaling = None              # the published group, type "yarn"
    rope_parameters = None           # or this group: rope_theta inside
    # learned sparse attention (None: every row is attended)
    index_topk = None
    index_n_heads = 4
    index_head_dim = 16
    indexer_types = None             # a layer: "full" | "shared"
    mlp_layer_types = None           # a layer: "dense" | "sparse"
    layer_offset = 0                 # the published layer that is layer 0
    # FFN
    intermediate_size = 96
    moe_intermediate_size = 32
    n_routed_experts = 16
    n_shared_experts = 1
    num_experts_per_tok = 2
    routed_scaling_factor = 2.5
    norm_topk_prob = True
    experts_held = None              # None: all of them
    expert_offset = 0
    dtype = "bfloat16"
    max_len = 64
    eos_id = -1

    _KEYS = {"rms_norm_eps": "eps"}

    @classmethod
    def from_dict(cls, cfg):
        hp = super().from_dict(cfg)
        if hp.rope_parameters and "rope_theta" in hp.rope_parameters:
            hp.rope_theta = hp.rope_parameters["rope_theta"]
        return hp

    @property
    def latent_row(self):
        """Lanes of the cached row: ``[c_kv | k_r]`` and zeros up to the
        next multiple of 128."""
        return -(-(int(self.kv_lora_rank) + int(self.qk_rope_head_dim))
                 // 128) * 128

    @property
    def rope_attrs(self):
        rs = dict(self.rope_scaling or {})
        factor = float(rs.get("factor", 1.0))
        return {"rope_dim": int(self.qk_rope_head_dim),
                "theta": float(self.rope_theta), "factor": factor,
                "original_max": int(rs.get(
                    "original_max_position_embeddings", 4096)),
                "beta_fast": float(rs.get("beta_fast", 32)),
                "beta_slow": float(rs.get("beta_slow", 1)),
                "mscale": yarn_mscale(factor, rs.get("mscale", 1.0))
                / yarn_mscale(factor, rs.get("mscale_all_dim", 0.0))}

    @property
    def softmax_scale(self):
        """``(nope + rope)^-1/2 m^2``, ``m`` YaRN's temperature over all
        dimensions."""
        rs = dict(self.rope_scaling or {})
        m = yarn_mscale(float(rs.get("factor", 1.0)),
                        rs.get("mscale_all_dim", 0.0))
        return (int(self.qk_nope_head_dim)
                + int(self.qk_rope_head_dim)) ** -0.5 * m * m

    def is_moe(self, i):
        if self.mlp_layer_types:
            return self.mlp_layer_types[int(self.layer_offset) + i] \
                == "sparse"
        return i >= int(self.first_k_dense_replace)

    def indexer(self, i):
        """``"full"`` (the layer scores and selects), ``"shared"`` (it
        uses the selection of the nearest full layer before it) or None
        (no sparse attention; also a shared layer with no full layer
        before it in the layers held)."""
        if not self.index_topk:
            return None
        kinds = self.indexer_types
        at = int(self.layer_offset)
        kind = kinds[at + i] if kinds else "full"
        if kind == "full" or any(
                not kinds or kinds[at + j] == "full" for j in range(i)):
            return kind
        return None

    @property
    def full_layers(self):
        return [i for i in range(int(self.num_hidden_layers))
                if self.indexer(i) == "full"]

    @property
    def moe_layers(self):
        return [i for i in range(int(self.num_hidden_layers))
                if self.is_moe(i)]


def paged_cache_var_names(hp):
    """Page-pool tensors: ONE a layer (the latent row), in layer order,
    then one a layer that holds an indexer (its key row)."""
    return [f"lat{i}_paged_c" for i in range(int(hp.num_hidden_layers))] \
        + [f"lat{i}_paged_ik" for i in hp.full_layers]


def _indexer(h, c_q, hp, i, pos, mask=None, paged=None):
    """The indexer of a ``full`` layer, in :func:`_attention`'s three
    forms: over a whole sequence (``mask`` alone), ONE CHUNK of a prompt
    (``mask`` and ``paged`` = key pool, page table [1, P]) or the decode
    step (``paged`` = key pool, page table, lens).  Returns the
    selection."""
    d, Hi, Di = (int(hp.hidden_size), int(hp.index_n_heads),
                 int(hp.index_head_dim))
    k = int(hp.index_topk)
    inputs = {"Cq": c_q, "X": h, "Pos": pos,
              "Wq": matrix(hp, f"lat{i}_idx_qb.w",
                           [int(hp.q_lora_rank), Hi * Di]),
              "Wk": matrix(hp, f"lat{i}_idx_k.w", [d, Di]),
              "KScale": vector(f"lat{i}_idx_knorm.scale", Di, 1.0),
              "KBias": vector(f"lat{i}_idx_knorm.bias", Di, 0.0),
              "Ww": matrix(hp, f"lat{i}_idx_w.w", [d, Hi])}
    attrs = {"n_head": Hi, "rope_dim": int(hp.qk_rope_head_dim),
             "theta": float(hp.rope_theta), "top_k": k}
    if paged is None:
        scores = op("dsa_index", inputs, {"Key": hp.dtype,
                                          "Scores": "float32"},
                    attrs)["Scores"]
        return op("dsa_select", {"Scores": scores, "Mask": mask},
                  {"Select": "int8"}, {"top_k": k})["Select"]
    pool, page_table, *lens = paged
    if not lens:
        scores = op("dsa_index_chunk",
                    {**inputs, "Mask": mask, "Cache": pool,
                     "PageTable": page_table},
                    {"Scores": "float32", "CacheOut": pool},
                    attrs)["Scores"]
        return op("dsa_select", {"Scores": scores, "Mask": mask,
                                 "Pos": pos},
                  {"Select": "int8"}, {"top_k": k})["Select"]
    scores = op("dsa_index_paged",
                {**inputs, "Cache": pool, "PageTable": page_table,
                 "Lens": lens[0]},
                {"Scores": "float32", "CacheOut": pool}, attrs)["Scores"]
    return op("dsa_select", {"Scores": scores, "Lens": lens[0]},
              {"Select": "int32"}, {"top_k": k})["Select"]


def _attention(h, hp, i, pos, mask=None, paged=None, select=None,
               index_pool=None):
    """MLA, one of three forms.  ``mask`` alone: a whole sequence,
    expanded, nothing cached (the training forward).  ``mask`` and
    ``paged`` = (pool, page table [1, P]): ONE CHUNK of a prompt over the
    slot's own pages, which it writes and reads as they are cached.
    ``paged`` = (pool, page table, lens): the absorbed paged decode.
    ``select``: the selection a ``shared`` layer attends under; a
    ``full`` layer makes its own (``index_pool``: its key pool in the
    serving forms).  Returns ``(out, selection)``."""
    d, H = int(hp.hidden_size), int(hp.num_attention_heads)
    L, R = int(hp.kv_lora_rank), int(hp.qk_rope_head_dim)
    nope, vd = int(hp.qk_nope_head_dim), int(hp.v_head_dim)
    rope = hp.rope_attrs
    c_q = rms(layers.matmul(h, matrix(hp, f"lat{i}_qa.w",
                                      [d, int(hp.q_lora_rank)])),
              f"lat{i}_qnorm.scale", hp)
    q = layers.matmul(c_q, matrix(hp, f"lat{i}_qb.w",
                                  [int(hp.q_lora_rank), H * (nope + R)]))
    if hp.indexer(i) == "full":
        select = _indexer(h, c_q, hp, i, pos, mask=mask,
                          paged=paged and (index_pool,) + tuple(paged[1:]))
    elif hp.indexer(i) is None:
        select = None
    sparse = {} if select is None else {"Select": select}
    sparse_attrs = {} if select is None else {
        "select_top_k": int(hp.index_topk)}
    q = op("rope", {"X": q, "Pos": pos}, {"Out": hp.dtype},
           {"n_head": H, **rope})["Out"]
    kva = layers.matmul(h, matrix(hp, f"lat{i}_kva.w", [d, L + R]))
    c_kv, k_r = layers.split(kva, [L, R], dim=2)
    c_kv = rms(c_kv, f"lat{i}_kvnorm.scale", hp)
    k_r = op("rope", {"X": k_r, "Pos": pos}, {"Out": hp.dtype},
             {"n_head": 1, **rope})["Out"]
    row = layers.concat([c_kv, k_r], axis=2)
    if hp.latent_row > L + R:
        row = layers.pad(row, [0, 0, 0, 0, 0, hp.latent_row - L - R])
    w_kvb = matrix(hp, f"lat{i}_kvb.w", [L, H * (nope + vd)])
    attrs = {"n_head": H, "nope_dim": nope, "v_dim": vd}
    scale = float(hp.softmax_scale)
    if paged is None:
        row = layers.elementwise_mul(row, layers.cast(mask, hp.dtype),
                                     axis=0)
        ctx = op("mla_attention", {"Q": q, "Latent": row, "Wkvb": w_kvb,
                                   "Mask": mask, **sparse},
                 {"Out": hp.dtype},
                 {**attrs, "rope_dim": R, "scale": scale,
                  **sparse_attrs})["Out"]
    elif mask is not None:
        pool, page_table = paged
        ctx = op("mla_attention_chunk",
                 {"Q": q, "Latent": row, "Wkvb": w_kvb, "Cache": pool,
                  "PageTable": page_table, "Pos": pos, "Mask": mask,
                  **sparse},
                 {"Out": hp.dtype, "CacheOut": pool},
                 {**attrs, "rope_dim": R, "scale": scale,
                  **sparse_attrs})["Out"]
    else:
        pool, page_table, lens = paged
        q_lat = op("mla_absorb", {"X": q, "Wkvb": w_kvb},
                   {"Out": hp.dtype},
                   {**attrs, "side": "q",
                    "pad": hp.latent_row - L - R})["Out"]
        ctx = op("paged_attention_latent",
                 {"Q": q_lat, "Row": row, "Cache": pool,
                  "PageTable": page_table, "Lens": lens, **sparse},
                 {"Out": hp.dtype, "CacheOut": pool},
                 {"n_head": H, "v_width": L, "scale": scale,
                  **sparse_attrs})["Out"]
        ctx = op("mla_absorb", {"X": ctx, "Wkvb": w_kvb},
                 {"Out": hp.dtype}, {**attrs, "side": "o"})["Out"]
    return layers.matmul(ctx, matrix(hp, f"lat{i}_o.w", [H * vd, d])), \
        select


def _ffn(h, hp, i, lens):
    """Layer ``i``'s feed-forward: the dense SwiGLU, or routed experts
    over the share held + the shared expert.  Returns ``(out, the
    experts' stats or None)``."""
    if not hp.is_moe(i):
        return gated_ffn(h, hp, f"lat{i}_ffn",
                         int(hp.intermediate_size)), None
    routed, stats = routed_experts(
        h, hp, f"lat{i}", lens, experts=int(hp.n_routed_experts),
        held=hp.held, expert_offset=hp.expert_offset,
        scaling=hp.routed_scaling_factor)
    shared = gated_ffn(h, hp, f"lat{i}_sh", int(hp.moe_intermediate_size)
                       * int(hp.n_shared_experts))
    return routed + shared, stats


def _layer(x, hp, i, pos, lens, mask=None, paged=None, select=None,
           index_pool=None):
    """One layer; returns ``(x, stats or None, selection)``: the
    selection is the layer's own where it holds an indexer, else the one
    it was handed.  ``lens`` [rows, 1] int32: a row with 0 (a free slot,
    a pad row) takes no routed expert."""
    x, select, stats = decoder_layer(
        x, hp, f"lat{i}",
        lambda h: _attention(h, hp, i, pos, mask=mask, paged=paged,
                             select=select, index_pool=index_pool),
        lambda h: _ffn(h, hp, i, lens), routed=hp.is_moe(i))
    return x, stats, select


def _pools(hp, page_len, num_pages):
    """The persistable page pools of the CURRENT program, ``{name:
    var}``, all ``hp.dtype``: one latent pool a layer ``[num_pages,
    page_len, latent_row]`` and one index-key pool a layer that holds an
    indexer ``[num_pages, page_len, index_head_dim]``."""
    return {name: persistable(
        name, [int(num_pages), int(page_len),
               int(hp.index_head_dim) if name.endswith("_ik")
               else hp.latent_row], hp.dtype)
        for name in paged_cache_var_names(hp)}


@program_role("gen_chunk")
def build_chunk_program(hp, num_slots, page_len, num_pages):
    """The prefill of ONE CHUNK of a prompt in the CURRENT program guard.

    Feeds (length-dynamic; the predictor pads to a chunk rung), the
    names and meanings of ``window_moe.build_chunk_program``'s:
    ``gen_ids`` [1, C] int32, ``gen_pos`` [1, C] int32 (the rows'
    positions ``P .. P + C - 1``), ``gen_mask`` [1, C] f32 (1 = real
    token, real tokens first), ``gen_last`` [1, C] f32 (one-hot of the
    prompt's last row where this chunk holds it, else zeros) and
    ``gen_page_table`` [1, P] int32 (the slot's row, P bucketed by the
    predictor and covering the chunk's last real row); no ``gen_slot``:
    nothing here is kept a slot.  Persistable state, read and updated in
    place, as the decode step's: the latent pools and the index-key
    pools.  Fetches ``[logits [1, V]]`` (of the row ``gen_last``
    names)."""
    ids, pos, mask, last = prefill_inputs()
    page_table = data("gen_page_table", [1, -1], "int32")
    pools = _pools(hp, page_len, num_pages)
    lens = live_rows(mask)
    x = embed(ids, hp, "lat")
    select = None
    for i in range(int(hp.num_hidden_layers)):
        x, _, select = _layer(
            x, hp, i, pos, lens, mask=mask,
            paged=(pools[f"lat{i}_paged_c"], page_table), select=select,
            index_pool=pools.get(f"lat{i}_paged_ik"))
    return (["gen_ids", "gen_pos", "gen_mask", "gen_last",
             "gen_page_table"], [logits(last_row(x, last, hp), hp, "lat")])


def latent_moe_train_program(seq_len, hp: LatentMoEConfig = None):
    """Teacher-forced training forward over ONE sequence in the current
    program guard (the ops take one prompt at a time), over the serving
    programs' parameter names and the prefill's expanded attention; also
    the model-zoo lint gate's view of this model.  Returns ``(avg_cost,
    feed_names)``; feeds ``gen_ids`` / ``gen_labels`` [1, T] int32."""
    hp = hp or LatentMoEConfig()
    ids, labels, rows = train_inputs(seq_len, "pos", "mask", "lens")
    x = embed(ids, hp, "lat")
    select = None
    for i in range(int(hp.num_hidden_layers)):
        x, _, select = _layer(x, hp, i, rows["pos"], rows["lens"],
                              mask=rows["mask"], select=select)
    return train_loss(x, labels, hp, "lat")


@program_role("gen_decode")
def build_paged_decode_program(hp, num_slots, page_len, num_pages):
    """The single-token decode step in the CURRENT program guard.

    Feeds: ``gen_token`` [S, 1] int32, ``gen_pos`` [S, 1] int32 (the
    token's position), ``gen_page_table`` [S, P] int32 (P bucketed by
    the predictor), ``gen_lens`` [S, 1] int32 (rows INCLUDING the current
    token; 0 = free slot: no page is written).  Persistable state,
    updated in place: one latent pool a layer, ``[num_pages, page_len,
    latent_row]`` in ``hp.dtype``, and one index-key pool a layer that
    holds an indexer, ``[num_pages, page_len, index_head_dim]``.  Fetches
    ``[logits [S, V], stats [n_moe, 3]]``."""
    S = int(num_slots)
    token, pos, page_table, lens = decode_inputs(S)
    pools = _pools(hp, page_len, num_pages)
    x = embed(token, hp, "lat", lead=[S, 1])
    stats, select = [], None
    for i in range(int(hp.num_hidden_layers)):
        x, st, select = _layer(
            x, hp, i, pos, lens,
            paged=(pools[f"lat{i}_paged_c"], page_table, lens),
            select=select, index_pool=pools.get(f"lat{i}_paged_ik"))
        if st is not None:
            stats.append(st)
    return (["gen_token", "gen_pos", "gen_page_table", "gen_lens"],
            decode_fetches(x, stats, S, hp, "lat"))


def export_latent_model(dirname, hp: LatentMoEConfig = None, num_slots=8,
                        prompt_buckets=None, page_len=PAGE_LEN_DEFAULT,
                        num_pages=None, page_buckets=None):
    """Export a generation bundle (``decoder.export_bundle``), its
    ``prefill`` the chunk program (``prefill_chunks`` in the meta, from
    the bundle's shapes; ``prompt_buckets`` bounds the longest prompt
    and is what ``GenPredictor.prefill`` + ``write_slot`` hand rows over
    in).  ``cache_vars`` names ONE pool a layer, and one a layer that
    holds an indexer.  Returns ``dirname``."""
    hp = hp or LatentMoEConfig()

    def sections(meta):
        own = {"decode_stats": DECODE_STATS if hp.moe_layers else [],
               "prefill_chunks": chunk_rows(
                   meta["page_len"], meta["prompt_buckets"], hp.max_len)}
        if hp.full_layers:
            # what the predictor counts a step's selections from
            own["sparse_attention"] = {"top_k": int(hp.index_topk),
                                       "indexers": len(hp.full_layers)}
        return own

    return export_bundle(
        dirname, hp, "latent_moe.export_latent_model",
        lambda *pool: build_chunk_program(hp, *pool),
        lambda *pool: build_paged_decode_program(hp, *pool),
        paged_cache_var_names(hp), hp.num_hidden_layers,
        num_slots=num_slots, prompt_buckets=prompt_buckets,
        page_len=page_len, num_pages=num_pages, page_buckets=page_buckets,
        state_vars=[], sections=sections)
