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
  attention forms over the same weights.  EXPANDED, K and V of every
  head made from the latent: a whole sequence (the training forward,
  ``mla_attention``) and a chunk of a prompt, which writes its rows into
  the slot's pages and attends the pages' rows under its diagonal, a
  key block expanded where the kernel uses it (``mla_attention_chunk``:
  a thousand query rows a key row pay for the expansion).  ABSORBED,
  ``W_kvb`` folded into the query and out of the context: the decode
  step, whose one row a slot attends the cached rows as they are,
  reading each once for scores and values (``mla_absorb``,
  ``paged_attention_latent``).
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
* **Window layers** (a configuration with ``layer_types``, the
  ``dots3_note`` family).  A ``sliding_attention`` layer is the same
  latent form at the ``swa_*`` sizes (its own heads, ranks, rotary base
  and scale; ``LatentMoEConfig.attention(i)``), row ``t`` seeing rows
  ``u <= t`` with ``t - u < sliding_window_size``, no indexer.  What a
  full layer keeps in pages it keeps in a RING a slot (``state_vars``
  ``lat<i>_ring_c``, ``[num_slots, ring, its latent row]``, position
  ``p`` at row ``p mod ring``): its bytes are a constant of the bundle.
  ``latent_window_attention`` is its whole-sequence form and its chunk
  form, both EXPANDED (a chunk: K and V of every head from the ring's
  rows before the chunk and the chunk's own, under the band; the
  chunk's last rows left in the ring), ``latent_window_step`` between
  two ``mla_absorb`` its decode step (absorbed: one row a slot over a
  ring it reads once).
  ``gen_meta.json`` then carries ``window_attention`` beside
  ``sparse_attention``, and the chunk program one more feed,
  ``gen_slot``.  Every ``full_attention`` layer there holds its own
  indexer.  **A head-wise gate** (``attention_gate_type`` /
  ``swa_attention_gate_type`` ``"headwise"``): ``g = sigmoid(h W_g)``,
  ``W_g`` hidden x heads, head ``j``'s output times ``g_j`` before
  ``W_o`` (``head_gate``).  **The rescale**
  (``apply_mla_qkv_lora_rescale``): ``c_q`` and ``c_kv`` times (hidden /
  their rank)^1/2 behind their norms; the cached ``c_kv`` is the
  rescaled one.  A configuration without these keys builds none of it.
* **FFN**.  The first ``first_k_dense_replace`` layers: ``W_d (silu(W_g
  h) * W_u h)``, width ``intermediate_size`` (or the layers that
  ``mlp_layer_types`` calls ``"dense"``).  The others: a sigmoid
  top-k router over ALL the model's experts on the full hidden state
  (``moe_route``; the correction bias moves the choice only), gated
  routed experts of width ``moe_intermediate_size`` over the experts
  HELD (``experts_held`` from ``expert_offset``: one chip's share of an
  expert-parallel deployment; ``moe_experts_gated``, a routed product),
  plus one shared expert on every token.

* **Hyper-connections** (a configuration with ``hc_mult`` = n > 1;
  ``decoder.hc_sublayer``, ``ops/mhc_ops.py``).  The residual is n
  streams ``[..., n, d]``: the embedding's row is copied into them, every
  sublayer (the MTP block's two among them) is wrapped (``u = H_pre x``,
  ``x <- H_res x + H_post^T F(u)``, ``H_res`` balanced by
  ``hc_sinkhorn_iters`` Sinkhorn rounds) and the streams are summed
  before the final norm.  ``gen_meta.json`` then says
  ``hyper_connections: {streams, sinkhorn_iters}``.
* **Self-speculative decoding** (``num_nextn_predict_layers`` 1): the
  multi-token-prediction module (``decoder.mtp_module``: one more MoE
  block of latent attention, with a latent pool of its own,
  ``lat_mtp_paged_c``) is loaded and DRAFTS, as ``models/window_moe.py``
  has it (``decoder.draft_turn`` / ``chunk_draft``): a decode turn
  forwards two rows a slot, the committed token and the draft kept in
  the per-slot state ``lat_draft``, through the absorbed latent kernel
  under a limit a row, verifies, yields one or two tokens and drafts
  again; the chunk program runs the module over the prompt's rows and
  seeds the first draft (feeds ``gen_slot`` and ``gen_next_ids`` more).
  The module takes the SUMMED residual; its block copies its input into
  the streams and sums them out as the main model does.  Beside window
  layers or an indexer the module is not loaded (a ring of latent rows
  under two rows, a selection a row: not built).

Matrices and activations are ``dtype`` (bfloat16) with float32
accumulation; router scores, norm statistics, rotary angles, softmax and
logits are float32; the latent pool is ``dtype``; a wrapper's
coefficients are float32, the streams ``dtype``.

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
                                       DecoderConfig, chunk_draft,
                                       chunk_rows, data, decode_fetches,
                                       decode_inputs, decoder_layer,
                                       draft_turn, embed, export_bundle,
                                       gated_ffn, hc_copy_in, hc_sum_out,
                                       last_row, live_rows, logits, matrix,
                                       op, persistable, prefill_inputs,
                                       program_role, rms, routed_experts,
                                       speculative_meta, train_inputs,
                                       train_loss, vector)
from paddle_tpu.ops.mla_ops import yarn_mscale

__all__ = ["LatentMoEConfig", "build_chunk_program",
           "build_paged_decode_program", "latent_moe_train_program",
           "export_latent_model", "paged_cache_var_names",
           "ring_var_names", "MTP", "DRAFT_VAR"]

#: the layer key of the MTP module's block (its parameters are
#: ``lat_mtp_*``), and the per-slot state that holds a slot's draft
MTP = "_mtp"
DRAFT_VAR = "lat_draft"


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
    # window layers of latent attention beside the full ones (None: every
    # layer is full): a layer "full_attention" | "sliding_attention"; a
    # sliding layer takes the ``swa_*`` sizes and keeps a ring a slot
    layer_types = None
    sliding_window_size = None
    ring = None                      # None: whole 128-row tiles
    swa_num_attention_heads = 4
    swa_q_lora_rank = 48
    swa_kv_lora_rank = 32
    swa_qk_nope_head_dim = 16
    swa_qk_rope_head_dim = 8
    swa_v_head_dim = 16
    swa_rope_theta = 10000.0
    # "headwise": head j's output times sigmoid((h W_g)_j) before W_o
    attention_gate_type = None
    swa_attention_gate_type = None
    # c_q and c_kv times (hidden / their rank)^1/2 behind their norms
    apply_mla_qkv_lora_rescale = False
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
    # hyper-connections: residual streams (1: the one residual), and the
    # Sinkhorn rounds, their epsilon and the clamp of a wrapper's H_res
    hc_mult = 1
    hc_sinkhorn_iters = 20
    hc_eps = 1e-6
    mhc_h_res_clamp_min = -30.0
    mhc_h_res_clamp_max = 30.0
    # the multi-token-prediction module: 0 = not loaded, 1 = it drafts
    num_nextn_predict_layers = 0
    dtype = "bfloat16"
    max_len = 64
    eos_id = -1

    _KEYS = {"rms_norm_eps": "eps"}

    @classmethod
    def from_dict(cls, cfg):
        hp = super().from_dict(cfg)
        if hp.rope_parameters and "rope_theta" in hp.rope_parameters:
            hp.rope_theta = hp.rope_parameters["rope_theta"]
        if hp.drafts and (hp.layer_types or hp.index_topk):
            # beside window layers or an indexer the module is NOT loaded
            # (a ring of latent rows, and a selection, under two rows a
            # slot are not built): the key is dropped, as it was before
            # this builder could draft at all
            hp.num_nextn_predict_layers = 0
        if int(hp.num_nextn_predict_layers or 0) > 1:
            raise NotImplementedError("drafts deeper than one row")
        return hp

    @property
    def drafts(self):
        """The MTP module is loaded and a decode turn carries its draft."""
        return int(self.num_nextn_predict_layers or 0) > 0

    @property
    def blocks(self):
        """Every block that caches: the layers, then the MTP module's."""
        return list(range(int(self.num_hidden_layers))) \
            + ([MTP] if self.drafts else [])

    @property
    def latent_row(self):
        """Lanes of a full layer's cached row: ``[c_kv | k_r]`` and
        zeros up to the next multiple of 128."""
        return self.attention(None)["row"]

    def is_window(self, i):
        """Layer ``i`` is a ``sliding_attention`` one (None: a full
        layer's sizes are asked for)."""
        return i is not None and i != MTP and bool(self.layer_types) \
            and self.layer_types[int(self.layer_offset) + i] \
            == "sliding_attention"

    def attention(self, i):
        """Layer ``i``'s attention sizes by its kind: ``H``, ``q_rank``,
        ``L`` (the latent's rank), ``nope``, ``R`` (rotary lanes),
        ``vd``, ``theta``, ``gate``, ``window`` (0: full) and ``row``
        (lanes of the cached row)."""
        key = "swa_" if self.is_window(i) else ""
        a = {name: int(getattr(self, key + attr)) for name, attr in (
            ("H", "num_attention_heads"), ("q_rank", "q_lora_rank"),
            ("L", "kv_lora_rank"), ("nope", "qk_nope_head_dim"),
            ("R", "qk_rope_head_dim"), ("vd", "v_head_dim"))}
        a["theta"] = float(getattr(self, key + "rope_theta"))
        a["gate"] = getattr(self, key + "attention_gate_type")
        a["window"] = int(self.sliding_window_size) if key else 0
        a["row"] = -(-(a["L"] + a["R"]) // 128) * 128
        return a

    @property
    def ring_rows(self):
        """Rows of a window layer's ring: the window in whole 128-row
        tiles (the ring kernel's scores are ``[heads, ring]``: the ring
        is their lane axis)."""
        return int(self.ring or -(-int(self.sliding_window_size) // 128)
                   * 128)

    @property
    def rope_attrs(self):
        return self.rope_of(None)

    def rope_of(self, i):
        """The ``rope`` op's attrs of layer ``i``'s kind."""
        a = self.attention(i)
        rs = dict(self.rope_scaling or {})
        factor = float(rs.get("factor", 1.0))
        return {"rope_dim": a["R"],
                "theta": a["theta"], "factor": factor,
                "original_max": int(rs.get(
                    "original_max_position_embeddings", 4096)),
                "beta_fast": float(rs.get("beta_fast", 32)),
                "beta_slow": float(rs.get("beta_slow", 1)),
                "mscale": yarn_mscale(factor, rs.get("mscale", 1.0))
                / yarn_mscale(factor, rs.get("mscale_all_dim", 0.0))}

    @property
    def softmax_scale(self):
        return self.scale_of(None)

    def scale_of(self, i):
        """``(nope + rope)^-1/2 m^2`` of layer ``i``'s kind, ``m`` YaRN's
        temperature over all dimensions."""
        a = self.attention(i)
        rs = dict(self.rope_scaling or {})
        m = yarn_mscale(float(rs.get("factor", 1.0)),
                        rs.get("mscale_all_dim", 0.0))
        return (a["nope"] + a["R"]) ** -0.5 * m * m

    def is_moe(self, i):
        if i == MTP:     # the MTP block's feed-forward is the sparse one
            return True
        if self.mlp_layer_types:
            return self.mlp_layer_types[int(self.layer_offset) + i] \
                == "sparse"
        return i >= int(self.first_k_dense_replace)

    def indexer(self, i):
        """``"full"`` (the layer scores and selects), ``"shared"`` (it
        uses the selection of the nearest full layer before it) or None
        (no sparse attention; also a shared layer with no full layer
        before it in the layers held)."""
        if not self.index_topk or self.is_window(i) or i == MTP:
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
    def window_layers(self):
        return [i for i in range(int(self.num_hidden_layers))
                if self.is_window(i)]

    @property
    def paged_layers(self):
        """The blocks that keep pages: every one that is no window
        layer, the MTP module's among them."""
        return [i for i in self.blocks if not self.is_window(i)]

    @property
    def moe_layers(self):
        return [i for i in range(int(self.num_hidden_layers))
                if self.is_moe(i)]


def paged_cache_var_names(hp):
    """Page-pool tensors: ONE a layer that is not a window layer (the
    latent row), in layer order, then one a layer that holds an indexer
    (its key row)."""
    return [f"lat{i}_paged_c" for i in hp.paged_layers] \
        + [f"lat{i}_paged_ik" for i in hp.full_layers]


def ring_var_names(hp):
    """Per-slot ring tensors, ONE a window layer (its latent row), in
    layer order."""
    return [f"lat{i}_ring_c" for i in hp.window_layers]


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
    ``paged`` = (pool, page table, lens): the absorbed paged decode;
    with one more entry, ``row_lens`` [S * L, 1], a step of ``L`` rows a
    slot, each under its own limit (``ops/spec_ops.py``).
    ``select``: the selection a ``shared`` layer attends under; a
    ``full`` layer makes its own (``index_pool``: its key pool in the
    serving forms).  A WINDOW layer (``hp.is_window``) keeps a ring a
    slot where a full layer keeps pages: its ``paged`` is (ring, slot
    [1, 1]) in a chunk, (ring, lens) in the decode step, and it takes no
    selection.  Returns ``(out, selection)``."""
    d, a = int(hp.hidden_size), hp.attention(i)
    H, L, R, nope, vd = a["H"], a["L"], a["R"], a["nope"], a["vd"]
    rope = hp.rope_of(i)
    rescale = lambda c, rank: layers.scale(
        c, scale=(d / rank) ** 0.5) if hp.apply_mla_qkv_lora_rescale else c
    c_q = rescale(rms(layers.matmul(h, matrix(hp, f"lat{i}_qa.w",
                                              [d, a["q_rank"]])),
                      f"lat{i}_qnorm.scale", hp), a["q_rank"])
    q = layers.matmul(c_q, matrix(hp, f"lat{i}_qb.w",
                                  [a["q_rank"], H * (nope + R)]))
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
    c_kv = rescale(rms(c_kv, f"lat{i}_kvnorm.scale", hp), L)
    k_r = op("rope", {"X": k_r, "Pos": pos}, {"Out": hp.dtype},
             {"n_head": 1, **rope})["Out"]
    row = layers.concat([c_kv, k_r], axis=2)
    if a["row"] > L + R:
        row = layers.pad(row, [0, 0, 0, 0, 0, a["row"] - L - R])
    w_kvb = matrix(hp, f"lat{i}_kvb.w", [L, H * (nope + vd)])
    attrs = {"n_head": H, "nope_dim": nope, "v_dim": vd}
    scale = float(hp.scale_of(i))
    whole = {**attrs, "rope_dim": R, "scale": scale, **sparse_attrs}
    absorb = lambda x, side, **pad: op(
        "mla_absorb", {"X": x, "Wkvb": w_kvb}, {"Out": hp.dtype},
        {**attrs, "side": side, **pad})["Out"]
    if paged is None:
        row = layers.elementwise_mul(row, layers.cast(mask, hp.dtype),
                                     axis=0)
    if a["window"] and (paged is None or mask is not None):
        ring, slot = paged or (None, None)
        ctx = op("latent_window_attention",
                 {"Q": q, "Latent": row, "Wkvb": w_kvb, "Mask": mask,
                  "Ring": ring, "Slot": slot,
                  "Pos": pos if paged else None},
                 {"Out": hp.dtype, **({"RingOut": ring} if paged else {})},
                 {**whole, "window": a["window"]})["Out"]
    elif a["window"]:
        ring, lens = paged
        ctx = op("latent_window_step",
                 {"Q": absorb(q, "q", pad=a["row"] - L - R), "Row": row,
                  "Ring": ring, "Lens": lens},
                 {"Out": hp.dtype, "RingOut": ring},
                 {"n_head": H, "v_width": L, "scale": scale,
                  "window": a["window"]})["Out"]
        ctx = absorb(ctx, "o")
    elif paged is None:
        ctx = op("mla_attention", {"Q": q, "Latent": row, "Wkvb": w_kvb,
                                   "Mask": mask, **sparse},
                 {"Out": hp.dtype}, whole)["Out"]
    elif mask is not None:
        pool, page_table = paged
        ctx = op("mla_attention_chunk",
                 {"Q": q, "Latent": row, "Wkvb": w_kvb, "Cache": pool,
                  "PageTable": page_table, "Pos": pos, "Mask": mask,
                  **sparse},
                 {"Out": hp.dtype, "CacheOut": pool}, whole)["Out"]
    else:
        pool, page_table, lens, *row_lens = paged
        ctx = op("paged_attention_latent",
                 {"Q": absorb(q, "q", pad=a["row"] - L - R), "Row": row,
                  "Cache": pool, "PageTable": page_table, "Lens": lens,
                  "RowLens": row_lens[0] if row_lens else None, **sparse},
                 {"Out": hp.dtype, "CacheOut": pool},
                 {"n_head": H, "v_width": L, "scale": scale,
                  **sparse_attrs})["Out"]
        ctx = absorb(ctx, "o")
    if a["gate"] == "headwise":
        ctx = op("head_gate",
                 {"X": ctx, "Gate": layers.matmul(
                     h, matrix(hp, f"lat{i}_og.w", [d, H]))},
                 {"Out": hp.dtype}, {"n_head": H})["Out"]
    elif a["gate"]:
        raise NotImplementedError(f"an attention gate of type "
                                  f"{a['gate']!r}")
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
        lambda h: _ffn(h, hp, i, lens), routed=hp.is_moe(i),
        hc_mult=hp.hc_mult)
    return x, stats, select


def _streams(x, hp):
    """The embedding's rows ``x`` [..., d] as the layers take them: the
    ``hc_mult`` residual streams, or ``x`` itself without
    hyper-connections."""
    return hc_copy_in(x, hp.hc_mult) if int(hp.hc_mult) > 1 else x


def _residual(x, hp):
    """The ONE residual the final norm (and the MTP module) takes: the
    streams summed, or ``x`` itself."""
    return hc_sum_out(x) if int(hp.hc_mult) > 1 else x


def _mtp_block(h, hp, *args, **kwargs):
    """The MTP module's block over ITS input ``h`` [..., d] (one
    residual in, one out: ``decoder.mtp_module``'s contract): the block
    is a layer of the model's own kind, so under hyper-connections its
    input is copied into the streams and its output summed.  Returns
    ``(g, stats)``."""
    g, stats, _ = _layer(_streams(h, hp), hp, MTP, *args, **kwargs)
    return _residual(g, hp), stats


def _pools(hp, num_slots, page_len, num_pages):
    """The persistable caches of the CURRENT program, ``{name: var}``,
    all ``hp.dtype``: one latent pool a full layer ``[num_pages,
    page_len, latent_row]``, one index-key pool a layer that holds an
    indexer ``[num_pages, page_len, index_head_dim]`` and one ring a
    window layer ``[num_slots, ring, its latent row]``."""
    pools = {name: persistable(
        name, [int(num_pages), int(page_len),
               int(hp.index_head_dim) if name.endswith("_ik")
               else hp.latent_row], hp.dtype)
        for name in paged_cache_var_names(hp)}
    for i in hp.window_layers:
        pools[f"lat{i}_ring_c"] = persistable(
            f"lat{i}_ring_c", [int(num_slots), hp.ring_rows,
                               hp.attention(i)["row"]], hp.dtype)
    return pools


def _cache(pools, hp, i, *rows):
    """Layer ``i``'s ``paged`` of :func:`_attention`: its ring and the
    first of ``rows`` (the slot, or the lens) where it is a window
    layer, else its pool and the others (the page table, the lens)."""
    if hp.is_window(i):
        return (pools[f"lat{i}_ring_c"], rows[0])
    return (pools[f"lat{i}_paged_c"],) + rows[1:]


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
    predictor and covering the chunk's last real row); ``gen_slot`` [1,
    1] int32 with window layers or a drafting MTP module alone: without
    them nothing here is kept a slot; ``gen_next_ids`` [1, C] int32
    where the module drafts (``decoder.chunk_draft``: the module runs
    over the chunk's rows behind the main layers and seeds the slot's
    first draft).  Persistable state, read and updated in place, as the decode
    step's: the latent pools, the index-key pools and the window layers'
    rings.  Fetches ``[logits [1, V]]`` (of the row ``gen_last``
    names)."""
    ids, pos, mask, last = prefill_inputs()
    per_slot = bool(hp.window_layers) or hp.drafts
    slot = data("gen_slot", [1, 1], "int32") if per_slot else None
    page_table = data("gen_page_table", [1, -1], "int32")
    pools = _pools(hp, num_slots, page_len, num_pages)
    lens = live_rows(mask)
    x = _streams(embed(ids, hp, "lat"), hp)
    select = None
    for i in range(int(hp.num_hidden_layers)):
        x, _, select = _layer(
            x, hp, i, pos, lens, mask=mask,
            paged=_cache(pools, hp, i, slot, page_table), select=select,
            index_pool=pools.get(f"lat{i}_paged_ik"))
    x = _residual(x, hp)
    feeds = ["gen_ids", "gen_pos", "gen_mask", "gen_last"] \
        + ["gen_slot"] * per_slot + ["gen_page_table"]
    first = logits(last_row(x, last, hp), hp, "lat")
    if hp.drafts:
        chunk_draft(x, first, last, slot, hp, "lat", num_slots, DRAFT_VAR,
                    lambda h: _mtp_block(
                        h, hp, pos, lens, mask=mask,
                        paged=_cache(pools, hp, MTP, slot, page_table)))
        feeds.append("gen_next_ids")
    return feeds, [first]


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
    x = _streams(x, hp)
    for i in range(int(hp.num_hidden_layers)):
        x, _, select = _layer(x, hp, i, rows["pos"], rows["lens"],
                              mask=rows["mask"], select=select)
    return train_loss(_residual(x, hp), labels, hp, "lat")


@program_role("gen_decode")
def build_paged_decode_program(hp, num_slots, page_len, num_pages):
    """The single-token decode step in the CURRENT program guard.

    Feeds: ``gen_token`` [S, 1] int32, ``gen_pos`` [S, 1] int32 (the
    token's position), ``gen_page_table`` [S, P] int32 (P bucketed by
    the predictor), ``gen_lens`` [S, 1] int32 (rows INCLUDING the current
    token; 0 = free slot: no page is written).  Persistable state,
    updated in place: one latent pool a layer, ``[num_pages, page_len,
    latent_row]`` in ``hp.dtype``, and one index-key pool a layer that
    holds an indexer, ``[num_pages, page_len, index_head_dim]``; a window
    layer's ring ``[S, ring, its latent row]`` in their place.  Fetches
    ``[logits [S, V], stats [n_moe, 3]]``.

    Where the MTP module drafts (``hp.drafts``) the step is a TURN of
    two rows a slot, ``decoder.draft_turn``'s: one more feed
    (``gen_spec``), the per-slot state ``lat_draft``, the latent kernel
    under a limit a row, and the fetches ``[logits of the committed
    token's row, stats [n_moe + 1, 3], yield [S, 3]]``."""
    S = int(num_slots)
    token, pos, page_table, lens = decode_inputs(S)
    pools = _pools(hp, S, page_len, num_pages)
    if hp.drafts:
        def cached(i, end, row_lens):
            return _cache(pools, hp, i, end, page_table, end, row_lens)

        def forward(rows):
            x = _streams(embed(rows["Ids"], hp, "lat", lead=[S, 2]), hp)
            stats = []
            for i in range(int(hp.num_hidden_layers)):
                x, st, _ = _layer(x, hp, i, rows["RowPos"], rows["RowLens"],
                                  paged=cached(i, rows["End"],
                                               rows["RowLens"]))
                if st is not None:
                    stats.append(st)
            return _residual(x, hp), stats

        return (["gen_token", "gen_pos", "gen_page_table", "gen_lens",
                 "gen_spec"],
                draft_turn(hp, "lat", S, DRAFT_VAR, token, pos, lens,
                           forward, lambda h, row_pos, end, row_lens:
                           _mtp_block(h, hp, row_pos, row_lens,
                                      paged=cached(MTP, end, row_lens))))
    x = _streams(embed(token, hp, "lat", lead=[S, 1]), hp)
    stats, select = [], None
    for i in range(int(hp.num_hidden_layers)):
        x, st, select = _layer(
            x, hp, i, pos, lens,
            paged=_cache(pools, hp, i, lens, page_table, lens),
            select=select, index_pool=pools.get(f"lat{i}_paged_ik"))
        if st is not None:
            stats.append(st)
    return (["gen_token", "gen_pos", "gen_page_table", "gen_lens"],
            decode_fetches(_residual(x, hp), stats, S, hp, "lat"))


def _window_section(hp):
    """``gen_meta.json``'s ``window_attention``, the keys
    ``window_moe``'s has: which layer keeps a ring and which pages, and
    what the predictor counts a step's reads and a chunk's pairs from.
    ``heads``: the (query heads, K/V heads) the banded kernel is handed
    for a window layer's chunk (expanded: every head its own K/V head);
    ``full_heads``: a full layer's heads over its one cached row."""
    import jax.numpy as jnp
    item = jnp.dtype(hp.dtype).itemsize
    win, full = hp.attention(hp.window_layers[0]), hp.paged_layers
    return {
        "window": win["window"], "ring": hp.ring_rows,
        "layers": hp.window_layers, "full_layers": full,
        "ring_vars": ring_var_names(hp),
        "row_bytes": [hp.attention(i)["row"] * item
                      for i in hp.window_layers],
        "heads": [win["H"], win["H"]],
        "full_heads": [hp.attention(full[0])["H"], 1] if full else None,
    }


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
        if hp.window_layers:
            own["window_attention"] = _window_section(hp)
        if int(hp.hc_mult) > 1:
            # ``wrappers``: what a row passes (two a block): the
            # predictor counts a chunk's ``mhc_rows`` from it
            own["hyper_connections"] = {
                "streams": int(hp.hc_mult),
                "sinkhorn_iters": int(hp.hc_sinkhorn_iters),
                "wrappers": 2 * len(hp.blocks)}
        if hp.drafts:
            own["speculative"] = speculative_meta(DRAFT_VAR)
        return own

    return export_bundle(
        dirname, hp, "latent_moe.export_latent_model",
        lambda *pool: build_chunk_program(hp, *pool),
        lambda *pool: build_paged_decode_program(hp, *pool),
        paged_cache_var_names(hp), hp.num_hidden_layers,
        num_slots=num_slots, prompt_buckets=prompt_buckets,
        page_len=page_len, num_pages=num_pages, page_buckets=page_buckets,
        state_vars=ring_var_names(hp) + [DRAFT_VAR] * hp.drafts,
        sections=sections)
