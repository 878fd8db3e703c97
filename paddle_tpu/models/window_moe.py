"""Sliding-window / full attention mixture-of-experts LM (the
``mimo_v2_flash`` layout) on the generative serving path: the same
prefill + paged-decode program pair and bundle layout as
``models/gen_lm.py``, ``hybrid_moe.py``, ``latent_moe.py`` and
``block_moe.py``, for a model whose layers differ in KIND by two
published lists, both kept whole and read from ``layer_offset``:

* ``hybrid_layer_pattern[l]`` 0 = **full** attention (``num_attention_
  heads`` over ``num_key_value_heads``, rotary at ``rope_theta``, plain
  causal); 1 = **window** attention (``swa_num_attention_heads`` over
  ``swa_num_key_value_heads``, rotary at ``swa_rope_theta``, a row sees
  itself and the ``sliding_window - 1`` rows before it, and a learnable
  sink logit a head joins the softmax's denominator where
  ``add_swa_attention_sink_bias``).
* ``moe_layer_freq[l]`` 0 = a dense SwiGLU of ``intermediate_size``; 1 =
  a sigmoid ``noaux_tc`` router over ``n_routed_experts`` gated experts
  (the ``num_experts_per_tok`` largest of ``score + correction bias``,
  weights renormalised over the chosen), of which the layer HOLDS
  ``experts_held`` from ``expert_offset`` on (one chip's share of an
  expert-parallel deployment); no shared expert.

Every layer is pre-norm, no biases.  Attention: ``q = a W_q`` (H heads
of ``head_dim``), ``k = a W_k`` (Hkv heads of ``head_dim``), ``v =
attention_value_scale * a W_v`` (Hkv heads of ``v_head_dim``); the
first ``floor(head_dim * partial_rotary_factor)`` lanes of every q and k
head turn by the row's position (``ops/window_ops.rope_partial``), the
rest pass; scores scale by ``head_dim^-1/2``.  A key head is laid out
``key_head_stored`` lanes wide (zeros behind its ``head_dim``) wherever
it is cached, and the queries alike: the chip slices a row at whole
128-lane groups.

**Two kinds of cache in one bundle.**  A full layer's K/V live in the
page pool, rows of ``Hkv * key_head_stored`` and ``Hkv * v_head_dim``
(``cache_vars``; ``paged_attention`` takes the two widths).  A window
layer's live in a RING a slot, ``[num_slots, ring, ...]`` (``state_vars``:
position ``p`` at row ``p mod ring``, ``ring >= sliding_window``): its
bytes a slot are a constant of the bundle, whatever ``max_len``;
``gen_meta.json``'s ``window_attention`` says which layer has which.

**The prefill is a CHUNK program.**  A prompt runs as a sequence of
chunks of ``prefill_chunks`` rows (``gen_meta.json``; the largest for
every chunk but the last, which takes the smallest that holds it), each
one compiled call over the SAME pools and rings the decode step reads:
a full layer writes the chunk's K/V into the slot's pages and attends
the pages' rows ``0 .. P + C - 1``, a window layer attends the ring's
rows before ``P`` followed by the chunk and leaves the chunk's last rows
in the ring (``ops/window_ops.py``).  ``P`` = 0 is the first chunk: a
prompt that fits one chunk runs the same program.  Nothing seeds a slot
afterwards, and the scheduler can run a decode step for the live
streams between two chunks (``gen/scheduler.py``).

**The ``exaone_moe`` layout of the same** (K-EXAONE: ``from_dict`` takes
its published keys, ``layer_types`` / ``mlp_layer_types`` / ``num_experts``
/ ``num_shared_experts`` / ``rope_parameters``): one head shape for both
kinds of layer, ``qk_norm`` (an RMSNorm over each q and k head's lanes
before the rotary), ``full_attention_rotary`` false (a full layer's q and
k are not rotated), no sink, and ``n_shared_experts`` shared SwiGLU
experts on every token beside the routed ones.

**Self-speculative decoding** (``num_nextn_predict_layers`` 1): the
model's multi-token-prediction module (``decoder.mtp_module``: one more
full-attention block, with a page pool of its own) is loaded and DRAFTS.  A decode turn forwards two rows a slot, the
committed token and the draft kept in the per-slot state ``win_draft``,
verifies the draft against the main model's own greedy pick, yields one
or two tokens a slot and drafts again, all in the one program
(``ops/spec_ops.py``); the chunk program runs the module over the
prompt's rows, so that its cache is filled, and seeds the first draft.
A window layer's ring then holds ``sliding_window + 1`` rows or more.

Matrices and activations are ``dtype`` (bfloat16) with float32
accumulation; router scores, norm statistics, rotary angles, softmax and
logits are float32; pool and rings are ``dtype``.
"""

from __future__ import annotations

import paddle_tpu.layers as layers
from paddle_tpu.models.decoder import (DECODE_STATS, PAGE_LEN_DEFAULT,
                                       DecoderConfig, chunk_draft,
                                       chunk_rows, data, decode_fetches,
                                       decode_inputs, decoder_layer,
                                       draft_turn, embed, export_bundle,
                                       gated_ffn, head_norm, last_row,
                                       live_rows, logits, matrix, op,
                                       persistable, prefill_inputs,
                                       program_role, routed_experts,
                                       speculative_meta, train_inputs,
                                       train_loss, vector)

__all__ = ["WindowMoEConfig", "build_chunk_program",
           "build_paged_decode_program", "window_moe_train_program",
           "export_window_model", "paged_cache_var_names",
           "ring_var_names", "MTP", "DRAFT_VAR"]

#: the layer key of the MTP module's block (its parameters are
#: ``win_mtp_*``), and the per-slot state that holds a slot's draft
MTP = "_mtp"
DRAFT_VAR = "win_draft"


class WindowMoEConfig(DecoderConfig):
    """Toy-scale defaults; ``from_dict`` takes the published keys of a
    ``mimo_v2_flash`` ``config.json``."""
    vocab_size = 64
    hidden_size = 64
    num_hidden_layers = 3
    layer_offset = 0
    hybrid_layer_pattern = (0, 1, 1)
    moe_layer_freq = (0, 1, 1)
    eps = 1e-5                       # layernorm_epsilon
    # full layers
    num_attention_heads = 4
    num_key_value_heads = 1
    head_dim = 24
    v_head_dim = 16
    rope_theta = 5000000.0
    add_full_attention_sink_bias = False
    # window layers
    swa_num_attention_heads = 4
    swa_num_key_value_heads = 2
    swa_head_dim = 24
    swa_v_head_dim = 16
    swa_rope_theta = 10000.0
    sliding_window = 8
    add_swa_attention_sink_bias = True
    ring = None                      # None: sliding_window rows
    partial_rotary_factor = 0.334
    attention_value_scale = 0.707
    key_head_stored = None           # None: head_dim lanes
    qk_norm = False                  # RMSNorm over each q and k head
    full_attention_rotary = True     # False: full layers do not rotate
    # feed-forward
    intermediate_size = 96
    moe_intermediate_size = 32
    n_routed_experts = 8
    num_experts_per_tok = 2
    norm_topk_prob = True
    routed_scaling_factor = None     # None: 1
    n_shared_experts = 0             # shared experts beside the routed
    experts_held = None              # None: all of them
    expert_offset = 0
    # the multi-token-prediction module: 0 = not loaded, 1 = it drafts
    num_nextn_predict_layers = 0
    dtype = "bfloat16"
    max_len = 64
    eos_id = -1

    _KEYS = {"layernorm_epsilon": "eps", "rms_norm_eps": "eps",
             "num_experts": "n_routed_experts",
             "num_shared_experts": "n_shared_experts"}

    @classmethod
    def from_dict(cls, cfg):
        """The published keys of a ``mimo_v2_flash`` ``config.json``, or
        of an ``exaone_moe`` one (``layer_types`` names the kinds): its
        two lists, one head shape and one theta for both kinds, a rotary
        over the whole head, no value scale and no sink."""
        cfg = dict(cfg)
        kind = (cfg.get("mtp_layer_types") or ["full_attention"])[0]
        if cfg.get("num_nextn_predict_layers") and kind != "full_attention":
            raise NotImplementedError(
                f"an MTP block of kind {kind!r}: the module's block is a "
                "full-attention one over its own page pool")
        if "layer_types" in cfg:
            theta = (cfg.get("rope_parameters") or {}).get(
                "rope_theta", cfg.get("rope_theta", 10000.0))
            cfg.update(
                hybrid_layer_pattern=[int(t == "sliding_attention")
                                      for t in cfg["layer_types"]],
                moe_layer_freq=[int(t == "sparse")
                                for t in cfg["mlp_layer_types"]],
                swa_num_attention_heads=cfg["num_attention_heads"],
                swa_num_key_value_heads=cfg["num_key_value_heads"],
                swa_head_dim=cfg["head_dim"], v_head_dim=cfg["head_dim"],
                swa_v_head_dim=cfg["head_dim"], rope_theta=theta,
                swa_rope_theta=theta, partial_rotary_factor=1.0,
                attention_value_scale=None,
                add_swa_attention_sink_bias=False,
                add_full_attention_sink_bias=False)
        return super().from_dict(cfg)

    @property
    def ring_rows(self):
        return int(self.ring or self.sliding_window)

    @property
    def drafts(self):
        """The MTP module is loaded and a decode turn carries its draft."""
        return int(self.num_nextn_predict_layers or 0) > 0

    def is_window(self, i):
        # the MTP block is a full-attention one
        return i != MTP and bool(
            self.hybrid_layer_pattern[int(self.layer_offset) + i])

    def is_moe(self, i):
        # the MTP block's feed-forward is the model's sparse one
        return i == MTP or bool(
            self.moe_layer_freq[int(self.layer_offset) + i])

    @property
    def blocks(self):
        """Every block that caches: the layers, then the MTP module's."""
        return list(range(int(self.num_hidden_layers))) \
            + ([MTP] if self.drafts else [])

    @property
    def window_layers(self):
        return [i for i in range(int(self.num_hidden_layers))
                if self.is_window(i)]

    @property
    def full_layers(self):
        return [i for i in range(int(self.num_hidden_layers))
                if not self.is_window(i)]

    @property
    def moe_layers(self):
        return [i for i in range(int(self.num_hidden_layers))
                if self.is_moe(i)]

    def attention(self, i):
        """Layer ``i``'s ``(H, Hkv, Dk, Dv, theta, sink)`` by its kind."""
        if self.is_window(i):
            return (int(self.swa_num_attention_heads),
                    int(self.swa_num_key_value_heads),
                    int(self.swa_head_dim), int(self.swa_v_head_dim),
                    float(self.swa_rope_theta),
                    bool(self.add_swa_attention_sink_bias))
        return (int(self.num_attention_heads),
                int(self.num_key_value_heads), int(self.head_dim),
                int(self.v_head_dim), float(self.rope_theta),
                bool(self.add_full_attention_sink_bias))

    def stored(self, dk):
        """Lanes a key head of ``dk`` is laid out over where it is
        cached."""
        return max(int(self.key_head_stored or dk), dk)

    def row_widths(self, i):
        """Layer ``i``'s cached ``(K row, V row)`` widths, as stored."""
        _, hkv, dk, dv, _, _ = self.attention(i)
        return hkv * self.stored(dk), hkv * dv


def paged_cache_var_names(hp):
    """Page-pool tensors, (k, v) a FULL layer (the MTP module's block
    among them), in layer order."""
    return [f"win{i}_paged_{r}" for i in hp.blocks if not hp.is_window(i)
            for r in "kv"]


def ring_var_names(hp):
    """Per-slot ring tensors, (k, v) a WINDOW layer, in layer order."""
    return [f"win{i}_ring_{r}" for i in hp.window_layers for r in "kv"]


def _attention(h, hp, i, pos, chunk=None, cache=None):
    """Layer ``i``'s attention, one of three forms.  Neither ``chunk``
    nor ``cache``: a whole sequence, nothing cached (the training
    forward).  ``chunk`` = (k cache, v cache, page table [1, P] of a full
    layer or slot [1, 1] of a window layer, mask [1, C]): ONE CHUNK of a
    prompt over the slot's own caches, which it reads and writes.
    ``cache`` = (k pool, v pool, page table, lens) of a full layer, (k
    ring, v ring, lens) of a window layer: the decode step; with one more
    entry, ``row_lens`` [S * L, 1], a step of ``L`` rows a slot, each
    under its own limit (``ops/spec_ops.py``)."""
    d = int(hp.hidden_size)
    H, Hkv, Dk, Dv, theta, has_sink = hp.attention(i)
    window = int(hp.sliding_window) if hp.is_window(i) else 0
    if has_sink and not window:
        raise NotImplementedError(
            "a sink on a full-attention layer: paged_attention has none")
    rope = {"rope_dim": int(Dk * float(hp.partial_rotary_factor)) // 2 * 2,
            "theta": theta, "pad_to": hp.stored(Dk)}
    q = layers.matmul(h, matrix(hp, f"win{i}_q.w", [d, H * Dk]))
    k = layers.matmul(h, matrix(hp, f"win{i}_k.w", [d, Hkv * Dk]))
    v = layers.matmul(h, matrix(hp, f"win{i}_v.w", [d, Hkv * Dv]))
    if hp.attention_value_scale is not None:
        v = layers.scale(v, scale=float(hp.attention_value_scale))
    if hp.qk_norm:
        q = head_norm(q, f"win{i}_qnorm.scale", hp, H, Dk)
        k = head_norm(k, f"win{i}_knorm.scale", hp, Hkv, Dk)
    if window or hp.full_attention_rotary:
        q = op("rope_partial", {"X": q, "Pos": pos}, {"Out": hp.dtype},
               {"n_head": H, **rope})["Out"]
        k = op("rope_partial", {"X": k, "Pos": pos}, {"Out": hp.dtype},
               {"n_head": Hkv, **rope})["Out"]
    elif hp.stored(Dk) != Dk:
        raise NotImplementedError(
            "a key head stored wider than it is, on a layer without the "
            "rotary op that lays it out")
    sink = vector(f"win{i}_sink", H, 0.0) if has_sink else None
    attrs = {"n_head": H, "scale": float(Dk) ** -0.5}
    heads = {**attrs, "n_kv_head": Hkv}
    if chunk is not None and window:
        k_ring, v_ring, slot, mask = chunk
        ctx = op("window_attention",
                 {"Q": q, "K": k, "V": v, "Sink": sink, "KRing": k_ring,
                  "VRing": v_ring, "Slot": slot, "Pos": pos, "Mask": mask},
                 {"Out": hp.dtype, "KRingOut": k_ring, "VRingOut": v_ring},
                 {**heads, "window": window})["Out"]
    elif chunk is not None:
        pk, pv, page_table, mask = chunk
        ctx = op("gqa_flash_attention_chunk",
                 {"Q": q, "K": k, "V": v, "KCache": pk, "VCache": pv,
                  "PageTable": page_table, "Pos": pos, "Mask": mask},
                 {"Out": hp.dtype, "KCacheOut": pk, "VCacheOut": pv},
                 heads)["Out"]
    elif cache is None and window:
        ctx = op("window_attention", {"Q": q, "K": k, "V": v, "Sink": sink},
                 {"Out": hp.dtype}, {**heads, "window": window})["Out"]
    elif cache is None:
        ctx = op("gqa_flash_attention", {"Q": q, "K": k, "V": v},
                 {"Out": hp.dtype}, heads)["Out"]
    elif window:
        k_ring, v_ring, lens, *row_lens = cache
        ctx = op("window_attention_step",
                 {"Q": q, "K": k, "V": v, "KRing": k_ring, "VRing": v_ring,
                  "Lens": lens, "Sink": sink,
                  "RowLens": row_lens[0] if row_lens else None},
                 {"Out": hp.dtype, "KRingOut": k_ring, "VRingOut": v_ring},
                 {**attrs, "window": window})["Out"]
    else:
        pk, pv, page_table, lens, *row_lens = cache
        ctx = op("paged_attention",
                 {"Q": q, "K": k, "V": v, "KCache": pk, "VCache": pv,
                  "PageTable": page_table, "Lens": lens,
                  "RowLens": row_lens[0] if row_lens else None},
                 {"Out": hp.dtype, "KCacheOut": pk, "VCacheOut": pv},
                 heads)["Out"]
    return layers.matmul(ctx, matrix(hp, f"win{i}_o.w", [H * Dv, d]))


def _ffn(h, hp, i, lens):
    """Layer ``i``'s feed-forward: the dense SwiGLU, or the routed
    experts over the share held and, where the model has them, the
    shared experts on every row.  Returns ``(out, the experts' stats or
    None)``."""
    if not hp.is_moe(i):
        return gated_ffn(h, hp, f"win{i}_ffn",
                         int(hp.intermediate_size)), None
    out, stats = routed_experts(
        h, hp, f"win{i}", lens, experts=int(hp.n_routed_experts),
        held=hp.held, expert_offset=hp.expert_offset,
        scaling=hp.routed_scaling_factor or 1.0)
    if hp.n_shared_experts:
        out = out + gated_ffn(h, hp, f"win{i}_sh",
                              int(hp.moe_intermediate_size)
                              * int(hp.n_shared_experts))
    return out, stats


def _layer(x, hp, i, pos, lens, chunk=None, cache=None):
    """One layer; returns ``(x, stats or None)``.  ``lens`` [rows, 1]
    int32: a row with 0 (a free slot's, a pad row) takes no routed
    expert."""
    x, _, stats = decoder_layer(
        x, hp, f"win{i}",
        lambda h: (_attention(h, hp, i, pos, chunk=chunk, cache=cache),
                   None),
        lambda h: _ffn(h, hp, i, lens), routed=hp.is_moe(i))
    return x, stats


def _caches(hp, num_slots, page_len, num_pages):
    """The persistable caches of the CURRENT program, ``{(layer, "k" |
    "v"): var}``, all ``hp.dtype``: a full layer's pools ``[num_pages,
    page_len, row]``, a window layer's rings ``[num_slots, ring,
    row]``."""
    cache = {}
    for i in hp.blocks:
        lead, kind = ([int(num_slots), hp.ring_rows], "ring") \
            if hp.is_window(i) else ([int(num_pages), int(page_len)], "paged")
        for r, width in zip("kv", hp.row_widths(i)):
            cache[i, r] = persistable(f"win{i}_{kind}_{r}", lead + [width],
                                      hp.dtype)
    return cache


@program_role("gen_chunk")
def build_chunk_program(hp, num_slots, page_len, num_pages):
    """The prefill of ONE CHUNK of a prompt in the CURRENT program guard.

    Feeds (length-dynamic; the predictor pads to a chunk rung):
    ``gen_ids`` [1, C] int32, ``gen_pos`` [1, C] int32 (the rows'
    positions ``P .. P + C - 1``), ``gen_mask`` [1, C] f32 (1 = real
    token, real tokens first), ``gen_last`` [1, C] f32 (one-hot of the
    prompt's last row where this chunk holds it, else zeros),
    ``gen_slot`` [1, 1] int32 and ``gen_page_table`` [1, P] int32 (the
    slot's row, P bucketed by the predictor and covering the chunk's
    last real row).  Persistable state, read and updated in place, as
    the decode step's: the full layers' pools and the window layers'
    rings.  Fetches ``[logits [1, V]]`` (of the row ``gen_last``
    names).

    Where the MTP module drafts (``hp.drafts``) one more feed,
    ``gen_next_ids`` [1, C] int32: the token that FOLLOWS each row (the
    prompt shifted by one; -1 behind the prompt's last row, which takes
    the main model's own pick).  The module runs
    over the chunk's rows behind the main layers, so that its cache
    holds the prompt's rows too, and where the chunk holds the prompt's
    last row its pick there becomes the slot's first draft
    (``win_draft``)."""
    ids, pos, mask, last = prefill_inputs()
    slot = data("gen_slot", [1, 1], "int32")
    page_table = data("gen_page_table", [1, -1], "int32")
    cache = _caches(hp, num_slots, page_len, num_pages)
    lens = live_rows(mask)

    def block(x, i):
        return _layer(x, hp, i, pos, lens, chunk=(
            cache[i, "k"], cache[i, "v"],
            slot if hp.is_window(i) else page_table, mask))

    x = embed(ids, hp, "win")
    for i in range(int(hp.num_hidden_layers)):
        x, _ = block(x, i)
    feeds = ["gen_ids", "gen_pos", "gen_mask", "gen_last", "gen_slot",
             "gen_page_table"]
    first = logits(last_row(x, last, hp), hp, "win")
    if hp.drafts:
        chunk_draft(x, first, last, slot, hp, "win", num_slots, DRAFT_VAR,
                    lambda h: block(h, MTP))
        feeds.append("gen_next_ids")
    return feeds, [first]


def window_moe_train_program(seq_len, hp: WindowMoEConfig = None):
    """Teacher-forced training forward over ONE sequence in the current
    program guard (the ops take one prompt at a time), over the serving
    programs' parameter names; also the model-zoo lint gate's view of
    this model.  Returns ``(avg_cost, feed_names)``; feeds ``gen_ids`` /
    ``gen_labels`` [1, T] int32."""
    hp = hp or WindowMoEConfig()
    ids, labels, rows = train_inputs(seq_len, "pos", "lens")
    x = embed(ids, hp, "win")
    for i in range(int(hp.num_hidden_layers)):
        x, _ = _layer(x, hp, i, rows["pos"], rows["lens"])
    return train_loss(x, labels, hp, "win")


@program_role("gen_decode")
def build_paged_decode_program(hp, num_slots, page_len, num_pages):
    """The single-token decode step in the CURRENT program guard.

    Feeds: ``gen_token`` [S, 1] int32, ``gen_pos`` [S, 1] int32 (the
    token's position), ``gen_page_table`` [S, P] int32 (P bucketed by
    the predictor; the FULL layers' alone), ``gen_lens`` [S, 1] int32
    (rows INCLUDING the current token; 0 = free slot: nothing is
    written).  Persistable state, updated in place, all ``hp.dtype``: a
    full layer's pools ``[num_pages, page_len, row]`` and a window
    layer's rings ``[S, ring, row]``.  Fetches ``[logits [S, V], stats
    [n_moe, 3]]``.

    Where the MTP module drafts (``hp.drafts``) the step is
    :func:`_build_draft_step`'s, of two rows a slot."""
    if hp.drafts:
        return _build_draft_step(hp, num_slots, page_len, num_pages)
    S = int(num_slots)
    token, pos, page_table, lens = decode_inputs(S)
    cache = _caches(hp, S, page_len, num_pages)
    x = embed(token, hp, "win", lead=[S, 1])
    stats = []
    for i in range(int(hp.num_hidden_layers)):
        held = (cache[i, "k"], cache[i, "v"])
        x, st = _layer(
            x, hp, i, pos, lens,
            cache=held + ((lens,) if hp.is_window(i)
                          else (page_table, lens)))
        if st is not None:
            stats.append(st)
    return (["gen_token", "gen_pos", "gen_page_table", "gen_lens"],
            decode_fetches(x, stats, S, hp, "win"))


def _build_draft_step(hp, num_slots, page_len, num_pages):
    """The decode TURN of a bundle whose MTP module drafts, two rows a
    slot (``ops/spec_ops.py``): the committed token ``gen_token`` at
    ``gen_pos`` and, behind it, the slot's draft (the per-slot state
    ``win_draft`` [S, 1] int32).  Feeds as every decode step's
    (``gen_lens``: rows INCLUDING the committed token) and ``gen_spec``
    [S, 1] int32: 0 turns a slot's draft row off (the row is dead: a
    blocking step that commits one token and returns the logits behind
    it).

    The main layers forward both rows (a row sees the rows at or before
    its own: the paged kernel's limit a row, the ring step's position a
    row); the verify keeps the draft where it is the first row's own
    greedy pick; the MTP module runs on the kept rows, fills its cache
    and its last live row's pick is the slot's next draft.  Fetches
    ``[logits [S, V] of the committed token's row, stats [n_moe + 1, 3],
    yield [S, 3] int32]``: a slot's (first token, second token or -1,
    how many: 0 for a free slot)."""
    S = int(num_slots)
    token, pos, page_table, lens = decode_inputs(S)
    cache = _caches(hp, S, page_len, num_pages)

    def block(x, i, row_pos, end, row_lens):
        held = (cache[i, "k"], cache[i, "v"])
        return _layer(x, hp, i, row_pos, row_lens,
                      cache=held + ((end, row_lens) if hp.is_window(i)
                                    else (page_table, end, row_lens)))

    def forward(rows):
        x = embed(rows["Ids"], hp, "win", lead=[S, 2])
        stats = []
        for i in range(int(hp.num_hidden_layers)):
            x, st = block(x, i, rows["RowPos"], rows["End"], rows["RowLens"])
            if st is not None:
                stats.append(st)
        return x, stats

    return (["gen_token", "gen_pos", "gen_page_table", "gen_lens",
             "gen_spec"],
            draft_turn(hp, "win", S, DRAFT_VAR, token, pos, lens, forward,
                       lambda h, *rows: block(h, MTP, *rows)))


def _window_section(hp):
    """``gen_meta.json``'s ``window_attention``: which layer keeps which
    kind of cache, and what the predictor counts a step's reads from."""
    import jax.numpy as jnp
    item = jnp.dtype(hp.dtype).itemsize
    return {
        "window": int(hp.sliding_window),
        "ring": hp.ring_rows,
        "layers": hp.window_layers,
        # the MTP module's block counts as one more full layer (its index
        # the one behind the last layer's)
        "full_layers": hp.full_layers
        + [int(hp.num_hidden_layers)] * hp.drafts,
        "ring_vars": ring_var_names(hp),
        "row_bytes": [sum(hp.row_widths(i)) * item
                      for i in hp.window_layers],
        # (query heads, K/V heads) of a window and of a full layer
        "heads": list(hp.attention(hp.window_layers[0])[:2]),
        "full_heads": list(hp.attention(hp.full_layers[0])[:2])
        if hp.full_layers else None,
    }


def export_window_model(dirname, hp: WindowMoEConfig = None, num_slots=8,
                        prompt_buckets=None, page_len=PAGE_LEN_DEFAULT,
                        num_pages=None, page_buckets=None):
    """Export a generation bundle (``decoder.export_bundle``), its
    ``prefill`` the chunk program (``prefill_chunks`` in the meta;
    ``prompt_buckets`` bounds the longest prompt and is what
    ``GenPredictor.prefill`` + ``write_slot`` hand rows over in).
    ``cache_vars`` names the full layers' pools, ``state_vars`` the
    window layers' rings, and ``window_attention`` which layer has which
    and what a ring row takes.  Returns ``dirname``."""
    hp = hp or WindowMoEConfig()
    # a turn with a draft writes one row past the committed one, which
    # the committed row's window must not have lost
    if hp.ring_rows < int(hp.sliding_window) + hp.drafts:
        raise ValueError(f"a ring of {hp.ring_rows} rows cannot hold a "
                         f"window of {hp.sliding_window}"
                         + " and a draft's row" * hp.drafts)

    def sections(meta):
        own = {"decode_stats": DECODE_STATS if hp.moe_layers else [],
               "prefill_chunks": chunk_rows(
                   meta["page_len"], meta["prompt_buckets"], hp.max_len)}
        if hp.window_layers:
            own["window_attention"] = _window_section(hp)
        if hp.drafts:
            # a turn carries ``rows`` rows a slot and yields 1 .. rows
            # tokens; the draft lives in ``draft_var`` (a state array)
            own["speculative"] = speculative_meta(DRAFT_VAR)
        return own

    return export_bundle(
        dirname, hp, "window_moe.export_window_model",
        lambda *pool: build_chunk_program(hp, *pool),
        lambda *pool: build_paged_decode_program(hp, *pool),
        paged_cache_var_names(hp), hp.num_hidden_layers,
        num_slots=num_slots, prompt_buckets=prompt_buckets,
        page_len=page_len, num_pages=num_pages, page_buckets=page_buckets,
        state_vars=ring_var_names(hp) + [DRAFT_VAR] * hp.drafts,
        sections=sections)
