"""Hybrid state-space / attention / mixture-of-experts causal LM (the
``nemotron_h`` family's block layout) on the generative serving path: the
same prefill + paged-decode program pair and bundle layout as
``models/gen_lm.py``, for a model whose layers each hold ONE sublayer,
named by a pattern string:

* ``M``  Mamba-2 mixer.  Its cache is a fixed per-slot state (the
  recurrent state and the conv's window, ``ops/ssm_ops.py``), not pages:
  the bundle's ``state_vars``.
* ``*``  causal attention with grouped query heads and no positional
  embedding (the mixers carry position); its K/V live in the page pool,
  rows of ``Hkv * head_dim`` (``cache_vars``).
* ``E``  LatentMoE feed-forward: a sigmoid top-k router over ALL the
  model's experts on the full hidden state, the routed experts in a
  latent of ``moe_latent_size`` (``ops/moe_ops.py``), one shared expert
  on the full hidden state.  The layer HOLDS ``experts_held`` experts
  from ``expert_offset`` on (one chip's share of an expert-parallel
  deployment); what the absent ones would add is left out.
* ``K``  KDA mixer: gated delta-rule linear attention with a decay a key
  channel (``ops/kda_ops.py``).  Its cache is a per-slot MATRIX state a
  head ``[slots, heads, head_dim, head_dim]`` and the window of the conv
  over q | k | v, both float32 ``state_vars``.  Both of its ops over
  that state are Pallas kernels where the shapes allow (heads of 128,
  rungs of whole 64-row blocks: the published widths), updating the
  state in place: ``kda_update`` every slot's a decode step, ``kda_scan``
  ONE slot's a prompt chunk, with the head's state in VMEM for the whole
  chunk; at toy widths both lower as plain XLA (``ops/kda_ops.py``).
* ``G``  ``*`` with an element-wise sigmoid output gate, a projection of
  the sublayer's input.
* ``S``  the same router over gated SwiGLU experts on the hidden state
  itself (``moe_experts_gated``) and ``n_shared_experts`` shared ones of
  the same width on every row.

A ``solar_open2`` ``config.json`` (``gqa_layers``) names no pattern:
``from_dict`` writes one, ``G`` or ``K`` then ``S`` for each published
layer from ``layer_offset`` on.

Every layer is pre-norm, ``x <- x + f(RMSNorm(x))``; a final RMSNorm
precedes the untied head.  Matrices and activations are ``dtype``
(bfloat16) with float32 accumulation; router scores, norm statistics,
the recurrence and the logits are float32.

**A pattern with ``K`` prefills by CHUNKS** (``build_chunk_program``,
``gen_meta.json``'s ``prefill_chunks``, as ``models/window_moe.py``): a
chunk takes the slot's matrix state and conv window from the persistable
arrays the decode step reads and leaves its own there (from zeros where
it is the prompt's first), and an attention layer writes the chunk's K/V
into the slot's pages and attends the pages' rows.  Nothing seeds a slot
afterwards.  ``M`` has no chunk form yet.

``export_hybrid_model`` writes ``prefill/``, ``decode/`` and
``gen_meta.json`` as ``gen_lm.export_gen_model`` does.  The prefill
fetches ``[logits, k, v per attention layer ..., window, state per mixer
layer ...]``; the decode step fetches ``[logits, stats]`` with ``stats``
``[n_moe_layers, 3]`` int32 (``gen_meta.json``'s ``decode_stats``).
"""

from __future__ import annotations

import paddle_tpu.layers as layers
from paddle_tpu import initializer as init_mod
from paddle_tpu.models.decoder import (DECODE_STATS, PAGE_LEN_DEFAULT,
                                       DecoderConfig, chunk_rows, data,
                                       decode_fetches,
                                       decode_inputs, embed, export_bundle,
                                       gated_ffn, group,
                                       last_row, live_rows, logits, matrix,
                                       op, param,
                                       persistable, prefill_inputs,
                                       program_role, rms,
                                       routed_experts, train_inputs,
                                       train_loss, vector)

__all__ = ["HybridConfig", "build_prefill_program", "build_chunk_program",
           "build_paged_decode_program", "hybrid_moe_train_program",
           "export_hybrid_model",
           "paged_cache_var_names", "state_var_names", "DECODE_STATS"]


class HybridConfig(DecoderConfig):
    """Toy-scale defaults (the serving mechanics are what the tests
    exercise); ``from_dict`` takes the published keys of a
    ``nemotron_h`` ``config.json``."""
    vocab_size = 64
    hidden_size = 64
    pattern = "EM*"                  # hybrid_override_pattern
    eps = 1e-5                       # layer_norm_epsilon
    # M
    mamba_num_heads = 4
    mamba_head_dim = 16
    n_groups = 2
    ssm_state_size = 16
    conv_kernel = 4
    chunk_size = 128
    # K
    kda_num_heads = 4
    kda_head_dim = 16
    kda_conv_kernel = 4
    kda_beta_scale = 2.0             # 2: negative eigenvalues allowed
    # * and G
    num_attention_heads = 4
    num_key_value_heads = 2
    head_dim = 16
    pool_dtype = "float32"           # the page pools' rows
    # E and S
    n_routed_experts = 16
    num_experts_per_tok = 4
    moe_latent_size = 32
    moe_intermediate_size = 48
    moe_shared_expert_intermediate_size = 96
    routed_scaling_factor = 2.5
    norm_topk_prob = True
    n_shared_experts = 1             # S: shared experts beside the routed
    experts_held = None              # None: all of them
    expert_offset = 0
    dtype = "bfloat16"
    max_len = 64
    eos_id = -1
    # the larger rung of a chunk program's prefill (None: the library's)
    prefill_chunk_rows = None

    _KEYS = {"hybrid_override_pattern": "pattern",
             "layer_norm_epsilon": "eps", "rms_norm_eps": "eps"}

    @classmethod
    def from_dict(cls, cfg):
        """The published keys of a ``nemotron_h`` ``config.json``, or of
        a ``solar_open2`` one (``gqa_layers`` names the softmax layers;
        every other is a KDA mixer, and every layer's feed-forward the
        shared-expert MoE): its pattern is written here, two sublayers a
        published layer, ``num_hidden_layers`` of them from
        ``layer_offset`` on."""
        cfg = dict(cfg)
        if "gqa_layers" in cfg:
            if cfg.get("use_rope") or cfg.get("kda_use_full_proj") \
                    or cfg.get("first_k_dense_replace"):
                raise NotImplementedError(
                    "solar_open2 with use_rope, kda_use_full_proj or "
                    "leading dense layers: no such sublayer is written")
            lin = cfg["linear_attn_config"]
            first = int(cfg.get("layer_offset", 0))
            softmax = "G" if cfg.get("use_gqa_gate") else "*"
            cfg.update(
                pattern="".join(
                    (softmax if l in cfg["gqa_layers"] else "K") + "S"
                    for l in range(first,
                                   first + int(cfg["num_hidden_layers"]))),
                kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
                kda_conv_kernel=lin["short_conv_kernel_size"],
                kda_beta_scale=2.0 if cfg.get("kda_allow_neg_eigval")
                else 1.0)
        return super().from_dict(cfg)

    @property
    def mamba_inner(self):
        return int(self.mamba_num_heads) * int(self.mamba_head_dim)

    @property
    def conv_dim(self):
        return self.mamba_inner + 2 * int(self.n_groups) \
            * int(self.ssm_state_size)

    @property
    def kda_inner(self):
        return int(self.kda_num_heads) * int(self.kda_head_dim)

    @property
    def chunked(self):
        """The prefill is a chunk program (a pattern with ``K``)."""
        return "K" in self.pattern

    def layers_of(self, kinds):
        """The layers whose kind is one of the letters ``kinds``."""
        return [i for i, c in enumerate(self.pattern) if c in kinds]


def paged_cache_var_names(hp):
    """Page-pool tensors, (k, v) per attention layer, in layer order."""
    return [f"hyb{i}_paged_{r}" for i in hp.layers_of("*G") for r in "kv"]


def state_var_names(hp):
    """Per-slot state tensors, (conv window, recurrent state) per mixer
    layer of either kind, in layer order."""
    return [f"hyb{i}_{r}" for i in hp.layers_of("MK")
            for r in ("conv_state",
                      "ssm_state" if hp.pattern[i] == "M" else "kda_state")]


def _state_shapes(hp, slots):
    """Of :func:`state_var_names`' arrays, in their order."""
    H, D = int(hp.kda_num_heads), int(hp.kda_head_dim)
    per = {"M": [(slots, int(hp.conv_kernel) - 1, hp.conv_dim),
                 (slots, int(hp.mamba_num_heads), int(hp.mamba_head_dim),
                  int(hp.ssm_state_size))],
           "K": [(slots, int(hp.kda_conv_kernel) - 1, 3 * hp.kda_inner),
                 (slots, H, D, D)]}
    return [shape for i in hp.layers_of("MK") for shape in per[hp.pattern[i]]]


def _mixer(h, hp, i, mask=None, lens=None, states=None):
    """``M``: prefill with ``mask`` (returns the layer's new window and
    state), decode with ``lens`` and the persistable ``states``."""
    d, H = int(hp.hidden_size), int(hp.mamba_num_heads)
    inner, conv_dim = hp.mamba_inner, hp.conv_dim
    w_in = matrix(hp, f"hyb{i}_in.w", [d, inner + conv_dim + H])
    zxbcdt = layers.matmul(h, w_in)
    z, xbc, dt = layers.split(zxbcdt, [inner, conv_dim, H], dim=2)
    conv_w = param(f"hyb{i}_conv.w", [int(hp.conv_kernel), conv_dim],
                   "float32", init_mod.Uniform(-0.5, 0.5))
    conv_b = vector(f"hyb{i}_conv.b", conv_dim, 0.0)
    ssm_in = {"ALog": vector(f"hyb{i}_a_log", H, 0.0),
              "D": vector(f"hyb{i}_d", H, 1.0),
              "DtBias": vector(f"hyb{i}_dt_bias", H, -3.0)}
    attrs = {"n_head": H, "head_dim": int(hp.mamba_head_dim),
             "n_groups": int(hp.n_groups),
             "state": int(hp.ssm_state_size)}
    new = []
    if lens is None:
        conv = op("ssm_scan_conv", {"X": xbc, "W": conv_w, "Bias": conv_b,
                                    "Mask": mask},
                  {"Out": hp.dtype, "Window": "float32"})
        ssm = op("ssm_scan", {"X": conv["Out"], "Dt": dt, "Mask": mask,
                              **ssm_in},
                 {"Out": hp.dtype, "State": "float32"},
                 {**attrs, "chunk": int(hp.chunk_size)})
        new = [conv["Window"], ssm["State"]]
    else:
        window, state = states
        conv = op("ssm_update_conv", {"X": xbc, "Window": window,
                                      "W": conv_w, "Bias": conv_b,
                                      "Lens": lens},
                  {"Out": hp.dtype, "WindowOut": window})
        ssm = op("ssm_update", {"X": conv["Out"], "Dt": dt, "State": state,
                                "Lens": lens, **ssm_in},
                 {"Out": hp.dtype, "StateOut": state}, attrs)
    gscale = vector(f"hyb{i}_gnorm.scale", inner, 1.0)
    y = op("gated_group_rms_norm", {"X": ssm["Out"], "Gate": z,
                                    "Scale": gscale}, {"Out": hp.dtype},
           {"groups": int(hp.n_groups), "epsilon": float(hp.eps)})["Out"]
    return layers.matmul(y, matrix(hp, f"hyb{i}_out.w", [inner, d])), new


def _kda(h, hp, i, states, chunk=None, lens=None):
    """``K``: ONE CHUNK of a prompt (``chunk`` = slot, positions, mask)
    or the decode step (``lens``), over the persistable ``states`` (conv
    window, matrix state), read and left in place."""
    d, H, inner = int(hp.hidden_size), int(hp.kda_num_heads), hp.kda_inner
    rank = int(hp.kda_head_dim)     # the low-rank pairs' (no full projection)
    window, state = states

    def f32(x, w):      # the decay's inputs stay float32
        return op("matmul", {"X": x, "Y": w}, {"Out": "float32"},
                  {"out_dtype": "float32"})["Out"]

    def low_rank(name):
        return (layers.matmul(h, matrix(hp, f"hyb{i}_{name}_a.w",
                                        [d, rank])),
                matrix(hp, f"hyb{i}_{name}_b.w", [rank, inner]))

    qkv = layers.matmul(h, matrix(hp, f"hyb{i}_qkv.w", [d, 3 * inner]))
    conv_w = param(f"hyb{i}_conv.w", [int(hp.kda_conv_kernel), 3 * inner],
                   "float32", init_mod.Uniform(-0.5, 0.5))
    rec_in = {"F": f32(*low_rank("f")),
              "B": f32(h, matrix(hp, f"hyb{i}_b.w", [d, H])),
              "ALog": vector(f"hyb{i}_a_log", H, 0.0),
              "DtBias": vector(f"hyb{i}_dt_bias", inner, 0.0),
              "State": state}
    attrs = {"n_head": H, "beta_scale": float(hp.kda_beta_scale)}
    outs = {"Out": hp.dtype, "StateOut": state}
    if lens is None:
        slot, pos, mask = chunk
        where = {"Slot": slot, "Pos": pos, "Mask": mask}
        conv = op("ssm_chunk_conv", {"X": qkv, "W": conv_w,
                                     "Window": window, **where},
                  {"Out": hp.dtype, "WindowOut": window})
        rec = op("kda_scan", {"X": conv["Out"], **rec_in, **where}, outs,
                 attrs)
    else:
        conv = op("ssm_update_conv", {"X": qkv, "Window": window,
                                      "W": conv_w, "Lens": lens},
                  {"Out": hp.dtype, "WindowOut": window})
        rec = op("kda_update", {"X": conv["Out"], **rec_in, "Lens": lens},
                 outs, attrs)
    y = op("kda_gated_norm",
           {"X": rec["Out"], "Gate": layers.matmul(*low_rank("g")),
            "Scale": vector(f"hyb{i}_onorm.scale", int(hp.kda_head_dim),
                            1.0)},
           {"Out": hp.dtype}, {"n_head": H, "epsilon": float(hp.eps)})["Out"]
    return layers.matmul(y, matrix(hp, f"hyb{i}_o.w", [inner, d]))


def _attention(h, hp, i, mask=None, paged=None, chunk=None, gate=False):
    """``*``: prefill (composed, returns the masked K/V that seed the
    pool), ONE CHUNK of a prompt over the slot's pages (``chunk`` =
    pools, page table, positions, mask) or paged decode (``paged`` =
    pools, page table, lens).  ``gate`` (``G``): the context times the
    sigmoid of a projection of ``h``."""
    d = int(hp.hidden_size)
    H, Hkv, D = (int(hp.num_attention_heads), int(hp.num_key_value_heads),
                 int(hp.head_dim))
    q = layers.matmul(h, matrix(hp, f"hyb{i}_q.w", [d, H * D]))
    k = layers.matmul(h, matrix(hp, f"hyb{i}_k.w", [d, Hkv * D]))
    v = layers.matmul(h, matrix(hp, f"hyb{i}_v.w", [d, Hkv * D]))
    attrs = {"n_head": H, "n_kv_head": Hkv, "scale": float(D) ** -0.5}
    kv = []
    if chunk is not None:
        pk, pv, page_table, pos, mask = chunk
        ctx = op("gqa_flash_attention_chunk",
                 {"Q": q, "K": k, "V": v, "KCache": pk, "VCache": pv,
                  "PageTable": page_table, "Pos": pos, "Mask": mask},
                 {"Out": hp.dtype, "KCacheOut": pk, "VCacheOut": pv},
                 attrs)["Out"]
    elif paged is None:
        mask_t = layers.cast(mask, hp.dtype)
        k = layers.elementwise_mul(k, mask_t, axis=0)
        v = layers.elementwise_mul(v, mask_t, axis=0)
        kv = [k, v]
        ctx = op("gqa_attention", {"Q": q, "K": k, "V": v, "Mask": mask},
                 {"Out": hp.dtype}, attrs)["Out"]
    else:
        pk, pv, page_table, lens = paged
        ctx = op("paged_attention",
                 {"Q": q, "K": k, "V": v, "KCache": pk, "VCache": pv,
                  "PageTable": page_table, "Lens": lens},
                 {"Out": hp.dtype, "KCacheOut": pk, "VCacheOut": pv},
                 attrs)["Out"]
    if gate:
        ctx = op("attention_out_gate",
                 {"X": ctx, "Gate": layers.matmul(
                     h, matrix(hp, f"hyb{i}_gate.w", [d, H * D]))},
                 {"Out": hp.dtype})["Out"]
    return layers.matmul(ctx, matrix(hp, f"hyb{i}_o.w", [H * D, d])), kv


def _moe(h, hp, i, lens=None):
    """``E``: returns the layer's output and the experts' stats."""
    d, Fs = int(hp.hidden_size), int(hp.moe_shared_expert_intermediate_size)
    y, stats = routed_experts(
        h, hp, f"hyb{i}", lens, experts=int(hp.n_routed_experts),
        held=hp.held, expert_offset=hp.expert_offset,
        scaling=hp.routed_scaling_factor, latent=int(hp.moe_latent_size))
    with group("dense"):
        s = layers.matmul(h, matrix(hp, f"hyb{i}_sh1.w", [d, Fs]))
        s = op("relu2", {"X": s}, {"Out": hp.dtype})["Out"]
        s = layers.matmul(s, matrix(hp, f"hyb{i}_sh2.w", [Fs, d]))
    return y + s, stats


def _shared_moe(h, hp, i, lens=None):
    """``S``: returns the layer's output and the experts' stats."""
    y, stats = routed_experts(
        h, hp, f"hyb{i}", lens, experts=int(hp.n_routed_experts),
        held=hp.held, expert_offset=hp.expert_offset,
        scaling=hp.routed_scaling_factor or 1.0)
    if hp.n_shared_experts:
        y = y + gated_ffn(h, hp, f"hyb{i}_sh",
                          int(hp.moe_intermediate_size)
                          * int(hp.n_shared_experts))
    return y, stats


#: the feed-forward sublayers by their letter
_FFN = {"E": _moe, "S": _shared_moe}

#: a sublayer's group (``decoder.GROUPS``) by its letter: its norm, what
#: it computes and its residual add; a shared expert inside ``E`` / ``S``
#: is ``dense``
_GROUP = {"M": "mixer", "K": "mixer", "*": "attn", "G": "attn",
          "E": "experts", "S": "experts"}


def _caches(hp, num_slots, page_len, num_pages):
    """The persistable caches of the CURRENT program: ``(pools, state)``,
    each ``{name: var}``: an attention layer's pools ``[num_pages,
    page_len, Hkv * D]`` in ``hp.pool_dtype``, a mixer's window and state
    ``[num_slots, ...]`` in float32."""
    row = int(hp.num_key_value_heads) * int(hp.head_dim)
    pools = {n: persistable(n, [int(num_pages), int(page_len), row],
                            hp.pool_dtype)
             for n in paged_cache_var_names(hp)}
    state = {n: persistable(n, shape, "float32") for n, shape in
             zip(state_var_names(hp), _state_shapes(hp, int(num_slots)))}
    return pools, state


@program_role("gen_chunk")
def build_chunk_program(hp, num_slots, page_len, num_pages):
    """The prefill of ONE CHUNK of a prompt in the CURRENT program guard
    (a pattern of ``K``, ``*`` / ``G`` and ``E`` / ``S``).

    Feeds (length-dynamic; the predictor pads to a chunk rung):
    ``gen_ids`` [1, C] int32, ``gen_pos`` [1, C] int32 (the rows'
    positions ``P .. P + C - 1``; no layer turns by them: they say where
    the chunk stands), ``gen_mask`` [1, C] f32 (1 = real token, real
    tokens first), ``gen_last`` [1, C] f32 (one-hot of the prompt's last
    row where this chunk holds it, else zeros), ``gen_slot`` [1, 1] int32
    and ``gen_page_table`` [1, P] int32 (the slot's row, P bucketed by
    the predictor and covering the chunk's last real row).  Persistable
    state, read and updated in place, as the decode step's: the attention
    layers' pools and each ``K`` mixer's conv window and matrix state,
    which the chunk at ``P`` = 0 starts from zeros.  Fetches ``[logits
    [1, V]]`` (of the row ``gen_last`` names)."""
    if hp.layers_of("M"):
        raise NotImplementedError(
            "a pattern with K and M: a Mamba-2 mixer has no chunk form (its "
            "scan starts from zeros and hands its state out)")
    ids, pos, mask, last = prefill_inputs()
    slot = data("gen_slot", [1, 1], "int32")
    page_table = data("gen_page_table", [1, -1], "int32")
    pools, state = _caches(hp, num_slots, page_len, num_pages)
    lens = live_rows(mask)
    x = embed(ids, hp, "hyb")
    for i, kind in enumerate(hp.pattern):
        with group(_GROUP[kind]):
            h = rms(x, f"hyb{i}_norm.scale", hp)
            if kind == "K":
                out = _kda(h, hp, i, (state[f"hyb{i}_conv_state"],
                                      state[f"hyb{i}_kda_state"]),
                           chunk=(slot, pos, mask))
            elif kind in "*G":
                out, _ = _attention(h, hp, i, gate=kind == "G", chunk=(
                    pools[f"hyb{i}_paged_k"], pools[f"hyb{i}_paged_v"],
                    page_table, pos, mask))
            else:
                out, _ = _FFN[kind](h, hp, i, lens=lens)
            x = x + out
    return (["gen_ids", "gen_pos", "gen_mask", "gen_last", "gen_slot",
             "gen_page_table"],
            [logits(last_row(x, last, hp), hp, "hyb")])


@program_role("gen_prefill")
def build_prefill_program(hp):
    """The prefill forward in the CURRENT program guard.

    Feeds (length-dynamic; callers pad to a bucket): ``gen_ids`` [1, T]
    int32, ``gen_mask`` [1, T] f32 (1 = real token, real tokens first),
    ``gen_last`` [1, T] f32 (one-hot of the last real position).
    Fetches ``[logits [1, V], k, v ..., window, state ...]``: K/V
    [1, T, Hkv*D] zeroed on pad rows, each mixer's conv window
    [1, K-1, C] and state [1, H, P, N] after the LAST REAL token."""
    ids, _, mask, last = prefill_inputs(pos=False)
    x = embed(ids, hp, "hyb")
    kv, states = [], []
    for i, kind in enumerate(hp.pattern):
        with group(_GROUP[kind]):
            h = rms(x, f"hyb{i}_norm.scale", hp)
            if kind == "M":
                out, new = _mixer(h, hp, i, mask=mask)
                states += new
            elif kind in "*G":
                out, new = _attention(h, hp, i, mask=mask, gate=kind == "G")
                kv += new
            else:
                out, _ = _FFN[kind](h, hp, i)
            x = x + out
    return (["gen_ids", "gen_mask", "gen_last"],
            [logits(last_row(x, last, hp), hp, "hyb")] + kv + states)


def hybrid_moe_train_program(seq_len, hp: HybridConfig = None):
    """Teacher-forced training forward over ONE sequence in the current
    program guard (the ops take one prompt at a time), over the serving
    programs' parameter names; also the model-zoo lint gate's view of
    this model.  Returns ``(avg_cost, feed_names)``; feeds ``gen_ids`` /
    ``gen_labels`` [1, T] int32."""
    hp = hp or HybridConfig()
    if hp.chunked:
        raise NotImplementedError(
            "training a pattern with K: kda_scan has no backward")
    ids, labels, rows = train_inputs(seq_len, "mask")
    x = embed(ids, hp, "hyb")
    for i, kind in enumerate(hp.pattern):
        with group(_GROUP[kind]):
            h = rms(x, f"hyb{i}_norm.scale", hp)
            if kind == "M":
                out, _ = _mixer(h, hp, i, mask=rows["mask"])
            elif kind in "*G":
                out, _ = _attention(h, hp, i, mask=rows["mask"],
                                    gate=kind == "G")
            else:
                out, _ = _FFN[kind](h, hp, i)
            x = x + out
    return train_loss(x, labels, hp, "hyb")


@program_role("gen_decode")
def build_paged_decode_program(hp, num_slots, page_len, num_pages):
    """The single-token decode step in the CURRENT program guard.

    Feeds: ``gen_token`` [S, 1] int32, ``gen_page_table`` [S, P] int32
    (P bucketed by the predictor), ``gen_lens`` [S, 1] int32 (rows
    INCLUDING the current token; 0 = free slot: no page, window or state
    is written).  Persistable state, updated in place: the page pools
    ``[num_pages, page_len, Hkv*D]`` and each mixer's window and state
    ``[S, ...]``, float32 (the pools: ``hp.pool_dtype``).  Fetches
    ``[logits [S, V], stats [n_moe, 3]]``."""
    S = int(num_slots)
    token, _, page_table, lens = decode_inputs(S, pos=False)
    pools, state = _caches(hp, S, page_len, num_pages)

    x = embed(token, hp, "hyb", lead=[S, 1])
    stats = []
    for i, kind in enumerate(hp.pattern):
        with group(_GROUP[kind]):
            h = rms(x, f"hyb{i}_norm.scale", hp)
            if kind == "M":
                out, _ = _mixer(h, hp, i, lens=lens,
                                states=(state[f"hyb{i}_conv_state"],
                                        state[f"hyb{i}_ssm_state"]))
            elif kind == "K":
                out = _kda(h, hp, i, (state[f"hyb{i}_conv_state"],
                                      state[f"hyb{i}_kda_state"]),
                           lens=lens)
            elif kind in "*G":
                out, _ = _attention(h, hp, i, gate=kind == "G", paged=(
                    pools[f"hyb{i}_paged_k"], pools[f"hyb{i}_paged_v"],
                    page_table, lens))
            else:
                out, st = _FFN[kind](h, hp, i, lens=lens)
                stats.append(st)
            x = x + out
    return (["gen_token", "gen_page_table", "gen_lens"],
            decode_fetches(x, stats, S, hp, "hyb"))


def export_hybrid_model(dirname, hp: HybridConfig = None, num_slots=8,
                        prompt_buckets=None, page_len=PAGE_LEN_DEFAULT,
                        num_pages=None, page_buckets=None):
    """Export a generation bundle (``decoder.export_bundle``).
    ``gen_meta.json`` names, beside the paged ``cache_vars``, the
    per-slot ``state_vars`` and the decode step's ``decode_stats``; of a
    pattern with ``K`` (its prefill the chunk program) also
    ``prefill_chunks``.  Returns ``dirname``."""
    hp = hp or HybridConfig()

    def sections(meta):
        own = {"decode_stats": DECODE_STATS if hp.layers_of("ES") else []}
        if hp.chunked:
            own["prefill_chunks"] = chunk_rows(
                meta["page_len"], meta["prompt_buckets"], hp.max_len,
                top=hp.prefill_chunk_rows)
        return own

    return export_bundle(
        dirname, hp, "hybrid_moe.export_hybrid_model",
        (lambda *pool: build_chunk_program(hp, *pool)) if hp.chunked
        else (lambda *pool: build_prefill_program(hp)),
        lambda *pool: build_paged_decode_program(hp, *pool),
        paged_cache_var_names(hp), len(hp.pattern), num_slots=num_slots,
        prompt_buckets=prompt_buckets, page_len=page_len,
        num_pages=num_pages, page_buckets=page_buckets,
        state_vars=state_var_names(hp), sections=sections)
