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
bytes a slot are a constant of the bundle, whatever ``max_len``.  The
prefill returns the full layers' K/V and then each window layer's ring
(the prompt's last ``ring`` rows); ``gen_meta.json``'s
``window_attention`` says which layer has which.

Matrices and activations are ``dtype`` (bfloat16) with float32
accumulation; router scores, norm statistics, rotary angles, softmax and
logits are float32; pool and rings are ``dtype``.
"""

from __future__ import annotations

import json
import os

import numpy as np

import paddle_tpu.layers as layers
from paddle_tpu.models.gen_lm import (META_FILENAME, PAGE_LEN_DEFAULT,
                                      _write_model, default_page_buckets)
from paddle_tpu.models.hybrid_moe import (DECODE_STATS, _data, _embed,
                                          _logits, _matrix, _op, _rms,
                                          _vector)
from paddle_tpu.models.latent_moe import _gated_ffn

__all__ = ["WindowMoEConfig", "build_prefill_program",
           "build_paged_decode_program", "window_moe_train_program",
           "export_window_model", "paged_cache_var_names",
           "ring_var_names"]


class WindowMoEConfig:
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
    # feed-forward
    intermediate_size = 96
    moe_intermediate_size = 32
    n_routed_experts = 8
    num_experts_per_tok = 2
    norm_topk_prob = True
    routed_scaling_factor = None     # None: 1
    experts_held = None              # None: all of them
    expert_offset = 0
    dtype = "bfloat16"
    max_len = 64
    eos_id = -1

    _KEYS = {"layernorm_epsilon": "eps"}

    @classmethod
    def from_dict(cls, cfg):
        hp = cls()
        for key, value in cfg.items():
            name = cls._KEYS.get(key, key)
            if hasattr(cls, name) and not name.startswith("_"):
                setattr(hp, name, value)
        return hp

    @property
    def held(self):
        return int(self.n_routed_experts if self.experts_held is None
                   else self.experts_held)

    @property
    def ring_rows(self):
        return int(self.ring or self.sliding_window)

    def is_window(self, i):
        return bool(self.hybrid_layer_pattern[int(self.layer_offset) + i])

    def is_moe(self, i):
        return bool(self.moe_layer_freq[int(self.layer_offset) + i])

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
    """Page-pool tensors, (k, v) a FULL layer, in layer order."""
    return [f"win{i}_paged_{r}" for i in hp.full_layers for r in "kv"]


def ring_var_names(hp):
    """Per-slot ring tensors, (k, v) a WINDOW layer, in layer order."""
    return [f"win{i}_ring_{r}" for i in hp.window_layers for r in "kv"]


def _attention(h, hp, i, pos, mask=None, last=None, cache=None):
    """Layer ``i``'s attention: prefill (``mask``; returns the rows that
    seed its cache: the masked K/V of a full layer, the ring of a window
    layer where ``last`` is given) or the decode step (``cache`` = (k
    pool, v pool, page table, lens) of a full layer, (k ring, v ring,
    lens) of a window layer)."""
    d = int(hp.hidden_size)
    H, Hkv, Dk, Dv, theta, has_sink = hp.attention(i)
    window = int(hp.sliding_window) if hp.is_window(i) else 0
    if has_sink and not window:
        raise NotImplementedError(
            "a sink on a full-attention layer: paged_attention has none")
    rope = {"rope_dim": int(Dk * float(hp.partial_rotary_factor)) // 2 * 2,
            "theta": theta, "pad_to": hp.stored(Dk)}
    q = layers.matmul(h, _matrix(hp, f"win{i}_q.w", [d, H * Dk]))
    k = layers.matmul(h, _matrix(hp, f"win{i}_k.w", [d, Hkv * Dk]))
    v = layers.scale(
        layers.matmul(h, _matrix(hp, f"win{i}_v.w", [d, Hkv * Dv])),
        scale=float(hp.attention_value_scale))
    q = _op("rope_partial", {"X": q, "Pos": pos}, {"Out": hp.dtype},
            {"n_head": H, **rope})["Out"]
    k = _op("rope_partial", {"X": k, "Pos": pos}, {"Out": hp.dtype},
            {"n_head": Hkv, **rope})["Out"]
    sink = _vector(f"win{i}_sink", H, 0.0) if has_sink else None
    attrs = {"n_head": H, "scale": float(Dk) ** -0.5}
    seeds = []
    if cache is None and window:
        out = _op("window_attention",
                  {"Q": q, "K": k, "V": v, "Sink": sink, "Last": last},
                  {"Out": hp.dtype, **({} if last is None else {
                      "KRing": hp.dtype, "VRing": hp.dtype})},
                  {**attrs, "n_kv_head": Hkv, "window": window,
                   "ring": hp.ring_rows})
        ctx = out["Out"]
        seeds = [] if last is None else [out["KRing"], out["VRing"]]
    elif cache is None:
        mask_t = layers.cast(mask, hp.dtype)
        k = layers.elementwise_mul(k, mask_t, axis=0)
        v = layers.elementwise_mul(v, mask_t, axis=0)
        seeds = [k, v]
        ctx = _op("gqa_flash_attention", {"Q": q, "K": k, "V": v},
                  {"Out": hp.dtype}, {**attrs, "n_kv_head": Hkv})["Out"]
    elif window:
        k_ring, v_ring, lens = cache
        ctx = _op("window_attention_step",
                  {"Q": q, "K": k, "V": v, "KRing": k_ring, "VRing": v_ring,
                   "Lens": lens, "Sink": sink},
                  {"Out": hp.dtype, "KRingOut": k_ring, "VRingOut": v_ring},
                  {**attrs, "window": window})["Out"]
    else:
        pk, pv, page_table, lens = cache
        ctx = _op("paged_attention",
                  {"Q": q, "K": k, "V": v, "KCache": pk, "VCache": pv,
                   "PageTable": page_table, "Lens": lens},
                  {"Out": hp.dtype, "KCacheOut": pk, "VCacheOut": pv},
                  {**attrs, "n_kv_head": Hkv})["Out"]
    return layers.matmul(ctx, _matrix(hp, f"win{i}_o.w", [H * Dv, d])), seeds


def _moe(h, hp, i, lens):
    """The routed experts over the share held, and their stats.  ``lens``
    [rows, 1] int32: a row with 0 (a free slot's, a pad row) has no
    assignment."""
    d, E = int(hp.hidden_size), int(hp.n_routed_experts)
    F = int(hp.moe_intermediate_size)
    route = _op("moe_route",
                {"X": h, "W": _matrix(hp, f"win{i}_gate.w", [d, E]),
                 "Bias": _vector(f"win{i}_gate.bias", E, 0.0)},
                {"TopkIdx": "int32", "TopkWeight": "float32"},
                {"top_k": int(hp.num_experts_per_tok),
                 "scaling": float(hp.routed_scaling_factor or 1.0),
                 "norm_topk": bool(hp.norm_topk_prob)})
    routed = _op("moe_experts_gated",
                 {"X": h, "TopkIdx": route["TopkIdx"],
                  "TopkWeight": route["TopkWeight"],
                  "Wg": _matrix(hp, f"win{i}_wg", [hp.held, d, F]),
                  "Wu": _matrix(hp, f"win{i}_wu", [hp.held, d, F]),
                  "Wd": _matrix(hp, f"win{i}_wd", [hp.held, F, d]),
                  "Lens": lens},
                 {"Out": hp.dtype, "Stats": "int32"},
                 {"expert_offset": int(hp.expert_offset)})
    return routed["Out"], routed["Stats"]


def _layer(x, hp, i, pos, lens, mask=None, last=None, cache=None):
    """One layer; returns ``(x, the rows that seed its cache or [],
    stats or None)``."""
    out, seeds = _attention(_rms(x, f"win{i}_norm1.scale", hp), hp, i, pos,
                            mask=mask, last=last, cache=cache)
    x = x + out
    h = _rms(x, f"win{i}_norm2.scale", hp)
    if hp.is_moe(i):
        out, stats = _moe(h, hp, i, lens)
    else:
        out, stats = _gated_ffn(h, hp, f"win{i}_ffn",
                                int(hp.intermediate_size)), None
    return x + out, seeds, stats


def build_prefill_program(hp):
    """The prefill forward in the CURRENT program guard.

    Feeds (length-dynamic; callers pad to a bucket): ``gen_ids`` [1, T]
    int32, ``gen_pos`` [1, T] int32 (0 .. T-1), ``gen_mask`` [1, T] f32
    (1 = real token, real tokens first), ``gen_last`` [1, T] f32 (one-hot
    of the last real position).  Fetches ``[logits [1, V], k, v a full
    layer [1, T, row] (zeros on pad rows) ..., k ring, v ring a window
    layer [1, ring, row] ...]``."""
    ids = _data("gen_ids", [1, -1], "int32")
    pos = _data("gen_pos", [1, -1], "int32")
    mask = _data("gen_mask", [1, -1])
    last = _data("gen_last", [1, -1])
    # pad rows take no routed expert
    lens = layers.reshape(layers.cast(mask, "int32"), shape=[-1, 1])
    x = _embed(ids, hp, "win")
    paged, rings = [], []
    for i in range(int(hp.num_hidden_layers)):
        x, seeds, _ = _layer(x, hp, i, pos, lens, mask=mask, last=last)
        (rings if hp.is_window(i) else paged).extend(seeds)
    last3 = layers.cast(layers.reshape(last, shape=[1, 1, -1]), hp.dtype)
    lasth = layers.reshape(layers.matmul(last3, x),
                           shape=[-1, int(hp.hidden_size)])
    return (["gen_ids", "gen_pos", "gen_mask", "gen_last"],
            [_logits(lasth, hp, "win")] + paged + rings)


def window_moe_train_program(seq_len, hp: WindowMoEConfig = None):
    """Teacher-forced training forward over ONE sequence in the current
    program guard (the ops take one prompt at a time), over the serving
    programs' parameter names; also the model-zoo lint gate's view of
    this model.  Returns ``(avg_cost, feed_names)``; feeds ``gen_ids`` /
    ``gen_labels`` [1, T] int32."""
    hp = hp or WindowMoEConfig()
    T = int(seq_len)
    ids = _data("gen_ids", [1, T], "int32")
    labels = _data("gen_labels", [1, T], "int32")
    pos = layers.assign(np.arange(T, dtype="int32").reshape(1, T))
    mask = layers.assign(np.ones((1, T), "float32"))
    lens = layers.assign(np.ones((T, 1), "int32"))
    for v in (pos, mask, lens):
        v.stop_gradient = True
    x = _embed(ids, hp, "win")
    for i in range(int(hp.num_hidden_layers)):
        x, _, _ = _layer(x, hp, i, pos, lens, mask=mask)
    logits = _logits(layers.reshape(x, shape=[T, int(hp.hidden_size)]), hp,
                     "win")
    cost = layers.softmax_with_cross_entropy(
        logits, layers.reshape(labels, shape=[T, 1]))
    return layers.mean(x=cost), ["gen_ids", "gen_labels"]


def build_paged_decode_program(hp, num_slots, page_len, num_pages):
    """The single-token decode step in the CURRENT program guard.

    Feeds: ``gen_token`` [S, 1] int32, ``gen_pos`` [S, 1] int32 (the
    token's position), ``gen_page_table`` [S, P] int32 (P bucketed by
    the predictor; the FULL layers' alone), ``gen_lens`` [S, 1] int32
    (rows INCLUDING the current token; 0 = free slot: nothing is
    written).  Persistable state, updated in place, all ``hp.dtype``: a
    full layer's pools ``[num_pages, page_len, row]`` and a window
    layer's rings ``[S, ring, row]``.  Fetches ``[logits [S, V], stats
    [n_moe, 3]]``."""
    import paddle_tpu as fluid

    S = int(num_slots)
    token = _data("gen_token", [S, 1], "int32")
    pos = _data("gen_pos", [S, 1], "int32")
    page_table = _data("gen_page_table", [S, -1], "int32")
    lens = _data("gen_lens", [S, 1], "int32")
    block = fluid.default_main_program().global_block()

    def persistable(name, shape):
        v = block.create_var(name=name, shape=list(shape), dtype=hp.dtype)
        v.persistable = True
        v.stop_gradient = True
        return v

    cache = {}
    for i in range(int(hp.num_hidden_layers)):
        lead, kind = ([S, hp.ring_rows], "ring") if hp.is_window(i) \
            else ([int(num_pages), int(page_len)], "paged")
        for r, width in zip("kv", hp.row_widths(i)):
            cache[i, r] = persistable(f"win{i}_{kind}_{r}", lead + [width])
    x = layers.reshape(_embed(token, hp, "win"),
                       shape=[S, 1, int(hp.hidden_size)])
    stats = []
    for i in range(int(hp.num_hidden_layers)):
        held = (cache[i, "k"], cache[i, "v"])
        x, _, st = _layer(
            x, hp, i, pos, lens,
            cache=held + ((lens,) if hp.is_window(i)
                          else (page_table, lens)))
        if st is not None:
            stats.append(st)
    fetches = [_logits(layers.reshape(x, shape=[S, int(hp.hidden_size)]),
                       hp, "win")]
    if stats:
        fetches.append(layers.concat(stats, axis=0))
    return ["gen_token", "gen_pos", "gen_page_table", "gen_lens"], fetches


def export_window_model(dirname, hp: WindowMoEConfig = None, num_slots=8,
                        prompt_buckets=None, page_len=PAGE_LEN_DEFAULT,
                        num_pages=None, page_buckets=None):
    """Export a generation bundle in ``gen_lm.export_gen_model``'s
    layout.  ``cache_vars`` names the full layers' pools, ``state_vars``
    the window layers' rings, and ``window_attention`` which layer has
    which and what a ring row takes.  Returns ``dirname``."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.lod import bucket_edges

    hp = hp or WindowMoEConfig()
    num_slots = int(num_slots)
    if hp.ring_rows < int(hp.sliding_window):
        raise ValueError(f"a ring of {hp.ring_rows} rows cannot hold a "
                         f"window of {hp.sliding_window}")
    if prompt_buckets is None:
        prompt_buckets = bucket_edges(1, hp.max_len)
    page_len = max(1, min(int(page_len), int(hp.max_len)))
    pps = -(-int(hp.max_len) // page_len)
    num_pages = num_slots * pps if num_pages is None else int(num_pages)
    if page_buckets is None:
        page_buckets = default_page_buckets(pps)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        pre_main, pre_startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(pre_main, pre_startup):
            pre_feeds, pre_fetches = build_prefill_program(hp)
        exe.run(pre_startup)
        _write_model(os.path.join(dirname, "prefill"), pre_main,
                     pre_feeds, pre_fetches, exe)
        dec_main, dec_startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(dec_main, dec_startup):
            dec_feeds, dec_fetches = build_paged_decode_program(
                hp, num_slots, page_len, num_pages)
        # decode shares the initialized parameters (its startup is never
        # run); pools and rings start as zeros of the model's own type
        block = dec_main.global_block()
        for name in paged_cache_var_names(hp) + ring_var_names(hp):
            scope.set_var(name, np.zeros(block.var(name).shape,
                                         jnp.dtype(hp.dtype)))
        _write_model(os.path.join(dirname, "decode"), dec_main,
                     dec_feeds, dec_fetches, exe)

    item = jnp.dtype(hp.dtype).itemsize
    meta = {
        "format": "paddle_tpu.gen/1",
        "num_slots": num_slots,
        "max_len": int(hp.max_len),
        "vocab_size": int(hp.vocab_size),
        "n_layer": int(hp.num_hidden_layers),
        "eos_id": int(hp.eos_id),
        "cache_vars": paged_cache_var_names(hp),
        "state_vars": ring_var_names(hp),
        "decode_stats": DECODE_STATS if hp.moe_layers else [],
        "prompt_buckets": [int(b) for b in prompt_buckets],
        "page_len": int(page_len),
        "num_pages": int(num_pages),
        "page_buckets": [int(b) for b in page_buckets],
        "page_table_feed": "gen_page_table",
    }
    if hp.window_layers:
        # which layer keeps which kind of cache, and what the predictor
        # counts a step's reads from
        meta["window_attention"] = {
            "window": int(hp.sliding_window),
            "ring": hp.ring_rows,
            "layers": hp.window_layers,
            "full_layers": hp.full_layers,
            "ring_vars": ring_var_names(hp),
            "row_bytes": [sum(hp.row_widths(i)) * item
                          for i in hp.window_layers],
            # (query heads, K/V heads) of a window and of a full layer
            "heads": list(hp.attention(hp.window_layers[0])[:2]),
            "full_heads": list(hp.attention(hp.full_layers[0])[:2])
            if hp.full_layers else None,
        }
    with open(os.path.join(dirname, META_FILENAME), "w") as f:
        json.dump(meta, f, indent=2)
    from paddle_tpu.analysis import verify_gen_bundle
    verify_gen_bundle(dirname, where="window_moe.export_window_model")
    return dirname
