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

Every layer is pre-norm, ``x <- x + f(RMSNorm(x))``; a final RMSNorm
precedes the untied head.  Matrices and activations are ``dtype``
(bfloat16) with float32 accumulation; router scores, norm statistics,
the recurrence and the logits are float32.

``export_hybrid_model`` writes ``prefill/``, ``decode/`` and
``gen_meta.json`` as ``gen_lm.export_gen_model`` does.  The prefill
fetches ``[logits, k, v per attention layer ..., window, state per mixer
layer ...]``; the decode step fetches ``[logits, stats]`` with ``stats``
``[n_moe_layers, 3]`` int32 (``gen_meta.json``'s ``decode_stats``).
"""

from __future__ import annotations

import json
import os

import numpy as np

import paddle_tpu.layers as layers
from paddle_tpu import initializer as init_mod
from paddle_tpu.layer_helper import LayerHelper
from paddle_tpu.models.gen_lm import (META_FILENAME, PAGE_LEN_DEFAULT,
                                      _write_model, default_page_buckets)
from paddle_tpu.param_attr import ParamAttr

__all__ = ["HybridConfig", "build_prefill_program",
           "build_paged_decode_program", "hybrid_moe_train_program",
           "export_hybrid_model",
           "paged_cache_var_names", "state_var_names", "DECODE_STATS"]

#: the columns of the decode step's second fetch, one row per ``E`` layer
DECODE_STATS = [{"name": "moe_assignments", "reduce": "sum"},
                {"name": "moe_experts_touched", "reduce": "sum"},
                {"name": "moe_max_load", "reduce": "max"}]


class HybridConfig:
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
    # *
    num_attention_heads = 4
    num_key_value_heads = 2
    head_dim = 16
    # E
    n_routed_experts = 16
    num_experts_per_tok = 4
    moe_latent_size = 32
    moe_intermediate_size = 48
    moe_shared_expert_intermediate_size = 96
    routed_scaling_factor = 2.5
    norm_topk_prob = True
    experts_held = None              # None: all of them
    expert_offset = 0
    dtype = "bfloat16"
    max_len = 64
    eos_id = -1

    _KEYS = {"hybrid_override_pattern": "pattern",
             "layer_norm_epsilon": "eps"}

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
    def mamba_inner(self):
        return int(self.mamba_num_heads) * int(self.mamba_head_dim)

    @property
    def conv_dim(self):
        return self.mamba_inner + 2 * int(self.n_groups) \
            * int(self.ssm_state_size)

    def layers_of(self, kind):
        return [i for i, c in enumerate(self.pattern) if c == kind]


def paged_cache_var_names(hp):
    """Page-pool tensors, (k, v) per attention layer, in layer order."""
    return [f"hyb{i}_paged_{r}" for i in hp.layers_of("*") for r in "kv"]


def state_var_names(hp):
    """Per-slot state tensors, (conv window, recurrent state) per mixer
    layer, in layer order."""
    return [f"hyb{i}_{r}" for i in hp.layers_of("M")
            for r in ("conv_state", "ssm_state")]


def _state_shapes(hp, slots):
    per = [(slots, int(hp.conv_kernel) - 1, hp.conv_dim),
           (slots, int(hp.mamba_num_heads), int(hp.mamba_head_dim),
            int(hp.ssm_state_size))]
    return per * len(hp.layers_of("M"))


def _param(name, shape, dtype, init):
    return layers.create_parameter(
        list(shape), dtype, attr=ParamAttr(name=name, initializer=init))


def _matrix(hp, name, shape):
    fan = shape[-2] + shape[-1]
    limit = (6.0 / fan) ** 0.5
    return _param(name, shape, hp.dtype, init_mod.Uniform(-limit, limit))


def _vector(name, n, value):
    return _param(name, [n], "float32", init_mod.Constant(value))


def _op(op_type, inputs, outputs, attrs=None):
    """Append ``op_type``; ``outputs`` maps slot -> dtype of a fresh
    temporary, or -> an existing variable (in-place state)."""
    helper = LayerHelper(op_type)
    outs = {slot: (helper.create_tmp_variable(v) if isinstance(v, str)
                   else v) for slot, v in outputs.items()}
    helper.append_op(type=op_type,
                     inputs={k: [v] for k, v in inputs.items()
                             if v is not None},
                     outputs={k: [v] for k, v in outs.items()},
                     attrs=attrs or {})
    return outs


def _rms(x, name, hp):
    scale = _vector(name, int(x.shape[-1]), 1.0)
    return _op("rms_norm", {"X": x, "Scale": scale}, {"Out": hp.dtype},
               {"epsilon": float(hp.eps)})["Out"]


def _mixer(h, hp, i, mask=None, lens=None, states=None):
    """``M``: prefill with ``mask`` (returns the layer's new window and
    state), decode with ``lens`` and the persistable ``states``."""
    d, H = int(hp.hidden_size), int(hp.mamba_num_heads)
    inner, conv_dim = hp.mamba_inner, hp.conv_dim
    w_in = _matrix(hp, f"hyb{i}_in.w", [d, inner + conv_dim + H])
    zxbcdt = layers.matmul(h, w_in)
    z, xbc, dt = layers.split(zxbcdt, [inner, conv_dim, H], dim=2)
    conv_w = _param(f"hyb{i}_conv.w", [int(hp.conv_kernel), conv_dim],
                    "float32", init_mod.Uniform(-0.5, 0.5))
    conv_b = _vector(f"hyb{i}_conv.b", conv_dim, 0.0)
    ssm_in = {"ALog": _vector(f"hyb{i}_a_log", H, 0.0),
              "D": _vector(f"hyb{i}_d", H, 1.0),
              "DtBias": _vector(f"hyb{i}_dt_bias", H, -3.0)}
    attrs = {"n_head": H, "head_dim": int(hp.mamba_head_dim),
             "n_groups": int(hp.n_groups),
             "state": int(hp.ssm_state_size)}
    new = []
    if lens is None:
        conv = _op("ssm_scan_conv", {"X": xbc, "W": conv_w, "Bias": conv_b,
                                     "Mask": mask},
                   {"Out": hp.dtype, "Window": "float32"})
        ssm = _op("ssm_scan", {"X": conv["Out"], "Dt": dt, "Mask": mask,
                               **ssm_in},
                  {"Out": hp.dtype, "State": "float32"},
                  {**attrs, "chunk": int(hp.chunk_size)})
        new = [conv["Window"], ssm["State"]]
    else:
        window, state = states
        conv = _op("ssm_update_conv", {"X": xbc, "Window": window,
                                       "W": conv_w, "Bias": conv_b,
                                       "Lens": lens},
                   {"Out": hp.dtype, "WindowOut": window})
        ssm = _op("ssm_update", {"X": conv["Out"], "Dt": dt, "State": state,
                                 "Lens": lens, **ssm_in},
                  {"Out": hp.dtype, "StateOut": state}, attrs)
    gscale = _vector(f"hyb{i}_gnorm.scale", inner, 1.0)
    y = _op("gated_group_rms_norm", {"X": ssm["Out"], "Gate": z,
                                     "Scale": gscale}, {"Out": hp.dtype},
            {"groups": int(hp.n_groups), "epsilon": float(hp.eps)})["Out"]
    return layers.matmul(y, _matrix(hp, f"hyb{i}_out.w", [inner, d])), new


def _attention(h, hp, i, mask=None, paged=None):
    """``*``: prefill (composed, returns the masked K/V that seed the
    pool) or paged decode (``paged`` = pools, page table, lens)."""
    d = int(hp.hidden_size)
    H, Hkv, D = (int(hp.num_attention_heads), int(hp.num_key_value_heads),
                 int(hp.head_dim))
    q = layers.matmul(h, _matrix(hp, f"hyb{i}_q.w", [d, H * D]))
    k = layers.matmul(h, _matrix(hp, f"hyb{i}_k.w", [d, Hkv * D]))
    v = layers.matmul(h, _matrix(hp, f"hyb{i}_v.w", [d, Hkv * D]))
    attrs = {"n_head": H, "n_kv_head": Hkv, "scale": float(D) ** -0.5}
    kv = []
    if paged is None:
        mask_t = layers.cast(mask, hp.dtype)
        k = layers.elementwise_mul(k, mask_t, axis=0)
        v = layers.elementwise_mul(v, mask_t, axis=0)
        kv = [k, v]
        ctx = _op("gqa_attention", {"Q": q, "K": k, "V": v, "Mask": mask},
                  {"Out": hp.dtype}, attrs)["Out"]
    else:
        pk, pv, page_table, lens = paged
        ctx = _op("paged_attention",
                  {"Q": q, "K": k, "V": v, "KCache": pk, "VCache": pv,
                   "PageTable": page_table, "Lens": lens},
                  {"Out": hp.dtype, "KCacheOut": pk, "VCacheOut": pv},
                  attrs)["Out"]
    return layers.matmul(ctx, _matrix(hp, f"hyb{i}_o.w", [H * D, d])), kv


def _moe(h, hp, i, lens=None):
    """``E``: returns the layer's output and the experts' stats."""
    d, L = int(hp.hidden_size), int(hp.moe_latent_size)
    E, F = int(hp.n_routed_experts), int(hp.moe_intermediate_size)
    Fs = int(hp.moe_shared_expert_intermediate_size)
    route = _op("moe_route",
                {"X": h, "W": _matrix(hp, f"hyb{i}_gate.w", [d, E]),
                 "Bias": _vector(f"hyb{i}_gate.bias", E, 0.0)},
                {"TopkIdx": "int32", "TopkWeight": "float32"},
                {"top_k": int(hp.num_experts_per_tok),
                 "scaling": float(hp.routed_scaling_factor),
                 "norm_topk": bool(hp.norm_topk_prob)})
    u = layers.matmul(h, _matrix(hp, f"hyb{i}_down.w", [d, L]))
    routed = _op("moe_experts",
                 {"X": u, "TopkIdx": route["TopkIdx"],
                  "TopkWeight": route["TopkWeight"],
                  "W1": _matrix(hp, f"hyb{i}_w1", [hp.held, L, F]),
                  "W2": _matrix(hp, f"hyb{i}_w2", [hp.held, F, L]),
                  "Lens": lens},
                 {"Out": hp.dtype, "Stats": "int32"},
                 {"expert_offset": int(hp.expert_offset)})
    y = layers.matmul(routed["Out"], _matrix(hp, f"hyb{i}_up.w", [L, d]))
    s = layers.matmul(h, _matrix(hp, f"hyb{i}_sh1.w", [d, Fs]))
    s = _op("relu2", {"X": s}, {"Out": hp.dtype})["Out"]
    s = layers.matmul(s, _matrix(hp, f"hyb{i}_sh2.w", [Fs, d]))
    return y + s, routed["Stats"]


def _embed(ids, hp, prefix="hyb"):
    limit = (6.0 / (hp.vocab_size + hp.hidden_size)) ** 0.5
    return layers.embedding(
        ids, size=[int(hp.vocab_size), int(hp.hidden_size)], dtype=hp.dtype,
        param_attr=ParamAttr(name=f"{prefix}_emb",
                             initializer=init_mod.Uniform(-limit, limit)))


def _logits(x2, hp, prefix="hyb"):
    """Final norm and the untied head over rows ``x2`` [R, d]; float32."""
    h = _rms(x2, f"{prefix}_norm.scale", hp)
    head = _matrix(hp, f"{prefix}_head.w", [int(hp.hidden_size),
                                            int(hp.vocab_size)])
    return _op("matmul", {"X": h, "Y": head}, {"Out": "float32"},
               {"out_dtype": "float32"})["Out"]


def _data(name, shape, dtype="float32"):
    return layers.data(name=name, shape=shape, dtype=dtype,
                       append_batch_size=False)


def build_prefill_program(hp):
    """The prefill forward in the CURRENT program guard.

    Feeds (length-dynamic; callers pad to a bucket): ``gen_ids`` [1, T]
    int32, ``gen_mask`` [1, T] f32 (1 = real token, real tokens first),
    ``gen_last`` [1, T] f32 (one-hot of the last real position).
    Fetches ``[logits [1, V], k, v ..., window, state ...]``: K/V
    [1, T, Hkv*D] zeroed on pad rows, each mixer's conv window
    [1, K-1, C] and state [1, H, P, N] after the LAST REAL token."""
    ids = _data("gen_ids", [1, -1], "int32")
    mask = _data("gen_mask", [1, -1])
    last = _data("gen_last", [1, -1])
    x = _embed(ids, hp)
    kv, states = [], []
    for i, kind in enumerate(hp.pattern):
        h = _rms(x, f"hyb{i}_norm.scale", hp)
        if kind == "M":
            out, new = _mixer(h, hp, i, mask=mask)
            states += new
        elif kind == "*":
            out, new = _attention(h, hp, i, mask=mask)
            kv += new
        else:
            out, _ = _moe(h, hp, i)
        x = x + out
    last3 = layers.cast(layers.reshape(last, shape=[1, 1, -1]), hp.dtype)
    lasth = layers.reshape(layers.matmul(last3, x),
                           shape=[-1, int(hp.hidden_size)])
    return (["gen_ids", "gen_mask", "gen_last"],
            [_logits(lasth, hp)] + kv + states)


def hybrid_moe_train_program(seq_len, hp: HybridConfig = None):
    """Teacher-forced training forward over ONE sequence in the current
    program guard (the ops take one prompt at a time), over the serving
    programs' parameter names; also the model-zoo lint gate's view of
    this model.  Returns ``(avg_cost, feed_names)``; feeds ``gen_ids`` /
    ``gen_labels`` [1, T] int32."""
    hp = hp or HybridConfig()
    T = int(seq_len)
    ids = _data("gen_ids", [1, T], "int32")
    labels = _data("gen_labels", [1, T], "int32")
    mask = layers.assign(np.ones((1, T), "float32"))
    mask.stop_gradient = True
    x = _embed(ids, hp)
    for i, kind in enumerate(hp.pattern):
        h = _rms(x, f"hyb{i}_norm.scale", hp)
        sub = {"M": _mixer, "*": _attention}.get(kind)
        out = sub(h, hp, i, mask=mask)[0] if sub else _moe(h, hp, i)[0]
        x = x + out
    logits = _logits(layers.reshape(x, shape=[T, int(hp.hidden_size)]), hp)
    cost = layers.softmax_with_cross_entropy(
        logits, layers.reshape(labels, shape=[T, 1]))
    return layers.mean(x=cost), ["gen_ids", "gen_labels"]


def build_paged_decode_program(hp, num_slots, page_len, num_pages):
    """The single-token decode step in the CURRENT program guard.

    Feeds: ``gen_token`` [S, 1] int32, ``gen_page_table`` [S, P] int32
    (P bucketed by the predictor), ``gen_lens`` [S, 1] int32 (rows
    INCLUDING the current token; 0 = free slot: no page, window or state
    is written).  Persistable state, updated in place: the page pools
    ``[num_pages, page_len, Hkv*D]`` and each mixer's window and state
    ``[S, ...]``.  Fetches ``[logits [S, V], stats [n_moe, 3]]``."""
    import paddle_tpu as fluid

    S = int(num_slots)
    token = _data("gen_token", [S, 1], "int32")
    page_table = _data("gen_page_table", [S, -1], "int32")
    lens = _data("gen_lens", [S, 1], "int32")

    block = fluid.default_main_program().global_block()

    def persistable(name, shape):
        v = block.create_var(name=name, shape=list(shape), dtype="float32")
        v.persistable = True
        v.stop_gradient = True
        return v

    row = int(hp.num_key_value_heads) * int(hp.head_dim)
    pools = {n: persistable(n, [int(num_pages), int(page_len), row])
             for n in paged_cache_var_names(hp)}
    state = {n: persistable(n, shape) for n, shape in
             zip(state_var_names(hp), _state_shapes(hp, S))}

    x = layers.reshape(_embed(token, hp), shape=[S, 1, int(hp.hidden_size)])
    stats = []
    for i, kind in enumerate(hp.pattern):
        h = _rms(x, f"hyb{i}_norm.scale", hp)
        if kind == "M":
            out, _ = _mixer(h, hp, i, lens=lens,
                            states=(state[f"hyb{i}_conv_state"],
                                    state[f"hyb{i}_ssm_state"]))
        elif kind == "*":
            out, _ = _attention(h, hp, i, paged=(
                pools[f"hyb{i}_paged_k"], pools[f"hyb{i}_paged_v"],
                page_table, lens))
        else:
            out, st = _moe(h, hp, i, lens=lens)
            stats.append(st)
        x = x + out
    fetches = [_logits(layers.reshape(x, shape=[S, int(hp.hidden_size)]),
                       hp)]
    if stats:
        fetches.append(layers.concat(stats, axis=0))
    return ["gen_token", "gen_page_table", "gen_lens"], fetches


def export_hybrid_model(dirname, hp: HybridConfig = None, num_slots=8,
                        prompt_buckets=None, page_len=PAGE_LEN_DEFAULT,
                        num_pages=None, page_buckets=None):
    """Export a generation bundle in ``gen_lm.export_gen_model``'s
    layout.  ``gen_meta.json`` names, beside the paged ``cache_vars``,
    the per-slot ``state_vars`` and the decode step's ``decode_stats``.
    Returns ``dirname``."""
    import paddle_tpu as fluid
    from paddle_tpu.lod import bucket_edges

    hp = hp or HybridConfig()
    num_slots = int(num_slots)
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
        # run); pools, windows and states start as zeros
        block = dec_main.global_block()
        for name in paged_cache_var_names(hp) + state_var_names(hp):
            scope.set_var(name, np.zeros(block.var(name).shape, "float32"))
        _write_model(os.path.join(dirname, "decode"), dec_main,
                     dec_feeds, dec_fetches, exe)

    meta = {
        "format": "paddle_tpu.gen/1",
        "num_slots": num_slots,
        "max_len": int(hp.max_len),
        "vocab_size": int(hp.vocab_size),
        "n_layer": len(hp.pattern),
        "eos_id": int(hp.eos_id),
        "cache_vars": paged_cache_var_names(hp),
        "state_vars": state_var_names(hp),
        "decode_stats": DECODE_STATS if hp.layers_of("E") else [],
        "prompt_buckets": [int(b) for b in prompt_buckets],
        "page_len": int(page_len),
        "num_pages": int(num_pages),
        "page_buckets": [int(b) for b in page_buckets],
        "page_table_feed": "gen_page_table",
    }
    with open(os.path.join(dirname, META_FILENAME), "w") as f:
        json.dump(meta, f, indent=2)
    from paddle_tpu.analysis import verify_gen_bundle
    verify_gen_bundle(dirname, where="hybrid_moe.export_hybrid_model")
    return dirname
