"""Model adapter ``solar_open2``: everything in the benchmark that knows
``paddle_tpu.models.hybrid_moe`` as it builds Solar-Open2-250B (KDA
linear-attention mixers with a per-slot matrix state, three to every
gated NoPE grouped-query layer by ``gqa_layers``, a sigmoid top-8 router
over gated experts and one shared expert in every layer).  The seven
functions of ``lib/models.py`` as ``models/gen_lm.py`` documents them,
and the byte and operation counts of this model's own per-layer metrics.

The configuration holds ONE CHIP'S SHARE of an expert-parallel deployment:
the published layers ``layer_offset .. layer_offset + num_hidden_layers -
1``, ``experts_held`` of ``n_routed_experts`` experts from
``expert_offset`` and ``vocab_size`` rows of the vocabulary; program and
reference leave out what the absent experts would add.  The program names
published layer ``j``'s mixer ``hyb{2j}`` and its feed-forward ``hyb{2j +
1}``.
"""

from __future__ import annotations

# the parent of the PR that brought the KDA mixer fails HERE, at once
from paddle_tpu.ops import kda_ops  # noqa: F401

from reference import solar_open2_ref as ref

SHAPE_KEYS = (
    "hidden_size", "num_hidden_layers", "layer_offset", "vocab_size",
    "gqa_layers", "use_gqa_gate", "use_rope", "linear_attn_config",
    "kda_use_full_proj", "kda_allow_neg_eigval", "num_attention_heads",
    "num_key_value_heads", "head_dim", "moe_intermediate_size",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
    "experts_held", "expert_offset", "pool_dtype")


def bundle_key(cfg):
    return [{k: cfg.get(k) for k in SHAPE_KEYS}, cfg["serving"]]


def export(path, cfg):
    from paddle_tpu.models import hybrid_moe
    sv = cfg["serving"]
    hp = hybrid_moe.HybridConfig.from_dict(cfg)
    hp.dtype = "bfloat16"
    hp.max_len = sv["max_len"]
    hp.prefill_chunk_rows = sv.get("chunk_rows")
    hybrid_moe.export_hybrid_model(
        path, hp, num_slots=sv["num_slots"],
        prompt_buckets=list(sv["prompt_buckets"]), page_len=sv["page_len"],
        num_pages=sv.get("num_pages"),
        page_buckets=list(sv["page_buckets"]))


def kda_layers(cfg):
    """The program's sublayer indices of the KDA mixers held."""
    return [2 * j for j, kind in enumerate(ref.layer_kinds(cfg))
            if kind == "K"]


def gqa_layers(cfg):
    """The program's sublayer indices of the softmax layers held."""
    return [2 * j for j, kind in enumerate(ref.layer_kinds(cfg))
            if kind == "G"]


def moe_layers(cfg):
    return [2 * j + 1 for j in range(cfg["num_hidden_layers"])]


def _kda_shape(cfg):
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


#: the seeded router (configuration file, ``assumed.router``), by
#: ``kimi_k2.6_text``'s construction: its matrix is drawn ROUTER_GAIN times
#: Xavier's width, and every expert's logit is lowered by about
#: ROUTER_OFFSET through a constant residual channel; the residual's rms
#: at the FFN of published layer j is RESIDUAL_RMS[j] (read off the
#: reference at the published widths on the chip: 768 rows of two seeds,
#: which agree to 0.01; my chip run, PR 49)
ROUTER_GAIN = 5.0
ROUTER_OFFSET = 28.0
EMBEDDING_RMS = 1.5
RESIDUAL_RMS = (1.75, 1.92, 2.08, 2.23, 2.44, 2.57, 2.69, 2.81)
#: the seeded softmax layers (``assumed.attention``): W_q and W_k are
#: drawn ATTENTION_GAIN times Xavier's width, so that a head's scores
#: spread by 2-3 (a softmax that is peaked, as a trained head's), W_v
#: VALUE_GAIN times so that the sublayer carries weight in the residual
ATTENTION_GAIN = 1.6
VALUE_GAIN = 2.0
#: the seeded decay (``assumed.decay``): A_log = log of uniform
#: [A_RANGE], dt_bias so that softplus gives dt log-uniform in DT_RANGE
#: (the published initialisation of the family's gate)
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)


def seeded_weights(cfg, seed31):
    """Every parameter of the model, drawn on the device, ONE jitted call
    a matrix, the largest (the stacked experts) first: the rig draws
    these beside the loaded ones, and a call's float32 scratch must fit
    while the device still has room.  Matrices Xavier-uniform (fan = the
    last two axes) cast to bfloat16; norm scales ones; the router, the
    softmax layers' gains and the decay as ``assumed`` of the
    configuration file says.  Returns ``{name: array}``."""
    import functools

    import jax
    import jax.numpy as jnp
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    E, F = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    held = cfg.get("experts_held") or E
    Fs = F * int(cfg.get("n_shared_experts") or 0)
    Hq, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    H, Dk, K = _kda_shape(cfg)
    inner, rank = H * Dk, Dk
    f32 = jnp.float32
    c0 = d ** 0.5 / 2       # the constant residual channel's value

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def xavier(key, shape, gain=1.0, writes=False):
        limit = gain * (6.0 / (shape[-2] + shape[-1])) ** 0.5
        w = jax.random.uniform(key, shape, f32, -limit, limit) \
            .astype(jnp.bfloat16)
        # a matrix whose product is added to the residual leaves the
        # constant channel alone
        return w.at[..., 0].set(0) if writes else w

    root = jax.random.PRNGKey(seed31)
    key = lambda i, j: jax.random.fold_in(jax.random.fold_in(root, i), j)
    ones = lambda n: jnp.ones((n,), f32)
    out = {}
    for i in moe_layers(cfg):
        out[f"hyb{i}_wg"] = xavier(key(i, 0), (held, d, F))
        out[f"hyb{i}_wu"] = xavier(key(i, 1), (held, d, F))
        out[f"hyb{i}_wd"] = xavier(key(i, 2), (held, F, d), 1.0, True)
    limit = 3 ** 0.5 * EMBEDDING_RMS
    out["hyb_emb"] = jax.jit(lambda k: jax.random.uniform(
        k, (v, d), f32, -limit, limit).astype(jnp.bfloat16)
        .at[:, 0].set(c0))(key(1 << 20, 0))
    out["hyb_head.w"] = xavier(key(1 << 20, 1), (d, v))
    out["hyb_norm.scale"] = ones(d)
    for i in kda_layers(cfg):
        p = f"hyb{i}_"
        out[p + "qkv.w"] = xavier(key(i, 0), (d, 3 * inner))
        out[p + "o.w"] = xavier(key(i, 1), (inner, d), 1.0, True)
        out[p + "f_a.w"] = xavier(key(i, 2), (d, rank))
        out[p + "f_b.w"] = xavier(key(i, 3), (rank, inner))
        out[p + "g_a.w"] = xavier(key(i, 4), (d, rank))
        out[p + "g_b.w"] = xavier(key(i, 5), (rank, inner))
        out[p + "b.w"] = xavier(key(i, 6), (d, H))
        out[p + "conv.w"] = jax.random.uniform(key(i, 7), (K, 3 * inner),
                                               f32, -0.5, 0.5)
        out[p + "a_log"] = jnp.log(jax.random.uniform(key(i, 8), (H,), f32,
                                                      *A_RANGE))
        dt = jnp.exp(jax.random.uniform(
            key(i, 9), (inner,), f32, *(jnp.log(x) for x in DT_RANGE)))
        out[p + "dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
        out[p + "onorm.scale"] = ones(Dk)
        out[p + "norm.scale"] = ones(d)
    for i in gqa_layers(cfg):
        p = f"hyb{i}_"
        out[p + "q.w"] = xavier(key(i, 0), (d, Hq * D), ATTENTION_GAIN)
        out[p + "k.w"] = xavier(key(i, 1), (d, Hkv * D), ATTENTION_GAIN)
        out[p + "v.w"] = xavier(key(i, 2), (d, Hkv * D), VALUE_GAIN)
        out[p + "gate.w"] = xavier(key(i, 3), (d, Hq * D))
        out[p + "o.w"] = xavier(key(i, 4), (Hq * D, d), 1.0, True)
        out[p + "norm.scale"] = ones(d)
    for j, i in enumerate(moe_layers(cfg)):
        p = f"hyb{i}_"
        rms = RESIDUAL_RMS[min(j, len(RESIDUAL_RMS) - 1)]
        out[p + "gate.w"] = xavier(key(i, 11), (d, E), ROUTER_GAIN) \
            .at[0].set(jnp.asarray(-ROUTER_OFFSET * rms / c0, jnp.bfloat16))
        out[p + "gate.bias"] = jnp.zeros((E,), f32)
        if Fs:
            out[p + "sh_gate.w"] = xavier(key(i, 12), (d, Fs))
            out[p + "sh_up.w"] = xavier(key(i, 13), (d, Fs))
            out[p + "sh_down.w"] = xavier(key(i, 14), (Fs, d), 1.0, True)
        out[p + "norm.scale"] = ones(d)
    return out


def reference_logits(weights, cfg, ids, positions):
    return ref.forward_logits(weights, cfg, ids, positions)


def control_logits(weights, cfg, ids, positions, kind="fp8"):
    """The controls a limit is set between: ``fp8`` (the reference one
    precision down: matrices float8 e4m3 a channel, bfloat16
    activations), ``bf16`` (the reference in the configuration's stated
    precision) and ``decay_off`` (the float32 reference with ``alpha =
    1``: what a program that ignored the decay gate computes)."""
    import jax.numpy as jnp
    if kind == "decay_off":
        return ref.forward_logits(weights, cfg, ids, positions, decay=False)
    stored = {"fp8": jnp.float8_e4m3fn, "bf16": None}[kind]
    return ref.forward_logits(weights, cfg, ids, positions,
                              dtype=jnp.bfloat16, stored=stored)


# -- bytes and operations (bfloat16 matrices) --------------------------------

def expert_bytes(cfg, bytes_per_param=2):
    """Bytes of ONE routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * bytes_per_param


def gqa_params(cfg):
    """Parameters of one softmax mixer: W_q, W_k, W_v, the gate, W_o."""
    d = cfg["hidden_size"]
    wide = cfg["num_attention_heads"] * cfg["head_dim"]
    narrow = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d * (3 * wide + 2 * narrow)


def kda_params(cfg):
    """Parameters of one KDA mixer: W_qkv, W_o, the two low-rank pairs,
    W_beta (the conv's taps, A_log, dt_bias and the norm's scale are
    float32 vectors: not counted among the matrices)."""
    d = cfg["hidden_size"]
    H, D, _ = _kda_shape(cfg)
    return 4 * d * H * D + 2 * (d * D + D * H * D) + d * H


def param_count(cfg):
    """Parameters this chip holds."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    held = cfg.get("experts_held") or cfg["n_routed_experts"]
    ffn = d * cfg["n_routed_experts"] + (held + int(
        cfg.get("n_shared_experts") or 0)) * expert_bytes(cfg, 1)
    return len(gqa_layers(cfg)) * gqa_params(cfg) \
        + len(kda_layers(cfg)) * kda_params(cfg) \
        + cfg["num_hidden_layers"] * ffn + 2 * d * v


def decode_weight_bytes(cfg, bytes_per_param=2):
    """Bytes of matrices one decode step reads if EVERY held expert of
    every layer has a token: an upper bound while some expert has none.
    The embedding is read by row, not whole."""
    return (param_count(cfg) - cfg["hidden_size"] * cfg["vocab_size"]) \
        * bytes_per_param


def kv_bytes_per_row(cfg, bytes_per_elem=2):
    """Bytes of K and V one live row of a slot holds in the page pools
    (the softmax layers' alone; rows of num_key_value_heads x head_dim in
    the pool's type, bfloat16)."""
    return 2 * len(gqa_layers(cfg)) * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * bytes_per_elem


def kda_state_bytes_per_slot(cfg):
    """Bytes of matrix state one slot holds over all KDA mixers (float32,
    heads x head_dim x head_dim each)."""
    H, D, _ = _kda_shape(cfg)
    return len(kda_layers(cfg)) * H * D * D * 4


def kda_conv_bytes_per_slot(cfg):
    """Bytes of conv window one slot holds over all KDA mixers (float32,
    kernel - 1 rows of q | k | v)."""
    H, D, K = _kda_shape(cfg)
    return len(kda_layers(cfg)) * (K - 1) * 3 * H * D * 4


def kda_update_bytes_per_slot(cfg, bytes_per_elem=2):
    """The LEAST bytes the one-token update moves a live slot, all KDA
    mixers: the matrix state read and written once, and the step's rows
    in (q | k | v in bfloat16, the decay's projection and beta in
    float32) and out (o in bfloat16)."""
    H, D, _ = _kda_shape(cfg)
    rows = 3 * H * D * bytes_per_elem + (H * D + H) * 4 \
        + H * D * bytes_per_elem
    return 2 * kda_state_bytes_per_slot(cfg) + len(kda_layers(cfg)) * rows


def kda_scan_flops_per_row(cfg, block=64):
    """FLOPs of the chunk-wise form a prompt row, all KDA mixers, in
    blocks of ``block`` rows: per head the block's two pair matrices (k .
    k and q . k against the block's rows: 2 x 2 block D), the triangular
    solve against v | k (block x 2 D), the three products with the
    carried state (W_k S, Q S and the state's own update: 3 x 2 D D) and
    the pairs' with the pseudo-values (2 block D)."""
    H, D, _ = _kda_shape(cfg)
    return len(kda_layers(cfg)) * H * (4 * block * D + 2 * block * D
                                       + 6 * D * D + 2 * block * D)


def decode_step_bytes(cfg, experts_touched, live, live_rows):
    """The LEAST bytes one decode step has to move: every matrix outside
    the routed experts once, the routed experts that had a token
    (``experts_touched``, summed over the layers), the matrix state and
    conv window of the ``live`` slots read and written once, and the
    softmax layers' K/V of the ``live_rows`` rows in the pool."""
    held = cfg["num_hidden_layers"] * (cfg.get("experts_held")
                                       or cfg["n_routed_experts"]) \
        * expert_bytes(cfg)
    return decode_weight_bytes(cfg) - held \
        + experts_touched * expert_bytes(cfg) \
        + 2 * live * (kda_state_bytes_per_slot(cfg)
                      + kda_conv_bytes_per_slot(cfg)) \
        + live_rows * kv_bytes_per_row(cfg)
