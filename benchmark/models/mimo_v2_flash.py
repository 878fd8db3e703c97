"""Model adapter ``mimo_v2_flash``: everything in the benchmark that knows
``paddle_tpu.models.window_moe`` (MiMo-V2-Flash: sliding-window layers
with a learnable sink beside full-attention layers by
``hybrid_layer_pattern``, key heads of 192 and value heads of 128, 8 or 4
K/V heads by kind, a rotary on the leading 64 lanes of a head at one of
two thetas, a leading dense SwiGLU layer and sigmoid ``noaux_tc`` top-8
MoE layers by ``moe_layer_freq``).  The seven functions of
``lib/models.py`` as ``models/gen_lm.py`` documents them, and the byte
and operation counts of this model's own per-layer metrics.

The configuration holds ONE CHIP'S SHARE of an expert-parallel deployment:
the published layers ``layer_offset .. layer_offset + num_hidden_layers -
1``, ``experts_held`` of ``n_routed_experts`` experts from
``expert_offset`` and ``vocab_size`` rows of the vocabulary; program and
reference leave out what the absent experts would add.
"""

from __future__ import annotations

# the parent of the PR that brought window layers fails HERE, at once
from paddle_tpu.ops import window_ops  # noqa: F401

from reference import mimo_v2_flash_ref as ref

SHAPE_KEYS = (
    "hidden_size", "num_hidden_layers", "layer_offset", "vocab_size",
    "intermediate_size", "moe_intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "v_head_dim",
    "swa_num_attention_heads", "swa_num_key_value_heads", "swa_head_dim",
    "swa_v_head_dim", "sliding_window", "ring", "key_head_stored",
    "partial_rotary_factor", "rope_theta", "swa_rope_theta",
    "attention_value_scale", "hybrid_layer_pattern", "moe_layer_freq",
    "add_swa_attention_sink_bias", "add_full_attention_sink_bias",
    "n_routed_experts", "num_experts_per_tok", "experts_held",
    "expert_offset")


def bundle_key(cfg):
    return [{k: cfg.get(k) for k in SHAPE_KEYS}, cfg["serving"]]


def export(path, cfg):
    from paddle_tpu.models import window_moe
    sv = cfg["serving"]
    hp = window_moe.WindowMoEConfig.from_dict(cfg)
    hp.dtype = "bfloat16"
    hp.max_len = sv["max_len"]
    window_moe.export_window_model(
        path, hp, num_slots=sv["num_slots"],
        prompt_buckets=list(sv["prompt_buckets"]), page_len=sv["page_len"],
        page_buckets=list(sv["page_buckets"]))


def _layers(cfg):
    return range(cfg["num_hidden_layers"])


def moe_layers(cfg):
    """The layers held whose FFN is the routed experts."""
    return [i for i in _layers(cfg) if ref.is_moe(cfg, i)]


def window_layers(cfg):
    """The layers held that attend inside the sliding window."""
    return [i for i in _layers(cfg) if ref.is_window(cfg, i)]


def full_layers(cfg):
    """The layers held that attend every row before theirs."""
    return [i for i in _layers(cfg) if not ref.is_window(cfg, i)]


#: the seeded router (configuration file, ``assumed.router``), by
#: ``kimi_k2.6_text``'s construction: its matrix is drawn ROUTER_GAIN times
#: Xavier's width, and every expert's logit is lowered by about
#: ROUTER_OFFSET through a constant residual channel; the residual's rms
#: grows from sublayer to sublayer as RESIDUAL_RMS lists it at the FFN of
#: layer i (read off the reference at the published widths on the CPU:
#: 384 rows of one seed)
ROUTER_GAIN = 5.0
ROUTER_OFFSET = 28.0
EMBEDDING_RMS = 1.5
RESIDUAL_RMS = (1.91, 2.16, 2.39, 2.61, 2.85, 3.24, 3.44)
ROUTER_BIAS = 2e-12
#: the seeded attention (configuration file, ``assumed.attention``): W_q
#: and W_k are drawn ATTENTION_GAIN times Xavier's width, so that a head's
#: scores spread by about 2 (a softmax that is peaked, as a trained
#: head's: dropping rows then moves the logits by more than bfloat16
#: does), W_v VALUE_GAIN times so that the attention sublayer carries
#: weight in the residual, and a window layer's sink logits are drawn
#: uniform in SINK_RANGE, near the log of a full window's summed weights,
#: so that the sink holds a real share of a row's weight
ATTENTION_GAIN = 1.6
VALUE_GAIN = 2.0
SINK_RANGE = (3.5, 5.5)


def seeded_weights(cfg, seed31):
    """Every parameter of the model, drawn on the device, ONE jitted call
    a matrix, the largest (the stacked experts, the dense layer's three)
    first: the rig draws these beside the loaded ones, and a call's
    float32 scratch must fit while the device still has room.  Matrices
    Xavier-uniform (fan = the last two axes) cast to bfloat16; norm scales
    ones; the router, its correction bias, the attention's gains and the
    sinks as ``assumed`` of the configuration file says.  Returns ``{name:
    array}``."""
    import functools

    import jax
    import jax.numpy as jnp
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    E, F = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    held = cfg.get("experts_held") or E
    I = cfg["intermediate_size"]
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
    moe = moe_layers(cfg)
    out = {}
    for i in moe:
        out[f"win{i}_wg"] = xavier(key(i, 0), (held, d, F))
        out[f"win{i}_wu"] = xavier(key(i, 1), (held, d, F))
        out[f"win{i}_wd"] = xavier(key(i, 2), (held, F, d), 1.0, True)
    for i in _layers(cfg):
        if i in moe:
            continue
        out[f"win{i}_ffn_gate.w"] = xavier(key(i, 0), (d, I))
        out[f"win{i}_ffn_up.w"] = xavier(key(i, 1), (d, I))
        out[f"win{i}_ffn_down.w"] = xavier(key(i, 2), (I, d), 1.0, True)
    limit = 3 ** 0.5 * EMBEDDING_RMS
    out["win_emb"] = jax.jit(lambda k: jax.random.uniform(
        k, (v, d), f32, -limit, limit).astype(jnp.bfloat16)
        .at[:, 0].set(c0))(key(1 << 20, 0))
    out["win_head.w"] = xavier(key(1 << 20, 1), (d, v))
    out["win_norm.scale"] = ones(d)
    for i in _layers(cfg):
        p = f"win{i}_"
        H, Hkv, Dk, Dv, _, _, sink = ref.attention_shape(cfg, i)
        out[p + "q.w"] = xavier(key(i, 3), (d, H * Dk), ATTENTION_GAIN)
        out[p + "k.w"] = xavier(key(i, 4), (d, Hkv * Dk), ATTENTION_GAIN)
        out[p + "v.w"] = xavier(key(i, 5), (d, Hkv * Dv), VALUE_GAIN)
        out[p + "o.w"] = xavier(key(i, 6), (H * Dv, d), 1.0, True)
        out.update({p + "norm1.scale": ones(d), p + "norm2.scale": ones(d)})
        if sink:
            out[p + "sink"] = jax.random.uniform(
                key(i, 7), (H,), f32, *SINK_RANGE)
        if i not in moe:
            continue
        rms = RESIDUAL_RMS[min(i, len(RESIDUAL_RMS) - 1)]
        out[p + "gate.w"] = xavier(key(i, 11), (d, E), ROUTER_GAIN) \
            .at[0].set(jnp.asarray(-ROUTER_OFFSET * rms / c0, jnp.bfloat16))
        out[p + "gate.bias"] = jax.random.uniform(
            key(i, 12), (E,), f32, -ROUTER_BIAS, ROUTER_BIAS)
    return out


def reference_logits(weights, cfg, ids, positions):
    return ref.forward_logits(weights, cfg, ids, positions)


def control_logits(weights, cfg, ids, positions, kind="fp8"):
    """The controls a limit is set between: ``fp8`` (the reference one
    precision down: matrices float8 e4m3 a channel, bfloat16
    activations), ``bf16`` (the reference in the configuration's stated
    precision), ``window_off`` (the float32 reference whose window layers
    attend every row before theirs: what a program that ignored the
    window computes) and ``sink_off`` (the float32 reference without the
    sink in the softmax's denominator)."""
    import jax.numpy as jnp
    if kind == "window_off":
        return ref.forward_logits(weights, cfg, ids, positions, window=False)
    if kind == "sink_off":
        return ref.forward_logits(weights, cfg, ids, positions, sink=False)
    stored = {"fp8": jnp.float8_e4m3fn, "bf16": None}[kind]
    return ref.forward_logits(weights, cfg, ids, positions,
                              dtype=jnp.bfloat16, stored=stored)


# -- bytes and operations (bfloat16) ----------------------------------------

def attention_params(cfg, i):
    """Parameters of layer ``i``'s attention."""
    H, Hkv, Dk, Dv, _, _, sink = ref.attention_shape(cfg, i)
    d = cfg["hidden_size"]
    return d * H * Dk + d * Hkv * (Dk + Dv) + H * Dv * d + (H if sink else 0)


def expert_bytes(cfg, bytes_per_param=2):
    """Bytes of ONE routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * bytes_per_param


def param_count(cfg):
    """Parameters this chip holds."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    held = cfg.get("experts_held") or cfg["n_routed_experts"]
    moe = d * cfg["n_routed_experts"] + held * expert_bytes(cfg, 1)
    n_moe = len(moe_layers(cfg))
    return sum(attention_params(cfg, i) for i in _layers(cfg)) \
        + n_moe * moe + (cfg["num_hidden_layers"] - n_moe) * 3 * d \
        * cfg["intermediate_size"] + 2 * d * v


def decode_weight_bytes(cfg, bytes_per_param=2):
    """Bytes of matrices one decode step reads if EVERY held expert of
    every layer has a token: an upper bound while some expert has none.
    The embedding is read by row, not whole."""
    return (param_count(cfg) - cfg["hidden_size"] * cfg["vocab_size"]) \
        * bytes_per_param


def _stored_row_bytes(cfg, i, bytes_per_elem=2):
    """Bytes of one cached row of layer ``i`` AS STORED: a key head laid
    out ``key_head_stored`` lanes wide, a value head as it is."""
    _, Hkv, Dk, Dv, _, _, _ = ref.attention_shape(cfg, i)
    return Hkv * (max(cfg.get("key_head_stored") or Dk, Dk) + Dv) \
        * bytes_per_elem


def kv_bytes_per_row(cfg, bytes_per_elem=2):
    """Bytes one LIVE row takes in the page pools, as stored: the FULL
    layers' K and V rows (a window layer keeps no row a live row: its
    ring is ``window_bytes_per_row`` a row of the window)."""
    return sum(_stored_row_bytes(cfg, i, bytes_per_elem)
               for i in full_layers(cfg))


def window_bytes_per_row(cfg, bytes_per_elem=2):
    """Bytes of ONE row of ONE window layer's ring, as stored."""
    return _stored_row_bytes(cfg, window_layers(cfg)[0], bytes_per_elem)


def window_flops_per_row(cfg):
    """FLOPs of a window layer's decode step a ring row read: one (query
    row, key row) pair of that layer."""
    return prefill_flops_per_pair(cfg, window_layers(cfg)[0])


def prefill_flops_per_pair(cfg, i):
    """FLOPs of one (query row, key row) pair of layer ``i``'s prefill
    attention: every head's score and its part of the context."""
    H, _, Dk, Dv, _, _, _ = ref.attention_shape(cfg, i)
    return 2 * H * (Dk + Dv)


def band_flops_per_pair(cfg):
    """A pair inside the band, all window layers."""
    return sum(prefill_flops_per_pair(cfg, i) for i in window_layers(cfg))


def causal_flops_per_pair(cfg):
    """A pair under the diagonal, all full layers."""
    return sum(prefill_flops_per_pair(cfg, i) for i in full_layers(cfg))


def decode_step_bytes(cfg, experts_touched, live, live_rows):
    """The LEAST bytes one decode step has to move: every matrix outside
    the routed experts once, the routed experts that had a token
    (``experts_touched``, summed over the expert layers), the full
    layers' K/V of every one of the ``live_rows`` rows, and of each
    window layer's ring no more than ``sliding_window`` rows a live slot
    (``live`` of them) and no more than there are."""
    held = len(moe_layers(cfg)) * (cfg.get("experts_held")
                                   or cfg["n_routed_experts"]) \
        * expert_bytes(cfg)
    in_window = min(live_rows, live * cfg["sliding_window"])
    return decode_weight_bytes(cfg) - held \
        + experts_touched * expert_bytes(cfg) \
        + live_rows * kv_bytes_per_row(cfg) \
        + in_window * len(window_layers(cfg)) * window_bytes_per_row(cfg)
