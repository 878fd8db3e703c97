"""Model adapter ``hybrid_moe``: everything in the benchmark that knows
``paddle_tpu.models.hybrid_moe`` (Mamba-2 mixers, grouped-query attention
and LatentMoE layers by a pattern string).  The seven functions of
``lib/models.py`` as ``models/gen_lm.py`` documents them, and the byte and
operation counts of this model's own per-layer metrics.

The configuration holds ONE CHIP'S SHARE of an expert-parallel deployment:
``experts_held`` of ``n_routed_experts`` experts from ``expert_offset``
and ``vocab_size`` rows of the vocabulary; program and reference leave out
what the absent experts would add.
"""

from __future__ import annotations

from reference import hybrid_moe_ref

SHAPE_KEYS = (
    "hidden_size", "hybrid_override_pattern", "vocab_size",
    "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
    "conv_kernel", "chunk_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "n_routed_experts",
    "num_experts_per_tok", "moe_latent_size", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "experts_held", "expert_offset")


def bundle_key(cfg):
    return [{k: cfg[k] for k in SHAPE_KEYS}, cfg["serving"]]


def export(path, cfg):
    from paddle_tpu.models import hybrid_moe
    sv = cfg["serving"]
    hp = hybrid_moe.HybridConfig.from_dict(cfg)
    hp.dtype = "bfloat16"
    hp.max_len = sv["max_len"]
    hybrid_moe.export_hybrid_model(
        path, hp, num_slots=sv["num_slots"],
        prompt_buckets=list(sv["prompt_buckets"]), page_len=sv["page_len"])


def _kinds(cfg, kind):
    return [i for i, c in enumerate(cfg["hybrid_override_pattern"])
            if c == kind]


def _conv_dim(cfg):
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


#: the seeded router (configuration file, ``assumed.router``): its matrix is
#: drawn ROUTER_GAIN times Xavier's width, and every expert's logit is
#: lowered by about ROUTER_OFFSET through a constant residual channel; the
#: residual's rms grows from layer to layer as sqrt(RESIDUAL_VAR + i *
#: LAYER_OUT_VAR) (read off the reference at the published widths)
ROUTER_GAIN = 3.0
ROUTER_OFFSET = 28.0
EMBEDDING_RMS = 1.5
RESIDUAL_VAR, LAYER_OUT_VAR = EMBEDDING_RMS ** 2 + 0.25, 1.5


def seeded_weights(cfg, seed31):
    """Every parameter of the model, drawn on the device, one jitted call
    per layer kind: matrices Xavier-uniform (fan = the last two axes)
    cast to bfloat16; ``A_log`` = log of uniform [1, 16], ``D`` ones,
    ``dt_bias`` so that softplus gives ``dt`` log-uniform in
    [time_step_min, time_step_max] (floored at time_step_floor), the
    conv's taps uniform +-0.5 and its bias uniform +-0.02, the router's
    correction bias zeros, norm scales ones (configuration file,
    ``assumed``).

    The router is seeded to score as a TRAINED sigmoid router does, which
    Xavier's cannot (512 near-equal scores of about 0.9: the 22nd and the
    23rd differ by a rounding error and each carries 5/22 of the routed
    sum): a token's chosen experts carry unequal weights, the marginal
    one next to none.  The published router has no bias, so the offset
    comes as it does in a trained model, from a constant component of the
    residual stream: channel 0 of every embedding row holds ``sqrt(d) /
    2`` and no layer writes to it (column 0 of every matrix that writes
    the residual is zero); row 0 of layer i's router matrix is
    ``-ROUTER_OFFSET * (the residual's rms there) / that constant``, so
    the normalised channel lowers every logit by about ROUTER_OFFSET; the
    router's other rows are ROUTER_GAIN times Xavier's, the embedding's
    other entries uniform with rms EMBEDDING_RMS, of the order of a
    layer's output (Xavier's 0.01 would drown under the first layer).
    Gain and offset keep the order of a token's scores: the experts
    chosen are those Xavier's router would choose, only the weights
    differ.  Returns ``{name: array}``."""
    import jax
    import jax.numpy as jnp
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    inner, conv_dim, K = H * P, _conv_dim(cfg), cfg["conv_kernel"]
    Hq, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    E, L, F = (cfg["n_routed_experts"], cfg["moe_latent_size"],
               cfg["moe_intermediate_size"])
    Fs, held = cfg["moe_shared_expert_intermediate_size"], cfg["experts_held"]
    f32 = jnp.float32

    c0 = d ** 0.5 / 2       # the constant residual channel's value

    def xavier(key, shape, gain=1.0):
        limit = gain * (6.0 / (shape[-2] + shape[-1])) ** 0.5
        return jax.random.uniform(key, shape, f32, -limit, limit) \
            .astype(jnp.bfloat16)

    def writes(key, shape):
        """A matrix whose product is added to the residual: it leaves
        the constant channel alone."""
        return xavier(key, shape).at[:, 0].set(0)

    ones = lambda n: jnp.ones((n,), f32)

    @jax.jit
    def mixer(key, i):
        k = jax.random.split(key, 6)
        dt = jnp.exp(jax.random.uniform(
            k[4], (H,), f32, jnp.log(cfg["time_step_min"]),
            jnp.log(cfg["time_step_max"])))
        dt = jnp.maximum(dt, cfg["time_step_floor"])
        return {"in.w": xavier(k[0], (d, inner + conv_dim + H)),
                "conv.w": jax.random.uniform(k[1], (K, conv_dim), f32,
                                             -0.5, 0.5),
                "conv.b": jax.random.uniform(k[2], (conv_dim,), f32,
                                             -0.02, 0.02),
                "a_log": jnp.log(jax.random.uniform(k[3], (H,), f32,
                                                    1.0, 16.0)),
                "d": ones(H),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "gnorm.scale": ones(inner),
                "out.w": writes(k[5], (inner, d)), "norm.scale": ones(d)}

    @jax.jit
    def attention(key, i):
        k = jax.random.split(key, 4)
        return {"q.w": xavier(k[0], (d, Hq * D)),
                "k.w": xavier(k[1], (d, Hkv * D)),
                "v.w": xavier(k[2], (d, Hkv * D)),
                "o.w": writes(k[3], (Hq * D, d)), "norm.scale": ones(d)}

    @jax.jit
    def moe(key, i):
        k = jax.random.split(key, 7)
        rms = jnp.sqrt(RESIDUAL_VAR + LAYER_OUT_VAR * i)
        gate = xavier(k[0], (d, E), ROUTER_GAIN).at[0].set(
            (-ROUTER_OFFSET * rms / c0).astype(jnp.bfloat16))
        return {"gate.w": gate, "gate.bias": jnp.zeros((E,), f32),
                "down.w": xavier(k[1], (d, L)), "up.w": writes(k[2], (L, d)),
                "w1": xavier(k[3], (held, L, F)),
                "w2": xavier(k[4], (held, F, L)),
                "sh1.w": xavier(k[5], (d, Fs)),
                "sh2.w": writes(k[6], (Fs, d)), "norm.scale": ones(d)}

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 2)
        limit = 3 ** 0.5 * EMBEDDING_RMS
        emb = jax.random.uniform(k[0], (v, d), f32, -limit, limit) \
            .astype(jnp.bfloat16).at[:, 0].set(c0)
        return {"hyb_emb": emb,
                "hyb_head.w": xavier(k[1], (d, v)),
                "hyb_norm.scale": ones(d)}

    draw = {"M": mixer, "*": attention, "E": moe}
    root = jax.random.PRNGKey(seed31)
    out = {}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        for name, arr in draw[kind](jax.random.fold_in(root, i),
                                    float(i)).items():
            out[f"hyb{i}_{name}"] = arr
    out.update(ends(jax.random.fold_in(root, 1 << 20)))
    return out


def reference_logits(weights, cfg, ids, positions):
    return hybrid_moe_ref.forward_logits(weights, cfg, ids, positions)


def control_logits(weights, cfg, ids, positions, kind="fp8"):
    import jax.numpy as jnp
    stored = {"fp8": jnp.float8_e4m3fn, "bf16": None}[kind]
    return hybrid_moe_ref.forward_logits(weights, cfg, ids, positions,
                                         dtype=jnp.bfloat16, stored=stored)


# -- bytes and operations (what the ALGORITHM needs; bfloat16 matrices) -----

def expert_bytes(cfg, bytes_per_param=2):
    """Bytes of ONE routed expert's two matrices."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"] \
        * bytes_per_param


def decode_weight_bytes(cfg, bytes_per_param=2):
    """Bytes of matrices one decode step reads if EVERY held expert of
    every layer has a token: an upper bound while some expert has none
    (which is why this cell is not under decode_step_hbm_roofline)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    mixer = d * (inner + _conv_dim(cfg) + cfg["mamba_num_heads"]) + inner * d
    hd = cfg["head_dim"]
    attn = 2 * d * hd * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])
    moe = d * cfg["n_routed_experts"] + 2 * d * cfg["moe_latent_size"] \
        + 2 * d * cfg["moe_shared_expert_intermediate_size"]
    params = (len(_kinds(cfg, "M")) * mixer + len(_kinds(cfg, "*")) * attn
              + len(_kinds(cfg, "E")) * moe + d * v)
    return params * bytes_per_param \
        + len(_kinds(cfg, "E")) * cfg["experts_held"] * expert_bytes(cfg)


def decode_step_bytes(cfg, experts_touched, live, live_rows):
    """The LEAST bytes one decode step has to move: every matrix outside
    the routed experts once, the routed experts that had a token
    (``experts_touched``: held experts touched, summed over the E layers),
    the recurrent state and conv windows of the ``live`` slots read and
    written once, and the K/V of the ``live_rows`` rows in the pool."""
    held = len(_kinds(cfg, "E")) * cfg["experts_held"] * expert_bytes(cfg)
    return decode_weight_bytes(cfg) - held \
        + experts_touched * expert_bytes(cfg) \
        + 2 * live * ssm_state_bytes_per_slot(cfg) \
        + live_rows * kv_bytes_per_row(cfg)


def kv_bytes_per_row(cfg, bytes_per_elem=4):
    """Bytes of K and V one live row of a slot holds in the page pool
    (float32 rows of num_key_value_heads x head_dim, attention layers
    only)."""
    return 2 * len(_kinds(cfg, "*")) * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * bytes_per_elem


def ssm_state_bytes_per_slot(cfg):
    """Bytes of recurrent state and conv window one slot holds over all
    mixer layers (float32): read and written once a decode step."""
    state = cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"]
    window = (cfg["conv_kernel"] - 1) * _conv_dim(cfg)
    return len(_kinds(cfg, "M")) * (state + window) * 4


def ssm_scan_flops(cfg, rows):
    """FLOPs of the chunked scan over ``rows`` prompt rows, all mixer
    layers: per row, C.B against the chunk's rows (2 q n a group), the
    masked product with x (2 q p a head), the chunk's own state and the
    read of the carried one (2 p n a head, each)."""
    q, n, p = cfg["chunk_size"], cfg["ssm_state_size"], cfg["mamba_head_dim"]
    per_row = 2 * q * n * cfg["n_groups"] \
        + cfg["mamba_num_heads"] * (2 * q * p + 4 * p * n)
    return len(_kinds(cfg, "M")) * rows * per_row


def ssm_scan_bytes(cfg, rows, bytes_per_elem=2):
    """Bytes the scan has to move for ``rows`` prompt rows: xBC and dt
    in, y out (bfloat16), all mixer layers."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    per_row = _conv_dim(cfg) + cfg["mamba_num_heads"] + inner
    return len(_kinds(cfg, "M")) * rows * per_row * bytes_per_elem
