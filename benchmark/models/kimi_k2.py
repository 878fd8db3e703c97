"""Model adapter ``kimi_k2``: everything in the benchmark that knows
``paddle_tpu.models.latent_moe`` (multi-head latent attention over one
compressed page pool, a leading dense layer, SwiGLU shared-expert MoE
layers).  The seven functions of ``lib/models.py`` as ``models/gen_lm.py``
documents them, and the byte and operation counts of this model's own
per-layer metrics.

The configuration holds ONE CHIP'S SHARE of an expert-parallel deployment:
``experts_held`` of ``n_routed_experts`` experts from ``expert_offset``
and ``vocab_size`` rows of the vocabulary; program and reference leave out
what the absent experts would add.
"""

from __future__ import annotations

from reference import kimi_k2_ref

SHAPE_KEYS = (
    "hidden_size", "num_hidden_layers", "first_k_dense_replace",
    "vocab_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
    "rope_scaling", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "experts_held", "expert_offset")


def bundle_key(cfg):
    return [{k: cfg[k] for k in SHAPE_KEYS}, cfg["serving"]]


def export(path, cfg):
    from paddle_tpu.models import latent_moe
    from paddle_tpu.models.gen_lm import default_page_buckets
    sv = cfg["serving"]
    hp = latent_moe.LatentMoEConfig.from_dict(cfg)
    hp.dtype = "bfloat16"
    hp.max_len = sv["max_len"]
    pages = -(-sv["max_len"] // sv["page_len"])
    latent_moe.export_latent_model(
        path, hp, num_slots=sv["num_slots"],
        prompt_buckets=list(sv["prompt_buckets"]), page_len=sv["page_len"],
        page_buckets=default_page_buckets(pages))


def _moe_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


#: the seeded router (configuration file, ``assumed.router``), by
#: ``nemotron3_super_ep8``'s construction: its matrix is drawn ROUTER_GAIN
#: times Xavier's width, and every expert's logit is lowered by about
#: ROUTER_OFFSET through a constant residual channel; the residual's rms
#: grows from sublayer to sublayer as RESIDUAL_RMS lists it at the router
#: of layer i (read off the reference at the published widths)
#: (a gain of 3, Nemotron's, spreads a token's 8 chosen of 384 over e^4.4:
#: a marginal expert still carries 1% of the routed sum and, where a
#: token's top scores lie close, far more: one served-token check in
#: twenty then read 0.024 of range against the float8 control's 0.033;
#: at 5 the marginal expert carries e^-7.3)
ROUTER_GAIN = 5.0
ROUTER_OFFSET = 28.0
EMBEDDING_RMS = 1.5
RESIDUAL_RMS = (1.584, 1.635, 1.759, 1.880, 1.990)
#: the correction bias: uniform in +-ROUTER_BIAS.  The scores lie on the
#: sigmoid's foot, where a token's 8th largest is about exp(-28 + 1.8 x
#: 4) = 9e-10 and its largest about 1e-7.  The bias has to stay far under
#: the 8th score of EVERY token: where it is of the order of a token's
#: scores (a token whose logits all lie low), the choice among experts of
#: comparable score is the bias's, their normalised weights are not
#: small, and one expert that flips under bfloat16 moves the logits by 2%
#: of their range (read on the chip with +-1.9e-10: one set-up check in
#: forty; +-1e-3 chose the same eight experts for every token)
ROUTER_BIAS = 2e-12


def seeded_weights(cfg, seed31):
    """Every parameter of the model, drawn on the device, ONE jitted call
    a matrix, the largest (the stacked experts, the dense layer's three)
    first: the rig draws these beside the loaded ones, and a call's
    float32 scratch must fit while the device still has room.  Matrices
    Xavier-uniform (fan = the last two axes) cast to bfloat16; norm scales
    ones; the router and its correction bias as ``assumed.router`` and
    ``assumed.e_score_correction_bias`` of the configuration file say (the
    construction is ``benchmark/models/hybrid_moe.py``'s: a constant
    residual channel that no layer writes to lowers every logit).
    Returns ``{name: array}``."""
    import functools

    import jax
    import jax.numpy as jnp
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    H, ql, L = (cfg["num_attention_heads"], cfg["q_lora_rank"],
                cfg["kv_lora_rank"])
    nope, R, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                   cfg["v_head_dim"])
    E, F, held = (cfg["n_routed_experts"], cfg["moe_intermediate_size"],
                  cfg["experts_held"])
    I, Fs = cfg["intermediate_size"], F * cfg["n_shared_experts"]
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
    layers = range(cfg["num_hidden_layers"])
    moe = [i for i in layers if i >= cfg["first_k_dense_replace"]]
    out = {}
    for i in moe:                                   # 1.06 GB a layer
        out[f"lat{i}_wg"] = xavier(key(i, 0), (held, d, F))
        out[f"lat{i}_wu"] = xavier(key(i, 1), (held, d, F))
        out[f"lat{i}_wd"] = xavier(key(i, 2), (held, F, d), 1.0, True)
    for i in layers:
        if i in moe:
            continue
        out[f"lat{i}_ffn_gate.w"] = xavier(key(i, 0), (d, I))
        out[f"lat{i}_ffn_up.w"] = xavier(key(i, 1), (d, I))
        out[f"lat{i}_ffn_down.w"] = xavier(key(i, 2), (I, d), 1.0, True)
    limit = 3 ** 0.5 * EMBEDDING_RMS
    out["lat_emb"] = jax.jit(lambda k: jax.random.uniform(
        k, (v, d), f32, -limit, limit).astype(jnp.bfloat16)
        .at[:, 0].set(c0))(key(1 << 20, 0))
    out["lat_head.w"] = xavier(key(1 << 20, 1), (d, v))
    out["lat_norm.scale"] = ones(d)
    for i in layers:
        p = f"lat{i}_"
        out[p + "qa.w"] = xavier(key(i, 3), (d, ql))
        out[p + "qb.w"] = xavier(key(i, 4), (ql, H * (nope + R)))
        out[p + "kva.w"] = xavier(key(i, 5), (d, L + R))
        out[p + "kvb.w"] = xavier(key(i, 6), (L, H * (nope + vd)))
        out[p + "o.w"] = xavier(key(i, 7), (H * vd, d), 1.0, True)
        out.update({p + "qnorm.scale": ones(ql),
                    p + "kvnorm.scale": ones(L),
                    p + "norm1.scale": ones(d), p + "norm2.scale": ones(d)})
        if i not in moe:
            continue
        out[p + "sh_gate.w"] = xavier(key(i, 8), (d, Fs))
        out[p + "sh_up.w"] = xavier(key(i, 9), (d, Fs))
        out[p + "sh_down.w"] = xavier(key(i, 10), (Fs, d), 1.0, True)
        rms = RESIDUAL_RMS[min(i, len(RESIDUAL_RMS) - 1)]
        out[p + "gate.w"] = xavier(key(i, 11), (d, E), ROUTER_GAIN) \
            .at[0].set(jnp.asarray(-ROUTER_OFFSET * rms / c0, jnp.bfloat16))
        out[p + "gate.bias"] = jax.random.uniform(
            key(i, 12), (E,), f32, -ROUTER_BIAS, ROUTER_BIAS)
    return out


def reference_logits(weights, cfg, ids, positions):
    return kimi_k2_ref.forward_logits(weights, cfg, ids, positions)


def control_logits(weights, cfg, ids, positions, kind="fp8"):
    import jax.numpy as jnp
    stored = {"fp8": jnp.float8_e4m3fn, "bf16": None}[kind]
    return kimi_k2_ref.forward_logits(weights, cfg, ids, positions,
                                      dtype=jnp.bfloat16, stored=stored)


# -- bytes and operations (what the ALGORITHM needs; bfloat16) --------------

def mla_params(cfg):
    """Parameters of one layer's latent attention."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, R, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                   cfg["v_head_dim"])
    return d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * H * (nope + R) \
        + d * (cfg["kv_lora_rank"] + R) \
        + cfg["kv_lora_rank"] * H * (nope + vd) + H * vd * d


def expert_bytes(cfg, bytes_per_param=2):
    """Bytes of ONE routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * bytes_per_param


def param_count(cfg):
    """Parameters this chip holds."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    shared = 3 * d * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    moe = mla_params(cfg) + shared + d * cfg["n_routed_experts"] \
        + cfg["experts_held"] * expert_bytes(cfg, 1)
    dense = mla_params(cfg) + 3 * d * cfg["intermediate_size"]
    return cfg["first_k_dense_replace"] * dense + _moe_layers(cfg) * moe \
        + 2 * d * v


def decode_weight_bytes(cfg, bytes_per_param=2):
    """Bytes of matrices one decode step reads if EVERY held expert of
    every layer has a token: an upper bound while some expert has none
    (which is why this cell is not under decode_step_hbm_roofline).  The
    embedding is read by row, not whole."""
    return (param_count(cfg) - cfg["hidden_size"] * cfg["vocab_size"]) \
        * bytes_per_param


def decode_step_bytes(cfg, experts_touched, live, live_rows):
    """The LEAST bytes one decode step has to move: every matrix outside
    the routed experts once, the routed experts that had a token
    (``experts_touched``: held experts touched, summed over the expert
    layers) and the latent rows of the ``live_rows`` rows in the pool.
    ``live`` is not used: this model keeps no per-slot state."""
    held = _moe_layers(cfg) * cfg["experts_held"] * expert_bytes(cfg)
    return decode_weight_bytes(cfg) - held \
        + experts_touched * expert_bytes(cfg) \
        + live_rows * kv_bytes_per_row(cfg)


def kv_bytes_per_row(cfg, bytes_per_elem=2):
    """Bytes one live row of a slot holds in the page pool that the
    ALGORITHM needs: ``kv_lora_rank + qk_rope_head_dim`` bfloat16 values a
    layer.  (The row is stored 640 wide, zeros behind: PERF.md.)"""
    return cfg["num_hidden_layers"] \
        * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * bytes_per_elem


def mla_decode_flops_per_row(cfg):
    """FLOPs of the latent kernel a cached row, all layers: every head's
    score over the row (``kv_lora_rank + qk_rope_head_dim`` wide) and its
    part of the context (``kv_lora_rank`` wide)."""
    L, R = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * (2 * L + R) * 2
