"""Model adapter ``dots3_note``: everything in the benchmark that knows
``paddle_tpu.models.latent_moe`` WITH WINDOW LAYERS (``dots3_note``,
dots3-note-prev's language model: ``sliding_attention`` layers of latent
attention at the ``swa_*`` sizes whose cache is a RING of latent rows a
slot, ``full_attention`` layers of latent attention over a page pool under
a lightning indexer each, a head-wise output gate on both, the low-rank
latents rescaled behind their norms, one dense layer and shared-expert MoE
layers behind it).  The seven functions of ``lib/models.py`` as
``models/gen_lm.py`` documents them, and the byte and operation counts of
this model's own per-layer metrics.

The configuration holds ONE CHIP'S SHARE of an expert-parallel deployment:
the published layers ``0 .. num_hidden_layers - 1``, ``experts_held`` of
``n_routed_experts`` experts from ``expert_offset`` and ``vocab_size`` rows
of the vocabulary; program and reference leave out what the absent experts
would add.
"""

from __future__ import annotations

from reference import dots3_note_ref as ref

SHAPE_KEYS = (
    "hidden_size", "num_hidden_layers", "first_k_dense_replace",
    "vocab_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
    "layer_types", "sliding_window_size", "swa_num_attention_heads",
    "swa_q_lora_rank", "swa_kv_lora_rank", "swa_qk_nope_head_dim",
    "swa_qk_rope_head_dim", "swa_v_head_dim", "swa_rope_theta",
    "attention_gate_type", "swa_attention_gate_type",
    "apply_mla_qkv_lora_rescale", "index_topk", "index_n_heads",
    "index_head_dim", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "experts_held", "expert_offset")


def bundle_key(cfg):
    return [{k: cfg[k] for k in SHAPE_KEYS}, cfg["serving"]]


def export(path, cfg):
    # a program without a ring of latent rows (the parent of the PR that
    # brought it) fails here, at once, not after a bundle's export
    from paddle_tpu.ops.mla_ops import latent_ring_step  # noqa: F401
    from paddle_tpu.models import latent_moe
    sv = cfg["serving"]
    hp = latent_moe.LatentMoEConfig.from_dict(cfg)
    hp.dtype = "bfloat16"
    hp.max_len = sv["max_len"]
    latent_moe.export_latent_model(
        path, hp, num_slots=sv["num_slots"],
        prompt_buckets=list(sv["prompt_buckets"]), page_len=sv["page_len"],
        page_buckets=list(sv["page_buckets"]))


def _layers(cfg):
    return range(cfg["num_hidden_layers"])


def sparse_layers(cfg):
    """The layers held whose FFN is the shared-expert MoE."""
    return [i for i in _layers(cfg) if ref.is_sparse_ffn(cfg, i)]


def window_layers(cfg):
    """The layers held that keep a ring of latent rows a slot."""
    return [i for i in _layers(cfg) if ref.is_sliding(cfg, i)]


def full_layers(cfg):
    """The layers held that keep pages: a latent pool and, each its own,
    an indexer with its key pool."""
    return [i for i in _layers(cfg) if not ref.is_sliding(cfg, i)]


#: the seeded router (configuration file, ``assumed.router``), by
#: ``kimi_k2.6_text``'s construction: its matrix is drawn ROUTER_GAIN times
#: Xavier's width, and every expert's logit is lowered by about
#: ROUTER_OFFSET through a constant residual channel; the residual's rms
#: grows from sublayer to sublayer as RESIDUAL_RMS lists it at the router
#: of layer i (read off the reference at the published widths on the CPU,
#: a layer at a time: 128 rows of one seed)
ROUTER_GAIN = 5.0
ROUTER_OFFSET = 28.0
EMBEDDING_RMS = 1.5
RESIDUAL_RMS = (1.551, 1.642, 1.842, 2.025, 2.187, 2.317, 2.468, 2.610,
                2.747)
ROUTER_BIAS = 2e-12
#: the seeded attention (configuration file, ``assumed.attention``): W_qb
#: and W_kvb are drawn ATTENTION_GAIN times Xavier's width.  The rescale
#: (sqrt 5 on c_q, sqrt 10 or sqrt 5 on c_kv) already widens a head's
#: scores seven or five times; with 2 x 2 more they spread by about 1.5
#: (full) and 2 (sliding), a softmax peaked enough that the rows the
#: selection or the band drops move the logits by more than bfloat16 does
ATTENTION_GAIN = 2.0
#: the gate's matrix is drawn GATE_GAIN times Xavier's width: its logits
#: spread by about 3, so g lies anywhere in (0, 1) and not at 0.5
GATE_GAIN = 2.0


def seeded_weights(cfg, seed31):
    """Every parameter of the model, drawn on the device, ONE jitted call
    a matrix, the largest (the stacked experts, the dense layer's three)
    first: the rig draws these beside the loaded ones, and a call's
    float32 scratch must fit while the device still has room.  Matrices
    Xavier-uniform (fan = the last two axes) cast to bfloat16; norm scales
    ones, the index key's LayerNorm bias zero; the router, its correction
    bias, the attention's gain and the gate's as ``assumed`` of the
    configuration file says.  Returns ``{name: array}``."""
    import functools

    import jax
    import jax.numpy as jnp
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
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
    moe = sparse_layers(cfg)
    out = {}
    for i in moe:
        out[f"lat{i}_wg"] = xavier(key(i, 0), (held, d, F))
        out[f"lat{i}_wu"] = xavier(key(i, 1), (held, d, F))
        out[f"lat{i}_wd"] = xavier(key(i, 2), (held, F, d), 1.0, True)
    for i in _layers(cfg):
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
    for i in _layers(cfg):
        p = f"lat{i}_"
        H, ql, L, nope, R, vd, _ = ref.attention_shape(cfg, i)
        out[p + "qa.w"] = xavier(key(i, 3), (d, ql))
        out[p + "qb.w"] = xavier(key(i, 4), (ql, H * (nope + R)),
                                 ATTENTION_GAIN)
        out[p + "kva.w"] = xavier(key(i, 5), (d, L + R))
        out[p + "kvb.w"] = xavier(key(i, 6), (L, H * (nope + vd)),
                                  ATTENTION_GAIN)
        out[p + "o.w"] = xavier(key(i, 7), (H * vd, d), 1.0, True)
        out[p + "og.w"] = xavier(key(i, 16), (d, H), GATE_GAIN)
        out.update({p + "qnorm.scale": ones(ql),
                    p + "kvnorm.scale": ones(L),
                    p + "norm1.scale": ones(d), p + "norm2.scale": ones(d)})
        if not ref.is_sliding(cfg, i):
            out[p + "idx_qb.w"] = xavier(key(i, 13), (ql, Hi * Di))
            out[p + "idx_k.w"] = xavier(key(i, 14), (d, Di))
            out[p + "idx_w.w"] = xavier(key(i, 15), (d, Hi))
            out[p + "idx_knorm.scale"] = ones(Di)
            out[p + "idx_knorm.bias"] = jnp.zeros((Di,), f32)
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
    return ref.forward_logits(weights, cfg, ids, positions)


#: what each control of ``control_logits`` drops of the float32 reference
CONTROL_DROPS = {"select_off": ("select",), "window_off": ("window",),
                 "gate_off": ("gate",)}


def control_logits(weights, cfg, ids, positions, kind="fp8"):
    """The controls a limit is set between: ``fp8`` (the reference one
    precision down: matrices float8 e4m3 a channel, bfloat16
    activations), ``bf16`` (the reference in the configuration's stated
    precision), and the float32 reference with one mechanism switched
    off: ``select_off`` (full layers attend every row), ``window_off``
    (sliding layers attend every row), ``gate_off`` (g = 1)."""
    import jax.numpy as jnp
    if kind in CONTROL_DROPS:
        return ref.forward_logits(weights, cfg, ids, positions,
                                  drop=CONTROL_DROPS[kind])
    stored = {"fp8": jnp.float8_e4m3fn, "bf16": None}[kind]
    return ref.forward_logits(weights, cfg, ids, positions,
                              dtype=jnp.bfloat16, stored=stored)


# -- bytes and operations (what the ALGORITHM needs; bfloat16) --------------

def attention_params(cfg, i):
    """Parameters of layer ``i``'s latent attention, its gate among
    them."""
    d = cfg["hidden_size"]
    H, ql, L, nope, R, vd, _ = ref.attention_shape(cfg, i)
    return d * ql + ql * H * (nope + R) + d * (L + R) \
        + L * H * (nope + vd) + H * vd * d + d * H


def indexer_params(cfg):
    """Parameters of one full layer's indexer (its LayerNorm's 2 x
    ``index_head_dim`` left out)."""
    Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * Hi * Di + cfg["hidden_size"] * (Di + Hi)


def expert_bytes(cfg, bytes_per_param=2):
    """Bytes of ONE routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * bytes_per_param


def param_count(cfg):
    """Parameters this chip holds."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    shared = 3 * d * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    moe = shared + d * cfg["n_routed_experts"] \
        + cfg["experts_held"] * expert_bytes(cfg, 1)
    n_moe = len(sparse_layers(cfg))
    return sum(attention_params(cfg, i) for i in _layers(cfg)) \
        + len(full_layers(cfg)) * indexer_params(cfg) + n_moe * moe \
        + (cfg["num_hidden_layers"] - n_moe) * 3 * d \
        * cfg["intermediate_size"] + 2 * d * v


def decode_weight_bytes(cfg, bytes_per_param=2):
    """Bytes of matrices one decode step reads if EVERY held expert of
    every layer has a token: an upper bound while some expert has none.
    The embedding is read by row, not whole."""
    return (param_count(cfg) - cfg["hidden_size"] * cfg["vocab_size"]) \
        * bytes_per_param


def kv_bytes_per_row(cfg, bytes_per_elem=2):
    """Bytes one ATTENDED row takes in the latent pools that the
    algorithm needs: ``kv_lora_rank + qk_rope_head_dim`` bfloat16 values
    a FULL layer (a window layer keeps no row a live row: its ring is
    ``latent_window_bytes_per_row`` a row of the window).  (The row is
    stored 640 wide, zeros behind.)"""
    return len(full_layers(cfg)) \
        * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * bytes_per_elem


def index_bytes_per_row(cfg, bytes_per_elem=2):
    """Bytes one SCORED row takes in the index-key pools: one
    ``index_head_dim`` row a full layer."""
    return len(full_layers(cfg)) * cfg["index_head_dim"] * bytes_per_elem


def index_flops_per_row(cfg):
    """FLOPs of scoring one cached row, all full layers: every index
    head's product over the row."""
    return len(full_layers(cfg)) * cfg["index_n_heads"] \
        * cfg["index_head_dim"] * 2


def latent_window_bytes_per_row(cfg, bytes_per_elem=2):
    """Bytes of ONE row of ONE window layer's ring that the algorithm
    needs: ``swa_kv_lora_rank + swa_qk_rope_head_dim`` values.  (The row
    is stored 1152 wide, zeros behind, and a ring holds 640 rows where
    the window is 513: both are the kernel's loss.)"""
    return (cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"]) \
        * bytes_per_elem


def latent_window_flops_per_row(cfg):
    """FLOPs of a window layer's decode step a ring row read, absorbed:
    every head's score over the row (``swa_kv_lora_rank +
    swa_qk_rope_head_dim`` wide) and its part of the context
    (``swa_kv_lora_rank`` wide)."""
    L, R = cfg["swa_kv_lora_rank"], cfg["swa_qk_rope_head_dim"]
    return cfg["swa_num_attention_heads"] * (2 * L + R) * 2


def latent_window_flops_per_pair(cfg):
    """FLOPs of one (query row, key row) pair inside the band, ALL window
    layers, in the form that needs the fewest: every head's score over
    the EXPANDED key (``swa_qk_nope_head_dim + swa_qk_rope_head_dim``)
    and its part of the context (``swa_v_head_dim``).  The absorbed form
    the chunk program runs takes (2 x 1024 + 64) lanes a head where this
    counts 384: what it spends beyond is its loss."""
    return len(window_layers(cfg)) * cfg["swa_num_attention_heads"] * 2 * (
        cfg["swa_qk_nope_head_dim"] + cfg["swa_qk_rope_head_dim"]
        + cfg["swa_v_head_dim"])


def decode_step_bytes(cfg, experts_touched, live, live_rows):
    """The LEAST bytes one decode step has to move: every matrix outside
    the routed experts once, the routed experts that had a token
    (``experts_touched``, summed over the expert layers), the index key
    of every one of the ``live_rows`` rows in the full layers' pools
    (each is scored), the latent rows that are ATTENDED there (no more
    than ``index_topk`` a live slot, ``live`` of them, and no more than
    there are) and of each window layer's ring no more than
    ``sliding_window_size`` rows a live slot."""
    held = len(sparse_layers(cfg)) * cfg["experts_held"] * expert_bytes(cfg)
    attended = min(live_rows, live * cfg["index_topk"])
    in_window = min(live_rows, live * cfg["sliding_window_size"])
    return decode_weight_bytes(cfg) - held \
        + experts_touched * expert_bytes(cfg) \
        + live_rows * index_bytes_per_row(cfg) \
        + attended * kv_bytes_per_row(cfg) \
        + in_window * len(window_layers(cfg)) \
        * latent_window_bytes_per_row(cfg)


def mla_decode_flops_per_row(cfg):
    """FLOPs of the latent kernel an ATTENDED row, all full layers: every
    head's score over the row (``kv_lora_rank + qk_rope_head_dim`` wide)
    and its part of the context (``kv_lora_rank`` wide)."""
    L, R = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return len(full_layers(cfg)) * cfg["num_attention_heads"] \
        * (2 * L + R) * 2
