"""Model adapter ``sdar``: everything in the benchmark that knows
``paddle_tpu.models.block_moe`` (the ``sdar_moe`` family: QK-norm grouped
attention, a softmax top-k router over gated experts with no shared one,
generated by diffusion over blocks).  The seven functions of
``lib/models.py`` as ``models/gen_lm.py`` documents them, and the byte
counts the shared roofline readers ask for.

Nothing is shared out: the configuration holds every expert, every head
and the whole vocabulary of the layers it keeps.
"""

from __future__ import annotations

from reference import sdar_ref

SHAPE_KEYS = (
    "hidden_size", "num_hidden_layers", "vocab_size", "head_dim",
    "num_attention_heads", "num_key_value_heads", "rope_theta",
    "rms_norm_eps", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "norm_topk_prob", "block_length",
    "mask_token_id")


def bundle_key(cfg):
    return [{k: cfg[k] for k in SHAPE_KEYS}, cfg["serving"]]


def export(path, cfg):
    from paddle_tpu.models import block_moe
    from paddle_tpu.models.gen_lm import default_page_buckets
    sv = cfg["serving"]
    hp = block_moe.BlockMoEConfig.from_dict(cfg)
    hp.dtype = "bfloat16"
    hp.max_len = sv["max_len"]
    pages = -(-sv["max_len"] // sv["page_len"])
    block_moe.export_block_model(
        path, hp, num_slots=sv["num_slots"],
        prompt_buckets=list(sv["prompt_buckets"]), page_len=sv["page_len"],
        page_buckets=default_page_buckets(pages))


#: the seeded router (configuration file, ``assumed.router``): its matrix
#: is drawn ROUTER_GAIN times Xavier's width.  The router reads a
#: normalised row (rms 1), so a token's 128 logits have a spread of
#: ROUTER_GAIN x 1.37 whatever the layer, and the softmax needs no offset:
#: the weights of the chosen are a softmax of their logits as they are.
#: At Xavier's own width the 8th and 9th largest of 128 probabilities
#: differ by a rounding error and each carries a twelfth of the layer's
#: output.  Two things pull against each other (readings on the chip,
#: ten seeds of ``tools/served_readings.py`` a gain; PERF.md section 6):
#: what a flipped marginal expert carries shrinks as e^-(gain x gap), and
#: what bfloat16 noise in the logits does to the weights of the chosen
#: grows with the gain.  At 5 nine served checks read 0.011-0.039 of the
#: logits' range and ONE 0.095 (a rare token whose top logits lie close)
#: against the float8 control's smallest 0.110; at 10 every check reads
#: 0.055-0.19 against 0.30; at 7 0.023-0.081 against 0.218, the widest
#: room of the three
ROUTER_GAIN = 7.0
EMBEDDING_RMS = 1.0


def seeded_weights(cfg, seed31):
    """Every parameter of the model, drawn on the device, ONE jitted call
    a matrix, the largest (the stacked experts) first: the rig draws
    these beside the loaded ones, and a call's float32 scratch must fit
    while the device still has room.  Matrices Xavier-uniform (fan = the
    last two axes) cast to bfloat16, the router's ROUTER_GAIN times as
    wide; the embedding uniform with rms EMBEDDING_RMS; norm scales
    ones.  Returns ``{name: array}``."""
    import functools

    import jax
    import jax.numpy as jnp
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    f32 = jnp.float32

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def xavier(key, shape, gain=1.0):
        limit = gain * (6.0 / (shape[-2] + shape[-1])) ** 0.5
        return jax.random.uniform(key, shape, f32, -limit, limit) \
            .astype(jnp.bfloat16)

    root = jax.random.PRNGKey(seed31)
    key = lambda i, j: jax.random.fold_in(jax.random.fold_in(root, i), j)
    ones = lambda n: jnp.ones((n,), f32)
    layers = range(cfg["num_hidden_layers"])
    out = {}
    for i in layers:                                # 1.21 GB a layer
        out[f"blk{i}_wg"] = xavier(key(i, 0), (E, d, F))
        out[f"blk{i}_wu"] = xavier(key(i, 1), (E, d, F))
        out[f"blk{i}_wd"] = xavier(key(i, 2), (E, F, d))
    limit = 3 ** 0.5 * EMBEDDING_RMS
    out["blk_emb"] = jax.jit(lambda k: jax.random.uniform(
        k, (v, d), f32, -limit, limit).astype(jnp.bfloat16))(key(1 << 20, 0))
    out["blk_head.w"] = xavier(key(1 << 20, 1), (d, v))
    out["blk_norm.scale"] = ones(d)
    for i in layers:
        p = f"blk{i}_"
        out[p + "q.w"] = xavier(key(i, 3), (d, H * D))
        out[p + "k.w"] = xavier(key(i, 4), (d, Hkv * D))
        out[p + "v.w"] = xavier(key(i, 5), (d, Hkv * D))
        out[p + "o.w"] = xavier(key(i, 6), (H * D, d))
        out[p + "gate.w"] = xavier(key(i, 7), (d, E), ROUTER_GAIN)
        out.update({p + "qnorm.scale": ones(D), p + "knorm.scale": ones(D),
                    p + "norm1.scale": ones(d), p + "norm2.scale": ones(d)})
    return out


def reference_logits(weights, cfg, ids, positions):
    return sdar_ref.forward_logits(weights, cfg, ids, positions)


def control_logits(weights, cfg, ids, positions, kind="fp8"):
    import jax.numpy as jnp
    stored = {"fp8": jnp.float8_e4m3fn, "bf16": None}[kind]
    return sdar_ref.forward_logits(weights, cfg, ids, positions,
                                   dtype=jnp.bfloat16, stored=stored)


# -- bytes (what the ALGORITHM needs; bfloat16) -----------------------------

def attention_params(cfg):
    """Parameters of one layer's attention matrices."""
    d, D = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * cfg["num_attention_heads"] * D \
        + 2 * d * cfg["num_key_value_heads"] * D


def expert_bytes(cfg, bytes_per_param=2):
    """Bytes of ONE expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * bytes_per_param


def param_count(cfg):
    """Parameters this chip holds (the norms' scales left out)."""
    d = cfg["hidden_size"]
    layer = attention_params(cfg) + d * cfg["num_experts"] \
        + cfg["num_experts"] * expert_bytes(cfg, 1)
    return cfg["num_hidden_layers"] * layer + 2 * d * cfg["vocab_size"]


def decode_weight_bytes(cfg, bytes_per_param=2):
    """Bytes of matrices one decode step reads if EVERY expert of every
    layer has a row (at 32 slots x 4 rows x top-8 over 128 experts about
    117 a layer do).  The embedding is read by row, not whole."""
    return (param_count(cfg) - cfg["hidden_size"] * cfg["vocab_size"]) \
        * bytes_per_param


def decode_step_bytes(cfg, experts_touched, live, live_rows):
    """The LEAST bytes one decode step has to move: every matrix outside
    the experts once, the experts that had a row (``experts_touched``,
    summed over the layers) and the K/V of the ``live_rows`` rows in the
    pool.  ``live`` is not used: the block tokens a slot keeps are 16
    bytes."""
    held = cfg["num_hidden_layers"] * cfg["num_experts"] * expert_bytes(cfg)
    return decode_weight_bytes(cfg) - held \
        + experts_touched * expert_bytes(cfg) \
        + live_rows * kv_bytes_per_row(cfg)


def kv_bytes_per_row(cfg, bytes_per_elem=2):
    """Bytes one live row of a slot holds in the page pool: a K and a V
    row of ``num_key_value_heads * head_dim`` bfloat16 values a layer."""
    return cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * bytes_per_elem
