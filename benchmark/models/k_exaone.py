"""Model adapter ``k_exaone``: everything in the benchmark that knows
``paddle_tpu.models.window_moe`` in its ``exaone_moe`` layout with the
multi-token-prediction (MTP) module loaded (K-EXAONE-236B-A23B: sliding-
window and full-attention layers by ``layer_types``, one head shape for
both, QK-norm, a rotary on the window layers only, a leading dense SwiGLU
layer, sigmoid top-8 MoE layers with a shared expert by
``mlp_layer_types``, and ONE MTP block that drafts: a decode turn forwards
two rows a slot and yields one or two tokens).  The seven functions of
``lib/models.py`` as ``models/gen_lm.py`` documents them, and the byte and
operation counts of this model's per-layer metrics, counted for TWO query
rows a slot a turn.

The configuration holds ONE CHIP'S SHARE of an expert-parallel deployment:
the published layers ``layer_offset .. layer_offset + num_hidden_layers -
1`` and the MTP module, ``experts_held`` of ``num_experts`` experts from
``expert_offset`` and ``vocab_size`` rows of the vocabulary; program and
reference leave out what the absent experts would add.
"""

from __future__ import annotations

# the parent of the PR that brought the drafting turn fails HERE, at once
from paddle_tpu.ops import spec_ops  # noqa: F401

from reference import k_exaone_ref as ref

MTP = ref.MTP
SHAPE_KEYS = (
    "hidden_size", "num_hidden_layers", "layer_offset", "vocab_size",
    "intermediate_size", "moe_intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "sliding_window", "ring",
    "rope_parameters", "layer_types", "mlp_layer_types", "mtp_layer_types",
    "num_nextn_predict_layers", "num_experts", "num_experts_per_tok",
    "num_shared_experts", "routed_scaling_factor", "qk_norm",
    "full_attention_rotary", "experts_held", "expert_offset")


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


def blocks(cfg):
    """The blocks held: the layers, then the MTP module's."""
    return list(range(cfg["num_hidden_layers"])) \
        + ([MTP] if cfg.get("num_nextn_predict_layers") else [])


def moe_layers(cfg):
    return [i for i in blocks(cfg) if ref.is_moe(cfg, i)]


def window_layers(cfg):
    return [i for i in blocks(cfg) if ref.is_window(cfg, i)]


def full_layers(cfg):
    return [i for i in blocks(cfg) if not ref.is_window(cfg, i)]


#: the seeded router (configuration file, ``assumed.router``), by
#: ``kimi_k2.6_text``'s construction as ``mimo_v2_flash`` took it over: a
#: matrix ROUTER_GAIN times Xavier's width, every expert's logit lowered
#: by about ROUTER_OFFSET through a constant residual channel; the
#: residual's rms at the FFN of layer i is RESIDUAL_RMS[i] (the MTP
#: block's MTP_RMS), read off the reference at the published widths on
#: the chip (benchmark/tools/draft_readings.py, 1024 rows of two seeds:
#: 2.03 / 2.68 / 3.4-3.6 / 4.0-4.2 / 4.8-4.9 BEHIND layers 0-4, 3.04 into
#: and 3.32-3.34 out of the MTP block; a layer's FFN stands between).
#: W_v and the FFNs read the constant channel too, so every row's
#: residual has a COMMON part (rms 3.6 of 4.8 behind layer 4), and a
#: router as drawn turns it into favourite experts by seed: each router is
#: then LEVELLED (:func:`level_routers`), the common part of its input over
#: LEVEL_ROWS random tokens projected out of its matrix: the 8 held see
#: about their sixteenth on every seed (by block still 0.5 to 1.7 times
#: it: the configuration file's assumed.router has the chip's readings)
ROUTER_GAIN = 5.0
ROUTER_OFFSET = 28.0
EMBEDDING_RMS = 1.5
RESIDUAL_RMS = (1.8, 2.35, 3.1, 3.8, 4.45)
ROUTER_BIAS = 2e-12
LEVEL_ROWS = 512
#: the seeded attention (``assumed.attention``): under QK-norm a head's
#: scores are ``sqrt(D) x cos(q, k)`` times the two norm scales, whatever
#: W_q and W_k are: the scales are drawn at QK_SCALE so that the scores
#: spread by about 2 (a peaked softmax: the window and the norm decide);
#: W_v VALUE_GAIN times Xavier's width so that attention carries weight
QK_SCALE = 1.4
VALUE_GAIN = 2.0
#: the drafter (``assumed.acceptance``): ``succ``, a seeded permutation of
#: the vocabulary that is ONE cycle.  The head's column ``succ[t]`` holds,
#: beside its Xavier draw, FOLLOW x HEAD_ALIGN times the unit vector of
#: token t's embedding (its constant channel left out), and the column
#: ``succ[succ[t]]`` SKIP x HEAD_ALIGN times the same vector: behind a row
#: whose token is t the main model's logits of t's successor and of the
#: token after it stand near FOLLOW and SKIP (HEAD_ALIGN is 1 / the cosine
#: of the last residual with its token's embedding, 0.31 on the chip),
#: both far above the other logits' largest (2.8-3.0), and FOLLOW - SKIP
#: apart, where the Xavier parts of the two columns, which read the
#: layers' own contribution to the residual, differ by about 1 (standard
#: deviation): the layers overturn the successor for the token after it
#: at the share of rows at which that difference passes FOLLOW - SKIP, a
#: quarter.  A stream so walks the one cycle forward by one or two and
#: never meets a token twice (a first construction, a spike under the
#: other logits' largest at a quarter of the TOKENS, sent every stream
#: into a loop of a few tokens within some hundred steps, and the
#: acceptance rate read 57-89% by seed: PERF.md section 6).  The MTP
#: module's projection passes the NEXT token's normed embedding through at
#: MTP_PASS (its hidden half at MTP_HIDDEN times Xavier's width): its
#: block's residual lies nearer that embedding than the main model's
#: does, the same two columns stand further apart in its logits, and it
#: drafts the successor of the next token nearly always; the draft is
#: kept wherever the main model follows
HEAD_ALIGN = 3.2
FOLLOW = 7.0
SKIP = 6.35
MTP_PASS = 3.0
MTP_HIDDEN = 0.5
MTP_RMS = 3.2


def successor(cfg, seed31):
    """The seeded permutation of the vocabulary, ONE cycle through every
    token (numpy, host side): ``succ[order[i]] = order[i + 1]``."""
    import numpy as np
    order = np.random.RandomState(seed31 % (2 ** 32)).permutation(
        cfg["vocab_size"])
    succ = np.empty(cfg["vocab_size"], np.int32)
    succ[order] = np.roll(order, -1)
    return succ


def seeded_weights(cfg, seed31):
    """Every parameter of the model, drawn on the device, ONE jitted call
    a matrix, the largest first (the rig draws these beside the loaded
    ones).  Matrices Xavier-uniform (fan = the last two axes) cast to
    bfloat16; the router, the attention's scales, the head's aligned
    columns and the MTP projection as ``assumed`` of the configuration
    file says.  Returns ``{name: array}``."""
    import functools

    import jax
    import jax.numpy as jnp
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    held = cfg.get("experts_held") or E
    I, D = cfg["intermediate_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    shared = F * int(cfg.get("num_shared_experts") or 0)
    f32, bf16 = jnp.float32, jnp.bfloat16
    c0 = d ** 0.5 / 2       # the constant residual channel's value

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def xavier(key, shape, gain=1.0, writes=False):
        limit = gain * (6.0 / (shape[-2] + shape[-1])) ** 0.5
        w = jax.random.uniform(key, shape, f32, -limit, limit).astype(bf16)
        # a matrix whose product is added to the residual leaves the
        # constant channel alone
        return w.at[..., 0].set(0) if writes else w

    root = jax.random.PRNGKey(seed31)
    tag = lambda i: (1 << 19) if i == MTP else i
    key = lambda i, j: jax.random.fold_in(jax.random.fold_in(root, tag(i)), j)
    ones = lambda n, value=1.0: jnp.full((n,), value, f32)
    moe = moe_layers(cfg)
    out = {}
    # embedding, and the head whose column succ[t] leans on row t of it
    # (FIRST: its float32 scratch is three times the head's size)
    limit = 3 ** 0.5 * EMBEDDING_RMS
    succ = jnp.asarray(successor(cfg, seed31))

    @jax.jit
    def emb_and_head(k_emb, k_head):
        emb = jax.random.uniform(k_emb, (v, d), f32, -limit, limit) \
            .astype(bf16).at[:, 0].set(c0)
        own = emb.astype(f32).at[:, 0].set(0.0)
        own = own / jnp.linalg.norm(own, axis=-1, keepdims=True) \
            * (HEAD_ALIGN / d ** 0.5)
        lean = jnp.zeros((v, d), f32).at[succ].set(own * FOLLOW) \
            .at[succ[succ]].add(own * SKIP)
        xav = (6.0 / (d + v)) ** 0.5
        head = jax.random.uniform(k_head, (d, v), f32, -xav, xav) + lean.T
        return emb, head.astype(bf16)

    out["win_emb"], out["win_head.w"] = emb_and_head(
        key(1 << 20, 0), key(1 << 20, 1))
    for i in moe:
        p = f"win{i}_"
        out[p + "wg"] = xavier(key(i, 0), (held, d, F))
        out[p + "wu"] = xavier(key(i, 1), (held, d, F))
        out[p + "wd"] = xavier(key(i, 2), (held, F, d), 1.0, True)
    for i in blocks(cfg):
        if i in moe:
            continue
        out[f"win{i}_ffn_gate.w"] = xavier(key(i, 0), (d, I))
        out[f"win{i}_ffn_up.w"] = xavier(key(i, 1), (d, I))
        out[f"win{i}_ffn_down.w"] = xavier(key(i, 2), (I, d), 1.0, True)

    out["win_norm.scale"] = ones(d)
    for i in blocks(cfg):
        p = f"win{i}_"
        out[p + "q.w"] = xavier(key(i, 3), (d, H * D))
        out[p + "k.w"] = xavier(key(i, 4), (d, Hkv * D))
        out[p + "v.w"] = xavier(key(i, 5), (d, Hkv * D), VALUE_GAIN)
        out[p + "o.w"] = xavier(key(i, 6), (H * D, d), 1.0, True)
        out.update({p + "norm1.scale": ones(d), p + "norm2.scale": ones(d)})
        if cfg.get("qk_norm", True):
            out.update({p + "qnorm.scale": ones(D, QK_SCALE),
                        p + "knorm.scale": ones(D, QK_SCALE)})
        if i not in moe:
            continue
        if shared:
            out[p + "sh_gate.w"] = xavier(key(i, 8), (d, shared))
            out[p + "sh_up.w"] = xavier(key(i, 9), (d, shared))
            out[p + "sh_down.w"] = xavier(key(i, 10), (shared, d), 1.0, True)
        # the constant channel as the block's residual holds it, and the
        # residual's rms at its FFN
        if i == MTP:
            # rms of an embedding row, its constant channel included
            row_rms = ((c0 * c0 + (d - 1) * EMBEDDING_RMS ** 2) / d) ** 0.5
            held0, rms = MTP_PASS * c0 / row_rms, MTP_RMS
        else:
            held0, rms = c0, RESIDUAL_RMS[min(i, len(RESIDUAL_RMS) - 1)]
        out[p + "gate.w"] = xavier(key(i, 11), (d, E), ROUTER_GAIN) \
            .at[0].set(jnp.asarray(-ROUTER_OFFSET * rms / held0, bf16))
        out[p + "gate.bias"] = jax.random.uniform(
            key(i, 12), (E,), f32, -ROUTER_BIAS, ROUTER_BIAS)
    if MTP in blocks(cfg):
        out.update({"win_mtp_hnorm.scale": ones(d),
                    "win_mtp_enorm.scale": ones(d),
                    "win_mtp_norm.scale": ones(d)})

        @jax.jit
        def projection(k):
            # rows 0 .. d - 1 take the hidden state's half (its product
            # leaves the constant channel alone), rows d .. take the next
            # token's normed embedding and pass it through
            top = xavier(k, (d, d), MTP_HIDDEN, True).astype(f32)
            return jnp.concatenate(
                [top, MTP_PASS * jnp.eye(d, dtype=f32)]).astype(bf16)

        out["win_mtp_proj.w"] = projection(key(MTP, 13))
    out.update(level_routers(cfg, out, seed31))
    return out


def level_routers(cfg, weights, seed31):
    """The routers' matrices ``{name: [d, E]}`` of ``weights``, each with
    the COMMON part of its input projected out: block by block the model
    runs (the plain reference's layers, bfloat16, the held experts alone,
    as served) over LEVEL_ROWS random tokens, the mean of a router's
    normed input there (its constant channel, which carries the offset,
    left out) is taken out of the matrix's columns, and the blocks behind
    run with the levelled router.  An expert's logit then keeps what a row
    has of its own, and no expert is a seed's favourite."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    ids = jnp.asarray(np.random.RandomState((seed31 + 1) % (2 ** 32)).randint(
        0, cfg["vocab_size"], LEVEL_ROWS), jnp.int32)
    return jax.jit(functools.partial(_levelled, cfg))(
        {k: v for k, v in weights.items() if k != "win_head.w"}, ids)


def _levelled(cfg, w, ids):
    import jax.numpy as jnp
    f32, bf16, eps = jnp.float32, jnp.bfloat16, cfg["rms_norm_eps"]
    value, done = ref._values(w, bf16, None), {}

    def block(x, i):
        p = lambda name, cast=True: done.get(
            f"win{i}_{name}", value(f"win{i}_{name}", cast))
        x = x + ref.attention(ref._rms(x, p("norm1.scale"), eps), p, cfg, i,
                              bf16)
        h = ref._rms(x, p("norm2.scale"), eps)
        if not ref.is_moe(cfg, i):
            return x + ref._gated(h, p("ffn_gate.w"), p("ffn_up.w"),
                                  p("ffn_down.w"))
        common = jnp.mean(h.astype(f32), axis=0).at[0].set(0.0)
        common = common / jnp.linalg.norm(common)
        gate = p("gate.w").astype(f32)
        gate = gate - common[:, None] * (common @ gate)[None, :]
        done[f"win{i}_gate.w"] = gate.astype(bf16)
        return x + ref.moe(h, p, cfg, bf16)

    x = ref._embed(w, ids, bf16, None)
    for i in range(cfg["num_hidden_layers"]):
        x = block(x, i)
    if MTP in blocks(cfg):
        both = jnp.concatenate(
            [ref._rms(x[:-1], w["win_mtp_hnorm.scale"], eps),
             ref._rms(ref._embed(w, ids[1:], bf16, None),
                      w["win_mtp_enorm.scale"], eps)], axis=-1)
        block(both @ w["win_mtp_proj.w"], MTP)
    return done


def reference_logits(weights, cfg, ids, positions):
    return ref.forward_logits(weights, cfg, ids, positions)


def draft_logits(weights, cfg, ids, positions):
    """The reference's MTP logits (teacher-forced), for the draft head's
    own comparison (``benchmark/tools/draft_readings.py``, the tests)."""
    return ref.draft_logits(weights, cfg, ids, positions)


def control_logits(weights, cfg, ids, positions, kind="float8"):
    """The controls a limit is set between: ``float8`` (the reference one
    precision down: matrices float8 e4m3 a channel, bfloat16
    activations), ``bf16`` (the reference in the configuration's stated
    precision), ``window_off`` (the float32 reference whose window layers
    attend every row before theirs) and ``draft`` (the reference's MTP
    logits at the same positions, each read one row earlier: what a
    program that accepted every draft serves)."""
    import jax.numpy as jnp
    if kind == "window_off":
        return ref.forward_logits(weights, cfg, ids, positions, window=False)
    if kind == "draft":
        # the MTP row i - 1 predicts the token the main row i predicts
        return ref.draft_logits(weights, cfg, ids,
                                jnp.asarray(positions) - 1)
    stored = {"float8": jnp.float8_e4m3fn, "fp8": jnp.float8_e4m3fn,
              "bf16": None}[kind]
    return ref.forward_logits(weights, cfg, ids, positions,
                              dtype=jnp.bfloat16, stored=stored)


# -- bytes and operations (bfloat16) ----------------------------------------

def attention_params(cfg):
    """Parameters of one block's attention (both kinds alike)."""
    d, D = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * H * D + 2 * d * Hkv * D + H * D * d \
        + (2 * D if cfg.get("qk_norm", True) else 0)


def expert_bytes(cfg, bytes_per_param=2):
    """Bytes of ONE routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * bytes_per_param


def param_count(cfg):
    """Parameters this chip holds, the MTP module's among them."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    held = cfg.get("experts_held") or cfg["num_experts"]
    n_moe, n = len(moe_layers(cfg)), len(blocks(cfg))
    moe = d * cfg["num_experts"] + (held + int(
        cfg.get("num_shared_experts") or 0)) * expert_bytes(cfg, 1)
    mtp = 2 * d * d if MTP in blocks(cfg) else 0
    return n * attention_params(cfg) + n_moe * moe \
        + (n - n_moe) * 3 * d * cfg["intermediate_size"] + 2 * d * v + mtp


def decode_weight_bytes(cfg, bytes_per_param=2):
    """Bytes of matrices one decode turn reads if EVERY held expert of
    every block has a token.  The embedding is read by row, not whole;
    the head is read TWICE where the MTP module drafts (the main model's
    two rows, then the module's pick, which needs the main model's)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    twice = d * v if MTP in blocks(cfg) else 0
    return (param_count(cfg) - d * v + twice) * bytes_per_param


def kv_bytes_per_row(cfg, bytes_per_elem=2):
    """Bytes one LIVE row takes in the page pools: the FULL blocks' K and
    V rows (the MTP module's block among them).  A turn's two query rows
    read them once (the kernel takes both rows' heads side by side)."""
    return len(full_layers(cfg)) * window_bytes_per_row(cfg, bytes_per_elem)


def window_bytes_per_row(cfg, bytes_per_elem=2):
    """Bytes of ONE row of ONE block's cache, K and V."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_elem


def window_flops_per_row(cfg):
    """FLOPs of a window layer's decode turn a ring row read: the pairs
    of that row with BOTH query rows of the turn."""
    return 2 * prefill_flops_per_pair(cfg)


def prefill_flops_per_pair(cfg):
    """FLOPs of one (query row, key row) pair of a block's attention."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"]


def band_flops_per_pair(cfg):
    """A pair inside the band of an admission's chunk, all window
    layers."""
    return len(window_layers(cfg)) * prefill_flops_per_pair(cfg)


def causal_flops_per_pair(cfg):
    """A pair under the diagonal, all full blocks (the MTP module's runs
    over the prompt's rows too)."""
    return len(full_layers(cfg)) * prefill_flops_per_pair(cfg)


def decode_step_bytes(cfg, experts_touched, live, live_rows):
    """The LEAST bytes one decode turn has to move: every matrix outside
    the routed experts once (the head twice), the routed experts that had
    a token (``experts_touched``, summed over the expert blocks), the
    full blocks' K/V of every one of the ``live_rows`` rows, and of each
    window layer's ring no more than ``sliding_window + 1`` rows a live
    slot (two query rows a turn) and no more than there are."""
    held = len(moe_layers(cfg)) * (cfg.get("experts_held")
                                   or cfg["num_experts"]) * expert_bytes(cfg)
    in_window = min(live_rows, live * (cfg["sliding_window"] + 1))
    return decode_weight_bytes(cfg) - held \
        + experts_touched * expert_bytes(cfg) \
        + live_rows * kv_bytes_per_row(cfg) \
        + in_window * len(window_layers(cfg)) * window_bytes_per_row(cfg)
