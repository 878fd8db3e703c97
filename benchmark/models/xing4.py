"""Model adapter ``xing4``: everything in the benchmark that knows
``paddle_tpu.models.latent_moe`` with hyper-connections and its
multi-token-prediction (MTP) module loaded (Xing4.0-29B-A4B: the
DeepSeek-V3 block, latent attention with a YaRN rotary key, leading dense
SwiGLU layers, then sigmoid top-4 routers over 64 gated experts plus a
shared one, under FOUR residual streams with a Sinkhorn-balanced mixing
matrix a sublayer, and ONE MTP block that drafts: a decode turn forwards
two rows a slot through the absorbed latent kernel and yields one or two
tokens).  The seven functions of ``lib/models.py`` as ``models/gen_lm.py``
documents them, and the byte and operation counts of this model's
per-layer metrics, counted for TWO query rows a slot a turn.

The configuration holds ONE CHIP'S SHARE of an expert-parallel deployment:
the published layers ``layer_offset .. layer_offset + num_hidden_layers -
1`` and the MTP module, ``experts_held`` of ``n_routed_experts`` experts
from ``expert_offset`` and ``vocab_size`` rows of the vocabulary; program
and reference leave out what the absent experts would add.
"""

from __future__ import annotations

# the parent of the PR that brought hyper-connections and the latent
# drafting turn fails HERE, at once: it would build a one-stream model
# with no drafter from this configuration (``from_dict`` drops keys it
# does not know) and serve it wrongly
from paddle_tpu.ops import mhc_ops  # noqa: F401

from reference import xing4_ref as ref

MTP = ref.MTP
SHAPE_KEYS = (
    "hidden_size", "num_hidden_layers", "layer_offset",
    "first_k_dense_replace", "vocab_size", "intermediate_size",
    "moe_intermediate_size", "num_attention_heads", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "rope_theta", "rope_scaling", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "routed_scaling_factor", "experts_held",
    "expert_offset", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
    "mhc_h_res_clamp_min", "mhc_h_res_clamp_max",
    "num_nextn_predict_layers")


def bundle_key(cfg):
    return [{k: cfg.get(k) for k in SHAPE_KEYS}, cfg["serving"]]


def export(path, cfg):
    from paddle_tpu.models import latent_moe
    sv = cfg["serving"]
    hp = latent_moe.LatentMoEConfig.from_dict(cfg)
    assert hp.hc_mult == cfg["hc_mult"] and hp.drafts, \
        "this tree's LatentMoEConfig knows neither hc_mult nor the MTP module"
    hp.dtype = "bfloat16"
    hp.max_len = sv["max_len"]
    latent_moe.export_latent_model(
        path, hp, num_slots=sv["num_slots"],
        prompt_buckets=list(sv["prompt_buckets"]), page_len=sv["page_len"],
        page_buckets=list(sv["page_buckets"]))


def blocks(cfg):
    """The blocks held: the layers, then the MTP module's."""
    return list(range(cfg["num_hidden_layers"])) \
        + ([MTP] if cfg.get("num_nextn_predict_layers") else [])


def moe_layers(cfg):
    return [i for i in blocks(cfg) if ref.is_moe(cfg, i)]


#: the seeded router (configuration file, ``assumed.router``), by
#: ``kimi_k2.6_text``'s construction under FOUR streams: a matrix
#: ROUTER_GAIN times Xavier's width, every expert's logit lowered by
#: ROUTER_OFFSET through a constant channel (channel 0 of every embedding
#: row, which no sublayer writes: it stays the same in every stream
#: because H_res's rows sum to one, and reaches a router as ``c0 x sum_j
#: H_pre,j`` over the rms of the aggregate).  The offset row and the
#: levelling are made at DRAW time from the model itself
#: (:func:`calibrate`): no table of residual rms by layer, as
#: ``k_exaone.py`` keeps, because under a wrapper the constant channel's
#: share of a router's input moves with H_pre
ROUTER_GAIN = 5.0
ROUTER_OFFSET = 28.0
EMBEDDING_RMS = 1.5
ROUTER_BIAS = 2e-12
LEVEL_ROWS = 512
#: the seeded wrappers (``assumed.hyper_connections``): phi uniform so
#: that ``x' phi`` has standard deviation PHI_STD over random tokens, the
#: three alphas ALPHA (pre, post, res) and biases uniform in +-BIAS_PRE /
#: +-BIAS_POST / +-BIAS_RES: the mappings MOVE with the token (H_pre and
#: H_post / 2 between 0.1 and 0.9) and ``exp(H~_res)``'s entries spread
#: over e^+-4, far from balanced before the first Sinkhorn round
PHI_STD = 1.0
ALPHA = (1.0, 1.0, 1.5)
BIAS_PRE, BIAS_POST, BIAS_RES = 1.0, 1.0, 2.0
#: the drafter (``assumed.acceptance``), ``k_exaone.py``'s: ``succ``, a
#: seeded permutation of the vocabulary that is ONE cycle; the head's
#: column ``succ[t]`` leans FOLLOW, the column ``succ[succ[t]]`` SKIP, on
#: the unit vector of token t's embedding, both divided by the cosine of
#: the last (summed) residual with its token's embedding, READ AT DRAW
#: TIME (:func:`calibrate`); the MTP module's projection passes the NEXT
#: token's normed embedding through at MTP_PASS, its hidden half at
#: MTP_HIDDEN times Xavier's width
FOLLOW = 7.0
SKIP = 6.35
MTP_PASS = 3.0
MTP_HIDDEN = 0.5


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
    a matrix (the rig draws these beside the loaded ones).  Matrices
    Xavier-uniform (fan = the last two axes) cast to bfloat16; the
    wrappers, the routers, the head's aligned columns and the MTP
    projection as ``assumed`` of the configuration file says.  Returns
    ``{name: array}``."""
    import functools

    import jax
    import jax.numpy as jnp
    d, v, n = cfg["hidden_size"], cfg["vocab_size"], cfg["hc_mult"]
    H, ql, L = (cfg["num_attention_heads"], cfg["q_lora_rank"],
                cfg["kv_lora_rank"])
    nope, R, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                   cfg["v_head_dim"])
    E, F = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    held = cfg.get("experts_held") or E
    I, Fs = cfg["intermediate_size"], F * cfg["n_shared_experts"]
    f32, bf16 = jnp.float32, jnp.bfloat16
    c0 = d ** 0.5 / 2       # the constant residual channel's value

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def xavier(key, shape, gain=1.0, writes=False):
        limit = gain * (6.0 / (shape[-2] + shape[-1])) ** 0.5
        w = jax.random.uniform(key, shape, f32, -limit, limit).astype(bf16)
        # a matrix whose product is added to the streams leaves the
        # constant channel alone
        return w.at[..., 0].set(0) if writes else w

    root = jax.random.PRNGKey(seed31)
    tag = lambda i: (1 << 19) if i == MTP else i
    key = lambda i, j: jax.random.fold_in(jax.random.fold_in(root, tag(i)), j)
    ones = lambda m: jnp.ones((m,), f32)
    moe = moe_layers(cfg)
    out = {}
    for i in moe:
        p = f"lat{i}_"
        out[p + "wg"] = xavier(key(i, 0), (held, d, F))
        out[p + "wu"] = xavier(key(i, 1), (held, d, F))
        out[p + "wd"] = xavier(key(i, 2), (held, F, d), 1.0, True)
    for i in blocks(cfg):
        if i in moe:
            continue
        out[f"lat{i}_ffn_gate.w"] = xavier(key(i, 0), (d, I))
        out[f"lat{i}_ffn_up.w"] = xavier(key(i, 1), (d, I))
        out[f"lat{i}_ffn_down.w"] = xavier(key(i, 2), (I, d), 1.0, True)
    limit = 3 ** 0.5 * EMBEDDING_RMS
    out["lat_emb"] = jax.jit(lambda k: jax.random.uniform(
        k, (v, d), f32, -limit, limit).astype(bf16).at[:, 0].set(c0))(
            key(1 << 20, 0))
    out["lat_norm.scale"] = ones(d)

    @jax.jit
    def wrapper(k):
        a = PHI_STD * (3.0 / (n * d)) ** 0.5
        k_phi, k_pre, k_post, k_res = jax.random.split(k, 4)
        side = lambda kk, m, b: jax.random.uniform(kk, (m,), f32, -b, b)
        return (jax.random.uniform(k_phi, (n * d, n * (n + 2)), f32, -a, a),
                jnp.asarray(ALPHA, f32),
                jnp.concatenate([side(k_pre, n, BIAS_PRE),
                                 side(k_post, n, BIAS_POST),
                                 side(k_res, n * n, BIAS_RES)]))

    for i in blocks(cfg):
        p = f"lat{i}_"
        out[p + "qa.w"] = xavier(key(i, 3), (d, ql))
        out[p + "qb.w"] = xavier(key(i, 4), (ql, H * (nope + R)))
        out[p + "kva.w"] = xavier(key(i, 5), (d, L + R))
        out[p + "kvb.w"] = xavier(key(i, 6), (L, H * (nope + vd)))
        out[p + "o.w"] = xavier(key(i, 7), (H * vd, d), 1.0, True)
        out.update({p + "qnorm.scale": ones(ql),
                    p + "kvnorm.scale": ones(L),
                    p + "norm1.scale": ones(d), p + "norm2.scale": ones(d)})
        for j, which in enumerate(("hc1", "hc2")):
            (out[p + which + ".phi"], out[p + which + ".alpha"],
             out[p + which + ".bias"]) = wrapper(key(i, 20 + j))
        if i not in moe:
            continue
        out[p + "sh_gate.w"] = xavier(key(i, 8), (d, Fs))
        out[p + "sh_up.w"] = xavier(key(i, 9), (d, Fs))
        out[p + "sh_down.w"] = xavier(key(i, 10), (Fs, d), 1.0, True)
        # (row 0, the offset, is made by ``calibrate``)
        out[p + "gate.w"] = xavier(key(i, 11), (d, E), ROUTER_GAIN)
        out[p + "gate.bias"] = jax.random.uniform(
            key(i, 12), (E,), f32, -ROUTER_BIAS, ROUTER_BIAS)
    if MTP in blocks(cfg):
        out.update({"lat_mtp_hnorm.scale": ones(d),
                    "lat_mtp_enorm.scale": ones(d),
                    "lat_mtp_norm.scale": ones(d)})

        @jax.jit
        def projection(k):
            # rows 0 .. d - 1 take the hidden state's half (its product
            # leaves the constant channel alone), rows d .. take the next
            # token's normed embedding and pass it through
            top = xavier(k, (d, d), MTP_HIDDEN, True).astype(f32)
            return jnp.concatenate(
                [top, MTP_PASS * jnp.eye(d, dtype=f32)]).astype(bf16)

        out["lat_mtp_proj.w"] = projection(key(MTP, 13))
    routers, cosine = calibrate(cfg, out, seed31)
    out.update(routers)
    succ = jnp.asarray(successor(cfg, seed31))

    @jax.jit
    def head(k, emb, cosine):
        own = emb.astype(f32).at[:, 0].set(0.0)
        own = own / jnp.linalg.norm(own, axis=-1, keepdims=True) \
            / (cosine * d ** 0.5)
        lean = jnp.zeros((v, d), f32).at[succ].set(own * FOLLOW) \
            .at[succ[succ]].add(own * SKIP)
        xav = (6.0 / (d + v)) ** 0.5
        return (jax.random.uniform(k, (d, v), f32, -xav, xav)
                + lean.T).astype(bf16)

    out["lat_head.w"] = head(key(1 << 20, 1), out["lat_emb"], cosine)
    return out


def calibrate(cfg, weights, seed31):
    """``({name: router matrix [d, E]}, cosine)`` from the model itself,
    run block by block (the plain reference's layers in bfloat16, the
    held experts alone, as served) over LEVEL_ROWS random tokens:

    * a router's matrix LEVELLED (``k_exaone.level_routers``): the mean
      of its normed input there, its constant channel left out, is
      projected out of the matrix's columns, so that no expert is a
      seed's favourite; and its row 0 set to ``-ROUTER_OFFSET`` over the
      mean of the input's constant channel, so that every expert's logit
      is lowered by ROUTER_OFFSET whatever H_pre made of that channel; the
      blocks behind run with the router so made;
    * ``cosine``: the mean cosine of the last SUMMED residual with its
      own token's embedding (constant channel left out), which the
      head's leaning columns are divided by."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    ids = jnp.asarray(np.random.RandomState((seed31 + 1) % (2 ** 32)).randint(
        0, cfg["vocab_size"], LEVEL_ROWS), jnp.int32)
    return jax.jit(functools.partial(_calibrated, cfg))(weights, ids)


def _calibrated(cfg, w, ids):
    import jax.numpy as jnp
    f32, bf16, eps = jnp.float32, jnp.bfloat16, cfg["rms_norm_eps"]
    value, done = ref._values(w, bf16, None), {}

    def block(x, i):
        p = lambda name, cast=True: done.get(
            f"lat{i}_{name}", value(f"lat{i}_{name}", cast))

        def feed_forward(u):
            h = ref._rms(u, p("norm2.scale"), eps)
            if ref.is_moe(cfg, i):
                hf = h.astype(f32)
                common = jnp.mean(hf, axis=0).at[0].set(0.0)
                common = common / jnp.linalg.norm(common)
                gate = p("gate.w").astype(f32)
                gate = gate - common[:, None] * (common @ gate)[None, :]
                gate = gate.at[0].set(-ROUTER_OFFSET / jnp.mean(hf[:, 0]))
                done[f"lat{i}_gate.w"] = gate.astype(bf16)
            return ref.ffn(h, p, cfg, i, bf16)

        x = ref.wrapped(x, lambda u: ref.attention(
            ref._rms(u, p("norm1.scale"), eps), p, cfg, bf16), p, "hc1", cfg)
        return ref.wrapped(x, feed_forward, p, "hc2", cfg)

    x = ref._copy_in(ref._embed(w, ids, bf16, None), cfg)
    for i in range(cfg["num_hidden_layers"]):
        x = block(x, i)
    last = jnp.sum(x.astype(f32), axis=1)
    own = w["lat_emb"][ids].astype(f32).at[:, 0].set(0.0)
    cosine = jnp.mean(jnp.sum(last * own, -1) / (
        jnp.linalg.norm(last, axis=-1) * jnp.linalg.norm(own, axis=-1)))
    if MTP in blocks(cfg):
        both = jnp.concatenate(
            [ref._rms(last[:-1].astype(bf16), w["lat_mtp_hnorm.scale"], eps),
             ref._rms(ref._embed(w, ids[1:], bf16, None),
                      w["lat_mtp_enorm.scale"], eps)], axis=-1)
        block(ref._copy_in(both @ w["lat_mtp_proj.w"], cfg), MTP)
    return done, cosine


def reference_logits(weights, cfg, ids, positions):
    return ref.forward_logits(weights, cfg, ids, positions)


def draft_logits(weights, cfg, ids, positions):
    """The reference's MTP logits (teacher-forced), for the draft head's
    own comparison (``benchmark/tools/draft_readings.py``, the tests)."""
    return ref.draft_logits(weights, cfg, ids, positions)


CONTROLS = ("fp8", "bf16", "mhc_static", "sinkhorn_1", "streams_mean",
            "draft")


def control_logits(weights, cfg, ids, positions, kind="fp8"):
    """The controls a limit is set between: ``fp8`` (the reference one
    precision down: matrices float8 e4m3 a channel, bfloat16
    activations), ``bf16`` (the reference in the configuration's stated
    precision), ``mhc_static`` (float32, alpha = 0: the three mappings do
    not move with the token), ``sinkhorn_1`` (float32, ONE Sinkhorn round
    for the twenty), ``streams_mean`` (float32, H_res = 1 / n everywhere)
    and ``draft`` (the reference's MTP logits at the same positions, each
    read one row earlier: what a program that accepted every draft
    serves)."""
    import jax.numpy as jnp
    if kind in ("mhc_static", "sinkhorn_1", "streams_mean"):
        return ref.forward_logits(weights, cfg, ids, positions, drop=(kind,))
    if kind == "draft":
        # the MTP row i - 1 predicts the token the main row i predicts
        return ref.draft_logits(weights, cfg, ids,
                                jnp.asarray(positions) - 1)
    stored = {"float8": jnp.float8_e4m3fn, "fp8": jnp.float8_e4m3fn,
              "bf16": None}[kind]
    return ref.forward_logits(weights, cfg, ids, positions,
                              dtype=jnp.bfloat16, stored=stored)


# -- bytes and operations (what the ALGORITHM needs; bfloat16) --------------

def mla_params(cfg):
    """Parameters of one block's latent attention."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, R, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                   cfg["v_head_dim"])
    return d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * H * (nope + R) \
        + d * (cfg["kv_lora_rank"] + R) \
        + cfg["kv_lora_rank"] * H * (nope + vd) + H * vd * d


def mhc_params(cfg):
    """Parameters of one block's two wrappers (float32: 4 bytes each)."""
    n = cfg["hc_mult"]
    return 2 * (n * cfg["hidden_size"] * n * (n + 2) + 3 + n * (n + 2))


def expert_bytes(cfg, bytes_per_param=2):
    """Bytes of ONE routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * bytes_per_param


def param_count(cfg):
    """bfloat16 parameters this chip holds, the MTP module's among them
    (the wrappers' float32 ones are :func:`mhc_params`)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    held = cfg.get("experts_held") or cfg["n_routed_experts"]
    n_moe, n = len(moe_layers(cfg)), len(blocks(cfg))
    moe = d * cfg["n_routed_experts"] \
        + (held + cfg["n_shared_experts"]) * expert_bytes(cfg, 1)
    mtp = 2 * d * d if MTP in blocks(cfg) else 0
    return n * mla_params(cfg) + n_moe * moe \
        + (n - n_moe) * 3 * d * cfg["intermediate_size"] + 2 * d * v + mtp


def decode_weight_bytes(cfg, bytes_per_param=2):
    """Bytes of matrices one decode turn reads if EVERY held expert of
    every block has a token.  The embedding is read by row, not whole;
    the head is read TWICE where the MTP module drafts (the main model's
    two rows, then the module's pick, which needs the main model's); the
    wrappers' float32 parameters once."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    twice = d * v if MTP in blocks(cfg) else 0
    return (param_count(cfg) - d * v + twice) * bytes_per_param \
        + len(blocks(cfg)) * mhc_params(cfg) * 4


def decode_step_bytes(cfg, experts_touched, live, live_rows):
    """The LEAST bytes one decode turn has to move: every matrix outside
    the routed experts once (the head twice), the routed experts that had
    a token (``experts_touched``, summed over the expert blocks) and the
    latent rows of the ``live_rows`` rows in every block's pool (a turn's
    two query rows read them once).  ``live`` is not used: the streams of
    a turn's 64 rows are a few MB."""
    held = len(moe_layers(cfg)) * (cfg.get("experts_held")
                                   or cfg["n_routed_experts"]) \
        * expert_bytes(cfg)
    return decode_weight_bytes(cfg) - held \
        + experts_touched * expert_bytes(cfg) \
        + live_rows * kv_bytes_per_row(cfg)


def kv_bytes_per_row(cfg, bytes_per_elem=2):
    """Bytes one LIVE row takes in the latent pools that the ALGORITHM
    needs: ``kv_lora_rank + qk_rope_head_dim`` bfloat16 values a block,
    the MTP module's among them.  (The row is stored 640 wide, zeros
    behind.)  A turn's two query rows read them once: the kernel takes
    both rows' heads side by side."""
    return len(blocks(cfg)) \
        * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * bytes_per_elem


def mla_decode_flops_per_row(cfg):
    """FLOPs of the latent kernel a cached row, all blocks, for the TWO
    query rows of a turn: every head's score over the row (``kv_lora_rank
    + qk_rope_head_dim`` wide) and its part of the context
    (``kv_lora_rank`` wide)."""
    L, R = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return 2 * len(blocks(cfg)) * cfg["num_attention_heads"] \
        * (2 * L + R) * 2


def mhc_wrappers(cfg):
    """Wrappers a row passes: two a block."""
    return 2 * len(blocks(cfg))


def mhc_bytes_per_row(cfg, bytes_per_elem=2):
    """The LEAST bytes ONE wrapper moves a row: it reads the n streams
    and the sublayer's output, and writes the n streams and the
    sublayer's input: (2 n + 2) C values (the span attribute ``mhc_rows``
    counts rows x wrappers)."""
    return (2 * cfg["hc_mult"] + 2) * cfg["hidden_size"] * bytes_per_elem
