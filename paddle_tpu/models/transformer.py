"""Transformer (Vaswani et al.) built on the Program IR layers.

The reference carries a full Transformer in its multi-device test
(``python/paddle/fluid/tests/unittests/test_parallel_executor.py:308``
``ModelHyperParams``/``transformer``) and benchmarks NMT under
``benchmark/fluid/machine_translation.py``.  This is the TPU-native
re-design: dense padded batches with explicit attention masks instead of
LoD ragged tensors, bfloat16-friendly matmuls that XLA tiles onto the MXU,
and one fused softmax(QK^T)V per head group.

Used as the flagship model for ``__graft_entry__.py`` / ``bench.py``
(north star: Transformer-base tokens/sec/chip, BASELINE.json).

Every op is built under a ``name_scope`` whose names are API
(docs/observability.md): ``embed``; ``enc<i>`` / ``dec<i>`` >
``self_attn`` | ``cross_attn`` | ``ffn``; inside an attention sublayer
``proj`` (the four ``fc`` and the head split / merge) and ``core`` (score
product, masked softmax, context product, or the one fused op); ``post``
around each ``pre_post_process_layer``; ``head`` (vocabulary ``fc`` and the
loss).  The device trace carries them (``profiler.compiled_op_table``).
"""

from __future__ import annotations

import numpy as np

import paddle_tpu.layers as layers
from paddle_tpu.framework import name_scope
from paddle_tpu.initializer import NumpyArrayInitializer
from paddle_tpu.param_attr import ParamAttr


class ModelHyperParams:
    """Transformer-base (mirrors test_parallel_executor.py:308 defaults)."""
    src_vocab_size = 10000
    trg_vocab_size = 10000
    pos_pad_idx = 0
    src_pad_idx = 0
    trg_pad_idx = 0
    max_length = 256
    d_model = 512
    d_inner_hid = 2048
    d_key = 64
    d_value = 64
    n_head = 8
    n_layer = 6
    dropout = 0.1
    # attention-weight dropout (reference uses hp.dropout here too; the
    # flash kernel path supports 0.0 only — set >0 to force the composed
    # softmax path with weight dropout)
    attention_dropout = 0.0
    use_flash = True


def position_encoding_init(n_position, d_model):
    """Sinusoid position encoding table."""
    position = np.arange(n_position)[:, None].astype("float64")
    div = np.exp(np.arange(0, d_model, 2).astype("float64")
                 * -(np.log(10000.0) / d_model))
    table = np.zeros((n_position, d_model))
    table[:, 0::2] = np.sin(position * div)
    table[:, 1::2] = np.cos(position * div[: d_model // 2])
    return table.astype("float32")


def _shared_padding_bias(k_mask):
    """[B,S] mask -> [B,1,1,S] additive bias, built ONCE per mask var
    (layers share the constant instead of re-emitting it)."""
    name = k_mask.name + "@attn_bias"
    block = k_mask.block
    if block.has_var(name) and any(name in op.output_arg_names
                                   for op in block.ops):
        return block.var(name)
    neg = layers.scale(k_mask, scale=1e9, bias=-1e9)
    b, sk = k_mask.shape
    out = layers.reshape(neg, shape=[b, 1, 1, sk])
    block.vars.pop(out.name, None)
    out.name = name
    block.vars[name] = out
    block.ops[-1].outputs["Out"] = [name]
    return out


def _shared_causal_bias(block, sq):
    """[1,1,S,S] causal constant, one copy per program per length."""
    name = f"@causal_bias_{sq}"
    if block.has_var(name):
        return block.var(name)
    tri = np.triu(np.full((sq, sq), -1e9, dtype="float32"), 1)
    out = layers.assign(tri.reshape(1, 1, sq, sq))
    block.vars.pop(out.name, None)
    out.name = name
    block.vars[name] = out
    block.ops[-1].outputs["Out"] = [name]
    return out


def multi_head_attention(queries, keys, values, d_key, d_value, d_model,
                         n_head=1, dropout_rate=0.0, k_mask=None,
                         causal=False, use_flash=True, prefix=None):
    """Multi-head scaled-dot-product attention over dense [B,S,D] tensors.

    ``k_mask`` [B, S_k] (1=attend) covers padding; ``causal`` covers the
    decoder self-attention triangle.  With ``use_flash`` the fused Pallas
    kernel runs QK^T->softmax->AV in VMEM (no [B,H,S,S] HBM tensor) on the
    projections' own [B, S, H*D] layout; the fused path applies no
    attention-weight dropout — the composed-op path is used instead when
    attention dropout is requested.
    """
    keys = queries if keys is None else keys
    values = keys if values is None else values

    def pa(role):
        # structured names let tensor-parallel sharding rules (tp_shardings)
        # address parameters by role
        return ParamAttr(name=f"{prefix}_{role}.w") if prefix else None

    def split_heads(x, d_per_head):
        # [B, S, H*D] -> [B, H, S, D]
        b, s = x.shape[0], x.shape[1]
        x = layers.reshape(x, shape=[b, s, n_head, d_per_head])
        return layers.transpose(x, perm=[0, 2, 1, 3])

    with name_scope("proj"):
        q = layers.fc(queries, d_key * n_head, num_flatten_dims=2,
                      bias_attr=False, param_attr=pa("q"))
        k = layers.fc(keys, d_key * n_head, num_flatten_dims=2,
                      bias_attr=False, param_attr=pa("k"))
        v = layers.fc(values, d_value * n_head, num_flatten_dims=2,
                      bias_attr=False, param_attr=pa("v"))
    scale = float(d_key) ** -0.5

    # Which ops the core gets is read off the operands' shapes, from the
    # ONE function that also chooses the fused op's lowering
    # (``attention_ops.attention_lowering``): the fused op where the packed
    # kernels take the projections' [B, S, H*D] as they are (equal
    # lengths, a multiple of 128 up to 1024, head width 32 / 64 / 128; no
    # head transposes, no [B,H,S,S] tensor in HBM) or the keys are 512 or
    # longer (the rule the streaming kernels were measured under: unequal
    # lengths, S > 1024).  Elsewhere the composed ops stay: short or odd
    # lengths and narrow heads, where XLA folds the transposes into the
    # projection matmuls and the [S,S] round trip is cheap.
    # BENCH_ATTENTION.md has one module alone on the three paths.
    from paddle_tpu.ops.attention_ops import attention_lowering
    use_flash = use_flash and attention_lowering(
        q.shape, k.shape, v.shape, n_head, causal).beats_composed
    # sequence/context parallelism: shard S over the mesh 'seq' axis and
    # attend with the ppermute ring (parallel/ring_attention.py); only for
    # self-attention (q and k share the sequence sharding)
    from paddle_tpu.executor import _env_flag
    seq_parallel = _env_flag("PADDLE_TPU_SEQ_PARALLEL") and \
        keys is queries and k_mask is None

    if use_flash and not dropout_rate and not seq_parallel:
        with name_scope("core"):
            ctx = layers.fused_attention(q, k, v, k_mask=k_mask,
                                         causal=causal, scale=scale,
                                         n_head=n_head)
        with name_scope("proj"):
            return layers.fc(ctx, d_model, num_flatten_dims=2,
                             bias_attr=False, param_attr=pa("attnout"))

    with name_scope("proj"):
        q = split_heads(q, d_key)
        k = split_heads(k, d_key)
        v = split_heads(v, d_value)

    with name_scope("core"):
        if seq_parallel and not dropout_rate:
            ctx = layers.ring_attention(q, k, v, causal=causal, scale=scale)
        else:
            product = layers.matmul(q, k, transpose_y=True, alpha=scale)
            # fold the mask into the softmax op: under bf16 AMP the
            # [B,H,S,S] scores then stay bf16 in HBM (an f32 add would
            # otherwise promote and double the attention hot spot's
            # traffic); softmax itself computes in f32 internally
            bias = None
            if k_mask is not None:
                bias = _shared_padding_bias(k_mask)
            if causal:
                cb = _shared_causal_bias(q.block, q.shape[2])
                bias = cb if bias is None else bias + cb
            weights = layers.softmax(product, bias=bias)
            if dropout_rate:
                weights = layers.dropout(weights, dropout_prob=dropout_rate)
            ctx = layers.matmul(weights, v)

    with name_scope("proj"):
        # [B, H, S, D] -> [B, S, H*D]
        ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
        b, s = ctx.shape[0], ctx.shape[1]
        ctx = layers.reshape(ctx, shape=[b, s, n_head * d_value])
        return layers.fc(ctx, d_model, num_flatten_dims=2, bias_attr=False,
                         param_attr=pa("attnout"))


def positionwise_feed_forward(x, d_inner_hid, d_hid, prefix=None):
    def pa(role, suffix="w"):
        return ParamAttr(name=f"{prefix}_{role}.{suffix}") if prefix \
            else None
    hidden = layers.fc(x, d_inner_hid, num_flatten_dims=2, act="relu",
                       param_attr=pa("ffn1"),
                       bias_attr=pa("ffn1", "b"))
    return layers.fc(hidden, d_hid, num_flatten_dims=2,
                     param_attr=pa("ffn2"),
                     bias_attr=pa("ffn2", "b"))


def pre_post_process_layer(prev_out, out, process_cmd, dropout_rate=0.0):
    for cmd in process_cmd:
        if cmd == "a":
            out = out + prev_out if prev_out is not None else out
        elif cmd == "n":
            out = layers.layer_norm(
                out, begin_norm_axis=len(out.shape) - 1,
                param_attr=ParamAttr(initializer=None),
                bias_attr=ParamAttr(initializer=None))
        elif cmd == "d" and dropout_rate:
            out = layers.dropout(out, dropout_prob=dropout_rate)
    return out


def _post(prev_out, out, hp):
    with name_scope("post"):
        return pre_post_process_layer(prev_out, out, "dan", hp.dropout)


def encoder_layer(enc_input, src_mask, hp: ModelHyperParams, idx=0):
    with name_scope(f"enc{idx}"):
        with name_scope("self_attn"):
            attn = multi_head_attention(enc_input, None, None,
                                        hp.d_key, hp.d_value, hp.d_model,
                                        hp.n_head, hp.attention_dropout,
                                        k_mask=src_mask,
                                        use_flash=hp.use_flash,
                                        prefix=f"enc{idx}_attn")
            attn = _post(enc_input, attn, hp)
        with name_scope("ffn"):
            ffd = positionwise_feed_forward(attn, hp.d_inner_hid, hp.d_model,
                                            prefix=f"enc{idx}")
            return _post(attn, ffd, hp)


def decoder_layer(dec_input, enc_output, src_mask, hp: ModelHyperParams,
                  idx=0):
    with name_scope(f"dec{idx}"):
        with name_scope("self_attn"):
            self_attn = multi_head_attention(dec_input, None, None,
                                             hp.d_key, hp.d_value,
                                             hp.d_model, hp.n_head,
                                             hp.attention_dropout,
                                             causal=True,
                                             use_flash=hp.use_flash,
                                             prefix=f"dec{idx}_self")
            self_attn = _post(dec_input, self_attn, hp)
        with name_scope("cross_attn"):
            cross = multi_head_attention(self_attn, enc_output, enc_output,
                                         hp.d_key, hp.d_value, hp.d_model,
                                         hp.n_head, hp.attention_dropout,
                                         k_mask=src_mask,
                                         use_flash=hp.use_flash,
                                         prefix=f"dec{idx}_cross")
            cross = _post(self_attn, cross, hp)
        with name_scope("ffn"):
            ffd = positionwise_feed_forward(cross, hp.d_inner_hid,
                                            hp.d_model, prefix=f"dec{idx}")
            return _post(cross, ffd, hp)


def prepare_embedding(ids, pos_ids, vocab_size, hp: ModelHyperParams,
                      name_prefix):
    word_emb = layers.embedding(
        ids, size=[vocab_size, hp.d_model],
        param_attr=ParamAttr(name=name_prefix + "_word_emb"))
    word_emb = layers.scale(word_emb, scale=float(hp.d_model) ** 0.5)
    pos_table = position_encoding_init(hp.max_length, hp.d_model)
    pos_emb = layers.embedding(
        pos_ids, size=[hp.max_length, hp.d_model],
        param_attr=ParamAttr(
            name=name_prefix + "_pos_emb", trainable=False,
            initializer=NumpyArrayInitializer(pos_table)))
    out = word_emb + pos_emb
    if hp.dropout:
        out = layers.dropout(out, dropout_prob=hp.dropout)
    return out


def encoder(src_ids, src_pos, src_mask, hp: ModelHyperParams):
    with name_scope("embed"):
        x = prepare_embedding(src_ids, src_pos, hp.src_vocab_size, hp, "src")
    for i in range(hp.n_layer):
        x = encoder_layer(x, src_mask, hp, idx=i)
    return x


def decoder(trg_ids, trg_pos, enc_output, src_mask, hp: ModelHyperParams):
    with name_scope("embed"):
        x = prepare_embedding(trg_ids, trg_pos, hp.trg_vocab_size, hp, "trg")
    for i in range(hp.n_layer):
        x = decoder_layer(x, enc_output, src_mask, hp, idx=i)
    return x


def build_inputs(batch_size, src_len, trg_len, hp: ModelHyperParams):
    """Declare the dense feed variables.

    Host→device traffic is the TPU bottleneck (feeds may cross DCN), so
    only ids and [B, S] masks are fed; position ids and the [B,1,S,S]
    additive attention biases are built IN-GRAPH as constants/cheap
    broadcasts (unlike the reference benchmark which feeds dense
    [B, n_head, S, S] bias tensors).
    """
    def data(name, shape, dtype):
        return layers.data(name=name, shape=shape, dtype=dtype,
                           append_batch_size=False)

    src_ids = data("src_word", [batch_size, src_len], "int32")
    trg_ids = data("trg_word", [batch_size, trg_len], "int32")
    src_mask = data("src_mask", [batch_size, src_len], "float32")
    labels = data("lbl_word", [batch_size, trg_len], "int32")
    weights = data("lbl_weight", [batch_size, trg_len], "float32")
    return src_ids, trg_ids, src_mask, labels, weights


def _position_ids(batch_size, seq_len):
    """Constant [B, S] int32 position-id tensor (in-graph)."""
    pos = np.tile(np.arange(seq_len, dtype="int32"), (batch_size, 1))
    return layers.assign(pos)


def transformer(batch_size, src_len, trg_len, hp: ModelHyperParams = None,
                input_vars=None):
    """Build the full training graph; returns (avg_cost, feed_vars).

    ``input_vars``: optional 5-tuple (src_ids, trg_ids, src_mask, labels,
    weights) of pre-built variables — e.g. ``layers.read_file`` outputs of
    a recordio reader pipeline — replacing the dense feed declarations.
    """
    hp = hp or ModelHyperParams()
    if input_vars is not None:
        src_ids, trg_ids, src_mask, labels, weights = input_vars
    else:
        src_ids, trg_ids, src_mask, labels, weights = build_inputs(
            batch_size, src_len, trg_len, hp)

    with name_scope("embed"):
        src_pos = _position_ids(batch_size, src_len)
        trg_pos = _position_ids(batch_size, trg_len)

    enc_out = encoder(src_ids, src_pos, src_mask, hp)
    dec_out = decoder(trg_ids, trg_pos, enc_out, src_mask, hp)

    with name_scope("head"):
        logits = layers.fc(dec_out, hp.trg_vocab_size, num_flatten_dims=2,
                           bias_attr=False,
                           param_attr=ParamAttr(name="proj_logits.w"))
        logits2d = layers.reshape(
            logits, shape=[batch_size * trg_len, hp.trg_vocab_size])
        labels2d = layers.reshape(labels, shape=[batch_size * trg_len, 1])
        cost = layers.softmax_with_cross_entropy(logits2d, labels2d)
        weights2d = layers.reshape(weights,
                                   shape=[batch_size * trg_len, 1])
        weighted = cost * weights2d
        sum_cost = layers.reduce_sum(weighted)
        token_count = layers.reduce_sum(weights2d)
        avg_cost = sum_cost / token_count
    feeds = ["src_word", "trg_word", "src_mask", "lbl_word", "lbl_weight"]
    return avg_cost, feeds


def fake_batch(batch_size, src_len, trg_len, hp: ModelHyperParams = None,
               seed=0):
    """Synthetic dense batch for benchmarking/compile checks."""
    hp = hp or ModelHyperParams()
    rng = np.random.RandomState(seed)
    src_word = rng.randint(1, hp.src_vocab_size,
                           size=(batch_size, src_len)).astype("int32")
    trg_word = rng.randint(1, hp.trg_vocab_size,
                           size=(batch_size, trg_len)).astype("int32")
    src_mask = np.ones((batch_size, src_len), dtype="float32")
    lbl_word = rng.randint(1, hp.trg_vocab_size,
                           size=(batch_size, trg_len)).astype("int32")
    lbl_weight = np.ones((batch_size, trg_len), dtype="float32")
    return {
        "src_word": src_word, "trg_word": trg_word, "src_mask": src_mask,
        "lbl_word": lbl_word, "lbl_weight": lbl_weight,
    }


def param_count(hp: ModelHyperParams = None):
    """Approximate dense parameter count: the matmul params plus the
    embedding tables and the per-layer layernorm scale/bias terms
    (2 layernorms/encoder layer, 3/decoder layer, 2 params each of
    width d)."""
    hp = hp or ModelHyperParams()
    d = hp.d_model
    emb = (hp.src_vocab_size + hp.trg_vocab_size) * d
    layernorm = hp.n_layer * (4 * d + 6 * d)
    return matmul_param_count(hp) + emb + layernorm


def matmul_param_count(hp: ModelHyperParams = None):
    """Parameters that participate in matmuls — the honest basis for the
    6N-FLOPs/token MFU estimate.  Excludes the input embedding tables
    (their forward is a gather, not a matmul; their backward is a
    scatter-add) and the layernorm scale/bias terms (elementwise), but
    includes the output projection, which IS a matmul.
    """
    hp = hp or ModelHyperParams()
    d, dff = hp.d_model, hp.d_inner_hid
    per_enc = 4 * d * d + 2 * d * dff
    per_dec = 8 * d * d + 2 * d * dff
    proj = d * hp.trg_vocab_size
    return hp.n_layer * (per_enc + per_dec) + proj


def train_flops_per_token(hp: ModelHyperParams = None, seq=None):
    """Analytical training FLOPs per (target) token — the 6N-matmul +
    attention accounting ``bench.py`` derives MFU from:

    * ``6 * matmul_param_count`` — fwd (2N) + bwd (4N) per matmul
      parameter; input embeddings excluded (gather, not matmul), the
      output projection included.
    * attention: 3 modules/layer (enc-self, dec-self, cross), each
      QK^T + AV = ``4*S*d`` FLOPs/token fwd, bwd 2x => ``12*S*d``.

    The cross-check test (``tests/test_perf.py``) holds this against
    the XLA ``cost_analysis()`` FLOPs of the compiled train step within
    a declared band, so drift in the hand accounting MFU claims rest on
    cannot land silently."""
    hp = hp or ModelHyperParams()
    seq = seq if seq is not None else hp.max_length
    attn_flops = 12 * seq * hp.d_model * (3 * hp.n_layer)
    return 6 * matmul_param_count(hp) + attn_flops


def tp_shardings():
    """Megatron-style tensor-parallel PartitionSpec rules for the model's
    parameters (and, by substring match, their Adam moments) over a mesh
    with a ``model`` axis.  Pass to
    ``ParallelExecutor(param_shardings=...)``; GSPMD inserts the
    collectives (replacing the reference's explicit pserver/NCCL plumbing,
    SURVEY.md §2.8)."""
    from jax.sharding import PartitionSpec as P
    return [
        (r"_(q|k|v)\.w", P(None, "model")),         # column parallel
        (r"_attnout\.w", P("model", None)),         # row parallel
        (r"_ffn1\.w", P(None, "model")),
        (r"_ffn1\.b", P("model")),                  # bias [FF]
        (r"_ffn2\.w", P("model", None)),
        (r"(src|trg)_word_emb", P(None, "model")),  # shard d_model
        (r"proj_logits\.w", P(None, "model")),      # shard vocab
    ]
