"""Neural-net structural ops: conv, pool, normalization, dropout, softmax.

Reference: ``paddle/fluid/operators/{conv_op,conv_transpose_op,pool_op,
batch_norm_op,layer_norm_op,lrn_op,dropout_op,softmax_op}``.  Data layout is
NCHW like the reference's default; XLA re-lays out for the MXU internally.
Convolutions lower to ``lax.conv_general_dilated`` (one XLA HLO, tiled onto
the MXU) instead of the reference's im2col+GEMM / cuDNN split.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.registry import (
    register_op, infer_shape_unary, ShapeInferenceSkip)


# ---------------------------------------------------------------------------
# conv2d / depthwise_conv2d / conv2d_transpose / conv3d
# ---------------------------------------------------------------------------

def _conv_out_size(i, k, s, p, d=1):
    if i == -1:
        return -1
    ke = d * (k - 1) + 1
    return (i + 2 * p - ke) // s + 1


def _infer_conv2d(op, block):
    x = block.var(op.input("Input")[0])
    w = block.var(op.input("Filter")[0])
    if x.shape is None or w.shape is None:
        raise ShapeInferenceSkip()
    strides = op.attr("strides", [1, 1])
    paddings = op.attr("paddings", [0, 0])
    dilations = op.attr("dilations", [1, 1])
    n, _, h, wd = x.shape
    oc, _, kh, kw = w.shape
    out = block.var(op.output("Output")[0])
    out.shape = (n, oc,
                 _conv_out_size(h, kh, strides[0], paddings[0], dilations[0]),
                 _conv_out_size(wd, kw, strides[1], paddings[1], dilations[1]))
    out.dtype = x.dtype


def _conv2d_lower_impl(ctx, depthwise=False):
    x = ctx.input("Input")
    w = ctx.input("Filter")
    strides = tuple(ctx.attr("strides", [1, 1]))
    paddings = ctx.attr("paddings", [0, 0])
    dilations = tuple(ctx.attr("dilations", [1, 1]))
    groups = ctx.attr("groups", 1) or 1
    if depthwise:
        groups = x.shape[1]
    pad = [(paddings[0], paddings[0]), (paddings[1], paddings[1])]
    # NOTE: no preferred_element_type=f32 here — the TPU MXU accumulates
    # bf16 convs in f32 regardless, and requesting an f32 output makes the
    # conv's transpose rule pair an f32 cotangent with a bf16 operand
    # (dtype-mismatch TypeError under AMP training).
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad,
        rhs_dilation=dilations, feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    ctx.set_output("Output", out.astype(x.dtype))


@register_op("conv2d", infer_shape=_infer_conv2d,
             amp_cast=("Input", "Filter"))
def conv2d_lower(ctx):
    _conv2d_lower_impl(ctx)


@register_op("depthwise_conv2d", infer_shape=_infer_conv2d,
             amp_cast=("Input", "Filter"))
def depthwise_conv2d_lower(ctx):
    _conv2d_lower_impl(ctx, depthwise=True)


def _infer_conv2d_transpose(op, block):
    x = block.var(op.input("Input")[0])
    w = block.var(op.input("Filter")[0])
    if x.shape is None or w.shape is None:
        raise ShapeInferenceSkip()
    strides = op.attr("strides", [1, 1])
    paddings = op.attr("paddings", [0, 0])
    dilations = op.attr("dilations", [1, 1])
    n, _, h, wd = x.shape
    _, oc, kh, kw = w.shape  # filter layout (C_in, C_out/groups, kh, kw)
    def osize(i, k, s, p, d):
        if i == -1:
            return -1
        return (i - 1) * s - 2 * p + d * (k - 1) + 1
    out = block.var(op.output("Output")[0])
    out.shape = (n, oc * (op.attr("groups", 1) or 1),
                 osize(h, kh, strides[0], paddings[0], dilations[0]),
                 osize(wd, kw, strides[1], paddings[1], dilations[1]))
    out.dtype = x.dtype


@register_op("conv2d_transpose", infer_shape=_infer_conv2d_transpose,
             amp_cast=("Input", "Filter"))
def conv2d_transpose_lower(ctx):
    x = ctx.input("Input")
    w = ctx.input("Filter")  # (C_in, C_out, kh, kw)
    strides = tuple(ctx.attr("strides", [1, 1]))
    paddings = ctx.attr("paddings", [0, 0])
    dilations = tuple(ctx.attr("dilations", [1, 1]))
    pad = [(paddings[0], paddings[0]), (paddings[1], paddings[1])]
    # The reference deconv is the GRADIENT of a forward conv: scatter-add
    # out[i*s - p + d*k'] += x[i] * w[k'], with out = (i-1)s - 2p + d(k-1)+1.
    # In jax that is transpose_kernel=True (flip spatial axes + swap the
    # kernel's channel roles — hence the forward-conv spec "OIHW" for our
    # (C_in, C_out, kh, kw) layout) with use_consistent_padding=True
    # (integer pads read as the forward conv's padding).  The defaults
    # only coincide when p == d(k-1)/2 and the kernel is symmetric.
    # conv_transpose has no feature_group_count: grouped deconv runs one
    # transpose per channel group, concatenated on the channel axis.
    groups = ctx.attr("groups", 1) or 1

    def one(xg, wg):
        return jax.lax.conv_transpose(
            xg, wg, strides=strides, padding=pad, rhs_dilation=dilations,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            transpose_kernel=True, use_consistent_padding=True)

    if groups == 1:
        out = one(x, w)
    else:
        cg = x.shape[1] // groups
        out = jnp.concatenate(
            [one(x[:, g * cg:(g + 1) * cg], w[g * cg:(g + 1) * cg])
             for g in range(groups)], axis=1)
    ctx.set_output("Output", out)


def _infer_conv3d_transpose(op, block):
    x = block.var(op.input("Input")[0])
    w = block.var(op.input("Filter")[0])
    if x.shape is None or w.shape is None:
        raise ShapeInferenceSkip()
    s = op.attr("strides", [1, 1, 1])
    p = op.attr("paddings", [0, 0, 0])
    d = op.attr("dilations", [1, 1, 1])
    n = x.shape[0]
    spatial = x.shape[2:]
    _, oc = w.shape[0], w.shape[1]  # filter layout (C_in, C_out/groups, ...)
    ks = w.shape[2:]

    def osize(i, k, st, pd, dl):
        if i == -1:
            return -1
        return (i - 1) * st - 2 * pd + dl * (k - 1) + 1

    out = block.var(op.output("Output")[0])
    out.shape = (n, oc * (op.attr("groups", 1) or 1)) + tuple(
        osize(spatial[i], ks[i], s[i], p[i], d[i]) for i in range(3))
    out.dtype = x.dtype


@register_op("conv3d_transpose", infer_shape=_infer_conv3d_transpose,
             amp_cast=("Input", "Filter"))
def conv3d_transpose_lower(ctx):
    """NCDHW transposed 3-D convolution (reference
    ``conv_transpose_op.cc:314`` registers conv3d_transpose); filter
    layout (C_in, C_out, kd, kh, kw), same as conv2d_transpose."""
    x = ctx.input("Input")
    w = ctx.input("Filter")
    s = tuple(ctx.attr("strides", [1, 1, 1]))
    p = ctx.attr("paddings", [0, 0, 0])
    d = tuple(ctx.attr("dilations", [1, 1, 1]))
    pad = [(p[i], p[i]) for i in range(3)]
    # gradient-of-conv semantics + per-group transposes — see
    # conv2d_transpose_lower
    groups = ctx.attr("groups", 1) or 1

    def one(xg, wg):
        return jax.lax.conv_transpose(
            xg, wg, strides=s, padding=pad, rhs_dilation=d,
            dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
            transpose_kernel=True, use_consistent_padding=True)

    if groups == 1:
        out = one(x, w)
    else:
        cg = x.shape[1] // groups
        out = jnp.concatenate(
            [one(x[:, g * cg:(g + 1) * cg], w[g * cg:(g + 1) * cg])
             for g in range(groups)], axis=1)
    ctx.set_output("Output", out)


def _infer_conv3d(op, block):
    x = block.var(op.input("Input")[0])
    w = block.var(op.input("Filter")[0])
    if x.shape is None or w.shape is None:
        raise ShapeInferenceSkip()
    s = op.attr("strides", [1, 1, 1])
    p = op.attr("paddings", [0, 0, 0])
    d = op.attr("dilations", [1, 1, 1])
    n, _, d0, h, wd = x.shape
    oc, _, kd, kh, kw = w.shape
    out = block.var(op.output("Output")[0])
    out.shape = (n, oc, _conv_out_size(d0, kd, s[0], p[0], d[0]),
                 _conv_out_size(h, kh, s[1], p[1], d[1]),
                 _conv_out_size(wd, kw, s[2], p[2], d[2]))
    out.dtype = x.dtype


@register_op("conv3d", infer_shape=_infer_conv3d,
             amp_cast=("Input", "Filter"))
def conv3d_lower(ctx):
    x = ctx.input("Input")
    w = ctx.input("Filter")
    s = tuple(ctx.attr("strides", [1, 1, 1]))
    p = ctx.attr("paddings", [0, 0, 0])
    d = tuple(ctx.attr("dilations", [1, 1, 1]))
    pad = [(p[i], p[i]) for i in range(3)]
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=s, padding=pad, rhs_dilation=d,
        feature_group_count=ctx.attr("groups", 1) or 1,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
    ctx.set_output("Output", out)


# ---------------------------------------------------------------------------
# pooling  (reference pool_op.cc + math/pooling.cc)
# ---------------------------------------------------------------------------

def _infer_pool2d(op, block):
    x = block.var(op.input("X")[0])
    if x.shape is None:
        raise ShapeInferenceSkip()
    ksize = op.attr("ksize")
    strides = op.attr("strides", [1, 1])
    paddings = op.attr("paddings", [0, 0])
    gp = op.attr("global_pooling", False)
    ceil_mode = op.attr("ceil_mode", False)
    n, c, h, w = x.shape
    if gp:
        oh = ow = 1
    else:
        def osize(i, k, s, p):
            if i == -1:
                return -1
            if ceil_mode:
                return (i - k + 2 * p + s - 1) // s + 1
            return (i - k + 2 * p) // s + 1
        oh = osize(h, ksize[0], strides[0], paddings[0])
        ow = osize(w, ksize[1], strides[1], paddings[1])
    out = block.var(op.output("Out")[0])
    out.shape = (n, c, oh, ow)
    out.dtype = x.dtype


@register_op("pool2d", infer_shape=_infer_pool2d)
def pool2d_lower(ctx):
    x = ctx.input("X")
    ptype = ctx.attr("pooling_type", "max")
    ksize = list(ctx.attr("ksize"))
    strides = list(ctx.attr("strides", [1, 1]))
    paddings = list(ctx.attr("paddings", [0, 0]))
    if ctx.attr("global_pooling", False):
        ksize = [x.shape[2], x.shape[3]]
        strides = [1, 1]
        paddings = [0, 0]
    window = (1, 1, ksize[0], ksize[1])
    strides4 = (1, 1, strides[0], strides[1])
    pad4 = [(0, 0), (0, 0), (paddings[0], paddings[0]),
            (paddings[1], paddings[1])]
    if ctx.attr("ceil_mode", False):
        # extend right/bottom padding so the last partial window is included
        def extra(i, k, s, p):
            out = (i - k + 2 * p + s - 1) // s + 1
            needed = (out - 1) * s + k - i - p
            return max(needed - p, 0) + p
        pad4[2] = (paddings[0], extra(x.shape[2], ksize[0], strides[0],
                                      paddings[0]))
        pad4[3] = (paddings[1], extra(x.shape[3], ksize[1], strides[1],
                                      paddings[1]))
    if ptype == "max":
        init = -jnp.inf
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, strides4,
                                    pad4)
    else:
        ssum = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides4,
                                     pad4)
        if ctx.attr("exclusive", True):
            ones = jnp.ones_like(x)
            counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                           strides4, pad4)
            out = ssum / counts
        else:
            out = ssum / (ksize[0] * ksize[1])
    ctx.set_output("Out", out)


@register_op("pool2d_with_index", infer_shape=None, no_grad_inputs=())
def pool2d_with_index_lower(ctx):
    x = ctx.input("X")
    ksize = list(ctx.attr("ksize"))
    strides = list(ctx.attr("strides", [1, 1]))
    paddings = list(ctx.attr("paddings", [0, 0]))
    if ctx.attr("global_pooling", False):
        ksize = [x.shape[2], x.shape[3]]
        strides = [1, 1]
        paddings = [0, 0]
    window = (1, 1, ksize[0], ksize[1])
    strides4 = (1, 1, strides[0], strides[1])
    pad4 = [(0, 0), (0, 0), (paddings[0], paddings[0]),
            (paddings[1], paddings[1])]
    out = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window, strides4,
                                pad4)
    # index of max within flattened H*W of input
    n, c, h, w = x.shape
    flat_idx = jnp.arange(h * w, dtype=jnp.float32).reshape(1, 1, h, w)
    flat_idx = jnp.broadcast_to(flat_idx, x.shape)
    # select index where value equals the max of its window: use a paired
    # reduce on (value, index)
    def sel_max(a, b):
        av, ai = a
        bv, bi = b
        take_b = bv > av
        return jnp.where(take_b, bv, av), jnp.where(take_b, bi, ai)
    vals, idxs = jax.lax.reduce_window(
        (x, flat_idx), (-jnp.inf, 0.0), sel_max, window, strides4, pad4)
    ctx.set_output("Out", vals)
    ctx.set_output("Mask", idxs.astype(jnp.int64))


# ---------------------------------------------------------------------------
# batch_norm  (reference batch_norm_op.cc)
# ---------------------------------------------------------------------------

def _infer_batch_norm(op, block):
    x = block.var(op.input("X")[0])
    y = block.var(op.output("Y")[0])
    y.shape = x.shape
    y.dtype = x.dtype
    if x.shape is not None:
        layout = op.attr("data_layout", "NCHW")
        c = x.shape[1] if layout == "NCHW" else x.shape[-1]
        for slot in ("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"):
            names = op.output(slot)
            if names:
                v = block.var(names[0])
                v.shape = (c,)
                v.dtype = "float32"


@register_op("batch_norm", infer_shape=_infer_batch_norm,
             no_grad_inputs=("Mean", "Variance"))
def batch_norm_lower(ctx):
    x = ctx.input("X")
    scale, bias = ctx.input("Scale"), ctx.input("Bias")
    mean, var = ctx.input("Mean"), ctx.input("Variance")
    eps = ctx.attr("epsilon", 1e-5)
    momentum = ctx.attr("momentum", 0.9)
    layout = ctx.attr("data_layout", "NCHW")
    is_test = ctx.attr("is_test", False) or not ctx.training

    axes = tuple(i for i in range(x.ndim)
                 if i != (1 if layout == "NCHW" and x.ndim > 2 else x.ndim - 1))
    caxis = 1 if (layout == "NCHW" and x.ndim > 2) else x.ndim - 1
    bshape = [1] * x.ndim
    bshape[caxis] = x.shape[caxis]

    xf = x.astype(jnp.float32)
    if is_test:
        use_mean, use_var = mean, var
        saved_mean, saved_var = mean, var
        mean_out, var_out = mean, var
    else:
        use_mean = jnp.mean(xf, axis=axes)
        use_var = jnp.mean(jnp.square(xf - use_mean.reshape(bshape)),
                           axis=axes)
        saved_mean, saved_var = use_mean, use_var
        mean_out = mean * momentum + use_mean * (1.0 - momentum)
        var_out = var * momentum + use_var * (1.0 - momentum)

    inv_std = jax.lax.rsqrt(use_var + eps)
    y = (xf - use_mean.reshape(bshape)) * inv_std.reshape(bshape)
    y = y * scale.reshape(bshape) + bias.reshape(bshape)
    ctx.set_output("Y", y.astype(x.dtype))
    ctx.set_output("MeanOut", mean_out)
    ctx.set_output("VarianceOut", var_out)
    ctx.set_output("SavedMean", saved_mean)
    ctx.set_output("SavedVariance", jax.lax.rsqrt(saved_var + eps))


# ---------------------------------------------------------------------------
# layer_norm  (reference layer_norm_op.cc)
# ---------------------------------------------------------------------------

def _infer_layer_norm(op, block):
    x = block.var(op.input("X")[0])
    y = block.var(op.output("Y")[0])
    y.shape = x.shape
    y.dtype = x.dtype


@register_op("layer_norm", infer_shape=_infer_layer_norm,
             amp_cast=("X",))
def layer_norm_lower(ctx):
    """Under bf16 AMP the input (and hence the output, cast back to
    X's dtype) is bf16, keeping the transformer residual stream bf16
    end-to-end — the statistics are still computed in f32 below.  An
    f32-promoted residual stream doubles the HBM traffic of every
    LN/add pair (measured on the chip by a one-off study that is no
    longer in the tree)."""
    x = ctx.input("X")
    begin = ctx.attr("begin_norm_axis", 1)
    eps = ctx.attr("epsilon", 1e-5)
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    scale, bias = ctx.input("Scale"), ctx.input("Bias")
    norm_shape = (1,) * begin + tuple(x.shape[begin:])
    if scale is not None:
        y = y * scale.reshape(norm_shape)
    if bias is not None:
        y = y + bias.reshape(norm_shape)
    ctx.set_output("Y", y.astype(x.dtype))
    ctx.set_output("Mean", mean.reshape(x.shape[:begin]))
    ctx.set_output("Variance", var.reshape(x.shape[:begin]))


# ---------------------------------------------------------------------------
# lrn (local response normalization)
# ---------------------------------------------------------------------------

@register_op("lrn", infer_shape=infer_shape_unary())
def lrn_lower(ctx):
    x = ctx.input("X")  # NCHW
    n = ctx.attr("n", 5)
    k = ctx.attr("k", 2.0)
    alpha = ctx.attr("alpha", 1e-4)
    beta = ctx.attr("beta", 0.75)
    half = n // 2
    sq = jnp.square(x)
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = jnp.zeros_like(x)
    for i in range(n):
        acc = acc + pad[:, i:i + x.shape[1]]
    mid = k + alpha * acc
    ctx.set_output("Out", x / jnp.power(mid, beta))
    ctx.set_output("MidOut", mid)


# ---------------------------------------------------------------------------
# dropout  (reference dropout_op.cc; old-fluid "downgrade_in_infer": train
# multiplies by the 0/1 mask, inference scales by (1-p))
# ---------------------------------------------------------------------------

def _infer_dropout(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape = x.shape
    out.dtype = x.dtype
    masks = op.output("Mask")
    if masks:
        m = block.var(masks[0])
        m.shape = x.shape
        m.dtype = x.dtype


def _dropout_grad_lower(ctx):
    g_out = ctx.env[ctx.op.input("Out@GRAD")[0]]
    mask = ctx.env[ctx.op.input("Mask")[0]]
    gname = ctx.op.output("X@GRAD")[0]
    ctx.outputs[gname] = g_out * mask


def _dropout_grad_maker(op, block, no_grad_set):
    from paddle_tpu.framework import grad_var_name
    x = op.input("X")[0]
    if x in no_grad_set:
        return [], {}
    g_x = grad_var_name(x)
    desc = {"type": "dropout_grad",
            "inputs": {"Out@GRAD": [grad_var_name(op.output("Out")[0])],
                       "Mask": [op.output("Mask")[0]]},
            "outputs": {"X@GRAD": [g_x]},
            "attrs": dict(op.attrs)}
    return [desc], {x: g_x}


# the generator's uint32[4] state is the op's two key words and their xor
# with these two (the 64-bit golden ratio): a fixed rule, so a mask stays a
# pure function of the op's key
_MASK_STATE_SALT = np.array([0x9E3779B9, 0x7F4A7C15], np.uint32)


def _mask_threshold(p):
    """``(bits dtype, width, threshold)`` of a dropout draw: an element is
    kept where its ``width`` random bits, read as an unsigned integer, are
    ``>= threshold = round(p * 2**width)``.  16 bits where they give ``p``
    to 1e-4 relative (0.1 -> 6554/65536 = 0.100006), else 32."""
    for dtype, width in ((jnp.uint16, 16), (jnp.uint32, 32)):
        threshold = int(round(p * 2 ** width))
        if abs(threshold / 2 ** width - p) <= 1e-4 * p:
            break
    return dtype, width, threshold


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw_bits(key, shape, dtype):
    # a jit of its own: the TPU compiler rebuilds the generator with no
    # metadata, and inside a ``run_steps`` scan the instruction then takes
    # the scan body's; an inlined call hands it the ``ptop_dropout`` scope
    # the device trace is read by
    words = jnp.asarray(key, jnp.uint32)
    state = jnp.concatenate([words, words ^ _MASK_STATE_SALT])
    return jax.lax.rng_bit_generator(state, shape, dtype=dtype)[1]


def _dropout_keep(key, p, shape, mesh=None, batch_axis=0):
    """The boolean keep mask of a training ``dropout``: XLA's own bit
    generator (``rng_bit_generator``, one stand-alone instruction on the
    TPU) seeded from the op's threefry key words, an integer compare
    against the threshold, no float uniform.

    On a mesh with a ``data`` axis that divides the batch axis, every
    shard draws its own block from the key with its row-block index
    folded in: GSPMD cannot partition the generator and would draw the
    GLOBAL shape on every chip and slice it."""
    from paddle_tpu.parallel.mesh import DATA_AXIS
    dtype, width, threshold = _mask_threshold(p)
    if threshold >= 2 ** width:          # p rounds to 1: nothing is kept
        return jnp.zeros(shape, jnp.bool_)
    shards = mesh.shape.get(DATA_AXIS, 1) if mesh is not None else 1
    if shards > 1 and len(shape) > batch_axis and \
            shape[batch_axis] % shards == 0:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        block = list(shape)
        block[batch_axis] //= shards
        spec = [None] * len(shape)
        spec[batch_axis] = DATA_AXIS

        def draw(key):
            key = jax.random.fold_in(key, jax.lax.axis_index(DATA_AXIS))
            return _draw_bits(key, tuple(block), dtype)

        bits = shard_map(draw, mesh=mesh, in_specs=P(),
                         out_specs=P(*spec))(key)
    else:
        bits = _draw_bits(key, shape, dtype)
    return bits >= dtype(threshold)


@register_op("dropout", infer_shape=_infer_dropout, uses_rng=True,
             grad_maker=_dropout_grad_maker, grad_lower=_dropout_grad_lower)
def dropout_lower(ctx):
    """Training: ``Out = X * Mask``, ``Mask`` 0/1 (``upscale_in_train``:
    0 or 1/(1-p)) in X's dtype, for ``dropout_grad``.

    A mask is a pure function of the op's key, i.e. of
    ``(program.random_seed, the executor's run counter, the op's rng
    slot)``, or of ``seed`` alone under ``fix_seed``: optimised and
    unoptimised programs, a sentinel's replay and a ``fix_seed`` op see
    the same mask again.  The key is threefry's (one ``fold_in`` an op);
    the mask's BITS come from XLA's bit generator, 16 an element
    (``_dropout_keep``: the fused 32-bit threefry draw cost the
    Transformer-base step 45-50 of its 367 ms), so the drop probability
    is ``round(p * 65536) / 65536`` (p = 0.1: 0.100006; a ``p`` that 16
    bits miss by more than 1e-4 relative draws 32), and masks repeat on
    one backend and one mesh shape, not across them: the generator's
    algorithm is the backend's own, and under GSPMD every shard draws
    its own block.  ``Mask`` is a second draw from the same key, so
    the backward regenerates the mask and the forward keeps none.
    Initialisers, ``sampling_id`` and ``nce`` keep threefry: their
    values are what seeded parameters and tests pin.

    Test mode draws nothing: ``downgrade_in_infer`` scales by ``1 - p``.
    """
    x = ctx.input("X")
    p = ctx.attr("dropout_prob", 0.5)
    is_test = ctx.attr("is_test", False) or not ctx.training
    impl = ctx.attr("dropout_implementation", "downgrade_in_infer")
    if is_test:
        out = x * (1.0 - p) if impl == "downgrade_in_infer" else x
        ctx.set_output("Out", out)
        ctx.set_output("Mask", jnp.ones_like(x))
        return
    seed = ctx.attr("seed", 0)
    key = jax.random.PRNGKey(seed) if ctx.attr("fix_seed", False) \
        else ctx.rng_key()
    from paddle_tpu.profiler import runtime_metrics
    runtime_metrics.inc("dropout.mask_sites")
    runtime_metrics.inc("dropout.mask_bits",
                        int(np.prod(x.shape)) * _mask_threshold(p)[1])

    def draw(key):
        keep = _dropout_keep(key, p, x.shape, ctx.aux.get("mesh"),
                             ctx.aux.get("batch_axis", 0))
        if impl == "upscale_in_train":
            return keep.astype(x.dtype) / (1.0 - p)
        return keep.astype(x.dtype)

    ctx.set_output("Out", x * draw(key))
    # ``Mask`` is the same mask drawn AGAIN (the barrier keeps XLA from
    # merging the two draws): the backward regenerates it where it reads
    # it, as XLA did of itself with the fused threefry, instead of
    # keeping 16 bits an element from the forward pass on (that cost
    # Transformer-base at B256/S256 another 9 ms a step of recomputing
    # what no longer fitted; PERF.md section 6, PR 36)
    ctx.set_output("Mask", draw(jax.lax.optimization_barrier(key)))


# ---------------------------------------------------------------------------
# softmax / log_softmax  (reference softmax_op.cc: normalizes the last dim)
# ---------------------------------------------------------------------------

@register_op("softmax", infer_shape=infer_shape_unary(),
             no_grad_inputs=("Bias",))
def softmax_lower(ctx):
    """Last-axis softmax with an optional fused additive ``Bias``
    (attention masks).  Internally f32, output in X's dtype: under bf16
    AMP the [B,H,S,S] score tensor then stays bf16 in HBM — the bias
    add and the f32 upcast fuse into the reduction passes instead of
    materializing an f32 score tensor (reference softmax_op.cc is plain
    f32; the fused-bias form is the TPU redesign of the transformer's
    ``scores + mask`` pattern)."""
    x = ctx.input("X")
    bias = ctx.input("Bias")
    out_dtype = x.dtype
    if bias is not None:
        # add in X's dtype: under bf16 AMP the materialization candidate
        # between the softmax reduction passes is then bf16, not f32
        # (-1e9 is representable in bf16; exp/sum still run in f32)
        x = x + bias.astype(x.dtype)
    ctx.set_output("Out", jax.nn.softmax(
        x.astype(jnp.float32), axis=-1).astype(out_dtype))


@register_op("log_softmax", infer_shape=infer_shape_unary())
def log_softmax_lower(ctx):
    ctx.set_output("Out", jax.nn.log_softmax(ctx.input("X"), axis=-1))


# ---------------------------------------------------------------------------
# label_smooth / im2sequence helpers
# ---------------------------------------------------------------------------

@register_op("label_smooth", infer_shape=infer_shape_unary())
def label_smooth_lower(ctx):
    x = ctx.input("X")
    eps = ctx.attr("epsilon", 0.0)
    dist = ctx.input("PriorDist")
    k = x.shape[-1]
    if dist is not None:
        out = (1.0 - eps) * x + eps * dist
    else:
        out = (1.0 - eps) * x + eps / k
    ctx.set_output("Out", out)


# ---------------------------------------------------------------------------
# pool3d — reference ``pool_op.cc`` 3-D variant (NCDHW).
# ---------------------------------------------------------------------------

def _infer_pool3d(op, block):
    x = block.var(op.input("X")[0])
    if x.shape is None:
        raise ShapeInferenceSkip()
    n, c, d, h, w = x.shape
    k = list(op.attr("ksize"))
    s = list(op.attr("strides", [1, 1, 1]))
    p = list(op.attr("paddings", [0, 0, 0, 0]))[:3] + [0, 0, 0]
    if op.attr("global_pooling", False):
        k, s, p = [d, h, w], [1, 1, 1], [0, 0, 0]
    ceil = op.attr("ceil_mode", False)
    dims = []
    for i, size in enumerate((d, h, w)):
        num = size - k[i] + 2 * p[i]
        dims.append((num + s[i] - 1) // s[i] + 1 if ceil
                    else num // s[i] + 1)
    out = block.var(op.output("Out")[0])
    out.shape = (n, c) + tuple(dims)
    out.dtype = x.dtype


@register_op("pool3d", infer_shape=_infer_pool3d)
def pool3d_lower(ctx):
    x = ctx.input("X")                   # [N, C, D, H, W]
    ptype = ctx.attr("pooling_type", "max")
    ksize = list(ctx.attr("ksize"))
    strides = list(ctx.attr("strides", [1, 1, 1]))
    paddings = list(ctx.attr("paddings", [0, 0, 0]))
    if ctx.attr("global_pooling", False):
        ksize = [x.shape[2], x.shape[3], x.shape[4]]
        strides = [1, 1, 1]
        paddings = [0, 0, 0]
    window = (1, 1) + tuple(ksize)
    strides5 = (1, 1) + tuple(strides)
    pad5 = [(0, 0), (0, 0)] + [(p, p) for p in paddings[:3]]
    if ctx.attr("ceil_mode", False):
        # extend trailing padding so the last partial window is included
        # (same recipe as pool2d above)
        for i, size in enumerate((x.shape[2], x.shape[3], x.shape[4])):
            k_, s_, p_ = ksize[i], strides[i], paddings[i]
            out_dim = (size - k_ + 2 * p_ + s_ - 1) // s_ + 1
            needed = (out_dim - 1) * s_ + k_ - size - p_
            pad5[2 + i] = (p_, max(needed, p_))
    if ptype == "max":
        out = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window,
                                    strides5, pad5)
    else:
        ssum = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides5,
                                     pad5)
        if ctx.attr("exclusive", True):
            counts = jax.lax.reduce_window(jnp.ones_like(x), 0.0,
                                           jax.lax.add, window, strides5,
                                           pad5)
            out = ssum / counts
        else:
            out = ssum / (ksize[0] * ksize[1] * ksize[2])
    ctx.set_output("Out", out)


# ---------------------------------------------------------------------------
# unpool — reference ``unpool_op.cc``: max-unpool via the flat indices from
# max_pool2d_with_index.
# ---------------------------------------------------------------------------

def _infer_unpool(op, block):
    x = block.var(op.input("X")[0])
    if x.shape is None:
        raise ShapeInferenceSkip()
    n, c, h, w = x.shape
    k = list(op.attr("ksize"))
    s = list(op.attr("strides", [2, 2]))
    p = list(op.attr("paddings", [0, 0]))
    oh = (h - 1) * s[0] - 2 * p[0] + k[0]
    ow = (w - 1) * s[1] - 2 * p[1] + k[1]
    out = block.var(op.output("Out")[0])
    out.shape = (n, c, oh, ow)
    out.dtype = x.dtype


@register_op("unpool", infer_shape=_infer_unpool,
             no_grad_inputs=("Indices",))
def unpool_lower(ctx):
    x = ctx.input("X")                   # [N, C, h, w] pooled values
    indices = ctx.input("Indices")       # [N, C, h, w] flat out positions
    n, c, h, w = x.shape
    k = list(ctx.attr("ksize"))
    s = list(ctx.attr("strides", [2, 2]))
    p = list(ctx.attr("paddings", [0, 0]))
    oh = (h - 1) * s[0] - 2 * p[0] + k[0]
    ow = (w - 1) * s[1] - 2 * p[1] + k[1]
    flat = jnp.zeros((n, c, oh * ow), x.dtype)
    ni = jnp.arange(n)[:, None, None]
    ci = jnp.arange(c)[None, :, None]
    idx = indices.reshape(n, c, h * w).astype(jnp.int32)
    flat = flat.at[jnp.broadcast_to(ni, idx.shape).reshape(-1),
                   jnp.broadcast_to(ci, idx.shape).reshape(-1),
                   idx.reshape(-1)].add(x.reshape(-1))
    ctx.set_output("Out", flat.reshape(n, c, oh, ow))


# ---------------------------------------------------------------------------
# spp — reference ``spp_op.h``: spatial pyramid pooling, levels 0..H-1 of
# 2^l x 2^l adaptive pooling, flattened and concatenated.
# ---------------------------------------------------------------------------

def _infer_spp(op, block):
    x = block.var(op.input("X")[0])
    if x.shape is None:
        raise ShapeInferenceSkip()
    n, c = x.shape[0], x.shape[1]
    ph = op.attr("pyramid_height")
    feats = sum(c * (2 ** l) * (2 ** l) for l in range(ph))
    out = block.var(op.output("Out")[0])
    out.shape = (n, feats)
    out.dtype = x.dtype


def _adaptive_pool_axis(x, axis, bins, ptype):
    """Adaptive pooling along one axis: bin i covers
    [floor(i*size/bins), ceil((i+1)*size/bins)) — never empty (the
    reference's spp bin boundaries; fixed-window padding can produce
    all-padding windows when bins doesn't divide the size)."""
    size = x.shape[axis]
    rows = jnp.arange(size)
    starts = np.floor(np.arange(bins) * size / bins).astype(int)
    ends = np.ceil((np.arange(bins) + 1) * size / bins).astype(int)
    mask = (rows[None, :] >= starts[:, None]) & \
        (rows[None, :] < ends[:, None])                 # [bins, size]
    xm = jnp.moveaxis(x, axis, -1)                      # [..., size]
    if ptype == "max":
        vals = jnp.where(mask, xm[..., None, :], -jnp.inf)  # [...,bins,size]
        out = jnp.max(vals, axis=-1)
    else:
        vals = jnp.where(mask, xm[..., None, :], 0.0)
        out = jnp.sum(vals, axis=-1) / jnp.sum(mask, axis=-1)
    return jnp.moveaxis(out, -1, axis)


@register_op("spp", infer_shape=_infer_spp)
def spp_lower(ctx):
    x = ctx.input("X")                   # [N, C, H, W]
    n = x.shape[0]
    ph = int(ctx.attr("pyramid_height"))
    ptype = ctx.attr("pooling_type", "max")
    parts = []
    for level in range(ph):
        bins = 2 ** level
        o = _adaptive_pool_axis(x, 2, bins, ptype)
        o = _adaptive_pool_axis(o, 3, bins, ptype)
        parts.append(o.reshape(n, -1))
    ctx.set_output("Out", jnp.concatenate(parts, axis=1))


# ---------------------------------------------------------------------------
# conv_shift — reference ``conv_shift_op.cc``: circular correlation
# out[i, j] = sum_k x[i, (j + k - M//2) mod N] * y[i, k].
# ---------------------------------------------------------------------------

def _infer_conv_shift(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape = x.shape
    out.dtype = x.dtype


@register_op("conv_shift", infer_shape=_infer_conv_shift)
def conv_shift_lower(ctx):
    x = ctx.input("X")                   # [B, N]
    y = ctx.input("Y")                   # [B, M], M odd, M <= N
    n = x.shape[1]
    m = y.shape[1]
    half = m // 2
    out = jnp.zeros_like(x)
    for k in range(m):
        out = out + jnp.roll(x, half - k, axis=1) * y[:, k:k + 1]
    ctx.set_output("Out", out)


# ---------------------------------------------------------------------------
# image_resize — spatial up/down-sampling of NCHW feature maps (reference
# BilinearInterpLayer.cpp / UpsampleLayer.cpp in paddle/gserver/layers).
# Lowered to jax.image.resize, which is differentiable.
# ---------------------------------------------------------------------------

def _infer_image_resize(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    if x.shape is None:
        raise ShapeInferenceSkip()
    n, c = x.shape[0], x.shape[1]
    out.shape = (n, c, op.attr("out_h"), op.attr("out_w"))
    out.dtype = x.dtype


def _bilinear_align_corners(x, out_h, out_w):
    """Align-corners bilinear resize of NCHW maps: source coordinate
    ``i * (in-1)/(out-1)`` per the reference BilinearInterpLayer ratios
    (vs jax.image.resize's half-pixel convention). Gather + lerp, so it
    is differentiable."""
    _, _, h, w = x.shape

    def axis(in_sz, out_sz):
        if out_sz == 1 or in_sz == 1:
            zero = jnp.zeros((out_sz,), jnp.int32)
            return zero, zero, jnp.zeros((out_sz,), x.dtype)
        pos = jnp.arange(out_sz, dtype=x.dtype) * ((in_sz - 1) / (out_sz - 1))
        lo = jnp.floor(pos).astype(jnp.int32)
        lo = jnp.minimum(lo, in_sz - 2)
        return lo, lo + 1, pos - lo.astype(x.dtype)

    h0, h1, fh = axis(h, out_h)
    w0, w1, fw = axis(w, out_w)
    fh = fh[None, None, :, None]
    fw = fw[None, None, None, :]
    rows = x[:, :, h0, :] * (1 - fh) + x[:, :, h1, :] * fh
    return rows[:, :, :, w0] * (1 - fw) + rows[:, :, :, w1] * fw


@register_op("image_resize", infer_shape=_infer_image_resize)
def image_resize_lower(ctx):
    x = ctx.input("X")                   # [N, C, H, W]
    method = ctx.attr("method", "bilinear")
    out_h, out_w = ctx.attr("out_h"), ctx.attr("out_w")
    xf = x.astype(jnp.float32)
    if method == "bilinear" and ctx.attr("align_corners", True):
        out = _bilinear_align_corners(xf, out_h, out_w)
    else:
        jmethod = {"bilinear": "linear", "nearest": "nearest"}[method]
        out = jax.image.resize(
            xf, (x.shape[0], x.shape[1], out_h, out_w), method=jmethod)
    ctx.set_output("Out", out.astype(x.dtype))
